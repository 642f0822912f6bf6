"""95th percentile of the gap between consecutive streamed tokens of one
request, over all gaps that end in the window.
"""

from benchmark.lib import stats

KIND = "end_to_end"
LAYER = None
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(obs):
    return stats.quantile(obs.gaps_ms(), 0.95)
