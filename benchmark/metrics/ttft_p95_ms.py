"""95th percentile of the time from DUE to first token. A per-layer metric
without a bound: ten samples beyond it want 200 requests in the window
and the cells have about 60 (run.py prints the count); its runs spread
by 23-35 % (PERF.md, Findings, PR 23).
"""

from benchmark.lib import stats

KIND = "per_layer"
LAYER = "HTTP edge"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p50_ms"


def read(obs):
    return stats.quantile(obs.ttfts_ms(), 0.95)
