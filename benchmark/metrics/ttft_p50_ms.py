"""Median time from when a request was DUE to its first streamed token,
over the requests due in the window, on the load generator's clock. A
failed request ranks last.
"""

from benchmark.lib import stats

KIND = "end_to_end"
LAYER = None
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(obs):
    return stats.quantile(obs.ttfts_ms(), 0.50)
