"""Median TTFT of the requests of turn 2 and later (expected prefix hits):
probe, restore, tail prefill.
"""

from benchmark.lib import stats

KIND = "per_layer"
LAYER = "HTTP edge"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p50_ms"


def read(obs):
    return stats.quantile(obs.ttfts_ms(lambda r: r["turn"] > 1), 0.50)
