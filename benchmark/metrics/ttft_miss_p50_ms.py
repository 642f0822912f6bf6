"""Median TTFT of the requests of turn 1 (cold prefill of the whole
prompt).
"""

from benchmark.lib import stats

KIND = "per_layer"
LAYER = "HTTP edge"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "ttft_p50_ms"


def read(obs):
    return stats.quantile(obs.ttfts_ms(lambda r: r["turn"] == 1), 0.50)
