"""99th percentile of the store's native per-op latency histogram for
COMMIT, which ends every offloaded batch: window delta of /stats.
"""

from benchmark.lib import stats

KIND = "per_layer"
LAYER = "Store client and server"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return stats.hist_percentile_us(obs.store_hist.get("COMMIT") or [],
                                    0.99)
