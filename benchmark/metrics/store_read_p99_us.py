"""99th percentile of the store's native per-op latency histogram for PIN,
the read op of the engine's shared-memory restores: window delta of
/stats (log2 buckets, bucket midpoint).

Moves itl_mean_ms: every admission (probe, restore, prefill) runs on the
one engine thread and stalls all decoding slots. Where ttft_p50_ms is an
end-to-end metric of the cell, it moves that too.
"""

from benchmark.lib import stats

KIND = "per_layer"
LAYER = "Store client and server"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return stats.hist_percentile_us(obs.store_hist.get("PIN") or [], 0.99)
