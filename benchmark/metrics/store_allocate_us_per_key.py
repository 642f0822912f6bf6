"""What the store's allocate costs a key, median: over the program's
istpu.store.allocate spans that started in the window (one a store
batch of an offload: TpuKVStore.put_kv_pages around conn.allocate),
the span's duration over its `keys`. One round trip to the store
server and its index's work a key; store_write_p99_us (the COMMIT's
histogram bucket) cannot tell 20 us a key from 40.

Moves itl_mean_ms: an offload's store batch runs on the engine thread.
"""

from benchmark.lib import program_spans, stats

KIND = "per_layer"
LAYER = "Store client and server"
UNIT = "us"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def value(obs, spans):
    return stats.quantile(
        [s.dur_ns / 1e3 / s.fields["keys"]
         for s in program_spans.started_in_window(
             obs, spans, "istpu.store.allocate") if s.fields.get("keys")],
        0.50)


def read(obs):
    return program_spans.read(obs, value)
