"""Rate of an offload's copy into the store's pool: the `bytes` of the
program's istpu.store.write spans that started in the window over
their summed duration (TpuKVStore.put_kv_pages around write_cache: the
host copy into the shared-memory pool, first touch of its pages, and
the commit's submission). offload_gbps divides the same bytes by the
whole put and the sync.

Moves itl_mean_ms: the copy runs on the engine thread.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Store client and server"
UNIT = "GB/s"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def value(obs, spans):
    writes = program_spans.started_in_window(obs, spans,
                                             "istpu.store.write")
    ns = sum(s.dur_ns for s in writes)
    return sum(s.fields["bytes"] for s in writes) / ns if ns else None


def read(obs):
    return program_spans.read(obs, value)
