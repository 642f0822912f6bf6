"""Median of the program's istpu.cache.offload spans of reason `finish`
that started in the window: the whole of what a finished sequence's
offload holds the engine thread for (digests, keys, page gathers,
device to host, copies into the pool, sync), which offload_gbps' spans
around put_kv_pages see only in part.

Moves itl_mean_ms: every decoding slot waits it out.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def value(obs, spans):
    return program_spans.p50_ms(
        s.dur_ns for s in program_spans.started_in_window(
            obs, spans, "istpu.cache.offload")
        if s.fields.get("reason") == "finish")


def read(obs):
    return program_spans.read(obs, value)
