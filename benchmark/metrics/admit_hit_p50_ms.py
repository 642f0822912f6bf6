"""Median of the program's istpu.sched.admit spans that started in the
window, were admitted and had hit pages: probe, restore, pages_to_kv,
pool write and the prefix prefill of one prefix hit.

Moves itl_mean_ms: an admission runs on the one engine thread, so every
decoding slot sees it as a gap between two tokens.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def value(obs, spans):
    return program_spans.p50_ms(program_spans.admitted_ns(obs, spans, True))


def read(obs):
    return program_spans.read(obs, value)
