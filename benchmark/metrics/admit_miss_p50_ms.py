"""Median of the program's istpu.sched.admit spans that started in the
window, were admitted and had no hit page: the probe and the cold
prefill of the whole prompt.

Moves itl_mean_ms, as admit_hit_p50_ms does.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def value(obs, spans):
    return program_spans.p50_ms(program_spans.admitted_ns(obs, spans, False))


def read(obs):
    return program_spans.read(obs, value)
