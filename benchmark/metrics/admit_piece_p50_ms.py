"""Median of the program's istpu.sched.admit_piece spans that started in
the window: one piece of an admission in pieces (ServingConfig.
admit_piece tokens of a long prompt, or its tail), one program call
over the pages the slot holds so far, with the gather of those pages
and the row pull.

Moves itl_mean_ms: a piece runs on the one engine thread between two
decode steps, so it is the stall one piece puts into every decoding
slot's gap. An engine that admits in one program records no such span.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.sched.admit_piece"


def value(obs, spans):
    return program_spans.p50_ms(
        s.dur_ns for s in program_spans.started_in_window(obs, spans, SPAN))


def read(obs):
    return program_spans.read(obs, value)
