"""Median of the program's istpu.cache.state_in spans that started in
the window: the way in of a hit's state snapshot, store to HBM (its
placement into the slot is inside the one hit program and costs no
dispatch of its own).

Moves itl_mean_ms: the call runs on the one engine thread, inside the
admission every decoding slot waits for.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.cache.state_in"


def value(obs, spans):
    return program_spans.p50_ms(
        s.dur_ns for s in program_spans.started_in_window(obs, spans, SPAN))


def read(obs):
    return program_spans.read(obs, value)
