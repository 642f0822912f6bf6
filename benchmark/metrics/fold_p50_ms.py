"""Median of the program's istpu.cache.fold spans that started in the
window: what ONE fold holds the engine thread for (the dispatch of the
fold program behind the program that wrote the window's last row, and
the slot's table rewritten; the program's own time on the device is
`fold_roofline_share`'s and delays the next decode step, where it
reads as `gap_step_ms`). A program that folds nothing records no such
span.

Moves itl_mean_ms: it runs on the one engine thread between two decode
steps.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.cache.fold"


def value(obs, spans):
    return program_spans.p50_ms(
        s.dur_ns for s in program_spans.started_in_window(obs, spans, SPAN))


def read(obs):
    return program_spans.read(obs, value)
