"""Median of the program's istpu.cache.offload spans of reason "window"
that started in the window: one batch of banded layers' pages that left
their band during decode, over every slot whose short table was full at
that step, written to the store (one gather, chunks of at most 16 MiB,
one sync) before their pool pages are freed.

Moves itl_mean_ms: it runs on the one engine thread between two decode
steps. A model with one kind of attention layer records no such span.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.cache.offload"


def value(obs, spans):
    return program_spans.p50_ms(
        s.dur_ns for s in program_spans.started_in_window(obs, spans, SPAN)
        if s.fields.get("reason") == "window" and "slots" in s.fields)


def read(obs):
    return program_spans.read(obs, value)
