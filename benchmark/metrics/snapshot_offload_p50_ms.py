"""Median of the program's istpu.cache.state_out spans, inside finish
offloads, that started in the window: the snapshot's part of a finish
offload (one gather program, then a device-to-host transfer and a store
batch a chunk of at most 16 MiB).

Moves itl_mean_ms: a finish offload runs on the one engine thread
between two decode steps.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.cache.state_out"


def value(obs, spans):
    finish = {s.id for s in program_spans.started_in_window(
        obs, spans, "istpu.cache.offload")
        if s.fields.get("reason") == "finish"}
    return program_spans.p50_ms(
        s.dur_ns for s in program_spans.started_in_window(obs, spans, SPAN)
        if s.parent in finish)


def read(obs):
    return program_spans.read(obs, value)
