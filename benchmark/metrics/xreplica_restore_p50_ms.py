"""Median of the program's istpu.cache.restore spans (the store call of
a hit: pin, copy out of the pool, host to device) inside the window's
admissions that restored pages another replica wrote: what one read
through the four-client store costs its engine thread.

A program whose spans carry no `foreign_pages` gives nothing.

Moves itl_mean_ms: it is part of the admission every decoding slot of
the replica waits out.
"""

from benchmark.lib import program_spans
from benchmark.metrics import xreplica_admit_hit_p50_ms

KIND = "per_layer"
LAYER = "Store client and server"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"


def value(obs, spans):
    admits = {s.id for s in
              xreplica_admit_hit_p50_ms.foreign_admissions(obs, spans)}
    return program_spans.p50_ms(
        s.dur_ns for s in spans  # a child may start after the window
        if s.name == "istpu.cache.restore" and s.parent in admits)


def read(obs):
    return program_spans.read(obs, value)
