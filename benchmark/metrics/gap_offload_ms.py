"""Of the mean gap between tokens (gap_engine_mean_ms), the ms the engine
thread spent under `istpu.cache.offload` spans that lay in no
admission: the ENGINE thread's part of a finish's, a window's or a
preemption's offload (digests, gathers dispatched, transfers started,
a wait for room under the upload cap), which the slots that go on
decoding wait out (_gap_by_cause.py). The upload thread's part counts
nowhere here.

Moves itl_mean_ms: offload_stall_p50_ms times how many gaps met one.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return _gap_by_cause.ms_per_token(obs, "gap_ns_offload")
