"""Of the mean gap between tokens (gap_engine_mean_ms), the ms the engine
thread spent under `istpu.sched.admit_piece`: a piece of another
request's long prompt (its gather of the pages held so far and its
program), run between two decode steps (_gap_by_cause.py). 0.0 in a
window without a piece.

Moves itl_mean_ms: admit_piece_p50_ms times how many gaps met one.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return _gap_by_cause.ms_per_token(obs, "gap_ns_admit_piece")
