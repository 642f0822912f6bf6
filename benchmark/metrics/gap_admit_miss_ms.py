"""Of the mean gap between tokens (gap_engine_mean_ms), the ms the engine
thread spent under `istpu.sched.admit` spans that closed WITHOUT hit
pages: another request's cold admission, whole (its probe, its program,
a retry for want of pages), which every decoding slot waits out
(_gap_by_cause.py). A request's own admission is in none of its gaps.

Moves itl_mean_ms: admit_miss_p50_ms times how many gaps met one.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return _gap_by_cause.ms_per_token(obs, "gap_ns_admit_miss")
