"""Of the mean gap between tokens (gap_engine_mean_ms), the ms the engine
thread spent under `istpu.model.decode`: dispatching decode programs
and waiting for their tokens, whatever the program (_gap_by_cause.py).
Where the engine runs one step ahead this is the step's device time
plus the host's part that the run ahead does not hide.

Moves itl_mean_ms: every gap holds at least one step.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return _gap_by_cause.ms_per_token(obs, "gap_ns_step")
