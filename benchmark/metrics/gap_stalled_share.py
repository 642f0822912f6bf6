"""Of the gaps between two tokens of one request in the window, the share
in which the engine thread ran anything but decode steps and its loop:
another request's admission or piece, or an offload (ServingEngine.stats
`gaps_stalled` / `gap_tokens`; _gap_by_cause.py). How OFTEN a token is
late for such a cause; the gap_*_ms parts say by how much a token.

Moves itl_mean_ms: the stalled gaps are its second population.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    share = _gap_by_cause.per_gap(obs, "gaps_stalled")
    return None if share is None else 100.0 * share
