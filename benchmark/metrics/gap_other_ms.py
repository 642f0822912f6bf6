"""Of the mean gap between tokens (gap_engine_mean_ms), the ms the engine
thread spent under NONE of the five causes (_gap_by_cause.py): the
serving loop between two steps (delivery to the handlers, a submit, a
finish's bookkeeping, acknowledgements collected), the step's own host
work outside its decode span, and the interpreter lock held by a
handler or the upload thread.

Moves itl_mean_ms: it is paid between every two tokens.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "HTTP edge"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return _gap_by_cause.other_ms(obs)
