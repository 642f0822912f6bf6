"""The mean gap between two tokens of one request by the ENGINE's clock,
over the whole window: `gap_ns` / `gap_tokens` of ServingEngine.stats,
counted where the tokens are emitted (`_emit`). It telescopes to the
same first and last token of each request as the client's itl_mean_ms;
what the two differ by is the HTTP edge's. The six gap_*_ms parts
(_gap_by_cause.py) sum to it.

Moves itl_mean_ms: it is that metric, seen from inside.
"""

from benchmark.metrics import _gap_by_cause

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    return _gap_by_cause.ms_per_token(obs, "gap_ns")
