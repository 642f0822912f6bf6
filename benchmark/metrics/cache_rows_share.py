"""Of the positions the window's decode steps stood for, the cache rows
their tables held: the engine's counters `attn_rows_read` over
`attn_positions_live` (rows and positions of every decode step's
sequences, over the layers), window delta. Over a folded cache a
sequence at position p in window w holds 128 w + (p - 2,048 w) rows:
10-20 % of its positions 6 to 14 windows deep; 100 % would say nothing
folded. A program without the counters (every other family's: rows are
positions there; the parent) reads nothing.

Moves itl_mean_ms: it is the factor by which the fold cuts a decode
step's cache rows.
"""

KIND = "per_layer"
LAYER = "Model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    rows = obs.counters.get("attn_rows_read", 0)
    live = obs.counters.get("attn_positions_live", 0)
    return 100.0 * rows / live if live else None
