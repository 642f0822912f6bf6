"""Of the rows the expert matmuls of the window's admission programs
ran, the share that were pairs held on this chip: `pairs_held` over
`rows_computed`, summed over the istpu.model.prefill spans that started
in the window (both counted by the program, summed over its layers). A
chip that holds a share of its layers' experts runs them over a STATIC
number of rows a program: this says what the chosen shapes waste. A
long cold prompt runs passes of 1.25 x the expected held pairs in whole
tiles of 512 rows (near 79 %); a hit's short suffix runs every token
through every held expert (held pairs / tokens x experts held, 6.25 %
under even routing), because there the weights' read is the cost and
not the rows. A program without these fields gives nothing.

Moves itl_mean_ms: an admission's program stalls every decoding slot.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Model step"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.model.prefill"


def value(obs, spans):
    held = rows = 0
    for s in program_spans.started_in_window(obs, spans, SPAN):
        held += s.fields.get("pairs_held", 0)
        rows += s.fields.get("rows_computed", 0)
    return 100.0 * held / rows if rows else None


def read(obs):
    return program_spans.read(obs, value)
