"""Of the slots a decode step's learned selection ran over, the share
that held a sequence: `select_rows_active` over `select_rows_run`,
summed over the istpu.model.decode spans that started in the window.
`select_rows_run` is the device's count of the slots the three stages
of the selection (index scores, top-k, the gather with its attention)
ran over in that step: the least of 1, 2, 4, ... `max_slots` that holds
the decoding sequences, pulled with the step's tokens and written on
the span the step lands in beside `select_rows_active`, the sequences
the step decoded. 100 % says no slot was scored, sorted or gathered
for but a decoding one; a selection over every slot of 8 with 1.6
decoding reads 20 %. A program without these fields (the selection
over every slot whatever is decoding) gives nothing.

Moves itl_mean_ms: it is the factor by which the selection's three
stages in a decode step exceed what the decoding sequences need.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"
SPAN = "istpu.model.decode"


def value(obs, spans):
    active = run = 0
    for s in program_spans.started_in_window(obs, spans, SPAN):
        active += s.fields.get("select_rows_active", 0)
        run += s.fields.get("select_rows_run", 0)
    return 100.0 * active / run if run else None


def read(obs):
    return program_spans.read(obs, value)
