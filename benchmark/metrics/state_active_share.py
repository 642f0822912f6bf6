"""Of the slots whose recurrent state a decode step moved, the share
that held a sequence: `state_rows_active` over `state_rows_run`, summed
over the istpu.model.decode spans that started in the window. The
engine writes both on the span a step lands in: `state_rows_active`,
the sequences the step decoded, and `state_rows_run`, the slots whose
state (`h` and the convolution's tail, every state layer) its program
read, advanced and wrote back.

Under the form the tree keeps (`ops/ssm.py` `step_kernel`: the grid's
first bound IS the count of decoding slots) the two are one number, and
this reads 100 % BY CONSTRUCTION: it cannot move, and it does not show
that no other row was touched (the compiled program's text and the
pools' rows do: tests/test_model.py, tests/test_hybrid_state.py). What
it tells is this program from one without the fields (the update over
every slot whatever is decoding: 22 % at 3.5 of 16, were it counted),
which gives nothing; a form that runs a rung of slots for the
sequences it holds would read between the two.

Moves itl_mean_ms: it is the factor by which the state's traffic in a
decode step exceeds what the decoding sequences need.
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
        active += s.fields.get("state_rows_active", 0)
        run += s.fields.get("state_rows_run", 0)
    return 100.0 * active / run if run else None


def read(obs):
    return program_spans.read(obs, value)
