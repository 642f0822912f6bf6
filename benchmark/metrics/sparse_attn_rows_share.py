"""Of the cache rows live in the window's decode steps, the share their
attention read: `rows_selected` over `rows_live`, summed over the
istpu.model.decode spans that started in the window, over layers and
active sequences. `rows_selected` is the device's count of the rows its
selections took (summed inside the decode program, pulled with the
step's tokens, and written on the span the step lands in: a window's
two edges may differ by one step); `rows_live` is the lengths the
engine holds. Under a learned selection of `index_topk` rows a
sequence it is index_topk over the mean live length (5.8-12.5 % at
16-35k tokens); 100 % would say the selection is not taken. A program
without these fields gives nothing.

Moves itl_mean_ms: it is the factor by which the selection cuts a
decode step's cache rows.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Model step"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.model.decode"


def value(obs, spans):
    taken = live = 0
    for s in program_spans.started_in_window(obs, spans, SPAN):
        taken += s.fields.get("rows_selected", 0)
        live += s.fields.get("rows_live", 0)
    return 100.0 * taken / live if live else None


def read(obs):
    return program_spans.read(obs, value)
