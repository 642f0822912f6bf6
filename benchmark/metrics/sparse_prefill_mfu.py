"""FLOPs the attention of the traced seconds' admission programs needs
over the pairs the selection LEAVES (`sparse_prefill_flops` of the
configuration's costs module per program call, from its
istpu.model.prefill span: each of the tokens it prefilled attends
min(keys it may see, topk) rows of the pages it attended, 4 x head_dim
FLOPs a query head a pair a layer) over the published bf16 peak and the
device time under the `attn.kernel`, `attn.gather` and `attn.mask`
scopes in the admission programs: the attention under the selection in
whatever form the program gives it (every row of prefix and suffix
under the selection's mask, or the selected rows gathered), with what
builds the mask or gathers the rows counted as its time. How far an
admission's attention is from what the selection leaves: a program
that attends all 35k rows under a mask does 17 x the FLOPs counted
here and cannot pass 6 %.

A configuration whose costs module has no such count (every other
family's, and the parent's) reads nothing.

Moves itl_mean_ms: a piece of a cold prompt and a hit's tail stall
every decoding slot.
"""

from benchmark.lib import program_spans, serve
from benchmark.metrics import _scoped_ops
from benchmark.metrics.latent_prefill_mfu import needed
from benchmark.metrics.moe_prefill_mfu import mfu

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel", "attn.gather", "attn.mask")
COST = "sparse_prefill_flops"


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, COST):
        return None
    found = _scoped_ops.seconds(obs, "prefill", SCOPES)
    flops = needed(obs, program_spans.ring(obs), getattr(costs, COST))
    if found is None or not flops:
        return None
    return mfu(flops, obs.peaks["bf16_flops_per_s"], found[0])
