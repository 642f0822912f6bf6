"""FLOPs the index scores of the traced seconds' admission programs
need (`index_prefill_flops` of the configuration's costs module per
program call, from its istpu.model.prefill span: every causal pair of
the tokens it prefilled over the pages it attended, 8,192 FLOPs a pair
in each layer that owns an indexer) over the published bf16 peak and
the device time under the `attn.index` scope in the admission
programs: the indexer's projections and the scores of a block of
queries against every key it may see.

Moves itl_mean_ms: a piece of a cold prompt and a hit's tail stall
every decoding slot, and the scores are the part of both that grows
with the square of the context.
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
SCOPES = ("attn.index",)
COST = "index_prefill_flops"


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, COST):
        return None
    found = _scoped_ops.seconds(obs, "prefill", SCOPES)
    flops = needed(obs, program_spans.ring(obs), getattr(costs, COST))
    if found is None or not flops:
        return None
    return mfu(flops, obs.peaks["bf16_flops_per_s"], found[0])
