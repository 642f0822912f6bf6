"""FLOPs the latent attention of the traced seconds' admission programs
needs (`latent_prefill_flops` of the configuration's costs module per
program call, from its istpu.model.prefill span: the tokens it
prefilled over the pages it attended, scores and weighted values at
192 + 128 values a head a pair, and K and V of every head built from
the rows of prefix and suffix) over the published bf16 peak and the
device time under the `attn.kernel` and `attn.expand` scopes in the
admission programs: the flash kernel at a value width that is not its
key width, with the expansion that feeds it counted as its time.

Moves itl_mean_ms: a piece of a cold prompt and a hit's tail stall
every decoding slot, and over a 16-33k prefix attention is the larger
part of both.
"""

from benchmark.lib import program_spans, serve
from benchmark.metrics import _scoped_ops
from benchmark.metrics.moe_prefill_mfu import mfu

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel", "attn.expand")
COST = "latent_prefill_flops"


def prefills_traced(obs, spans):
    """The istpu.model.prefill spans that started in the traced
    seconds."""
    if obs.trace_window is None or spans is None:
        return []
    t0, t1 = (t * 1e9 for t in obs.trace_window)
    return [s for s in spans if s.name == "istpu.model.prefill"
            and t0 <= s.t0_ns < t1]


def needed(obs, spans, cost):
    page = obs.conf["serving"]["page_size"]
    return sum(cost(obs.conf, s.fields["tokens"],
                    s.fields.get("restored_pages", 0) * page)
               for s in prefills_traced(obs, spans))


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, COST):
        return None
    found = _scoped_ops.seconds(obs, "prefill", SCOPES)
    flops = needed(obs, program_spans.ring(obs), getattr(costs, COST))
    if found is None or not flops:
        return None
    return mfu(flops, obs.peaks["bf16_flops_per_s"], found[0])
