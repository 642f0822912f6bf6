"""FLOPs the expert blocks of the traced seconds' admissions need
(`moe_prefill_flops` of the configuration's costs module per admission,
over the real tokens its istpu.model.prefill span says it prefilled:
the chosen experts only, and the router) over the published bf16 peak
and the device time of the operations under the `moe.` scopes in the
admission programs: the grouped matmul's share of its roofline, with
the sort and the gathers around it counted as its time.

Moves itl_mean_ms: every admission stalls all decoding slots, and the
experts are the larger part of a long prefill's matmuls.
"""

from benchmark.lib import program_spans, serve
from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("moe.",)


def mfu(flops, flops_per_s, seconds):
    return 100.0 * flops / flops_per_s / seconds


def needed_flops(obs, spans, costs):
    """Over the admissions that started in the traced seconds."""
    if obs.trace_window is None:
        return 0
    t0, t1 = (t * 1e9 for t in obs.trace_window)
    return sum(costs.moe_prefill_flops(obs.conf, s.fields["tokens"])
               for s in spans if s.name == "istpu.model.prefill"
               and t0 <= s.t0_ns < t1)


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, "moe_prefill_flops"):
        return None
    found = _scoped_ops.seconds(obs, "prefill", SCOPES)
    spans = program_spans.ring(obs)
    if found is None or spans is None:
        return None
    flops = needed_flops(obs, spans, costs)
    if not flops:
        return None
    return mfu(flops, obs.peaks["bf16_flops_per_s"], found[0])
