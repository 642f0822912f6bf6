"""FLOPs the chunked scans of the traced seconds' prefills need
(`ssm_scan_flops` of the configuration's costs module per admission,
over the real tokens its istpu.model.prefill span says it prefilled)
over the published bf16 peak and the device time of the operations
under the `ssm.scan` scope in the admission programs.

Moves itl_mean_ms: every admission stalls all decoding slots, and the
scan runs in 36 of this configuration's 40 layers.
"""

from benchmark.lib import program_spans, serve
from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("ssm.scan",)


def mfu(flops, flops_per_s, seconds):
    return 100.0 * flops / flops_per_s / seconds


def needed_flops(obs, spans, costs):
    """Over the admissions that started in the traced seconds."""
    if obs.trace_window is None:
        return 0
    t0, t1 = (t * 1e9 for t in obs.trace_window)
    return sum(costs.ssm_scan_flops(obs.conf, s.fields["tokens"])
               for s in spans if s.name == "istpu.model.prefill"
               and t0 <= s.t0_ns < t1)


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, "ssm_scan_flops"):
        return None
    found = _scoped_ops.seconds(obs, "prefill", SCOPES)
    spans = program_spans.ring(obs)
    if found is None or spans is None:
        return None
    flops = needed_flops(obs, spans, costs)
    if not flops:
        return None
    return mfu(flops, obs.peaks["bf16_flops_per_s"], found[0])
