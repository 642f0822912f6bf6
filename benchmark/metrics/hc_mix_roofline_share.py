"""Least time the residual path of the traced seconds' admission
programs could take on this chip - the bytes it must move
(`hc_prefill_bytes` of the configuration's costs module per program
call, over the tokens its istpu.model.prefill span says it prefilled:
around each sublayer the n streams read once for the coefficients and
the sublayer's input, read and written once for the mix) over the
published HBM bandwidth - as a share of the device time under the
`hc.` scopes (hc.coef, hc.mix) in the admission programs.

A family with one residual stream traces no such scope and its costs
module has no such count: nothing is read.

Moves itl_mean_ms: four streams make the residual path 12 stream reads
and writes a sublayer where one stream has an add, in the programs
that stall every decoding slot.
"""

from benchmark.lib import program_spans, serve
from benchmark.metrics import _scoped_ops
from benchmark.metrics.latent_prefill_mfu import prefills_traced

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("hc.",)
COST = "hc_prefill_bytes"


def share(need_bytes, hbm_bytes_per_s, scoped_s):
    return 100.0 * need_bytes / hbm_bytes_per_s / scoped_s


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, COST):
        return None
    found = _scoped_ops.seconds(obs, "prefill", SCOPES)
    need = sum(getattr(costs, COST)(obs.conf, s.fields["tokens"])
               for s in prefills_traced(obs, program_spans.ring(obs)))
    if found is None or not need:
        return None
    return share(need, obs.peaks["hbm_bytes_per_s"], found[0])
