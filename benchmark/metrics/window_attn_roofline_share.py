"""Least time the banded layers' attention could take in one decode step
on this chip - the K and V it must read (the band of every active
sequence, in each banded layer: `window_attn_bytes` of the
configuration's costs module) over the published HBM bandwidth - as a
share of the device time of the operations under the
`attn.kernel.window` scope in one run of the decode program. The
kernel is a Pallas program whose transfers are its own, so its time
holds them.

Beside `full_attn_roofline_share` it says what a step of the kernel's
grid costs by table length: the banded layers walk a short table
(band / page + 1 live entries), the full layers the whole page table.
A model whose attention layers are of one kind has no such scope, and
the reader finds nothing.

Moves itl_mean_ms: attention is the larger part of a decode step at
these context lengths.
"""

from benchmark.lib import serve, stats
from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel.window",)
COST = "window_attn_bytes"


def share(need_bytes, hbm_bytes_per_s, scoped_s, runs, programs_s=None):
    return 100.0 * (need_bytes / hbm_bytes_per_s) / (scoped_s / runs)


def read_kind(obs, cost, scopes, of=lambda s: (s.active, s.live_tokens)):
    """The share for one stage of the decode program: `cost` names the
    costs module's bytes(conf, *of(step)) at the median traced step,
    `scopes` the stage's operations."""
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, cost):
        return None
    found = _scoped_ops.seconds(obs, "decode", scopes)
    steps = [s for s in obs.steps_traced() if s.moved["decode_steps"] > 0]
    if found is None or not steps:
        return None
    need = stats.quantile(
        [getattr(costs, cost)(obs.conf, *of(s)) for s in steps], 0.50)
    return share(need, obs.peaks["hbm_bytes_per_s"], *found)


def read(obs):
    return read_kind(obs, COST, SCOPES)
