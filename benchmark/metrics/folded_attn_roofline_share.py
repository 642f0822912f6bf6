"""Least time the attention of one decode step could take over a FOLDED
cache on this chip - the cache rows the step's tables held (the
`cache_rows` the engine writes on its istpu.model.decode spans: the
summary rows of every finished window and the exact rows of the window
each sequence is in, summed over the step's sequences; the median
traced step's) x K and V of 32 heads x the layers
(`folded_attn_bytes` of the configuration's costs module), over the
published HBM bandwidth - as a share of the device time of the
operations under the `attn.kernel` scope in one run of the decode
program: the bf16 paged-decode kernel at a head group of ONE (32 query
rows a sequence) over a table of rows.

A program without the field (every other family's, and the parent's)
or a configuration whose costs module has no such count reads nothing.

Moves itl_mean_ms: at 2,300-3,800 rows a sequence the cache is a third
to a half of a decode step's bytes.
"""

from benchmark.lib import program_spans, serve, stats
from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel",)
COST = "folded_attn_bytes"
SPAN = "istpu.model.decode"


def traced_rows(obs, spans):
    """`cache_rows` of the decode spans that started in the traced
    seconds."""
    if obs.trace_window is None:
        return []
    t0, t1 = (t * 1e9 for t in obs.trace_window)
    return [s.fields["cache_rows"] for s in spans
            if s.name == SPAN and "cache_rows" in s.fields
            and t0 <= s.t0_ns < t1]


def share(need_bytes, hbm_bytes_per_s, scoped_s, runs, programs_s=None):
    return 100.0 * (need_bytes / hbm_bytes_per_s) / (scoped_s / runs)


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, COST):
        return None
    found = _scoped_ops.seconds(obs, "decode", SCOPES)
    spans = program_spans.ring(obs)
    if found is None or spans is None:
        return None
    rows = traced_rows(obs, spans)
    if not rows:
        return None
    need = getattr(costs, COST)(obs.conf, stats.quantile(rows, 0.50))
    return share(need, obs.peaks["hbm_bytes_per_s"], *found)
