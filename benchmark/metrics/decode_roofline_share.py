"""Least time a decode step could take on this chip - the bytes it must
read (weights, for a sparse model the experts its tokens touch, and the
live cache: decode_bytes of the configuration's costs module, lib/costs.py
unless its file names another) over the published HBM bandwidth - as a
share of decode_step_ms. At 16 slots decode is bound by bytes, not
FLOPs.
"""

from benchmark.lib import serve, stats, trace

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    if obs.trace is None or obs.peaks is None:
        return None
    t = trace.times_of(obs, "decode")
    steps = [s for s in obs.steps_traced() if s.moved["decode_steps"] > 0]
    if not t or not steps:
        return None
    page = obs.conf["serving"]["page_size"]
    costs = serve.costs_module(obs.conf)
    need = sorted(
        costs.decode_bytes(obs.conf, s.active, s.live_tokens, page)
        for s in steps)
    least = need[len(need) // 2] / obs.peaks["hbm_bytes_per_s"]
    return 100.0 * least / stats.quantile(t, 0.50)
