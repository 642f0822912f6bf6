"""Median device time of the decode program's runs in the traced seconds
(XLA Modules line; module names as the configuration gives them,
decode_fused unless its file says otherwise).
"""

from benchmark.lib import stats, trace

KIND = "per_layer"
LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    if obs.trace is None:
        return None
    t = trace.times_of(obs, "decode")
    return stats.quantile(t, 0.50) * 1e3 if t else None
