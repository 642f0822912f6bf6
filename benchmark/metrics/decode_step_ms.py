"""Median device time of the fused decode program's runs in the traced
seconds (XLA Modules line, module name contains decode_fused).
"""

from benchmark.lib import stats, trace

KIND = "per_layer"
LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(obs):
    if obs.trace is None:
        return None
    t = trace.program_times(obs.trace, "decode_fused")
    return stats.quantile(t, 0.50) * 1e3 if t else None
