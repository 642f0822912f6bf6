"""Device time of the cold- and prefix-prefill programs in the traced
seconds over the thousands of prompt tokens the engine prefilled in them
(prefill_tokens moved by the steps that started there).

Moves itl_mean_ms: every admission (probe, restore, prefill) runs on the
one engine thread and stalls all decoding slots. Where ttft_p50_ms is an
end-to-end metric of the cell, it moves that too.
"""

from benchmark.lib import trace

KIND = "per_layer"
LAYER = "Model step"
UNIT = "ms/ktok"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    if obs.trace is None:
        return None
    t = sum(trace.times_of(obs, "prefill"))
    toks = sum(s.moved["prefill_tokens"] for s in obs.steps_traced())
    return t * 1e3 / (toks / 1e3) if t and toks else None
