"""Of the traced WINDOW, the share in which the device is idle although
the engine has work: all idle time that does not lie under an
istpu.engine.no_work span (_idle_by_span.py; `idle_by_program_span` on
the log names its parts: the store write of an offload, the dispatch of
a step, the wait's return, the loop between steps). What the host's
work on the engine thread holds the device back by, and so the most
that moving it off that thread or shortening it can win.

Moves itl_mean_ms: every decoding slot waits through it.
"""

from benchmark.metrics import _idle_by_span

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    found = _idle_by_span.joined(obs)
    if found is None or not found["loop_spans"] or not found["window_s"] > 0:
        return None
    held = found["idle_s"] - found["idle_by"].get("no_work", 0.0)
    return 100.0 * held / found["window_s"]
