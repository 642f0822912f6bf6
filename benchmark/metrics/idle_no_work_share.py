"""Of the traced window's device idle time, the share that lies under
the program's istpu.engine.no_work spans: the engine had nothing to
step (no request queued, no slot taken), so nothing the program does
faster would fill it. The idle gaps are those of lib/trace.py's busy /
idle; each is split among the engine thread's spans laid on the trace's
clock (_idle_by_span.py), mean over the device planes, each against its
own engine.

Moves itl_mean_ms only by what it takes away from the idle share that
could: a cell whose idle time is mostly this measures latency at a low
load, not throughput.
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
    # A program without the loop's spans (a parent commit) cannot say.
    if found is None or not found["loop_spans"] or not found["idle_s"] > 0:
        return None
    return 100.0 * found["idle_by"].get("no_work", 0.0) / found["idle_s"]
