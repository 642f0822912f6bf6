"""Host time of a plain decode step, median: over the program's
istpu.engine.step spans of kind `decode` that started in the window
and hold no admission and no offload, the step's duration less its
istpu.model.decode child (dispatch of the decode program to the token
array on the host). What is left is the scheduler's own work: building
and uploading inputs, page bookkeeping, emitting tokens.

Moves itl_mean_ms: it is paid between every two tokens.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"

OTHER_WORK = ("istpu.sched.admit", "istpu.cache.offload")


def value(obs, spans):
    steps = {s.id: s for s in program_spans.started_in_window(
        obs, spans, "istpu.engine.step")
        if s.fields.get("kind") == "decode"}
    device_ns = dict.fromkeys(steps, 0)
    for s in spans:  # children may start after the window's end
        if s.parent not in steps:
            continue
        if s.name in OTHER_WORK:
            device_ns[s.parent] = None
        elif s.name == "istpu.model.decode" \
                and device_ns[s.parent] is not None:
            device_ns[s.parent] += s.dur_ns
    return program_spans.p50_ms(
        steps[i].dur_ns - d for i, d in device_ns.items() if d is not None)


def read(obs):
    return program_spans.read(obs, value)
