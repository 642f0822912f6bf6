"""Plain decode steps whose program was dispatched before the step
before it had landed (ServingEngine.stats `decode_steps_ahead`), of all
the decode steps that landed in the window (`decode_steps`): how often
the engine's run ahead engages. What breaks it is a change of the
active set (an admission, a finish), a sampling slot, a pool that is
out. A program without the counter reads 0.

Moves itl_mean_ms: a step that ran ahead pays no dispatch lead and no
return lag on the device between two tokens.
"""

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "itl_mean_ms"


def read(obs):
    steps = obs.counters.get("decode_steps", 0)
    if not steps:
        return None
    return 100.0 * obs.counters.get("decode_steps_ahead", 0) / steps
