"""decoded_tokens over decode_steps x max_slots, window delta: how full the
fixed decode batch ran.
"""

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(obs):
    steps = obs.counters.get("decode_steps", 0)
    if not steps:
        return None
    decoded = obs.counters.get("decoded_tokens", 0)
    return 100.0 * decoded / (steps * obs.max_slots)
