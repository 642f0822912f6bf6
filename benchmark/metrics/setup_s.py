"""Process start to window start: the native build where the library is
missing, store, weights, warm-up of every shape (compilation, in a first
run), the correctness sample and the ramp.
"""

KIND = "end_to_end"
LAYER = None
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(obs):
    return obs.setup_s
