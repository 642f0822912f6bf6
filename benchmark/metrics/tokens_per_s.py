"""Output tokens streamed inside the window per second of window, per chip:
all the work and all the time of the window.
"""

KIND = "end_to_end"
LAYER = None
UNIT = "tokens/s"
BETTER = "higher"
SOURCE = "host_clock"
MOVES = None


def read(obs):
    n = obs.tokens_in_window()
    return n / obs.seconds / obs.chips if n else None
