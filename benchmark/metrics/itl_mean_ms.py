"""Mean gap between consecutive streamed tokens of one request, over all
gaps that end in the window: the time a streamed token takes, every
stall (an admission, a restore, an offload on the one engine thread)
weighed by its length. A mean over all the work of the window: unlike a
percentile it has no edge to stand on (itl_p95_ms of mistral7b-sessions
stood where the stalls' share of the gaps crosses 5 %, PERF.md section
2).
"""

KIND = "end_to_end"
LAYER = None
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = None


def read(obs):
    gaps = obs.gaps_ms()
    return sum(gaps) / len(gaps) if gaps else None
