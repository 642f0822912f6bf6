"""Median of the program's istpu.sched.queue_wait spans that started in
the window: a request's arrival (the top of the HTTP handler) to the
start of the admission that took it, and the wait of re-queued work
after a preemption.

Moves ttft_p50_ms: the part of a first token's time in which nothing
is done for the request.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "ttft_p50_ms"


def value(obs, spans):
    waits = program_spans.started_in_window(
        obs, spans, "istpu.sched.queue_wait")
    return program_spans.p50_ms(s.dur_ns for s in waits)


def read(obs):
    return program_spans.read(obs, value)
