"""How long a stalled gap is, median over the slots that waited it out:
over the program's `istpu.model.decode` spans under which a plain step
LANDED in the window (they carry `waiting`: the slots that emitted
there and at the land before), the interval between the end of one and
the end of the one before it on the same engine, for those that carry
`stall_ns` (another cause than a step ran between the two lands),
each counted `waiting` times. gap_stalled_share says how often.

Moves itl_mean_ms: the stalled gaps are its second population.
"""

from benchmark.lib import program_spans

KIND = "per_layer"
LAYER = "Scheduler and cache manager"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
MOVES = "itl_mean_ms"
SPAN = "istpu.model.decode"


def value(obs, spans):
    lands = [s for s in spans if s.name == SPAN and "waiting" in s.fields]
    lands.sort(key=lambda s: s.t0_ns + s.dur_ns)
    w0_ns, w1_ns = obs.window[0] * 1e9, obs.window[1] * 1e9
    ended, gaps_ns = {}, []
    for s in lands:
        end = s.t0_ns + s.dur_ns
        before = ended.get(s.engine)
        ended[s.engine] = end
        if before is not None and "stall_ns" in s.fields \
                and w0_ns <= s.t0_ns < w1_ns:
            gaps_ns += [end - before] * s.fields["waiting"]
    return program_spans.p50_ms(gaps_ns)


def read(obs):
    return program_spans.read(obs, value)
