"""95th percentile of the gap between consecutive streamed tokens of one
request, over all gaps that end in the window: what itl_p95_ms reads,
reported per layer, from the traced run, in the cell where it is too
unsteady to carry a bound (mistral7b-sessions: stalls are about 5 % of
the gaps, so the percentile falls among the plain steps in one run and
among the stalls in the next; PERF.md section 2).

Moves itl_mean_ms: the stalls it reads are the part of the mean that is
not a plain step.
"""

from benchmark.lib import stats

KIND = "per_layer"
LAYER = "HTTP edge"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "itl_mean_ms"


def read(obs):
    return stats.quantile(obs.gaps_ms(), 0.95)
