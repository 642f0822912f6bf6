"""How late the host's wait for a decode step comes back, median: over
the same steps as decode_dispatch_lead_p50_ms, from the end of the LAST
program of the engine's device plane that started inside the step's
istpu.model.decode (the decode program; for a family with state the
boundary copies run behind it in the same span) to the end of that
span, where the tokens are on the host. The host's slow mode (PERF.md,
PR 29: every wait some 2.5 ms late for seconds) is this number at 2 or
more; its p95 goes on the log beside it.

Moves itl_mean_ms: it is paid after every token.
"""

import json

from benchmark.lib import program_spans
from benchmark.metrics import _idle_by_span

KIND = "per_layer"
LAYER = "Device and host transfer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    found = _idle_by_span.joined(obs)
    if found is None or not found["lag_ns"]:
        return None
    p50 = program_spans.p50_ms(found["lag_ns"])
    print("decode_return_lag: " + json.dumps({
        "steps": len(found["lag_ns"]), "p50_ms": p50,
        "p95_ms": _idle_by_span.p95_ms(found["lag_ns"])}), flush=True)
    return p50
