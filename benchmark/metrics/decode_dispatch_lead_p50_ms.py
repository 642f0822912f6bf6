"""From the start of a plain decode step to the start of its decode
program ON THE DEVICE, median: over the traced window's
istpu.engine.step spans of kind `decode` without admission or offload
(as decode_host_p50_ms picks them), each matched to the run of the
decode program (lib/serve.program_names) that starts inside the step's
istpu.model.decode on its engine's device plane, the ring's spans
shifted onto the trace's clock (_idle_by_span.py). The device is idle
for all of it: building and uploading the inputs, the dispatch, and the
runtime's way to the chip.

Moves itl_mean_ms: it is paid before every token.
"""

from benchmark.metrics import _idle_by_span

KIND = "per_layer"
LAYER = "Model step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"


def read(obs):
    return _idle_by_span.p50_ms(obs, "lead_ns")
