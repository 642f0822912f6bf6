"""`window_attn_roofline_share` for the FULL attention layers of a model
that has both kinds: the K and V of every live token of the active
sequences, in each full layer (`full_attn_bytes` of the configuration's
costs module), over the published HBM bandwidth, as a share of the
device time under the `attn.kernel.full` scope in one run of the decode
program. The full layers walk the whole page table (max_pages_per_seq
entries a sequence, live or not); the two shares side by side are the
grid's cost by table length (ROADMAP S5).

Moves itl_mean_ms.
"""

from benchmark.metrics import window_attn_roofline_share as kind

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel.full",)
COST = "full_attn_bytes"


def read(obs):
    return kind.read_kind(obs, COST, SCOPES)
