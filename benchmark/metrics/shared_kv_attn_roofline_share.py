"""`full_attn_roofline_share` for the layers that attend ANOTHER layer's
whole-context cache (models/phi_flash.py's cross layers: queries of
their own, the K and V pages of the one layer that owns them): the K
and V of every live token of the active sequences, read once by each
such layer (`shared_kv_attn_bytes` of the configuration's costs
module), over the published HBM bandwidth, as a share of the device
time under the `attn.kernel.cross` scope in one run of the decode
program. A program without that scope (every other family; a parent
commit) gives nothing.

Moves itl_mean_ms: at 11k live tokens a sequence the borrowed cache is
the largest part of a decode step after the weights.
"""

from benchmark.metrics import window_attn_roofline_share as kind

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel.cross",)
COST = "shared_kv_attn_bytes"


def read(obs):
    return kind.read_kind(obs, COST, SCOPES)
