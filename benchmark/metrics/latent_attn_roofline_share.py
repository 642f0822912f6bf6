"""Least time the latent attention could take in one decode step on
this chip - the cache rows it must read (every live token of the active
sequences, kv_lora_rank + qk_rope values a layer, once for all heads:
`latent_attn_bytes` of the configuration's costs module) over the
published HBM bandwidth - as a share of the device time of the
operations under the `attn.kernel` scope in one run of the decode
program: the absorbed paged-decode kernel
(ops/pallas_latent_attention.py), whose transfers are its own, so its
time holds them. At 32 heads a row a byte carries 60 FLOPs, so the
kernel may be bound by its matmuls and not by the read: the share says
how far it is from the read's time either way.

A configuration whose costs module has no such count (every other
family's, and the parent's) reads nothing.

Moves itl_mean_ms: at 16-33k tokens a sequence the cache rows are a
third of a decode step's bytes.
"""

from benchmark.metrics import window_attn_roofline_share as kind

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel",)
COST = "latent_attn_bytes"


def read(obs):
    return kind.read_kind(obs, COST, SCOPES)
