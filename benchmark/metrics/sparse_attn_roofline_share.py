"""Least time the attention over the SELECTED rows could take in one
decode step on this chip - the cache rows it must read (each active
sequence's min(length, index_topk) rows, kv_lora_rank + qk_rope values
a layer, once for all heads: `sparse_attn_bytes` of the configuration's
costs module) over the published HBM bandwidth - as a share of the
device time of the operations under the `attn.kernel` and `attn.gather`
scopes in one run of the decode program: the gather of the rows the
selection names through the page table, and the absorbed attention
over them (ops/sparse_select.py).

A configuration whose costs module has no such count (every other
family's, and the parent's) reads nothing.

Moves itl_mean_ms: the gather of 2,048 rows a sequence a layer is what
the selection leaves of a decode step's cache traffic.
"""

from benchmark.metrics import window_attn_roofline_share as kind

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.kernel", "attn.gather")
COST = "sparse_attn_bytes"


def read(obs):
    return kind.read_kind(obs, COST, SCOPES)
