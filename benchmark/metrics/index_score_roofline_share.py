"""Least time the indexers could take in one decode step on this chip -
the bytes they must read (every live token's index key in each layer
that owns an indexer, 256 B each, and those layers' indexer weights:
`index_score_bytes` of the configuration's costs module) over the
published HBM bandwidth - as a share of the device time of the
operations under the `attn.index` scope in one run of the decode
program: the indexer's projections, the gather of the sequence's index
keys through the page table, and the scores.

A configuration whose costs module has no such count reads nothing.

Moves itl_mean_ms: the index keys are the one part of a decode step's
cache traffic that still grows with the context.
"""

from benchmark.metrics import window_attn_roofline_share as kind

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.index",)
COST = "index_score_bytes"


def read(obs):
    return kind.read_kind(obs, COST, SCOPES)
