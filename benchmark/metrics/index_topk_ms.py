"""Device time of the operations under the `attn.topk` scope in one run
of the decode program: the exact top-`index_topk` of every active
sequence's index scores, once in each layer that owns an indexer. No
roofline: a sort has none worth quoting. It is the first candidate of
a later change (an exact selection faster than a sort).

A program without the scope (every other family's, and the parent's)
reads nothing.

Moves itl_mean_ms: every decode step pays it before it can gather a
row.
"""

from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.topk",)


def per_run_ms(scoped_s, runs, programs_s=None):
    return 1e3 * scoped_s / runs


def read(obs):
    found = _scoped_ops.seconds(obs, "decode", SCOPES)
    return None if found is None else per_run_ms(*found)
