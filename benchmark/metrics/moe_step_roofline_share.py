"""Least time the expert blocks could take in one decode step on this
chip - the bytes they must read (the experts the step's tokens touch in
expectation, and the routers: `moe_step_bytes` of the configuration's
costs module) over the published HBM bandwidth - as a share of the
device time of the operations under the `moe.` scopes (route, dispatch,
experts, combine) in one run of the decode program.

A form that reads every expert whatever the tokens chose cannot pass
E(touched) / E: 79 % at 16 tokens x 6 of 64.

Moves itl_mean_ms: the experts are two thirds of a decode step's
weight bytes.
"""

from benchmark.metrics import window_attn_roofline_share as kind

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("moe.",)
COST = "moe_step_bytes"


def read(obs):
    return kind.read_kind(obs, COST, SCOPES, of=lambda s: (s.active,))
