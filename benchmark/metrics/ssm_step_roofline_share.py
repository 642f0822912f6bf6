"""Least time the state layers' mixers could take in one decode step on
this chip - the bytes they must move (every active sequence's recurrent
state read and written, the mixers' weights read once: `ssm_step_bytes`
of the configuration's costs module) over the published HBM bandwidth -
as a share of the time they take: one run of the decode program LESS
the operations under every other stage's scope (attention, MLP, head,
embedding, pool update).

By subtraction, because the mixers' own operations do not hold their
transfers: the compiler moves weights and state into fast memory with
asynchronous copies that carry no scope (on a v5e the `ssm.*`
operations summed to 3.6 ms of a step whose state alone needs 3.0 ms of
HBM time for 16 slots; PERF.md, PR 31). What is left after the other
stages is the mixers' operations plus everything the compiler moved out
of any scope, so the share reads low rather than high.

Moves itl_mean_ms: the state is a quarter of a decode step's bytes.
"""

from benchmark.lib import serve, stats
from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
OTHER_STAGES = ("attn.", "mlp", "lm_head", "embed", "pool.update")


def share(need_bytes, hbm_bytes_per_s, others_s, runs, programs_s):
    return 100.0 * (need_bytes / hbm_bytes_per_s) / (
        (programs_s - others_s) / runs)


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, "ssm_step_bytes"):
        return None
    found = _scoped_ops.seconds(obs, "decode", OTHER_STAGES)
    steps = [s for s in obs.steps_traced() if s.moved["decode_steps"] > 0]
    if found is None or not steps:
        return None
    need = stats.quantile(
        [costs.ssm_step_bytes(obs.conf, s.active) for s in steps], 0.50)
    return share(need, obs.peaks["hbm_bytes_per_s"], *found)
