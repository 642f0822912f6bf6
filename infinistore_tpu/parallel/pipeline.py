"""GPipe-style pipeline parallelism over a mesh axis.

The reference has no model execution at all (SURVEY.md §2: none of
DP/TP/PP/SP/EP exist in it); on the TPU engine side of this stack,
pipeline parallelism completes the parallelism set next to dp/tp
(parallel/mesh.py), sp (ops/ring_attention.py) and ep (models/moe.py).

TPU-native formulation: the layer stack is split into S equal stages
whose parameters carry a leading [S, ...] axis sharded P("pp") — each
chip holds exactly one stage. One `shard_map` wraps a `lax.scan` over
n_micro + S - 1 ticks; every tick each chip applies its stage to its
current microbatch and hands the activation to the next chip with ONE
`lax.ppermute` (the i→i+1 chain rides neighboring ICI links — the whole
schedule is S-1 hops of nearest-neighbor traffic, no all-gathers). The
first stage feeds fresh microbatches from the input; the last stage
banks its outputs; a final masked psum replicates the result. All
shapes are static, the schedule is a compile-time unrolled-free scan,
and jax differentiates straight through it (ppermute's transpose is the
reversed permute), so pipelined training needs no extra machinery.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pp_mesh(n_stages, devices=None, axis="pp"):
    if devices is None:
        devices = jax.devices()[:n_stages]
    return Mesh(np.asarray(devices), axis_names=(axis,))


def stack_stage_params(per_stage_params):
    """[pytree, ...] (one per stage, identical structure) → one pytree
    with a leading [S, ...] axis — the layout `pipeline_apply` shards
    over pp."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params
    )


def stage_shardings(mesh, stacked_params, axis="pp"):
    """NamedShardings placing the leading stage axis on `axis`."""
    return jax.tree_util.tree_map(
        lambda leaf: NamedSharding(
            mesh, P(axis, *([None] * (leaf.ndim - 1)))
        ),
        stacked_params,
    )


def pipeline_apply(stage_fn, stacked_params, x_micro, mesh, axis="pp"):
    """Run microbatches through the S-stage pipeline.

    stage_fn(params_one_stage, x) -> y       (same shape as x)
    stacked_params: pytree with leading [S, ...] axis (shard over
        `axis` with :func:`stage_shardings` — or leave unsharded and let
        jit propagate).
    x_micro: [n_micro, mb, ...] microbatched input (replicated).

    Returns [n_micro, mb, ...] = stage_{S-1}( ... stage_0(x) ...),
    replicated. Wall-clock schedule: n_micro + S - 1 ticks, so pipeline
    bubble fraction = (S-1)/(n_micro+S-1) — choose n_micro >> S.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def per_chip(params_local, xs):
        # params_local: leading axis 1 (this chip's stage); strip it.
        params = jax.tree_util.tree_map(lambda l: l[0], params_local)
        idx = jax.lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == n_stages - 1

        def tick(carry, t):
            buf_in, outputs = carry
            feed_t = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                xs, feed_t, 0, keepdims=False
            )
            # Stage 0 ingests microbatch t (stale clamp rows are never
            # emitted); later stages consume what arrived last tick.
            inp = jnp.where(is_first, fresh, buf_in)
            out = stage_fn(params, inp)
            # Bank the last stage's finished microbatch t-(S-1).
            emit_t = t - (n_stages - 1)
            emit_c = jnp.clip(emit_t, 0, n_micro - 1)
            cur = jax.lax.dynamic_index_in_dim(
                outputs, emit_c, 0, keepdims=False
            )
            banked = jnp.where(jnp.logical_and(is_last, emit_t >= 0),
                               out, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, banked, emit_c, 0
            )
            # Hand activations down the chain (stage 0 receives zeros —
            # overwritten by `fresh` next tick anyway).
            buf_next = jax.lax.ppermute(out, axis, fwd_perm)
            return (buf_next, outputs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, outs0),
            jnp.arange(n_micro + n_stages - 1),
        )
        # Only the last stage's bank is meaningful; replicate it.
        return jax.lax.psum(
            jnp.where(is_last, outputs, jnp.zeros_like(outputs)), axis
        )

    smapped = shard_map(
        per_chip,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,  # masked psum IS the replication proof
    )
    return smapped(stacked_params, x_micro)


__all__ = [
    "make_pp_mesh", "stack_stage_params", "stage_shardings",
    "pipeline_apply",
]
