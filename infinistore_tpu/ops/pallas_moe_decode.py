"""A handful of rows through the experts they chose, and no others.

A decode step holds a few tokens (the engine's slots, most of them
empty at the rates users send) and each chose `k` of `E` experts. The
forms of models/moe.py that run every row through every expert read
ALL the experts' weights from HBM, and at these row counts that read
is the whole cost of the block. Here the experts run as ONE Pallas
call whose weight copies are addressed by expert id:

    out[t] = sum_e gate[t, e] * W_down[e](act(W_gate[e] u_t) * (W_up[e] u_t))

over the experts some VALID row chose, and only those are fetched.

Outside the kernel (`live_experts`, a few tiny XLA operations): the
[T, E] gate matrix (a row that is not valid is all zero, so an empty
slot's garbage row fetches nothing), the ids of the experts with any
non-zero column, first and in id order, padded by repeating the last,
and their count `n`; ids and n reach the kernel as scalar-prefetched
operands, so the index maps of the weight blocks read them.

Grid (G, f tiles), G = min(E, T x k) the most experts the rows can
touch: step (g, j) holds tile j of expert ids[g]'s three matrices. A
step at or past `n` names the block the last live step held, so the
pipeline issues no copy for it, and computes nothing. A live step adds
(act(u Wg) * (u Wu) * gate[:, e]) Wd to a float32 [T, d] scratch,
written once at the end. The f tile keeps three double-buffered weight
blocks inside `_WEIGHT_BLOCK_BYTES`.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One grid step's three weight blocks hold at most this, and the
# pipeline keeps two steps' worth. Measured on a v5e
# (tools/time_moe_decode.py; PERF.md, PR 41).
_WEIGHT_BLOCK_BYTES = 12 << 20


def live_experts(top_idx, gates, valid, n_experts):
    """(gate matrix [T, E] float32, ids [G] int32, n int32): the gate
    of every (row, expert), zero where the row did not choose the
    expert or is not valid; the experts with any non-zero gate, in id
    order, the tail repeating the last of them; how many they are.
    top_idx, gates: [T, k]; valid: [T] bool or None."""
    T, k = top_idx.shape
    dense = jnp.einsum(
        "tk,tke->te", gates.astype(jnp.float32),
        jax.nn.one_hot(top_idx, n_experts, dtype=jnp.float32))
    if valid is not None:
        dense = dense * valid.astype(jnp.float32)[:, None]
    live = jnp.any(dense != 0, axis=0)
    n = jnp.sum(live).astype(jnp.int32)
    G = min(n_experts, T * k)
    first = jnp.argsort(jnp.logical_not(live), stable=True)[:G]
    ids = jnp.where(jnp.arange(G) < n, first, first[jnp.maximum(n - 1, 0)])
    return dense, ids.astype(jnp.int32), n


def _f_tile(d, f, itemsize):
    """The widest tile of `f` (a multiple of 128 that divides it, or f
    whole) whose three blocks fit `_WEIGHT_BLOCK_BYTES`."""
    most = _WEIGHT_BLOCK_BYTES // (3 * d * itemsize)
    if f <= most or f % 128:
        return f
    tile = most // 128 * 128
    while tile > 128 and f % tile:
        tile -= 128
    return max(tile, 128)


def _kernel(ids_ref, n_ref, u_ref, gate_ref, wg_ref, wu_ref, wd_ref, o_ref,
            acc_ref, *, act):
    g, j = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(jnp.logical_and(g == 0, j == 0))
    def _open():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(g < n_ref[0])
    def _expert_tile():
        u = u_ref[...]
        precision = (jax.lax.Precision.HIGHEST if u.dtype == f32
                     else jax.lax.Precision.DEFAULT)
        dot = functools.partial(jnp.dot, preferred_element_type=f32,
                                precision=precision)
        h = act(dot(u, wg_ref[...])) * dot(u, wu_ref[...]) * gate_ref[...]
        acc_ref[...] += dot(h.astype(wd_ref.dtype), wd_ref[...])

    @pl.when(jnp.logical_and(g == pl.num_programs(0) - 1,
                             j == pl.num_programs(1) - 1))
    def _close():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def gathered_call(u, w_gate, w_up, w_down, dense, ids, n, act,
                  interpret=False):
    """The kernel over what `live_experts` returned. u: [T, d];
    w_gate, w_up: [E, d, f]; w_down: [E, f, d]. Returns [T, d]."""
    T, d = u.shape
    f = w_gate.shape[2]
    G = ids.shape[0]
    # rows padded to a whole sublane tile of the rows' type; the
    # padding's gates are zero
    sublanes = 32 // u.dtype.itemsize
    rows = -(-T // sublanes) * sublanes
    u = jnp.pad(u, ((0, rows - T), (0, 0)))
    cols = jnp.pad(jnp.take(dense, ids, axis=1), ((0, rows - T), (0, 0)))
    tile = _f_tile(d, f, w_gate.dtype.itemsize)
    last = f // tile - 1

    def tile_of(g, j, n):
        return jnp.where(g < n[0], j, last)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # ids, n
        grid=(G, f // tile),
        in_specs=[
            pl.BlockSpec((rows, d), lambda g, j, ids, n: (0, 0)),
            pl.BlockSpec((None, rows, 1), lambda g, j, ids, n: (g, 0, 0)),
            pl.BlockSpec((None, d, tile),
                         lambda g, j, ids, n: (ids[g], 0, tile_of(g, j, n))),
            pl.BlockSpec((None, d, tile),
                         lambda g, j, ids, n: (ids[g], 0, tile_of(g, j, n))),
            pl.BlockSpec((None, tile, d),
                         lambda g, j, ids, n: (ids[g], tile_of(g, j, n), 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda g, j, ids, n: (0, 0)),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((rows, d), u.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * _WEIGHT_BLOCK_BYTES + (16 << 20)),
        interpret=interpret,
    )(ids, n.reshape(1), u, cols.T[:, :, None], w_gate, w_up, w_down)
    return out[:T]


def gathered_experts(u, w_gate, w_up, w_down, top_idx, gates, valid, act,
                     interpret=False):
    """out[t] = sum over row t's chosen experts of gate * W_down(act(
    W_gate u_t) * (W_up u_t)), zero for a row that is not valid, by
    fetching the chosen experts alone. top_idx, gates: [T, k]; valid:
    [T] bool or None. Returns [T, d]."""
    dense, ids, n = live_experts(top_idx, gates, valid, w_gate.shape[0])
    return gathered_call(u, w_gate, w_up, w_down, dense, ids, n, act=act,
                         interpret=interpret)
