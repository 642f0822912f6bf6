"""Pallas TPU kernel: grouped-query attention of ONE block of queries
over all the contiguous K and V rows of a sequence under a mask that
is an operand (an admission under a learned selection over K and V
rows, ops/sparse_select.py). What `sparse_select.attend_masked` sums,
in tiles: no array of [block, heads, S] logits exists outside VMEM.

The query tile of a grid row is the H / G query heads of one kv head x
the block's n queries, head-major ([H / G * n, hd]: 512 rows of 128
lanes at 8 heads x 64 queries; hd a multiple of 128 on a chip), so a K and a V tile is fetched ONCE a
head group and a logits tile [H / G * n, bk] fills the MXU's rows. The
grid is (kv head, key tiles) with the key sweep innermost; accumulator,
running maximum and sum live in VMEM scratch across it
(pallas_flash_attention._kernel's online softmax). The mask crosses as
int8 [n, S]; its [n, bk] tile is the same for every head of the tile,
a broadcast along the leading axis of the logits seen as [H / G, n,
bk]: no lane moves.

`live_rows` (a traced scalar, prefetched): the mask keeps no row at or
past it (a block whose last query stands at position t: t + 1). Key
tiles that start there are dead: no compute (pl.when), and the index
maps freeze at the last live tile so pallas elides the fetch
(pallas_flash_attention._make_row_maps' trick under `causal`).

The arithmetic is `_attend_by_group`'s: float32 logits of the inputs'
products, `scale` on the float32 logits, what the mask drops at NEG,
the probabilities cast to the queries' type for the second product,
float32 accumulation. K and V come laid by kv head, [G, S, hd], so a
tile is ONE contiguous copy: as the cache's rows lie, [S, G * hd], a kv
head's tile is bk pieces of 256 bytes and the copies, not the
arithmetic, set the pace (477 us a block of 35,072 rows against 340;
PERF.md, PR 54), so the caller relays the rows once for all its blocks.
The queries and the output are read and written where they lie, [n, H *
hd] (a group's heads are a column block, laid head-major into scratch
once a group), and the mask as it is. Nothing is padded to the tile:
the tile across the rows' end drops the columns of the mask and zeroes
the rows of V it read past it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import matmul_precision

F32 = jnp.float32
NEG = -1e30
# Keys a tile. The logits tile [512, BLOCK_K] float32 is 2 MB at 1,024
# (tools/time_admit_select.py `--block-k` sweeps it; PERF.md, PR 54).
BLOCK_K = 1024


def _kernel(live_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
            qs_ref, acc_ref, m_ref, l_ref, *, bk, n_rows, scale):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    n, hd = mask_ref.shape[0], k_ref.shape[-1]
    heads = q_ref.shape[1] // hd

    @pl.when(ki == 0)
    def _init():
        # the group's queries [n, heads * hd] as rows, head-major
        for h in range(heads):
            qs_ref[h * n:(h + 1) * n, :] = q_ref[:, h * hd:(h + 1) * hd]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = ki * bk

    def _attend(across_end):
        q = qs_ref[...]                         # [heads * n, hd]
        k = k_ref[0]                            # [bk, hd]
        v = v_ref[0]
        keep = mask_ref[...].astype(jnp.int32) != 0       # [n, bk]
        if across_end:  # what was read past the rows' end: anything
            at = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(at < n_rows, v, jnp.zeros_like(v))
            at = k_start + jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
            keep = jnp.logical_and(keep, at < n_rows)
        precision = matmul_precision(q.dtype)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=F32, precision=precision) * scale
        # head-major rows: the mask's tile, once a head
        logits = jnp.where(keep[None], logits.reshape(heads, n, bk), NEG
                           ).reshape(logits.shape)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        # a row of which no tile yet kept a key sums ones here; its
        # first kept key's alpha, exp(NEG - m), wipes them
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=F32, precision=precision)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)

    live = k_start < live_ref[0]
    if n_rows % bk:
        across = k_start + bk > n_rows
        pl.when(jnp.logical_and(live, across))(
            functools.partial(_attend, True))
        pl.when(jnp.logical_and(live, jnp.logical_not(across)))(
            functools.partial(_attend, False))
    else:
        pl.when(live)(functools.partial(_attend, False))

    @pl.when(ki == nk - 1)
    def _finish():
        out = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        for h in range(heads):
            o_ref[:, h * hd:(h + 1) * hd] = out[h * n:(h + 1) * n]


def by_head(rows):
    """A sequence's K or V rows [S, G, hd] as the kernel reads them,
    [G, S, hd]."""
    return jnp.swapaxes(rows, 0, 1)


def tiles_run(n_rows, live_rows, block_k=BLOCK_K):
    """(key tiles a call runs, key tiles of the rows): what `live_rows`
    leaves of a sweep over `n_rows`."""
    return -(-min(live_rows, n_rows) // block_k), -(-n_rows // block_k)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_k", "interpret"))
def masked_flash_attention(q, k_heads, v_heads, mask, live_rows, *, scale,
                           block_k=BLOCK_K, interpret=False):
    """`sparse_select.attend_masked(q, k_rows, v_rows, mask, scale)` as
    ONE Pallas call over the rows laid by kv head. q: [n, H, hd];
    k_heads, v_heads: [G, S, hd] (`by_head`); mask: [n, S] bool, at
    least one row kept a query and none at or past `live_rows` (int32
    scalar, 1 <= live_rows). Returns [n, H, hd] in q's type."""
    n, h, hd = q.shape
    g, s, _ = k_heads.shape
    heads = h // g
    bk = min(block_k, -(-s // 128) * 128)
    # queries in whole int8 tiles of sublanes (32); a padded query
    # keeps no row and its (finite) output is cut off
    n_pad = -(-n // 32) * 32
    live = jnp.clip(live_rows, 1, s).astype(jnp.int32).reshape(1)

    def tile(ki, live):     # past the last live tile: that one again
        return jnp.minimum(ki, (live[0] - 1) // bk)

    def group(gi, ki, live):
        return (0, gi)

    def rows(gi, ki, live):
        return (gi, tile(ki, live), 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, n_rows=s, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, -(-s // bk)),
            in_specs=[
                pl.BlockSpec((n_pad, heads * hd), group),
                pl.BlockSpec((1, bk, hd), rows),
                pl.BlockSpec((1, bk, hd), rows),
                pl.BlockSpec((n_pad, bk),
                             lambda gi, ki, live: (0, tile(ki, live))),
            ],
            out_specs=pl.BlockSpec((n_pad, heads * hd), group),
            scratch_shapes=[
                pltpu.VMEM((heads * n_pad, hd), q.dtype),   # q, head-major
                pltpu.VMEM((heads * n_pad, hd), F32),       # acc
                pltpu.VMEM((heads * n_pad, 1), F32),        # m
                pltpu.VMEM((heads * n_pad, 1), F32),        # l
            ]),
        out_shape=jax.ShapeDtypeStruct((n_pad, h * hd), q.dtype),
        interpret=interpret,
    )(live, jnp.pad(q.reshape(n, h * hd), ((0, n_pad - n), (0, 0))),
      k_heads, v_heads,
      jnp.pad(mask.astype(jnp.int8), ((0, n_pad - n), (0, 0))))
    return out[:n].reshape(n, h, hd)
