"""One-token paged decode over a LATENT cache, in the absorbed form.

A latent family (models/xing.py; multi-head latent attention as in
DeepSeek-V2/V3) keeps ONE row a token a layer: the compressed key-value
c [rank] and the shared rotated key k_pe, zero-padded to a lane multiple
(`width`). With the key half of the up-projection folded into the query
and the value half into the output, every head attends the SAME rows:

    score[h, j] = q[h] . row_j          q = [q_nope Wk_h | q_pe | 0]
    o_lat[h]    = sum_j softmax_j(score[h]) row_j[:rank]

so a block of pages is read from HBM ONCE for all H heads (60 FLOPs a
byte at 32 heads against 4 for a GQA group of 4), and K and V per head
are never built. The caller scales q (decoder.latent_decode).

The pool is [layers, pages, page, width]: (page, width) are the tiled
dimensions, so a page is a [page, width] slab as it lies and a block of
P pages one [P * page, width] matmul operand. The kernel is
ops/pallas_paged_attention.py's decode kernel (PR 36) with one buffer
where that has K's and V's: one grid step a sequence, the sequence's
LIVE blocks only, each page fetched by the kernel's own asynchronous
copy into one half of a double buffer while the other half is folded,
float32 online softmax. `latent_decode_attention` picks the kernel on
TPU backends and the XLA gather form elsewhere; tests run the kernel in
interpret mode against that form.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_paged_attention import _fold_softmax

# Pages a block holds: 512 keys of 16-token pages; two halves of
# [512, 640] bf16 are 1.3 MB of VMEM.
_BLOCK_KEYS = 512


def latent_decode_xla(q, pool, page_table, seq_lens, rank, layer=None):
    """The same attention by gathering every table entry's page: the
    CPU path and the kernel's oracle. q: [b, H, width] (scaled); pool:
    [pages, page, width] or [layers, pages, page, width] with `layer`;
    page_table: [b, max_pages]; seq_lens: [b] keys to attend (a row
    with 0 attends position 0). Returns o_lat [b, H, rank]."""
    f32 = jnp.float32
    where = page_table if layer is None else (layer, page_table)
    rows = pool.at[where].get(mode="clip")        # [b, max_pages, page, w]
    b, n, page, w = rows.shape
    rows = rows.reshape(b, n * page, w)
    precision = jax.lax.Precision.HIGHEST if q.dtype == f32 else None
    logits = jnp.einsum("bhw,bjw->bhj", q, rows,
                        preferred_element_type=f32, precision=precision)
    live = jnp.arange(n * page)[None] < jnp.maximum(seq_lens, 1)[:, None]
    logits = jnp.where(live[:, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhj,bjr->bhr", p.astype(q.dtype), rows[..., :rank],
                     preferred_element_type=f32, precision=precision)
    return out.astype(q.dtype)


def _kernel(page_tbl_ref, seq_lens_ref, q_ref, pool_hbm, o_ref,
            buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
            layer, page_size, rank):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    max_pages = page_tbl_ref.shape[1]
    n_pool_pages = pool_hbm.shape[-3]
    _, P, _, width = buf.shape
    block = P * page_size

    def last_page(row):
        return jnp.minimum(jnp.maximum(seq_lens_ref[row] - 1, 0)
                           // page_size, max_pages - 1)

    def copies(row, blk, slot, act):
        """`act` (start or wait) on the copy of every live page of
        block `blk` of `row` into half `slot`."""
        def page_copy(j, carry):
            page = jnp.clip(page_tbl_ref[row, j], 0, n_pool_pages - 1)
            src = pool_hbm.at[page] if layer is None \
                else pool_hbm.at[layer, page]
            act(pltpu.make_async_copy(src, buf.at[slot, j - blk * P],
                                      sems.at[slot]))
            return carry

        jax.lax.fori_loop(blk * P,
                          jnp.minimum(last_page(row), blk * P + P - 1) + 1,
                          page_copy, None)

    n_blocks = last_page(b) // P + 1

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        # what a dead page leaves in a buffer is an earlier page or
        # these zeros, never bits that 0 x value could turn into a NaN
        buf[...] = jnp.zeros_like(buf)
        copies(b, 0, 0, lambda c: c.start())

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)

    slot0 = slot_ref[0]
    seq_len = jnp.maximum(seq_lens_ref[b], 1)
    next_row = jnp.minimum(b + 1, n_rows - 1)
    q = q_ref[b]                                           # [H, width]
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def fold(i, carry):
        slot = (slot0 + i) % 2
        ends = i + 1 == n_blocks

        @pl.when(jnp.logical_or(jnp.logical_not(ends), b + 1 < n_rows))
        def _prefetch():
            copies(jnp.where(ends, next_row, b), jnp.where(ends, 0, i + 1),
                   1 - slot, lambda c: c.start())

        copies(b, i, slot, lambda c: c.wait())
        rows = buf[slot].reshape(block, width)
        logits = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        pos = i * block + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(pos < seq_len, logits, -1e30)
        _fold_softmax(
            logits, acc_ref, m_ref, l_ref,
            lambda p: jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=precision))
        return carry

    jax.lax.fori_loop(0, n_blocks, fold, None)
    slot_ref[0] = (slot0 + n_blocks) % 2
    o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "layer", "interpret"))
def latent_flash_decode(q, pool, page_table, seq_lens, rank, layer=None,
                        interpret=False):
    """The kernel; same contract as `latent_decode_xla`. The pool goes
    to the call whole and as it lies (no pad, no slice of a layer)."""
    batch, n_heads, width = q.shape
    page_size = pool.shape[-2]
    max_pages = page_table.shape[1]
    P = max(1, min(_BLOCK_KEYS // page_size, max_pages))
    whole_q = pl.BlockSpec((batch, n_heads, width),
                           lambda b, pt, sl: (0, 0, 0))
    whole_o = pl.BlockSpec((batch, n_heads, rank),
                           lambda b, pt, sl: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, seq_lens
        grid=(batch,),
        in_specs=[whole_q, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=whole_o,
        scratch_shapes=[
            pltpu.VMEM((2, P, page_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_heads, rank), jnp.float32),   # acc
            pltpu.VMEM((n_heads, 1), jnp.float32),      # m
            pltpu.VMEM((n_heads, 1), jnp.float32),      # l
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, layer=layer, page_size=page_size,
                          rank=rank),
        out_shape=jax.ShapeDtypeStruct((batch, n_heads, rank), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table, seq_lens, q, pool)


def latent_decode_attention(q, pool, page_table, seq_lens, rank, layer=None):
    """The kernel on TPU backends, the XLA form elsewhere."""
    if jax.default_backend() == "tpu":
        return latent_flash_decode(q, pool, page_table, seq_lens, rank=rank,
                                   layer=layer)
    return latent_decode_xla(q, pool, page_table, seq_lens, rank, layer)
