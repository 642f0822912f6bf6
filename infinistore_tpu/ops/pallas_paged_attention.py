"""Pallas TPU kernels: flash attention over paged KV.

The XLA implementation (paged_attention.paged_decode_attention) gathers
every page into one [batch, T, heads, hd] tensor in HBM before the
matmuls. These kernels stream pages HBM → VMEM instead, pick each page
through the scalar-prefetched page table (pltpu.PrefetchScalarGridSpec),
compute the partial attention on the MXU and fold it into an
online-softmax accumulator held in VMEM scratch. Nothing is
materialized.

**The bf16 decode kernel** (`paged_flash_decode`, one call a layer in
every decode step of every cell) pays for live pages, not for table
length. Its grid is (batch,): one step a sequence. K and V go to the
call in HBM as they lie (`memory_space=pl.ANY`) and the kernel issues
its own asynchronous copies, one a page, of the next BLOCK of P pages
into one half of a double buffer while it folds the current block, one
`_attend_rows` over P x page keys. The walk over a sequence's blocks is
a loop whose bounds come from `seq_lens`: from the block that holds the
band's floor (`window`, else 0) to the block that holds position
seq_len - 1; a sequence's last block starts the next sequence's first
copy, so the copies run ahead across grid steps too. An inactive row
(length 1 over table entry 0) costs one block; a table entry past a
sequence's last page, below its band or beyond the table (a table that
is no multiple of P ends in a partial block) costs nothing: it is
neither copied nor, being masked, attended. P follows the shapes
(`_pages_per_block`); no option sets it. Before this form the grid was
(batch, max_pages) with ONE page a step, live or not, at 0.2-0.3 us a
step whatever it held: 16 x 192 steps a layer in mistral7b, nine in ten
dead (PERF.md, PR 36). The pattern is the one of JAX's own
`pallas/ops/tpu/paged_attention` kernel on this repo's pool layout.

`paged_flash_verify` (m tokens a sequence) and
`paged_flash_decode_quantized` (int8 pages) keep the one-page grid
(batch, max_pages): each step one page by BlockSpec, its index map
frozen at the sequence's last used page (`_make_page_idx`), folded by
`_attend`. No benchmark cell runs them.

Operand layout. The bf16 kernels take K and V in one of two forms,
chosen by the operand's shape alone (`_kv_aligned`):

- the WHOLE pool [n_layers, n_pages, page, n_kv, hd] plus a static
  `layer`, when head_dim is a lane multiple (128) and the kv heads
  already make the query rows a sublane multiple. The pool goes to the
  call as it lies in HBM and the kernel indexes the layer itself, so
  the serving step never produces a layer-sized array. Verify takes it
  5-D, a block (None, 1, page, n_kv, hd). Decode views a page as
  [page * n_kv, hd] ROWS (row = token * n_kv + kv head): merging (page,
  n_kv) leaves every (8, 128) tile of the TPU's tiled layout where it
  is, a bitcast for XLA, and a block of pages is then one
  [keys * n_kv, hd] matmul operand with no relayout in the kernel. The
  pool is NOT viewed as [..., page, n_kv * hd]: that reshape moves
  tiles, and XLA materialised the whole pool for it, once per layer and
  kind (seen in the AOT-compiled decode program of PR 25).
  tests/test_model.py compiles the three decode programs for a
  described v5e and holds their temporaries under a layer of the pool.
- one layer [n_pages, page, n_kv, hd] (or a pool that needs padding,
  which is sliced to its layer first): the wrapper pads head_dim to a
  lane multiple of 128 and the kv heads to the sublane multiple (rows
  for decode, [n_pages, page, n_kv * hd] for verify). That costs a copy
  of the layer per call. Padding contributes zeros to logits and is
  sliced off the output.

`decode_attention` picks the decode kernel on TPU backends and falls
back to the XLA gather path elsewhere (tests run the kernels in
interpret mode so CPU CI covers the same code path).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as xla_ref


# The decode kernel's block: K and V of `_pages_per_block` pages, two
# halves of each, within this many bytes of VMEM, and at most this many
# keys (their logits over every kv head stay in registers).
_BLOCK_VMEM_BYTES = 2 << 20
_BLOCK_MAX_KEYS = 512


def _pages_per_block(page_size, page_bytes, max_pages):
    """Pages the decode kernel fetches and folds at once, from what the
    call can see: as many as keep the two halves of K's and of V's
    buffer within `_BLOCK_VMEM_BYTES` (mistral7b: 32 KB a page a kind,
    so 16 pages = 256 keys), at least the 128 keys that fill one lane
    tile of logits, at most `_BLOCK_MAX_KEYS`, and never more than the
    table holds."""
    lane_tile = -(-128 // page_size)
    fit = _BLOCK_VMEM_BYTES // (4 * page_bytes)
    pages = max(lane_tile, min(fit, _BLOCK_MAX_KEYS // page_size))
    return max(1, min(pages, max_pages))


def _kernel(page_tbl_ref, seq_lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
            layer, page_size, n_kv, n_heads, scale, window=0):
    """One grid step a sequence. K and V stay in HBM, a page a
    [page * n_kv, hd] slab of rows (row = token * n_kv + kv head); the
    kernel walks the sequence's LIVE blocks of P pages (from the one
    that holds the band's floor to the one that holds position
    seq_len - 1), copies each block's live pages itself, one
    asynchronous copy a page, into one half of a double buffer while it
    folds the other half, and starts the next sequence's first block
    behind its own last. Dead pages and pages beyond the table are
    neither copied nor (being masked) attended; what they leave in a
    buffer is an earlier page or the zeros the first grid step wrote,
    never uninitialised bits that 0 x V could turn into a NaN. q and
    the output are whole in VMEM for the call: a grid step moves
    nothing but pages."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    max_pages = page_tbl_ref.shape[1]
    n_pool_pages = k_hbm.shape[-3]
    _, P, page_rows, hd = k_buf.shape
    block = P * page_size

    def live_pages(row):
        """(first, last) page of `row` that holds a key of its band;
        last clamped into the table as `_make_page_idx` clamps it."""
        seq_len = seq_lens_ref[row]
        last = jnp.minimum(jnp.maximum(seq_len - 1, 0) // page_size,
                           max_pages - 1)
        first = jnp.maximum(seq_len - window, 0) // page_size if window else 0
        return first, last

    def copies(row, blk, slot, act):
        """`act` (start or wait) on the copy of every live page of block
        `blk` of `row` into half `slot`. A loop over the live pages
        alone, not P guarded copies: a block with one live page (an
        inactive row's) then costs one page's scalar work, which read
        16 us a call of 16 such rows against 26-38 unrolled, for 4 %
        more on whole tables (PERF.md, PR 36)."""
        first, last = live_pages(row)

        def page_copies(j, carry):
            page = jnp.clip(page_tbl_ref[row, j], 0, n_pool_pages - 1)
            for kind, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                               (v_hbm, v_buf))):
                src = hbm.at[page] if layer is None else hbm.at[layer,
                                                                page]
                act(pltpu.make_async_copy(
                    src, buf.at[slot, j - blk * P], sems.at[kind, slot]))
            return carry

        jax.lax.fori_loop(jnp.maximum(first, blk * P),
                          jnp.minimum(last, blk * P + P - 1) + 1,
                          page_copies, None)

    first, last = live_pages(b)
    first_blk = first // P
    n_blocks = last // P - first_blk + 1

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        v_buf[...] = jnp.zeros_like(v_buf)
        copies(b, first_blk, 0, lambda c: c.start())

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)

    slot0 = slot_ref[0]
    seq_len = seq_lens_ref[b]
    low = jnp.maximum(seq_len - window, 0) if window else None
    next_row = jnp.minimum(b + 1, n_rows - 1)
    next_first_blk = live_pages(next_row)[0] // P
    q = q_ref[b]

    def fold(i, carry):
        slot = (slot0 + i) % 2
        blk = first_blk + i
        ends = i + 1 == n_blocks

        @pl.when(jnp.logical_or(jnp.logical_not(ends), b + 1 < n_rows))
        def _prefetch():
            copies(jnp.where(ends, next_row, b),
                   jnp.where(ends, next_first_blk, blk + 1), 1 - slot,
                   lambda c: c.start())

        copies(b, blk, slot, lambda c: c.wait())
        _attend_rows(q,
                     k_buf[slot].reshape(P * page_rows, hd),
                     v_buf[slot].reshape(P * page_rows, hd),
                     acc_ref, m_ref, l_ref, n_kv=n_kv, n_heads=n_heads,
                     scale=scale, start=blk * block, seq_len=seq_len,
                     low=low)
        return carry

    jax.lax.fori_loop(0, n_blocks, fold, None)
    slot_ref[0] = (slot0 + n_blocks) % 2
    o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _attend_rows(q, k, v, acc_ref, m_ref, l_ref, *, n_kv, n_heads, scale,
                 start, seq_len, low=None):
    """One block's online-softmax fold of the decode kernel: ONE product
    over every kv head. q: [n_heads, D], a kv head's query rows
    together; k/v: [T * n_kv, D], row = token * n_kv + kv head, as the
    pages lie. Logits are [n_heads, T * n_kv]; a column of another kv
    head than the row's is masked like a dead position. That is n_kv
    times the products and exponentials the keys need, on units a decode
    step leaves idle, against `_attend`'s strided read of every kv
    head's rows out of [T, n_kv, D] (the block form read 1.5-2 x slower
    with it on the chip, PERF.md, PR 36). Same masks as `_attend`:
    position < seq_len, and >= low for a band."""
    group = n_heads // n_kv
    precision = (
        jax.lax.Precision.HIGHEST
        if q.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ) * scale  # [n_heads, T * n_kv]
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    pos = start + jax.lax.div(col, n_kv)
    valid = jnp.logical_and(pos < seq_len,
                            jax.lax.rem(col, n_kv) == jax.lax.div(row, group))
    if low is not None:
        valid = jnp.logical_and(valid, pos >= low)
    logits = jnp.where(valid, logits, -1e30)
    _fold_softmax(
        logits, acc_ref, m_ref, l_ref,
        lambda p: jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        ))  # [n_heads, D]


def _fold_softmax(logits, acc_ref, m_ref, l_ref, weighted_values):
    """The online-softmax update every paged kernel ends a fold with:
    masked float32 `logits` [rows, keys] into the running maximum
    `m_ref`, denominator `l_ref` and accumulator `acc_ref`;
    `weighted_values(p)` is the fold's p x V product, [rows, D]."""
    m_prev = m_ref[...]  # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)  # [rows, keys]
    alpha = jnp.exp(m_prev - m_new)
    acc_ref[...] = acc_ref[...] * alpha + weighted_values(p)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)


def _kernel_q(page_tbl_ref, seq_lens_ref, q_ref, kq_ref, ks_ref, vq_ref,
              vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
              page_size, n_kv, hd, n_heads, scale, window=0):
    """Decode attention over INT8 pages: dequantize in VMEM right after
    the page DMA — HBM traffic per page is half the bf16 kernel's (int8
    values + per-token-per-head f32 scales ≈ 0.53x bf16 bytes)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens_ref[b]
    start = j * page_size
    low = jnp.maximum(seq_len - window, 0) if window else None
    live = start < seq_len
    if window:
        live = jnp.logical_and(live, start + page_size > low)

    @pl.when(live)
    def _step():
        kq = kq_ref[0].reshape(page_size, n_kv, hd)  # int8
        vq = vq_ref[0].reshape(page_size, n_kv, hd)
        ks = ks_ref[0]  # [P, n_kv] f32
        vs = vs_ref[0]
        kv = kq.astype(jnp.float32) * ks[..., None]
        vv = vq.astype(jnp.float32) * vs[..., None]
        _attend(q_ref[0].astype(jnp.float32), kv, vv,
                acc_ref, m_ref, l_ref, n_kv=n_kv, n_heads=n_heads,
                scale=scale, start=start, seq_len=seq_len, low=low)

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _attend(q, kv, vv, acc_ref, m_ref, l_ref, *, n_kv, n_heads, scale,
            start, seq_len, rows_per_kv=None, limit=None, low=None):
    """One page's online-softmax fold, shared by ALL paged kernels.

    q: [rows, D] with `rows_per_kv` consecutive query rows per kv head
    (decode: the GQA group; verify: m_tok * group — the m-token fold);
    kv/vv: [P, n_kv, D] (already dequantized if the pages are int8).
    `limit` masks position pos < limit; a scalar (decode: seq_len) or a
    [rows, 1] column (verify: per-token causal limits). `low`, when
    given (sliding-window attention), additionally masks pos < low —
    same scalar/column shapes as limit."""
    if rows_per_kv is None:
        rows_per_kv = n_heads // n_kv
    if limit is None:
        limit = seq_len
    # HIGHEST on f32 keeps full precision; on bf16 it would request a
    # multi-pass algorithm Mosaic rejects ("Bad lhs type") — the MXU
    # already accumulates bf16xbf16 in f32, so DEFAULT is exact there.
    precision = (
        jax.lax.Precision.HIGHEST
        if q.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    # Per-kv-head 2D matmuls, statically unrolled (Mosaic rejects 3D
    # batched dot_general; n_kv is small so the unroll is cheap and each
    # dot maps cleanly onto the MXU).
    logit_blocks = []
    for h in range(n_kv):
        qh = q[h * rows_per_kv : (h + 1) * rows_per_kv]  # [rows_kv, D]
        kh = kv[:, h]  # [P, D]
        logit_blocks.append(
            jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=precision,
            )  # [rows_kv, P]
        )
    logits = jnp.concatenate(logit_blocks, axis=0)  # [rows, P]
    logits = logits * scale  # true (unpadded) head-dim scale
    pos = start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = pos < limit
    if low is not None:
        valid = jnp.logical_and(valid, pos >= low)
    logits = jnp.where(valid, logits, -1e30)

    def weighted_values(p):  # [rows, P] -> [rows, D]
        pv_blocks = []
        for h in range(n_kv):
            ph = p[h * rows_per_kv : (h + 1) * rows_per_kv]  # [rows_kv, P]
            vvh = vv[:, h]  # [P, D]
            pv_blocks.append(
                jax.lax.dot_general(
                    ph.astype(vvh.dtype), vvh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                    precision=precision,
                )  # [rows_kv, D]
            )
        return jnp.concatenate(pv_blocks, axis=0)

    _fold_softmax(logits, acc_ref, m_ref, l_ref, weighted_values)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _decode_dims(q_dtype, n_kv, group):
    """Shared tile math for both decode kernels: (sublane, n_kv_p).
    Pad kv heads so n_heads_p = n_kv_p * group is a sublane multiple:
    n_kv_p must be a multiple of sublane/gcd(group, sublane) (works for
    any group size, incl. ones that don't divide the sublane count)."""
    import math as _math

    sublane = 16 if q_dtype == jnp.bfloat16 else 8
    kv_mult = sublane // _math.gcd(group, sublane)
    return sublane, ((n_kv + kv_mult - 1) // kv_mult) * kv_mult


def _make_page_idx(page_size, n_pages, tok_offset=0, layer=None):
    """Shared page index map: clamp against the table contract ("padded
    arbitrarily" — the XLA path's jnp.take clamps OOB ids) AND freeze j
    at the sequence's last used page, so pages past seq_len cost no HBM
    traffic (pallas elides same-index re-fetches). `tok_offset` extends
    the used range by the m new tokens a verify step appends (decode:
    0). With `layer` the operand is the whole 5-D pool and the map
    leads with that (static) layer coordinate."""

    def _page_idx(b, j, pt, sl):
        last_used = jnp.maximum(sl[b] + tok_offset - 1, 0) // page_size
        jj = jnp.minimum(j, last_used)
        page = jnp.clip(pt[b, jj], 0, n_pages - 1)
        return (page, 0, 0) if layer is None else (layer, page, 0, 0, 0)

    return _page_idx


def _kv_aligned(pages, layer, n_kv_p):
    """K or V for the bf16 paged kernels, from a layer
    [n_pages, page, n_kv, hd] or from the whole pool [n_layers, n_pages,
    page, n_kv, hd] plus a static `layer`.

    A pool whose lanes and kv heads are already tile-aligned
    (hd % 128 == 0, n_kv == n_kv_p) comes back WHOLE and as it is, 5-D.
    Anything else is sliced to its layer first and that slice is padded
    to [n_pages, page, n_kv_p, hd_p] — padding the pool itself would
    copy every layer on every layer's call. The shape decides, nothing
    else does."""
    if pages.ndim == 5:
        n_kv, hd = pages.shape[3:]
        if hd % 128 == 0 and n_kv == n_kv_p:
            return pages
        pages = pages[layer]
    pages, _ = _pad_to(pages, 3, 128)
    n_kv = pages.shape[2]
    if n_kv_p != n_kv:
        pages = jnp.pad(pages, ((0, 0), (0, 0), (0, n_kv_p - n_kv), (0, 0)))
    return pages


def _kv_operand(pages, layer, n_kv_p):
    """The K or V operand of the verify kernel: `_kv_aligned`'s whole
    pool, 5-D (see the module docstring for why it is not flattened),
    or its padded layer flattened to [n_pages, page, n_kv_p * hd_p]."""
    pages = _kv_aligned(pages, layer, n_kv_p)
    if pages.ndim == 5:
        return pages
    n_pages, page_size, n_kv, hd_p = pages.shape
    return pages.reshape(n_pages, page_size, n_kv * hd_p)


def _kv_spec(operand, layer, tok_offset=0):
    """BlockSpec of a `_kv_operand`: one page a grid step, picked by
    `_make_page_idx` — [1, page, n_kv_p * hd_p] of a flattened layer, or
    [1, page, n_kv, hd] of the whole pool with the layer dimension
    squeezed and led by `layer`. Either way the kernel reads the block
    as [page, n_kv, hd]."""
    if operand.ndim == 5:
        _, n_pages, page_size = operand.shape[:3]
        block = (None, 1, *operand.shape[2:])
    else:
        n_pages, page_size = operand.shape[:2]
        block, layer = (1, *operand.shape[1:]), None
    return pl.BlockSpec(
        block, _make_page_idx(page_size, n_pages, tok_offset, layer))


@functools.partial(jax.jit,
                   static_argnames=("interpret", "window", "layer", "rows"))
def paged_flash_decode(q, k_pages, v_pages, page_table, seq_lens,
                       interpret=False, window=0, layer=None, rows=0):
    """Flash-decode attention over paged KV (same contract as
    paged_attention.paged_decode_attention).

    q: [batch, n_heads, hd]; k_pages/v_pages: one layer
    [n_pages, page, n_kv, hd], or the whole pool
    [n_layers, n_pages, page, n_kv, hd] with a static `layer` (see
    `_kv_operand`: the kernel then indexes the layer itself);
    page_table: [batch, max_pages] int32; seq_lens: [batch] int32.
    `rows` (static; 0: not this form): the whole pool holds a page as
    FLAT ROWS, [n_layers, n_pages, page * rows, hd] with `rows` kv
    heads a token: the kernel's own view of a page, which a pool whose
    kv heads are no multiple of the 8 rows of a tile cannot be merged
    into without moving every tile (10 packed rows a token: the 5-D
    pool lies padded to 16 and the merge copied it, once a kind a
    layer a step). It goes to the kernel as it lies.
    Returns [batch, n_heads, hd].
    """
    batch, n_heads, hd = q.shape
    if rows:
        if k_pages.ndim != 4 or layer is None or hd % 128:
            raise ValueError("flat rows: the whole pool [layers, pages, "
                             "page * rows, hd] with hd a lane multiple")
        page_size, n_kv = k_pages.shape[-2] // rows, rows
    else:
        page_size, n_kv = k_pages.shape[-3:-1]
    as_lies = bool(rows) or k_pages.ndim == 5  # the pool goes whole
    max_pages = page_table.shape[1]

    # Pad to TPU tile boundaries: lanes (last dim) 128; sublane multiple
    # is dtype-dependent (8 for f32, 16 for bf16 — pallas guide tiling
    # table).
    q_p, _ = _pad_to(q, 2, 128)
    hd_p = q_p.shape[2]
    group = n_heads // n_kv
    sublane, n_kv_p = _decode_dims(q.dtype, n_kv, group)
    group_p = group
    if (n_kv_p != n_kv and as_lies and hd % 128 == 0
            and math.gcd(group, sublane) == 1):
        # A group that shares no factor with the sublane count, so
        # that only `sublane` kv heads make a sublane multiple of
        # query rows (7 query heads a kv head: 4 kv heads would become
        # 16), over a whole pool whose lanes are aligned: pad the
        # GROUP with zero query rows instead (7 -> 8), so that the
        # pool still goes to the kernel whole and as it is. Padding
        # its kv heads would slice out, copy and widen a layer of the
        # pool on every layer's call. The zero rows attend uniformly
        # and are dropped below.
        group_p = next(g for g in range(group, group + sublane + 1)
                       if (n_kv * g) % sublane == 0)
        n_kv_p = n_kv
        q_p = jnp.pad(q_p.reshape(batch, n_kv, group, hd_p),
                      ((0, 0), (0, 0), (0, group_p - group), (0, 0))
                      ).reshape(batch, n_kv * group_p, hd_p)
    elif (n_kv_p != n_kv and as_lies and hd % 128 == 0
            and -(-n_heads // sublane) * sublane // n_kv == group):
        # A group that does share a factor with the sublane count over
        # kv heads that are no multiple of what is left (4 query rows a
        # row of 10 packed kv heads: 40 query rows, and 12 kv heads
        # would make 48), over such a pool: pad the query rows' TAIL
        # with zero rows (40 -> 48) for the same reason. A tail row's
        # kv head (row // group: 10, 11) is none the pool has, so every
        # key is masked for it; its output is finite and dropped below.
        # Only where the padded rows still divide to the same group (48
        # // 10 == 4), which is what the kernel derives it from.
        n_kv_p = n_kv
        tail = -n_heads % sublane
        q_p = jnp.pad(q_p, ((0, 0), (0, tail), (0, 0)))
    elif n_kv_p != n_kv:
        q_p = jnp.pad(q_p, ((0, 0), (0, (n_kv_p - n_kv) * group), (0, 0)))
    n_heads_p = q_p.shape[1]

    # A page as [page * n_kv, hd] rows: token-major as it lies, so the
    # merge of (page, n_kv) leaves every (8, 128) tile where it is (a
    # bitcast for XLA, on the whole pool too) and a block of pages is
    # one [keys * n_kv, hd] operand of `_attend_rows`.
    if rows:
        if n_kv_p != n_kv:
            raise ValueError(f"{n_heads} query heads over flat rows of "
                             f"{n_kv} kv heads: no padding keeps the pool")
        k_f, v_f = k_pages, v_pages
    else:
        k_f = _kv_aligned(k_pages, layer, n_kv_p)
        v_f = _kv_aligned(v_pages, layer, n_kv_p)
        if k_f.ndim != 5:
            layer = None  # sliced out by `_kv_aligned`
        as_rows = (*k_f.shape[:-3], page_size * n_kv_p, hd_p)
        k_f, v_f = k_f.reshape(as_rows), v_f.reshape(as_rows)
    n_pages_block = _pages_per_block(
        page_size, math.prod(k_f.shape[-2:]) * k_f.dtype.itemsize,
        max_pages)
    buf = pltpu.VMEM((2, n_pages_block, *k_f.shape[-2:]), k_f.dtype)
    whole = pl.BlockSpec((batch, n_heads_p, hd_p),
                         lambda b, pt, sl: (0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, seq_lens
        grid=(batch,),
        in_specs=[
            whole,                              # q
            pl.BlockSpec(memory_space=pl.ANY),  # K, V: as they lie in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=whole,
        scratch_shapes=[
            buf, buf,                                    # K, V halves
            pltpu.SemaphoreType.DMA((2, 2)),             # [kind, half]
            pltpu.SMEM((1,), jnp.int32),                 # next block's half
            pltpu.VMEM((n_heads_p, hd_p), jnp.float32),  # acc
            pltpu.VMEM((n_heads_p, 1), jnp.float32),     # m
            pltpu.VMEM((n_heads_p, 1), jnp.float32),     # l
        ],
    )
    kernel = functools.partial(
        _kernel,
        layer=layer,
        page_size=page_size,
        n_kv=n_kv_p,
        n_heads=n_heads_p,
        window=window,
        scale=hd ** -0.5,  # NOT hd_p: zero-padded lanes add nothing, but
                           # the softmax temperature is the real head dim
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((batch, n_heads_p, hd_p), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table, seq_lens, q_p, k_f, v_f)
    if group_p != group:
        out = out.reshape(batch, n_kv, group_p, hd_p)[:, :, :group]
        return out.reshape(batch, n_heads, hd_p)[..., :hd]
    return out[:, :n_heads, :hd]


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_flash_decode_quantized(q, k_q, k_s, v_q, v_s, page_table,
                                 seq_lens, interpret=False, window=0):
    """Flash-decode attention DIRECTLY over int8-quantized KV pages
    (ops/kv_quant.py format): pages stay int8 in HBM — the decode cache
    holds 2x the tokens — and each page's DMA moves ~0.53x the bf16
    bytes, with dequantization fused into the kernel right after the
    load. Same contract as paged_flash_decode otherwise. Speed against
    the bf16 kernel: not measured; accuracy is the quantizer's
    (~0.4% rel).

    k_q/v_q: int8 [n_pages, page, n_kv, hd];
    k_s/v_s: f32 [n_pages, page, n_kv] (per-token-per-head scales).
    """
    batch, n_heads, hd = q.shape
    n_pages, page_size, n_kv, _ = k_q.shape
    max_pages = page_table.shape[1]

    q_p, _ = _pad_to(q, 2, 128)
    kq_p, _ = _pad_to(k_q, 3, 128)
    vq_p, _ = _pad_to(v_q, 3, 128)
    hd_p = q_p.shape[2]
    group = n_heads // n_kv
    _, n_kv_p = _decode_dims(q.dtype, n_kv, group)
    k_s_p, v_s_p = k_s, v_s
    if n_kv_p != n_kv:
        kq_p = jnp.pad(kq_p, ((0, 0), (0, 0), (0, n_kv_p - n_kv), (0, 0)))
        vq_p = jnp.pad(vq_p, ((0, 0), (0, 0), (0, n_kv_p - n_kv), (0, 0)))
        k_s_p = jnp.pad(k_s, ((0, 0), (0, 0), (0, n_kv_p - n_kv)))
        v_s_p = jnp.pad(v_s, ((0, 0), (0, 0), (0, n_kv_p - n_kv)))
        q_p = jnp.pad(q_p, ((0, 0), (0, (n_kv_p - n_kv) * group), (0, 0)))
    n_heads_p = n_kv_p * group

    kq_f = kq_p.reshape(n_pages, page_size, n_kv_p * hd_p)
    vq_f = vq_p.reshape(n_pages, page_size, n_kv_p * hd_p)

    _page_idx = _make_page_idx(page_size, n_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, max_pages),
        in_specs=[
            pl.BlockSpec((1, n_heads_p, hd_p), lambda b, j, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, page_size, n_kv_p * hd_p), _page_idx),
            pl.BlockSpec((1, page_size, n_kv_p), _page_idx),
            pl.BlockSpec((1, page_size, n_kv_p * hd_p), _page_idx),
            pl.BlockSpec((1, page_size, n_kv_p), _page_idx),
        ],
        out_specs=pl.BlockSpec(
            (1, n_heads_p, hd_p), lambda b, j, pt, sl: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((n_heads_p, hd_p), jnp.float32),
            pltpu.VMEM((n_heads_p, 1), jnp.float32),
            pltpu.VMEM((n_heads_p, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel_q,
        page_size=page_size,
        n_kv=n_kv_p,
        hd=hd_p,
        n_heads=n_heads_p,
        window=window,
        scale=hd ** -0.5,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((batch, n_heads_p, hd_p), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table, seq_lens, q_p, kq_f, k_s_p, vq_f, v_s_p)
    return out[:, :n_heads, :hd]


def _kernel_multi(page_tbl_ref, seq_lens_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size, n_kv, hd, group,
                  m_tok, scale, window=0):
    """m-token verify attention over paged KV (speculative verify).
    Query rows are laid out kv-head-major —
    row = h * (m_tok * group) + j * group + g for token j, query head
    h*group+g — so each kv head's dot covers all m tokens' heads in one
    MXU op; the causal limit is per ROW: token j sees positions
    < seq_len + j + 1 (its own KV was scattered into the pages before
    the call)."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = seq_lens_ref[b]
    start = j * page_size
    live = start < seq_len + m_tok
    if window:
        # A page wholly below the LOWEST band floor (token 0's:
        # seq_len + 1 - window) is dead for every row.
        live = jnp.logical_and(
            live, start + page_size > seq_len + 1 - window
        )

    @pl.when(live)
    def _step():
        rows_per_kv = m_tok * group
        rows = n_kv * rows_per_kv
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        tok = (row % rows_per_kv) // group  # token index per query row
        limit = seq_len + tok + 1
        low = jnp.maximum(limit - window, 0) if window else None
        _attend(q_ref[0],
                k_ref[0].reshape(page_size, n_kv, hd),
                v_ref[0].reshape(page_size, n_kv, hd),
                acc_ref, m_ref, l_ref, n_kv=n_kv, n_heads=rows,
                scale=scale, start=start, seq_len=seq_len,
                rows_per_kv=rows_per_kv, limit=limit, low=low)

    @pl.when(j == n_pages - 1)
    def _finish():
        # No l == 0 guard needed: page 0 holds position 0, which is
        # < seq_len + tok + 1 for every row, so every row folds at
        # least one valid logit (same invariant as the decode kernel).
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "window", "layer"))
def paged_flash_verify(q, k_pages, v_pages, page_table, seq_lens,
                       interpret=False, window=0, layer=None):
    """m-token flash verify over paged KV (same contract as
    paged_attention.multi_token_paged_attention): q [batch, m, n_heads,
    hd]; token j's KV must already be scattered at position
    seq_lens[b] + j. Streams pages HBM → VMEM like the decode kernel —
    nothing is gathered or materialized — with the causal limit applied
    per token row. k_pages/v_pages: one layer or the whole pool plus a
    static `layer`, as in paged_flash_decode. Returns
    [batch, m, n_heads, hd]."""
    batch, m_tok, n_heads, hd = q.shape
    page_size, n_kv = k_pages.shape[-3:-1]
    max_pages = page_table.shape[1]
    group = n_heads // n_kv

    q_p, _ = _pad_to(q, 3, 128)
    hd_p = q_p.shape[3]
    # Pad kv heads so n_kv_p * (m_tok * group) rows hit a sublane
    # multiple (same math as decode, with the m-fold group).
    _, n_kv_p = _decode_dims(q.dtype, n_kv, m_tok * group)
    if n_kv_p != n_kv:
        q_p = jnp.pad(
            q_p, ((0, 0), (0, 0), (0, (n_kv_p - n_kv) * group), (0, 0))
        )
    rows = n_kv_p * m_tok * group

    # kv-head-major query rows: [b, j, h*group+g] -> h*(m*group)+j*group+g.
    q_r = q_p.reshape(batch, m_tok, n_kv_p, group, hd_p)
    q_r = q_r.transpose(0, 2, 1, 3, 4).reshape(batch, rows, hd_p)

    k_f = _kv_operand(k_pages, layer, n_kv_p)
    v_f = _kv_operand(v_pages, layer, n_kv_p)
    kv_spec = _kv_spec(k_f, layer, tok_offset=m_tok)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, max_pages),
        in_specs=[
            pl.BlockSpec((1, rows, hd_p), lambda b, j, pt, sl: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, rows, hd_p), lambda b, j, pt, sl: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, hd_p), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _kernel_multi,
        page_size=page_size,
        n_kv=n_kv_p,
        hd=hd_p,
        group=group,
        m_tok=m_tok,
        window=window,
        scale=hd ** -0.5,
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((batch, rows, hd_p), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table, seq_lens, q_r, k_f, v_f)
    # Invert the kv-major layout and strip padding.
    out = out.reshape(batch, n_kv_p, m_tok, group, hd_p)
    out = out.transpose(0, 2, 1, 3, 4).reshape(
        batch, m_tok, n_kv_p * group, hd_p
    )
    return out[:, :, :n_heads, :hd]


def verify_attention(q, k_pages, v_pages, page_table, seq_lens, window=0,
                     layer=None):
    """m-token paged verify attention with automatic backend choice:
    the pallas streaming kernel on TPU, the XLA gather path elsewhere.
    k_pages/v_pages: one layer, or the whole pool plus `layer`."""
    if jax.default_backend() == "tpu":
        return paged_flash_verify(q, k_pages, v_pages, page_table, seq_lens,
                                  window=window, layer=layer)
    return xla_ref.multi_token_paged_attention(
        q, k_pages, v_pages, page_table, seq_lens, window=window,
        layer=layer
    )


def decode_attention(q, k_pages, v_pages, page_table, seq_lens, window=0,
                     layer=None, rows=0):
    """Paged decode attention with automatic backend choice: the pallas
    flash kernel on TPU, the XLA gather path elsewhere.
    k_pages/v_pages: one layer, or the whole pool plus `layer`; with
    `rows`, the whole pool with a page as flat rows
    (`paged_flash_decode`), which the gather path reads as the 5-D pool
    it is row for row."""
    if jax.default_backend() == "tpu":
        return paged_flash_decode(q, k_pages, v_pages, page_table, seq_lens,
                                  window=window, layer=layer, rows=rows)
    if rows:
        as_5d = (*k_pages.shape[:2], k_pages.shape[2] // rows, rows,
                 k_pages.shape[3])
        k_pages, v_pages = k_pages.reshape(as_5d), v_pages.reshape(as_5d)
    return xla_ref.paged_decode_attention(
        q, k_pages, v_pages, page_table, seq_lens, window=window,
        layer=layer
    )


def decode_attention_tp(mesh, q, k_pages, v_pages, page_table, seq_lens,
                        axis="tp", interpret=None, window=0):
    """paged_flash_decode under tensor parallelism: kv heads sharded
    over the mesh's `axis`, q heads co-sharded (each device keeps its
    kv heads' whole GQA group), page pool replicated batch-wise but
    SHARDED on the kv-head dim — the actual multi-chip serving layout,
    where each chip's HBM holds only its heads' KV. Decode attention is
    head-parallel, so shard_map needs NO collective: every device runs
    the pallas kernel on its local heads and the output concatenates
    over heads.

    shard_map (not GSPMD auto-partitioning) because pallas_call is a
    custom call XLA cannot split; this wrapper IS the distribution
    story for the kernel. `interpret=None` auto-selects interpret mode
    off-TPU, so the 8-device CPU mesh runs the REAL kernel code path
    (VERDICT r3 item 4), not the XLA fallback.

    Requires n_kv_heads % mesh.shape[axis] == 0.
    """
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tp = mesh.shape[axis]
    n_kv = k_pages.shape[2]
    if n_kv % tp:
        raise ValueError(f"n_kv_heads {n_kv} not divisible by {axis}={tp}")

    def local(q, kp, vp, pt, sl):  # window closes over statically
        return paged_flash_decode(q, kp, vp, pt, sl, interpret=interpret,
                                  window=window)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(
            P(None, axis, None),        # q: heads sharded
            P(None, None, axis, None),  # k_pages: kv heads sharded
            P(None, None, axis, None),  # v_pages
            P(None, None),              # page_table: replicated
            P(None),                    # seq_lens: replicated
        ),
        out_specs=P(None, axis, None),
        check_vma=False,
    )(q, k_pages, v_pages, page_table, seq_lens)


def decode_attention_quantized_tp(mesh, q, k_q, k_s, v_q, v_s, page_table,
                                  seq_lens, axis="tp", interpret=None,
                                  window=0):
    """Int8 variant of :func:`decode_attention_tp`: quantized pages and
    their per-token-per-head scales both shard on the kv-head dim; the
    fused dequant-in-kernel path runs per device on local heads."""
    from jax.sharding import PartitionSpec as P

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tp = mesh.shape[axis]
    if k_q.shape[2] % tp:
        raise ValueError(
            f"n_kv_heads {k_q.shape[2]} not divisible by {axis}={tp}"
        )

    def local(q, kq, ks, vq, vs, pt, sl):
        return paged_flash_decode_quantized(
            q, kq, ks, vq, vs, pt, sl, interpret=interpret, window=window
        )

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(
            P(None, axis, None),        # q
            P(None, None, axis, None),  # k int8 pages
            P(None, None, axis),        # k scales [n, page, n_kv]
            P(None, None, axis, None),  # v int8 pages
            P(None, None, axis),        # v scales
            P(None, None),
            P(None),
        ),
        out_specs=P(None, axis, None),
        check_vma=False,
    )(q, k_q, k_s, v_q, v_s, page_table, seq_lens)


def decode_attention_quantized(q, k_q, k_s, v_q, v_s, page_table, seq_lens,
                               window=0):
    """Decode over int8 pages with automatic backend choice: fused
    dequant-in-kernel on TPU; gather-then-dequantize + the XLA path
    elsewhere (gathering FIRST keeps the fallback's footprint at the
    referenced pages, not the whole pool — the capacity benefit
    quantization buys must survive the fallback)."""
    if jax.default_backend() == "tpu":
        return paged_flash_decode_quantized(
            q, k_q, k_s, v_q, v_s, page_table, seq_lens, window=window
        )
    from . import kv_quant

    sel = jnp.clip(page_table, 0, k_q.shape[0] - 1)  # [batch, max_pages]
    batch, max_pages = sel.shape
    kg = kv_quant.dequantize_kv_pages(
        jnp.take(k_q, sel.reshape(-1), axis=0),
        jnp.take(k_s, sel.reshape(-1), axis=0), q.dtype,
    )
    vg = kv_quant.dequantize_kv_pages(
        jnp.take(v_q, sel.reshape(-1), axis=0),
        jnp.take(v_s, sel.reshape(-1), axis=0), q.dtype,
    )
    # The gathered pages are already in table order: re-index with the
    # identity table over the gathered pool.
    ident = jnp.arange(batch * max_pages, dtype=jnp.int32).reshape(
        batch, max_pages
    )
    return xla_ref.paged_decode_attention(q, kg, vg, ident, seq_lens,
                                          window=window)
