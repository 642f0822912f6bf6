"""Pallas TPU kernel: flash attention for prefill (dense, causal, GQA).

The XLA path (paged_attention.prefill_attention) materializes the full
[batch, heads, S, S] logits tensor in HBM — O(S^2) memory, which is what
caps prefill sequence length, the expensive phase of prefill/decode
disaggregation. This kernel never materializes logits: the grid runs
(batch*heads, q_blocks, kv_blocks) with the kv sweep innermost, holding a
[BQ, head_dim] online-softmax accumulator in VMEM scratch; each step is
one [BQ, BK] logits tile on the MXU, masked, and folded in. HBM traffic
is one pass over Q and (per q-block) K/V; memory is O(S).

Causal handling: kv blocks strictly above the diagonal are skipped for
compute (pl.when) AND for HBM traffic — the k/v index map clamps the
block index at the last one the diagonal touches, and pallas elides the
re-fetch when consecutive grid steps map to the same block (same trick as
pallas_paged_attention's page freeze).

`flash_prefill` picks this kernel on TPU backends and falls back to the
XLA path elsewhere (tests run the kernel in interpret mode so CPU CI
covers the same code path bit-for-bit).

Training goes through a recompute-based O(S) flash BACKWARD (two pallas
kernels — dq with the kv sweep innermost, dk/dv with the q sweep
innermost; FlashAttention-2 recipe): the forward saves only q/k/v/o and
the row logsumexp, each backward tile recomputes its logits block from
q/k + lse, and no [S, S] tensor is ever materialized in either pass.
Measured on v5e at S=4096 (bf16, B=1, H=8, D=128): the compiled
grad(flash) allocates 0 MiB of temporaries where grad(XLA path)
allocates 1040 MiB (the [B, H, S, S] logits + its cotangent).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as xla_ref

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *rest,
            bq, bk, q_len, kv_len, scale, causal, window=0,
            with_lse=False):
    if with_lse:  # extra lse output slot before the scratch refs
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
        lse_ref = None
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk
    # A kv block strictly above the causal diagonal contributes nothing.
    # With a cached prefix (kv_len > q_len) the diagonal shifts right by
    # the prefix length: query row i may see kv columns <= i + offset.
    offset = kv_len - q_len
    live = (k_start <= q_start + bq - 1 + offset) if causal else (ki >= 0)
    if causal and window:
        # ...and a kv block entirely below every query's band floor is
        # equally dead (least-strict row is the tile's FIRST query).
        live = jnp.logical_and(
            live, k_start + bk - 1 > q_start + offset - window
        )

    def _attend(masked):
        q = q_ref[0]  # [BQ, D]
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]
        precision = xla_ref.matmul_precision(q.dtype)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        ) * scale  # [BQ, BK] f32
        if masked:
            mask = _tile_mask(logits.shape, q_start, k_start, q_len,
                              kv_len, causal, window)
            logits = jnp.where(mask, logits, _NEG_INF)

        m_prev = m_ref[...]  # [BQ, 1]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)  # [BQ, BK]
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision,
        )  # [BQ, D]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + l_cur

    # Interior tiles have an all-true mask: building it anyway costs
    # ~6 VPU ops/element (two iotas, compares, and, where) on a tile
    # whose MXU work it rivals (flash attention on TPU is VPU-bound at
    # hd=128). Skip the mask there; only boundary/diagonal tiles pay it.
    # At S=4096 with 1024-blocks, 6 of the 10 live tiles are interior.
    _masked_dispatch(
        live,
        _interior_tile(q_start, k_start, bq, bk, q_len, kv_len, causal,
                       window),
        _attend,
    )

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        if lse_ref is not None:
            # Row logsumexp, lane-replicated to the 128-lane tile (the
            # residual layout jax's own TPU flash kernels use) so the
            # backward reads it as a [BQ, 1] column with no relayout.
            lse = m_ref[...] + jnp.log(l_ref[...])  # [BQ, 1]
            lse_ref[0] = jax.lax.broadcast_in_dim(
                lse, lse_ref.shape[1:], (0, 1)
            )


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _auto_block(seq_len):
    # 1024x1024 blocks measured 1.7-2.2x faster than 512x512 at S=4096
    # on v5e (0.54-0.69 ms vs 1.16 ms, 50-65% MFU vs 30% — r4 sweep;
    # per-grid-step overhead amortizes over bigger tiles). 2048+ blocks
    # fail to compile (VMEM), so 1024 is the ceiling. Between 512 and
    # 1024, pick whichever pads the sequence less: fully-padded rows in
    # the last block still run full MXU tiles, so S=1025 at block 1024
    # would waste ~2x the compute that block 512 does.
    full = ((seq_len + 127) // 128) * 128
    if full <= 512:
        return full
    pad512 = -(-seq_len // 512) * 512
    pad1024 = -(-seq_len // 1024) * 1024
    return 512 if pad512 < pad1024 else 1024


def _tile_mask(shape, q_start, k_start, q_len, kv_len, causal, window=0):
    """Validity mask for one [BQ, BK] logits tile: padded query and key
    positions are dead, plus the causal triangle (and, with window > 0,
    the sliding band's floor: query i also needs
    pos_k > i + offset - window). ONE definition shared by the forward
    and both backward kernels — forward/backward masks must never
    diverge.

    kv_len may exceed q_len (prefix-cached prefill: suffix queries over
    prefix + suffix KV); the causal diagonal then shifts right by the
    prefix length kv_len - q_len."""
    pos_q = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    pos_k = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = jnp.logical_and(pos_k < kv_len, pos_q < q_len)
    if causal:
        mask = jnp.logical_and(mask, pos_k <= pos_q + (kv_len - q_len))
        if window:
            mask = jnp.logical_and(
                mask, pos_k > pos_q + (kv_len - q_len) - window
            )
    return mask


def _bwd_tile(q, k, v, do, lse, dvec, q_start, k_start, q_len, kv_len,
              scale, causal, window=0, masked=True):
    """Shared backward tile recompute: probabilities p from q/k + saved
    lse, and dS = P * (dP - D) * scale. Returns (p, ds, precision).
    ``masked=False`` skips the mask build for interior tiles (all-true
    mask — see _interior_tile)."""
    precision = xla_ref.matmul_precision(q.dtype)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    ) * scale
    if masked:
        mask = _tile_mask(logits.shape, q_start, k_start, q_len, kv_len,
                          causal, window)
        logits = jnp.where(mask, logits, _NEG_INF)
    p = jnp.exp(logits - lse)  # the forward's exact probabilities
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision,
    )
    ds = p * (dp - dvec) * scale
    return p, ds, precision


def _interior_tile(q_start, k_start, bq, bk, q_len, kv_len, causal,
                   window=0):
    """True for tiles whose validity mask is all-true — fully inside the
    q/kv bounds, (if causal) fully below the shifted diagonal, and (if
    windowed) fully above the band floor: the mask build (~6 VPU
    ops/element) is pure waste there. Shared by the forward and both
    backward kernels so the skip condition can never diverge from
    _tile_mask's semantics."""
    in_bounds = jnp.logical_and(k_start + bk <= kv_len,
                                q_start + bq <= q_len)
    if not causal:
        return in_bounds
    offset = kv_len - q_len
    interior = jnp.logical_and(in_bounds,
                               k_start + bk - 1 <= q_start + offset)
    if window:
        # Strictest row is the tile's LAST query (largest band floor):
        # every k in the tile must satisfy k > q + offset - window.
        interior = jnp.logical_and(
            interior,
            k_start > q_start + bq - 1 + offset - window,
        )
    return interior


def _masked_dispatch(live, interior, attend):
    """ONE dispatch structure for every kernel: live interior tiles run
    ``attend(masked=False)`` (no mask build), live boundary/diagonal
    tiles run ``attend(masked=True)``. Shared so the forward and both
    backward kernels can never diverge in how they apply the skip."""
    @pl.when(jnp.logical_and(live, interior))
    def _step_interior():
        attend(False)

    @pl.when(jnp.logical_and(live, jnp.logical_not(interior)))
    def _step_masked():
        attend(True)


def _make_row_maps(n_heads, n_kv, group, block_q, block_k, causal,
                   offset=0):
    """Index-map closures shared by forward and backward pallas calls.

    _kv_row: grid row (b, h) → GQA kv row (b, h // group).
    _kv_idx (kv sweep innermost): past the causal diagonal the kv block
    index freezes at the last live one — compute is skipped in-kernel
    and the repeated index lets pallas elide the HBM fetch entirely.
    _q_idx (q sweep innermost): mirror image — q blocks strictly below
    the diagonal freeze at the first live one.

    `offset` = kv_len - q_len (a cached prefix shifts the causal
    diagonal right: query row i sees kv columns <= i + offset).
    """

    def _kv_row(r):
        return (r // n_heads) * n_kv + (r % n_heads) // group

    def _kv_idx(r, qi, ki):
        if causal:
            last_live = (qi * block_q + block_q - 1 + offset) // block_k
            ki = jnp.minimum(ki, last_live)
        return (_kv_row(r), ki, 0)

    def _q_idx(r, ki, qi):
        if causal:
            first_live = jnp.maximum(ki * block_k - offset, 0) // block_q
            qi = jnp.maximum(qi, first_live)
        return (r, qi, 0)

    return _kv_row, _kv_idx, _q_idx


def _layout_rows(x, heads, block):
    """[B, S, heads, hd] → padded [B*heads, S_pad, hd_pad] rows (seq
    padded to the block size, head_dim to the 128-lane boundary)."""
    b, s, h, hd = x.shape
    return _pad_axis(_pad_axis(
        x.transpose(0, 2, 1, 3).reshape(b * h, s, hd), 1, block), 2, 128)


def _forward_impl(q, k, v, causal, block_q, block_k, interpret, with_lse,
                  window=0):
    batch, q_len, n_heads, hd = q.shape
    kv_len = k.shape[1]
    n_kv = k.shape[2]
    group = n_heads // n_kv
    scale = hd ** -0.5
    if causal and kv_len < q_len:
        raise ValueError(
            f"causal attention needs kv_len >= q_len, got {kv_len} < {q_len}"
        )

    qf = _layout_rows(q, n_heads, block_q)
    kf = _layout_rows(k, n_kv, block_k)
    vf = _layout_rows(v, n_kv, block_k)
    hd_p = qf.shape[2]
    # The values' width may differ from the queries' and keys' (latent
    # attention: 192 against 128); output and accumulator are V's.
    hd_v, hd_vp = v.shape[3], vf.shape[2]
    if with_lse and hd_v != hd:
        raise NotImplementedError(
            "flash backward with a value width that differs from the "
            "query width")
    nq = qf.shape[1] // block_q
    nk = kf.shape[1] // block_k
    _, _kv_idx, _ = _make_row_maps(
        n_heads, n_kv, group, block_q, block_k, causal,
        offset=kv_len - q_len,
    )

    out_shapes = [jax.ShapeDtypeStruct((*qf.shape[:2], hd_vp), q.dtype)]
    out_specs = [
        pl.BlockSpec((1, block_q, hd_vp), lambda bh, qi, ki: (bh, qi, 0))
    ]
    if with_lse:
        out_shapes.append(jax.ShapeDtypeStruct(
            (qf.shape[0], qf.shape[1], 128), jnp.float32
        ))
        out_specs.append(pl.BlockSpec(
            (1, block_q, 128), lambda bh, qi, ki: (bh, qi, 0)
        ))

    res = pl.pallas_call(
        functools.partial(
            _kernel, bq=block_q, bk=block_k, q_len=q_len, kv_len=kv_len,
            scale=scale, causal=causal, window=window, with_lse=with_lse,
        ),
        out_shape=out_shapes,
        grid=(batch * n_heads, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd_p), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd_p), _kv_idx),
            pl.BlockSpec((1, block_k, hd_vp), _kv_idx),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((block_q, hd_vp), jnp.float32),  # acc
            pltpu.VMEM((block_q, 1), jnp.float32),     # m
            pltpu.VMEM((block_q, 1), jnp.float32),     # l
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = res[0][:, :q_len, :hd_v]
    out = out.reshape(batch, n_heads, q_len, hd_v).transpose(0, 2, 1, 3)
    if not with_lse:
        return out
    # Residual logsumexp as unpadded [B, H, S] (lane 0 of the replicated
    # tile); padded rows are sliced off here and re-padded with ZEROS in
    # the backward — a padded row's raw lse is -inf (log 0), which would
    # turn the backward's exp/multiply chain into NaNs.
    lse = res[1][:, :q_len, 0].reshape(batch, n_heads, q_len)
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window"),
)
def flash_prefill_attention(q, k, v, causal=True, block_q=None, block_k=None,
                            interpret=False, window=0):
    """Flash prefill attention (same contract as
    paged_attention.prefill_attention).

    q: [batch, s_q, n_heads, hd]; k/v: [batch, s_kv, n_kv, hd] (GQA —
    n_heads must be a multiple of n_kv). Returns [batch, s_q, n_heads, hd].
    v may have a width of its own, which is then the output's.
    s_kv may exceed s_q (prefix-cached prefill: suffix queries attending
    over restored-prefix + suffix KV); under `causal` the diagonal then
    shifts right by s_kv - s_q, i.e. query i sees kv j <= i + prefix_len.

    block_q/block_k default via _auto_block: up to 1024, preferring the
    choice of {512, 1024} that pads the sequence least. Measured on
    v5e: 512x512 runs ~13x faster than 128x128 at S=4096 (per-step
    grid overhead dominates small blocks) and 1024x1024 another
    1.7-2.2x faster than 512x512 (50-65% MFU); smaller sequences
    shrink the block to avoid padding waste.
    """
    if block_q is None:
        block_q = _auto_block(q.shape[1])
    if block_k is None:
        block_k = _auto_block(k.shape[1])
    return _forward_impl(
        q, k, v, causal, block_q, block_k, interpret, with_lse=False,
        window=window,
    )


# ---------------------------------------------------------------------------
# Backward: recompute-based O(S) flash backward (FlashAttention-2 style).
#
# The forward saves only (q, k, v, o, lse) — no [S, S] tensor ever exists.
# Backward recomputes each logits tile from q/k plus the saved row
# logsumexp (p = exp(logits - lse), exactly the forward's normalized
# probabilities) and contracts it with the cotangent:
#   D  = rowsum(dO * O)                      (XLA elementwise, O(S*hd))
#   dV = P^T @ dO
#   dP = dO @ V^T
#   dS = P * (dP - D) * scale
#   dQ = dS @ K        (kernel A: kv sweep innermost, dq accumulator)
#   dK = dS^T @ Q      (kernel B: q sweep innermost, dk/dv accumulators)
# Two kernels because TPU pallas accumulates in VMEM scratch along the
# innermost grid axis — dq wants the kv axis innermost, dk/dv want q.
# Causal skipping mirrors the forward: dead tiles skip compute (pl.when)
# and freeze their index maps so the HBM fetch is elided too.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref,
                   dq_acc, *, bq, bk, q_len, kv_len, scale, causal,
                   window=0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * bq
    k_start = ki * bk
    offset = kv_len - q_len
    live = (k_start <= q_start + bq - 1 + offset) if causal else (ki >= 0)
    if causal and window:
        live = jnp.logical_and(
            live, k_start + bk - 1 > q_start + offset - window
        )

    def _accum(masked):
        k = k_ref[0]
        _, ds, precision = _bwd_tile(
            q_ref[0], k, v_ref[0], do_ref[0],
            lse_ref[0][:, :1], d_ref[0][:, :1],  # lane-replicated tiles
            q_start, k_start, q_len, kv_len, scale, causal, window,
            masked=masked,
        )
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )

    _masked_dispatch(
        live,
        _interior_tile(q_start, k_start, bq, bk, q_len, kv_len, causal,
                       window),
        _accum,
    )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, d_ref, k_ref, v_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    bq, bk, q_len, kv_len, scale, causal, window=0):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * bq
    k_start = ki * bk
    offset = kv_len - q_len
    live = (q_start + bq - 1 + offset >= k_start) if causal else (qi >= 0)
    if causal and window:
        # A q block whose every row's band floor is above this k block
        # contributes nothing (least-strict row: the tile's FIRST q).
        live = jnp.logical_and(
            live, q_start <= k_start + bk - 1 - offset + window - 1
        )

    def _accum(masked):
        q = q_ref[0]
        do = do_ref[0]
        p, ds, precision = _bwd_tile(
            q, k_ref[0], v_ref[0], do,
            lse_ref[0][:, :1], d_ref[0][:, :1],
            q_start, k_start, q_len, kv_len, scale, causal, window,
            masked=masked,
        )
        # dV += P^T @ dO — contract the BQ axis of both (no transpose).
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision,
        )

    _masked_dispatch(
        live,
        _interior_tile(q_start, k_start, bq, bk, q_len, kv_len, causal,
                       window),
        _accum,
    )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal, interpret,
                    block_q=None, block_k=None, window=0):
    """O(S)-memory gradients from the saved residuals. Returns
    (dq, dk, dv) with the input shapes/dtypes."""
    batch, q_len, n_heads, hd = q.shape
    kv_len = k.shape[1]
    n_kv = k.shape[2]
    group = n_heads // n_kv
    scale = hd ** -0.5
    if block_q is None:
        block_q = _auto_block(q_len)
    if block_k is None:
        block_k = _auto_block(kv_len)

    qf = _layout_rows(q, n_heads, block_q)
    dof = _layout_rows(g, n_heads, block_q)
    kf = _layout_rows(k, n_kv, block_k)
    vf = _layout_rows(v, n_kv, block_k)
    hd_p = qf.shape[2]
    sq_p = qf.shape[1]
    sk_p = kf.shape[1]
    nq = sq_p // block_q
    nk = sk_p // block_k
    bh = batch * n_heads

    # Row scalars, lane-replicated; padded rows become ZERO (not -inf /
    # NaN), which the masked kernels turn into exactly-zero contributions.
    dvec = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    dvec = dvec.transpose(0, 2, 1).reshape(bh, q_len)  # [BH, S]
    lsef = lse.reshape(bh, q_len)
    dvec = jnp.broadcast_to(
        _pad_axis(dvec, 1, block_q)[..., None], (bh, sq_p, 128)
    )
    lsef = jnp.broadcast_to(
        _pad_axis(lsef, 1, block_q)[..., None], (bh, sq_p, 128)
    )

    _kv_row, _kv_idx, _q_idx_b = _make_row_maps(
        n_heads, n_kv, group, block_q, block_k, causal,
        offset=kv_len - q_len,
    )

    # --- kernel A: dq (kv sweep innermost, like the forward) ---
    def _q_idx_a(r, qi, ki):
        return (r, qi, 0)

    dqf = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, bq=block_q, bk=block_k, q_len=q_len,
            kv_len=kv_len, scale=scale, causal=causal, window=window,
        ),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd_p), _q_idx_a),
            pl.BlockSpec((1, block_k, hd_p), _kv_idx),
            pl.BlockSpec((1, block_k, hd_p), _kv_idx),
            pl.BlockSpec((1, block_q, hd_p), _q_idx_a),
            pl.BlockSpec((1, block_q, 128), _q_idx_a),
            pl.BlockSpec((1, block_q, 128), _q_idx_a),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd_p), _q_idx_a),
        scratch_shapes=[pltpu.VMEM((block_q, hd_p), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, dvec)
    dq = dqf[:, :q_len, :hd].reshape(batch, n_heads, q_len, hd)
    dq = dq.transpose(0, 2, 1, 3)

    # --- kernel B: dk/dv per q-head (q sweep innermost), then GQA-sum ---
    def _k_idx_b(r, ki, qi):
        return (_kv_row(r), ki, 0)

    def _o_idx_b(r, ki, qi):
        return (r, ki, 0)

    dkf, dvf = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, bq=block_q, bk=block_k, q_len=q_len,
            kv_len=kv_len, scale=scale, causal=causal, window=window,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_p, hd_p), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_p, hd_p), v.dtype),
        ],
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, hd_p), _q_idx_b),
            pl.BlockSpec((1, block_q, hd_p), _q_idx_b),
            pl.BlockSpec((1, block_q, 128), _q_idx_b),
            pl.BlockSpec((1, block_q, 128), _q_idx_b),
            pl.BlockSpec((1, block_k, hd_p), _k_idx_b),
            pl.BlockSpec((1, block_k, hd_p), _k_idx_b),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd_p), _o_idx_b),
            pl.BlockSpec((1, block_k, hd_p), _o_idx_b),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd_p), jnp.float32),
            pltpu.VMEM((block_k, hd_p), jnp.float32),
        ],
        interpret=interpret,
    )(qf, dof, lsef, dvec, kf, vf)
    # Per-q-head grads → sum the GQA group onto each kv head.
    dk = dkf[:, :kv_len, :hd].reshape(batch, n_kv, group, kv_len, hd)
    dv = dvf[:, :kv_len, :hd].reshape(batch, n_kv, group, kv_len, hd)
    dk = dk.sum(axis=2).transpose(0, 2, 1, 3).astype(k.dtype)
    dv = dv.sum(axis=2).transpose(0, 2, 1, 3).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_with_vjp(q, k, v, causal, interpret, window):
    return flash_prefill_attention(q, k, v, causal=causal,
                                   interpret=interpret, window=window)


def _flash_fwd(q, k, v, causal, interpret, window):
    out, lse = _forward_impl(
        q, k, v, causal, _auto_block(q.shape[1]), _auto_block(k.shape[1]),
        interpret, with_lse=True, window=window,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, interpret, window, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_backward(q, k, v, o, lse, g, causal, interpret,
                           window=window)


_flash_with_vjp.defvjp(_flash_fwd, _flash_bwd)


def flash_prefill(q, k, v, causal=True, window=0):
    """Prefill attention with automatic backend choice: the pallas flash
    kernel on TPU (differentiable — see _flash_with_vjp), the XLA path
    elsewhere. window > 0 = sliding-window band (Mistral/Qwen2)."""
    if jax.default_backend() == "tpu":
        return _flash_with_vjp(q, k, v, causal, False, window)
    return xla_ref.prefill_attention(q, k, v, causal=causal, window=window)
