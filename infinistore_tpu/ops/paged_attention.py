"""Paged-KV attention ops (XLA implementation).

The reference is a KV *store*; the attention consuming those pages lives
in the inference engine (vLLM). These ops are the TPU-side consumer the
store was built for (BASELINE.json configs 3-5): KV lives in fixed-size
pages addressed by a page table — the same unit the store moves — so
offload/restore is a pure page-copy with no re-layout.

Design for the MXU/XLA: everything is static-shaped; page gathering is a
`jnp.take` (XLA gather, fuses with the following matmuls), masking is
arithmetic (no dynamic control flow), softmax/matmuls run in fp32
accumulation over bf16 operands. A pallas flash-decode kernel can replace
`paged_decode_attention` later without changing callers.
"""

import jax
import jax.numpy as jnp


def gather_pages(pages, page_indices, layer=None):
    """pages: [n_pages, page, ...]; page_indices: [batch, pages_per_seq]
    → [batch, pages_per_seq, page, ...]. With `layer` (static int),
    pages is the whole pool [n_layers, n_pages, page, ...] and the
    gather reads that layer's pages straight out of it — no layer-sized
    slice is produced on the way."""
    if layer is None:
        return jnp.take(pages, page_indices, axis=0)
    # jnp.take's out-of-range semantics (fill), so both forms agree on
    # every table however it is padded.
    return pages.at[layer, page_indices].get(mode="fill")


def scatter_kv_to_pages(pages, new_kv, page_indices, start_in_page,
                        layer=None):
    """Write `new_kv` [batch, 1, n_kv, hd] (one decode step per sequence)
    into `pages` at (page_indices[b], start_in_page[b]).

    Functional update (XLA scatter): returns the new pages array. Batch
    entries may target distinct pages; duplicate targets are undefined
    (callers allocate one page per sequence tail, as vLLM does).

    A whole pool of FOUR dimensions (with `layer`) holds a page as
    FLAT ROWS [n_layers, n_pages, page * n_kv, hd], row = token * n_kv
    + kv head (the form of a family whose kv rows a token are no
    multiple of the 8 a tile holds:
    pallas_paged_attention.paged_flash_decode); the token's n_kv rows
    go to rows [start * n_kv, (start + 1) * n_kv) of its page.
    """
    if layer is not None and pages.ndim == 4:
        n_kv = new_kv.shape[2]
        rows = start_in_page[:, None] * n_kv + jnp.arange(n_kv)[None]
        return pages.at[layer, page_indices[:, None], rows].set(
            new_kv[:, 0], mode="drop", unique_indices=False)
    return scatter_kv_multi(pages, new_kv[:, 0], page_indices,
                            start_in_page, layer=layer)


def scatter_kv_multi(pages, new_kv, page_indices, start_in_page,
                     layer=None):
    """Multi-token variant: write `new_kv` [batch, m, n_kv, hd] at
    (page_indices[b, j], start_in_page[b, j]) — the m tokens of a
    speculative-verify step. Out-of-range page ids are dropped.

    pages: one layer [n_pages, page, n_kv, hd], or with `layer` (static
    int) the whole pool [n_layers, n_pages, page, n_kv, hd]: the rows go
    into that layer of the pool itself, which inside a program that
    donates the pool is an update in place and touches nothing else."""
    where = (page_indices, start_in_page)
    if layer is not None:
        where = (layer, *where)
    return pages.at[where].set(new_kv, mode="drop", unique_indices=False)


def matmul_precision(dtype):
    """MXU precision policy shared by the XLA paths and pallas kernels.

    On TPU, DEFAULT precision downcasts f32 MXU operands to bf16
    (measured ~1e-2 attention-output error at S=256); HIGHEST keeps true
    f32. On bf16 operands DEFAULT is already exact (the MXU accumulates
    bf16xbf16 in f32) and HIGHEST would request a multi-pass algorithm
    Mosaic rejects inside pallas kernels."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _repeat_kv(x, n_rep):
    """GQA: repeat KV heads to match query heads.
    x: [..., n_kv, hd] → [..., n_kv*n_rep, hd]."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def prefill_attention(q, k, v, causal=True, window=0):
    """Dense causal attention for prefill.

    q: [batch, s_q, heads, hd]; k/v: [batch, s_kv, kv_heads, hd] (GQA).
    s_kv may exceed s_q — prefix-cached prefill, where suffix queries
    attend over restored-prefix + suffix KV; the causal diagonal shifts
    right by s_kv - s_q (query i sees kv j <= i + prefix_len).
    window > 0 adds the sliding-window band (Mistral/Qwen2 semantics:
    query i also needs kv j > i + prefix_len - window, i.e. each query
    sees at most the last `window` positions including itself).
    Returns [batch, s_q, heads, hd]. fp32 softmax accumulation.
    """
    if causal and k.shape[1] < q.shape[1]:
        # Same guard as the pallas path (_forward_impl): fully-masked
        # query rows would otherwise return garbage silently.
        raise ValueError(
            f"causal attention needs kv_len >= q_len, got "
            f"{k.shape[1]} < {q.shape[1]}"
        )
    n_rep = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    precision = matmul_precision(q.dtype)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32,
        precision=precision,
    ) * scale
    if causal:
        s_q, s_kv = q.shape[1], k.shape[1]
        pos_q = jnp.arange(s_q)[:, None]
        pos_k = jnp.arange(s_kv)[None, :]
        mask = pos_k <= pos_q + (s_kv - s_q)
        if window:
            mask &= pos_k > pos_q + (s_kv - s_q) - window
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)


def multi_token_paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                                window=0, layer=None):
    """m-token decode attention over paged KV — the verify step of
    speculative decoding.

    q:          [batch, m, n_heads, hd] — m new tokens per sequence,
                whose KV has ALREADY been scattered into the pages at
                positions seq_lens[b] + j.
    k_pages/v_pages: [n_pages, page, n_kv, hd], or the whole pool
                [n_layers, n_pages, page, n_kv, hd] plus `layer`
    page_table: [batch, max_pages] int32
    seq_lens:   [batch] int32 — tokens in cache BEFORE these m (so
                token j attends to positions < seq_lens[b] + j + 1:
                causal within the new block, full over the past).

    Returns [batch, m, n_heads, hd]. Static shapes; per-batch lengths
    are arithmetic masks (no dynamic control flow)."""
    batch, m, n_heads, hd = q.shape
    page, n_kv = k_pages.shape[-3:-1]
    max_pages = page_table.shape[1]
    n_rep = n_heads // n_kv

    k = gather_pages(k_pages, page_table, layer).reshape(
        batch, max_pages * page, n_kv, hd
    )
    v = gather_pages(v_pages, page_table, layer).reshape(
        batch, max_pages * page, n_kv, hd
    )
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    scale = hd ** -0.5
    precision = matmul_precision(q.dtype)
    logits = jnp.einsum(
        "bmhd,bthd->bhmt", q, k, preferred_element_type=jnp.float32,
        precision=precision,
    ) * scale
    t_pos = jnp.arange(max_pages * page)[None, None, :]  # [1, 1, T]
    limit = (seq_lens[:, None] + jnp.arange(m)[None, :] + 1)[..., None]
    valid = t_pos < limit  # [b, m, T]
    if window:  # sliding band: token at position p sees t > p - window
        valid &= t_pos >= limit - window
    logits = jnp.where(valid[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhmt,bthd->bmhd", probs, v, precision=precision)


def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens,
                           window=0, layer=None):
    """Single-token decode attention over paged KV.

    q:            [batch, n_heads, hd] (current-step queries)
    k_pages/v_pages: [n_pages, page, n_kv, hd] (the store's page unit),
                  or the whole pool [n_layers, n_pages, page, n_kv, hd]
                  plus `layer` (static int)
    page_table:   [batch, max_pages] int32 page ids (padded arbitrarily)
    seq_lens:     [batch] int32 — valid tokens per sequence (incl. current)

    Returns [batch, n_heads, hd]. Static shapes throughout: max_pages is
    the compile-time budget; invalid positions are masked arithmetically.
    """
    batch, n_heads, hd = q.shape
    page, n_kv = k_pages.shape[-3:-1]
    max_pages = page_table.shape[1]
    n_rep = n_heads // n_kv

    k = gather_pages(k_pages, page_table, layer)  # [b, mp, page, n_kv, hd]
    v = gather_pages(v_pages, page_table, layer)
    k = k.reshape(batch, max_pages * page, n_kv, hd)
    v = v.reshape(batch, max_pages * page, n_kv, hd)
    k = _repeat_kv(k, n_rep)  # [b, T, n_heads, hd]
    v = _repeat_kv(v, n_rep)

    scale = hd ** -0.5
    precision = matmul_precision(q.dtype)
    logits = jnp.einsum(
        "bhd,bthd->bht", q, k, preferred_element_type=jnp.float32,
        precision=precision,
    ) * scale
    positions = jnp.arange(max_pages * page)[None, :]  # [1, T]
    valid = positions < seq_lens[:, None]  # [b, T]
    if window:  # current token is at seq_lens - 1: band floor
        valid &= positions >= seq_lens[:, None] - window
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bthd->bhd", probs, v, precision=precision)
