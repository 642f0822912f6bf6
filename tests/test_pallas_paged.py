"""Pallas flash-decode kernel vs the XLA gather reference, in interpret
mode (bit-level same code path that compiles for real TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.ops.paged_attention import paged_decode_attention
from infinistore_tpu.ops.pallas_paged_attention import paged_flash_decode


def _mk(batch, n_heads, n_kv, hd, n_pages, page, max_pages, seed=0,
        dtype=np.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((batch, n_heads, hd)), dtype=dtype)
    k = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), dtype=dtype
    )
    v = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), dtype=dtype
    )
    pt = jnp.asarray(
        rng.permutation(n_pages)[: batch * max_pages].reshape(
            batch, max_pages
        ),
        dtype=jnp.int32,
    )
    sl = jnp.asarray(
        rng.integers(1, max_pages * page, batch), dtype=jnp.int32
    )
    return q, k, v, pt, sl


@pytest.mark.parametrize(
    "batch,n_heads,n_kv,hd,page",
    [
        (2, 8, 8, 128, 16),   # MHA, native tile sizes
        (2, 8, 2, 128, 16),   # GQA 4:1
        (1, 4, 2, 64, 8),     # padded head-dim + padded heads
        (3, 16, 4, 32, 8),    # heavy padding
    ],
)
def test_flash_matches_xla(batch, n_heads, n_kv, hd, page):
    q, k, v, pt, sl = _mk(batch, n_heads, n_kv, hd, 32, page, 4)
    out_ref = paged_decode_attention(q, k, v, pt, sl)
    out_pl = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )


def test_flash_single_token_seq():
    """seq_len 1: only the first slot of the first page is valid."""
    q, k, v, pt, _ = _mk(1, 8, 8, 128, 8, 16, 2, seed=3)
    sl = jnp.asarray([1], dtype=jnp.int32)
    out_ref = paged_decode_attention(q, k, v, pt, sl)
    out_pl = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )


def test_flash_full_pages():
    """seq_len exactly fills every page (no partial masking)."""
    q, k, v, pt, _ = _mk(2, 8, 4, 128, 16, 16, 3, seed=4)
    sl = jnp.asarray([48, 48], dtype=jnp.int32)
    out_ref = paged_decode_attention(q, k, v, pt, sl)
    out_pl = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )


def test_flash_bf16():
    """bfloat16 — the production dtype (LlamaConfig default)."""
    q, k, v, pt, sl = _mk(2, 8, 2, 128, 32, 16, 4, seed=5, dtype=jnp.bfloat16)
    out_ref = paged_decode_attention(q, k, v, pt, sl)
    out_pl = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_pl, dtype=np.float32),
        np.asarray(out_ref, dtype=np.float32),
        rtol=3e-2,
        atol=3e-2,
    )


def test_flash_odd_group_size():
    """GQA group 3 (does not divide the sublane count) — padding math."""
    q, k, v, pt, sl = _mk(2, 6, 2, 128, 32, 16, 4, seed=6)
    out_ref = paged_decode_attention(q, k, v, pt, sl)
    out_pl = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )


def test_flash_oob_page_table_padding():
    """Padding entries may be out of range (contract: 'padded
    arbitrarily'); the kernel must clamp, not fault."""
    q, k, v, pt, _ = _mk(2, 8, 8, 128, 8, 16, 4, seed=7)
    # Sequences use only the first 2 pages; pad the rest with garbage ids.
    pt = pt.at[:, 2:].set(jnp.asarray([[-1, 9999], [12345, -7]]))
    sl = jnp.asarray([20, 30], dtype=jnp.int32)  # within 2 pages
    out_ref = paged_decode_attention(
        q, k, v, jnp.clip(pt, 0, 7), sl
    )
    out_pl = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out_pl), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("dtype,n_heads,n_kv", [
    (jnp.float32, 8, 4),
    (jnp.float32, 4, 1),
    (jnp.bfloat16, 8, 2),
])
def test_quantized_decode_matches_dequantized_reference(dtype, n_heads, n_kv):
    """The int8 kernel (dequant fused after the page DMA) must match
    dequantize-then-attend through the XLA path."""
    from infinistore_tpu.ops import kv_quant
    from infinistore_tpu.ops.pallas_paged_attention import (
        paged_flash_decode_quantized,
    )

    rng = np.random.default_rng(17)
    batch, hd, page, n_pages, max_pages = 3, 64, 16, 24, 6
    q = jnp.asarray(rng.standard_normal((batch, n_heads, hd)), dtype)
    pages = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), dtype
    )
    k_q, k_s = kv_quant.quantize_kv_pages(pages)
    v_pages = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), dtype
    )
    v_q, v_s = kv_quant.quantize_kv_pages(v_pages)
    page_table = jnp.asarray(
        rng.permutation(n_pages)[: batch * max_pages].reshape(
            batch, max_pages
        ),
        jnp.int32,
    )
    seq_lens = jnp.asarray([5, 37, 96], jnp.int32)

    got = paged_flash_decode_quantized(
        q, k_q, k_s, v_q, v_s, page_table, seq_lens, interpret=True
    )
    k_deq = kv_quant.dequantize_kv_pages(k_q, k_s, jnp.float32)
    v_deq = kv_quant.dequantize_kv_pages(v_q, v_s, jnp.float32)
    ref = paged_decode_attention(
        q.astype(jnp.float32), k_deq, v_deq, page_table, seq_lens
    )
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
    assert err < tol, (dtype, n_heads, n_kv, err)


def test_quantized_chooser_fallback_gathers_first():
    """The non-TPU fallback of decode_attention_quantized must match the
    full-dequant reference (it gathers int8 pages by table first)."""
    from infinistore_tpu.ops import kv_quant
    from infinistore_tpu.ops.pallas_paged_attention import (
        decode_attention_quantized,
    )

    import jax

    assert jax.default_backend() != "tpu"
    rng = np.random.default_rng(23)
    batch, n_heads, n_kv, hd, page = 2, 4, 2, 32, 8
    n_pages, max_pages = 16, 4
    q = jnp.asarray(rng.standard_normal((batch, n_heads, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n_pages, page, n_kv, hd)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((n_pages, page, n_kv, hd)),
                    jnp.float32)
    k_q, k_s = kv_quant.quantize_kv_pages(k)
    v_q, v_s = kv_quant.quantize_kv_pages(v)
    page_table = jnp.asarray(
        rng.permutation(n_pages)[: batch * max_pages].reshape(
            batch, max_pages
        ),
        jnp.int32,
    )
    seq_lens = jnp.asarray([13, 29], jnp.int32)
    got = decode_attention_quantized(
        q, k_q, k_s, v_q, v_s, page_table, seq_lens
    )
    ref = paged_decode_attention(
        q,
        kv_quant.dequantize_kv_pages(k_q, k_s, jnp.float32),
        kv_quant.dequantize_kv_pages(v_q, v_s, jnp.float32),
        page_table, seq_lens,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Multi-token verify kernel (speculative verify).
# ---------------------------------------------------------------------------

def _mk_multi(batch, m, n_heads, n_kv, hd, n_pages, page, max_pages,
              seed=0, dtype=np.float32):
    from infinistore_tpu.ops.paged_attention import scatter_kv_multi

    rng = np.random.default_rng(seed)
    q = jnp.asarray(
        rng.standard_normal((batch, m, n_heads, hd)), dtype=dtype
    )
    k = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), dtype=dtype
    )
    v = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), dtype=dtype
    )
    pt = jnp.asarray(
        rng.permutation(n_pages)[: batch * max_pages].reshape(
            batch, max_pages
        ),
        dtype=jnp.int32,
    )
    # Leave room for the m new tokens inside the table's page budget.
    sl = jnp.asarray(
        rng.integers(1, max_pages * page - m, batch), dtype=jnp.int32
    )
    # The contract: the m tokens' KV is already scattered at positions
    # seq_lens + j before the attention call.
    new_k = jnp.asarray(
        rng.standard_normal((batch, m, n_kv, hd)), dtype=dtype
    )
    new_v = jnp.asarray(
        rng.standard_normal((batch, m, n_kv, hd)), dtype=dtype
    )
    positions = sl[:, None] + jnp.arange(m)[None, :]
    tgt = jnp.take_along_axis(pt, positions // page, axis=1)
    slot = positions % page
    k = scatter_kv_multi(k, new_k, tgt, slot)
    v = scatter_kv_multi(v, new_v, tgt, slot)
    return q, k, v, pt, sl


@pytest.mark.parametrize(
    "batch,m,n_heads,n_kv,hd,page",
    [
        (2, 4, 8, 8, 128, 16),   # MHA
        (2, 3, 8, 2, 128, 16),   # GQA 4:1, odd m
        (1, 5, 4, 2, 64, 8),     # padded head-dim + heads
        (3, 2, 16, 4, 32, 8),    # heavy padding
        (1, 1, 8, 4, 128, 16),   # m=1 degenerates to decode
    ],
)
def test_verify_kernel_matches_xla(batch, m, n_heads, n_kv, hd, page):
    from infinistore_tpu.ops.paged_attention import (
        multi_token_paged_attention,
    )
    from infinistore_tpu.ops.pallas_paged_attention import (
        paged_flash_verify,
    )

    q, k, v, pt, sl = _mk_multi(batch, m, n_heads, n_kv, hd, 32, page, 4)
    ref = multi_token_paged_attention(q, k, v, pt, sl)
    out = paged_flash_verify(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_verify_kernel_empty_cache_and_page_spanning_chunk():
    """The verify kernel's edge regimes: seq_len = 0 (each token
    attends only to the block's own scattered KV) and an m-token
    block spanning several pages (m > page_size)."""
    from infinistore_tpu.ops.paged_attention import (
        multi_token_paged_attention,
        scatter_kv_multi,
    )
    from infinistore_tpu.ops.pallas_paged_attention import (
        paged_flash_verify,
    )

    rng = np.random.default_rng(41)
    B, m, H, KV, hd, page, n_pages, mp = 2, 12, 4, 2, 64, 8, 16, 4
    q = jnp.asarray(rng.standard_normal((B, m, H, hd)), jnp.float32)
    k = jnp.asarray(
        rng.standard_normal((n_pages, page, KV, hd)), jnp.float32
    )
    v = jnp.asarray(
        rng.standard_normal((n_pages, page, KV, hd)), jnp.float32
    )
    pt = jnp.asarray(
        rng.permutation(n_pages)[: B * mp].reshape(B, mp), jnp.int32
    )
    # Row 0: empty cache; row 1: mid-page start. m=12 spans 2-3 pages.
    sl = jnp.asarray([0, 5], jnp.int32)
    new_k = jnp.asarray(
        rng.standard_normal((B, m, KV, hd)), jnp.float32
    )
    new_v = jnp.asarray(
        rng.standard_normal((B, m, KV, hd)), jnp.float32
    )
    positions = sl[:, None] + jnp.arange(m)[None, :]
    tgt = jnp.take_along_axis(pt, positions // page, axis=1)
    k = scatter_kv_multi(k, new_k, tgt, positions % page)
    v = scatter_kv_multi(v, new_v, tgt, positions % page)

    ref = multi_token_paged_attention(q, k, v, pt, sl)
    out = paged_flash_verify(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_verify_kernel_bf16():
    from infinistore_tpu.ops.paged_attention import (
        multi_token_paged_attention,
    )
    from infinistore_tpu.ops.pallas_paged_attention import (
        paged_flash_verify,
    )

    q, k, v, pt, sl = _mk_multi(
        2, 4, 8, 4, 128, 32, 16, 4, dtype=jnp.bfloat16
    )
    ref = multi_token_paged_attention(q, k, v, pt, sl)
    out = paged_flash_verify(q, k, v, pt, sl, interpret=True)
    err = float(
        jnp.max(
            jnp.abs(
                out.astype(jnp.float32) - ref.astype(jnp.float32)
            )
        )
    )
    assert err < 3e-2, err


# ---- TP shard_map: the kernel under tensor parallelism (VERDICT r3 #4)

def _tp_mesh(n=8):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("tp",))


def test_decode_kernel_under_tp_shard_map():
    """paged_flash_decode inside shard_map, kv heads sharded over an
    8-way tp axis on the virtual CPU mesh, interpret mode: the REAL
    kernel code path in the real multi-chip serving layout, pinned
    equal to the single-device XLA reference."""
    from infinistore_tpu.ops.pallas_paged_attention import (
        decode_attention_tp,
    )

    mesh = _tp_mesh()
    q, k, v, pt, sl = _mk(4, 16, 8, 64, 33, 8, 4, seed=9)
    ref = paged_decode_attention(q, k, v, pt, sl)
    out = decode_attention_tp(mesh, q, k, v, pt, sl)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_decode_kernel_tp_rejects_indivisible_heads():
    from infinistore_tpu.ops.pallas_paged_attention import (
        decode_attention_tp,
    )

    mesh = _tp_mesh()
    q, k, v, pt, sl = _mk(2, 12, 6, 64, 16, 8, 2)
    with pytest.raises(ValueError):
        decode_attention_tp(mesh, q, k, v, pt, sl)


def test_quantized_decode_kernel_under_tp_shard_map():
    """The fused-dequant int8 kernel under the same tp sharding, scales
    co-sharded on the kv-head dim."""
    from infinistore_tpu.ops import kv_quant
    from infinistore_tpu.ops.pallas_paged_attention import (
        decode_attention_quantized_tp,
    )

    mesh = _tp_mesh()
    q, k, v, pt, sl = _mk(2, 16, 8, 64, 17, 8, 2, seed=11)
    k_q, k_s = kv_quant.quantize_kv_pages(k)
    v_q, v_s = kv_quant.quantize_kv_pages(v)
    ref = paged_decode_attention(
        q,
        kv_quant.dequantize_kv_pages(k_q, k_s, q.dtype),
        kv_quant.dequantize_kv_pages(v_q, v_s, q.dtype),
        pt, sl,
    )
    out = decode_attention_quantized_tp(
        mesh, q, k_q, k_s, v_q, v_s, pt, sl
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_decode_kernel_sliding_window_matches_xla():
    import numpy as np

    from infinistore_tpu.ops import paged_attention as xr
    from infinistore_tpu.ops.pallas_paged_attention import paged_flash_decode

    rng = np.random.default_rng(41)
    k_pages = jnp.asarray(rng.standard_normal((9, 8, 2, 64)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((9, 8, 2, 64)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    sl = jnp.asarray([29, 17], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 4, 64)), jnp.float32)
    for w in (5, 12, 100):
        ref = xr.paged_decode_attention(q, k_pages, v_pages, pt, sl,
                                        window=w)
        ker = paged_flash_decode(q, k_pages, v_pages, pt, sl,
                                 interpret=True, window=w)
        err = float(jnp.max(jnp.abs(ker - ref)))
        assert err < 1e-4, (w, err)


def test_verify_kernel_sliding_window_matches_xla():
    import numpy as np

    from infinistore_tpu.ops import paged_attention as xr
    from infinistore_tpu.ops.pallas_paged_attention import paged_flash_verify

    rng = np.random.default_rng(43)
    k_pages = jnp.asarray(rng.standard_normal((9, 8, 2, 64)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((9, 8, 2, 64)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    sl = jnp.asarray([21, 13], jnp.int32)
    q = jnp.asarray(rng.standard_normal((2, 3, 4, 64)), jnp.float32)
    for w in (5, 12):
        ref = xr.multi_token_paged_attention(q, k_pages, v_pages, pt, sl,
                                             window=w)
        ker = paged_flash_verify(q, k_pages, v_pages, pt, sl,
                                 interpret=True, window=w)
        err = float(jnp.max(jnp.abs(ker - ref)))
        assert err < 1e-4, (w, err)


def test_quantized_decode_kernel_sliding_window():
    import numpy as np

    from infinistore_tpu.ops import kv_quant
    from infinistore_tpu.ops import paged_attention as xr
    from infinistore_tpu.ops.pallas_paged_attention import (
        paged_flash_decode_quantized,
    )

    rng = np.random.default_rng(45)
    k_pages = jnp.asarray(rng.standard_normal((9, 8, 2, 64)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((9, 8, 2, 64)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    sl = jnp.asarray([27], jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, 4, 64)), jnp.float32)
    kq, ks = kv_quant.quantize_kv_pages(k_pages)
    vq, vs = kv_quant.quantize_kv_pages(v_pages)
    kd = kv_quant.dequantize_kv_pages(kq, ks, jnp.float32)
    vd = kv_quant.dequantize_kv_pages(vq, vs, jnp.float32)
    for w in (5, 12):
        ref = xr.paged_decode_attention(q, kd, vd, pt, sl, window=w)
        ker = paged_flash_decode_quantized(q, kq, ks, vq, vs, pt, sl,
                                           interpret=True, window=w)
        err = float(jnp.max(jnp.abs(ker - ref)))
        assert err < 5e-2, (w, err)


def test_tp_decode_kernel_sliding_window():
    """decode_attention_tp threads the window to every shard — a
    windowed checkpoint under tensor parallelism must match the
    single-device banded reference."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from infinistore_tpu.ops import paged_attention as xr
    from infinistore_tpu.ops.pallas_paged_attention import (
        decode_attention_tp,
    )

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("tp",))
    rng = np.random.default_rng(47)
    k_pages = jnp.asarray(rng.standard_normal((9, 8, 4, 64)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((9, 8, 4, 64)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    sl = jnp.asarray([25], jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, 8, 64)), jnp.float32)
    ref = xr.paged_decode_attention(q, k_pages, v_pages, pt, sl, window=9)
    out = decode_attention_tp(mesh, q, k_pages, v_pages, pt, sl, window=9)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-4, err


# ---------------------------------------------------------------------------
# Whole-pool operand: [n_layers, n_pages, page, n_kv, hd] plus a static
# layer. Aligned shapes go to the kernel whole (verify: 5-D, its index
# map leads with the layer; decode: every layer still there, a page as
# [page * n_kv, hd] rows, which moves no tile); anything that needs
# padding is sliced to its layer first.
# ---------------------------------------------------------------------------

# rank of the kernel's K and V operands when the pool goes whole
_WHOLE_POOL_RANK = {"verify": 5, "decode": 4}


def _as_pool(pages, n_layers, layer, seed):
    """A pool whose `layer` is `pages` and whose other layers are other
    random numbers: a kernel that read the wrong layer cannot match."""
    rng = np.random.default_rng(1000 + seed)
    pool = jnp.asarray(
        rng.standard_normal((n_layers, *pages.shape)), dtype=pages.dtype
    )
    return pool.at[layer].set(pages)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", val)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


def _kernel_operand_ranks(fn, *args):
    """(ranks of the pallas_call's K and V operands, ranks of every
    `pad` operand) in fn's jaxpr."""
    import jax

    eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    pads = [e.invars[0].aval.ndim for e in eqns if e.primitive.name == "pad"]
    return [v.aval.ndim for v in call.invars[-2:]], pads


def _pool_case(kind, n_heads, n_kv, hd, seed, dtype):
    from infinistore_tpu.ops.pallas_paged_attention import paged_flash_verify

    if kind == "decode":
        q, k, v, pt, sl = _mk(2, n_heads, n_kv, hd, 16, 16, 3, seed=seed,
                              dtype=dtype)
        return paged_flash_decode, q, k, v, pt, sl
    q, k, v, pt, sl = _mk_multi(2, 3, n_heads, n_kv, hd, 16, 16, 3,
                                seed=seed, dtype=dtype)
    return paged_flash_verify, q, k, v, pt, sl


@pytest.mark.parametrize("window", [0, 20])
@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_pool_operand_equals_layer_slice_bit_for_bit(kind, layer, window):
    """Aligned widths (bf16, hd 128, kv heads a tile multiple): the 5-D
    pool goes to the kernel whole — no pad, no slice — and the result
    is the 4-D call on pool[layer], bit for bit."""
    fn, q, k, v, pt, sl = _pool_case(kind, 16, 4, 128, 11 + layer,
                                     jnp.bfloat16)
    k_pool = _as_pool(k, 3, layer, 1)
    v_pool = _as_pool(v, 3, layer, 2)
    want = fn(q, k_pool[layer], v_pool[layer], pt, sl, interpret=True,
              window=window)
    got = fn(q, k_pool, v_pool, pt, sl, interpret=True, window=window,
             layer=layer)
    np.testing.assert_array_equal(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32)
    )
    ranks, pads = _kernel_operand_ranks(
        lambda *a: fn(*a, interpret=True, window=window, layer=layer),
        q, k_pool, v_pool, pt, sl,
    )
    assert ranks == [_WHOLE_POOL_RANK[kind]] * 2 and pads == []


@pytest.mark.parametrize("n_heads,n_kv,hd", [
    (8, 2, 64),    # Llama-3.2-1B's head_dim: lanes need padding
    (4, 2, 128),   # group 2 in bf16: kv heads need padding
])
@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_pool_operand_that_needs_padding_is_sliced_first(kind, n_heads,
                                                         n_kv, hd):
    """Padding the pool would copy every layer on every layer's call:
    the wrapper slices the layer out and pads that, as the 4-D form
    does. Same result bit for bit, and no `pad` of a 5-D array."""
    fn, q, k, v, pt, sl = _pool_case(kind, n_heads, n_kv, hd, 21,
                                     jnp.bfloat16)
    k_pool = _as_pool(k, 3, 1, 3)
    v_pool = _as_pool(v, 3, 1, 4)
    want = fn(q, k, v, pt, sl, interpret=True)
    got = fn(q, k_pool, v_pool, pt, sl, interpret=True, layer=1)
    np.testing.assert_array_equal(
        np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32)
    )
    ranks, pads = _kernel_operand_ranks(
        lambda *a: fn(*a, interpret=True, layer=1), q, k_pool, v_pool, pt, sl
    )
    assert ranks == [3, 3]
    assert pads and all(r < 5 for r in pads)


@pytest.mark.parametrize("window", [0, 20])
def test_a_group_of_seven_pads_the_group_and_the_pool_goes_whole(window):
    """28 query heads over 4 kv heads (SmallThinker): no padding of kv
    heads short of 16 makes 7 a row a sublane multiple, and padding
    them would slice, copy and widen a layer of the pool on every
    layer's call (2.6 GB of temporaries in that model's decode step,
    lowered for a v5e). The wrapper pads the group with a zero query
    row instead: the 5-D pool goes to the kernel whole, and the result
    is the XLA reference's."""
    from infinistore_tpu.ops import paged_attention as xla_ref

    fn, q, k, v, pt, sl = _pool_case("decode", 28, 4, 128, 31, jnp.bfloat16)
    k_pool = _as_pool(k, 3, 1, 5)
    v_pool = _as_pool(v, 3, 1, 6)
    got = fn(q, k_pool, v_pool, pt, sl, interpret=True, window=window,
             layer=1)
    want = xla_ref.paged_decode_attention(q, k, v, pt, sl, window=window)
    assert got.shape == want.shape == (2, 28, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    sliced = fn(q, k, v, pt, sl, interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(sliced, np.float32), atol=1e-2)
    ranks, pads = _kernel_operand_ranks(
        lambda *a: fn(*a, interpret=True, window=window, layer=1),
        q, k_pool, v_pool, pt, sl)
    assert ranks == [4, 4] and all(r < 5 for r in pads)


# ---------------------------------------------------------------------------
# The block form of the decode kernel: blocks of `_pages_per_block` pages
# fetched by the kernel's own copies, and only a sequence's live blocks.
# Pages of 64 tokens keep a block at 8 pages (512 keys), so tables of a
# few dozen entries hold several blocks; the page-16 cases are the
# cells' own operand shapes.
# ---------------------------------------------------------------------------

def _block_pages(page, n_kv, hd, dtype, table):
    from infinistore_tpu.ops.pallas_paged_attention import _pages_per_block

    return _pages_per_block(
        page, page * n_kv * hd * jnp.dtype(dtype).itemsize, table)


def _mk_lens(lens, table, n_heads=4, n_kv=2, hd=128, page=64, seed=0,
             dtype=np.float32):
    """A case of len(lens) rows over tables of `table` entries, every
    entry a page of its own."""
    q, k, v, pt, _ = _mk(len(lens), n_heads, n_kv, hd,
                         len(lens) * table + 1, page, table, seed=seed,
                         dtype=dtype)
    return q, k, v, pt, jnp.asarray(lens, jnp.int32)


def _assert_block_matches(q, k, v, pt, sl, window=0, tol=2e-5, **kw):
    want = paged_decode_attention(q, k, v, pt, sl, window=window)
    got = paged_flash_decode(q, k, v, pt, sl, interpret=True, window=window,
                             **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


BLOCK_TABLE = 19  # 8 + 8 + 3: two whole blocks and a partial one


@pytest.mark.parametrize("edge", [-1, 0, 1])
@pytest.mark.parametrize("blocks", [1, 2])
def test_block_form_at_every_block_edge(blocks, edge):
    """A length one short of a block's end, at it and one past it, in a
    row beside a one-token row and a whole table."""
    P = _block_pages(64, 2, 128, np.float32, BLOCK_TABLE)
    assert P == 8 and BLOCK_TABLE % P
    n = blocks * P * 64 + edge
    _assert_block_matches(
        *_mk_lens([n, 1, BLOCK_TABLE * 64], BLOCK_TABLE, seed=50 + n))


@pytest.mark.parametrize("table", [9, 11, 26])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_block_form_table_no_multiple_of_the_block(table, dtype):
    """264 and 832 entries are no multiple of any block: the last block
    is partial, its absent pages neither copied (the table has no such
    entry) nor attended. Lengths that end in the partial block, at the
    table's end and just before the partial block."""
    P = _block_pages(64, 2, 128, dtype, table)
    assert table % P
    whole = table // P * P
    lens = [table * 64, whole * 64 + 5, whole * 64, table * 64 - 1]
    tol = 2e-5 if dtype is np.float32 else 3e-2
    _assert_block_matches(*_mk_lens(lens, table, seed=table, dtype=dtype),
                          tol=tol)


@pytest.mark.parametrize("long_rows", [(0,), (3,), (1, 2), (0, 3)])
def test_block_form_inactive_rows_over_entry_zero(long_rows):
    """What an engine with free slots passes: length 1 over table entry
    0 (the scratch page) in every inactive row, first, last and between
    long ones — the copy a row's last block starts for the NEXT row must
    be that row's own first block, whichever kind of row follows."""
    table = 19
    lens = [1] * 4
    for i, r in enumerate(long_rows):
        lens[r] = (table - i) * 64 - 7
    q, k, v, pt, sl = _mk_lens(lens, table, seed=60 + sum(long_rows))
    pt = pt.at[jnp.asarray([i for i in range(4) if lens[i] == 1])].set(0)
    _assert_block_matches(q, k, v, pt, sl)


def test_block_form_clamps_out_of_range_entries():
    """Entries past a row's last page may be anything ("padded
    arbitrarily"), inside a live block too, and an entry in use may lie
    outside the pool: clamped, as the XLA path's take clamps."""
    table = 19
    q, k, v, pt, sl = _mk_lens([9 * 64 + 3, 64, 17 * 64], table, seed=70)
    n_pages = k.shape[0]
    pt = pt.at[0, 10:].set(jnp.asarray([-1, 99999, 7, -5, 2 ** 30, 0, 1,
                                        n_pages, -n_pages], jnp.int32))
    pt = pt.at[1, 1:].set(-3)
    pt = pt.at[2, 4].set(n_pages + 17)  # in use: reads the last page
    want = paged_decode_attention(q, k, v, jnp.clip(pt, 0, n_pages - 1), sl)
    got = paged_flash_decode(q, k, v, pt, sl, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [
    5,          # inside the last page
    64 * 3,     # floor inside the last block
    64 * 8,     # exactly a block
    64 * 9 + 1,  # floor inside an earlier block
    64 * 30,    # wider than any row
])
def test_block_form_band_floor_inside_and_across_blocks(window):
    """A band whose floor falls inside a block (pages under it in that
    block are not copied, keys under it in its page are masked) and one
    whose floor skips whole blocks (they are not walked)."""
    table = 26
    lens = [table * 64, 17 * 64 + 9, 8 * 64, 3]
    _assert_block_matches(*_mk_lens(lens, table, seed=80 + window % 7),
                          window=window)


@pytest.mark.parametrize("n_heads,n_kv,dtype,tol", [
    (32, 8, jnp.bfloat16, 3e-2),   # mistral7b, mixtral8x7b: group 4
    (32, 4, jnp.bfloat16, 3e-2),   # granite4h-micro: kv_pack rows, group 8
    (28, 4, jnp.bfloat16, 3e-2),   # smallthinker21b: the group of 7
    (8, 2, np.float32, 2e-5),      # kv heads padded, a layer sliced out
])
@pytest.mark.parametrize("window", [0, 16 * 40])
def test_block_form_whole_pool_is_the_layer_slice_bit_for_bit(
        n_heads, n_kv, dtype, tol, window):
    """The cells' operand shapes at pages of 16 tokens over several
    blocks: the whole pool + `layer` gives the XLA reference's result
    and, bit for bit, what the call on the layer's slice gives."""
    table = 70
    P = _block_pages(16, n_kv, 128, dtype, table)
    assert table > 2 * P and table % P
    lens = [table * 16, 1, P * 16 + 1, 33 * 16 - 1]
    q, k, v, pt, sl = _mk_lens(lens, table, n_heads, n_kv, 128, 16,
                               seed=90 + n_heads, dtype=dtype)
    pt = pt.at[1].set(0)
    k_pool = _as_pool(k, 2, 1, 7)
    v_pool = _as_pool(v, 2, 1, 8)
    _assert_block_matches(q, k, v, pt, sl, window=window, tol=tol)
    got = paged_flash_decode(q, k_pool, v_pool, pt, sl, interpret=True,
                             window=window, layer=1)
    sliced = paged_flash_decode(q, k, v, pt, sl, interpret=True,
                                window=window)
    if n_heads // n_kv == 7:
        # the slice pads its kv heads 4 -> 16, the pool its group 7 -> 8:
        # two orders of the same sums
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(sliced, np.float32), atol=1e-2)
    else:
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(sliced, np.float32))


def test_pages_per_block_follows_the_shapes():
    """No option sets it: mistral7b's 32 KB a page a kind gives 16 pages
    (256 keys, 2 MB of buffers), the 16 KB pages of granite4h-micro and
    smallthinker21b 32 (512 keys), a short table its own length, and a
    block is never under the 128 keys of a lane tile where the table
    has them."""
    from infinistore_tpu.ops.pallas_paged_attention import _pages_per_block

    assert _pages_per_block(16, 16 * 8 * 128 * 2, 192) == 16
    assert _pages_per_block(16, 16 * 4 * 128 * 2, 384) == 32
    assert _pages_per_block(16, 16 * 4 * 128 * 2, 264) == 32
    assert _pages_per_block(16, 16 * 32 * 128 * 4, 192) == 8
    assert _pages_per_block(16, 16 * 8 * 128 * 2, 3) == 3
    assert _pages_per_block(256, 256 * 8 * 128 * 2, 64) == 1
