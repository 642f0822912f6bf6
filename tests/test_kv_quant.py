"""Int8 KV quantization tests: pack/unpack round trip, reconstruction
error bounds, end-to-end store round trip through TpuKVStore, and decode
attention on dequantized pages staying close to the bf16 path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from infinistore_tpu.ops import kv_quant
from infinistore_tpu.ops.paged_attention import paged_decode_attention
from infinistore_tpu.tpu import TpuKVStore


def test_quantize_roundtrip_error():
    rng = np.random.default_rng(0)
    pages = jnp.asarray(
        rng.standard_normal((8, 16, 4, 64)), jnp.float32
    )
    q, scales = kv_quant.quantize_kv_pages(pages)
    assert q.dtype == jnp.int8 and scales.shape == (8, 16, 4)
    back = kv_quant.dequantize_kv_pages(q, scales, jnp.float32)
    # Symmetric int8 with per-(token, head) scales: worst case half a
    # quantization step of the row absmax.
    absmax = np.abs(np.asarray(pages)).max(axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(pages))
    assert (err <= absmax / 127.0 * 0.5 + 1e-6).all()
    rel = np.linalg.norm(err) / np.linalg.norm(np.asarray(pages))
    assert rel < 0.01


def test_zero_page_safe():
    pages = jnp.zeros((2, 4, 2, 32), jnp.float32)
    q, scales = kv_quant.quantize_kv_pages(pages)
    back = kv_quant.dequantize_kv_pages(q, scales, jnp.float32)
    assert not np.isnan(np.asarray(back)).any()
    assert (np.asarray(back) == 0).all()


def test_pack_unpack_host():
    rng = np.random.default_rng(1)
    shape = (16, 4, 64)
    q = rng.integers(-127, 128, (5, *shape), dtype=np.int8)
    scales = rng.random((5, 16, 4)).astype(np.float32)
    packed = kv_quant.pack_pages_host(q, scales)
    assert packed.shape == (5, kv_quant.packed_page_bytes(shape))
    q2, s2 = kv_quant.unpack_pages_host(packed, shape)
    assert np.array_equal(q, q2)
    assert np.array_equal(scales, s2)


@pytest.mark.parametrize("ctype", ["SHM", "STREAM"])
def test_store_roundtrip_quantized(server, ctype):
    from infinistore_tpu import ClientConfig, InfinityConnection

    conn = InfinityConnection(
        ClientConfig(
            host_addr="127.0.0.1",
            service_port=server.service_port,
            connection_type=ctype,
        )
    )
    conn.connect()
    try:
        store = TpuKVStore(conn)
        rng = np.random.default_rng(2)
        page_shape = (16, 4, 64)
        pages = jnp.asarray(
            rng.standard_normal((6, *page_shape)), jnp.bfloat16
        )
        keys = [f"q_{ctype}_{i}" for i in range(6)]
        store.put_kv_pages_quantized(keys, pages, sync=True)
        back = store.get_kv_pages_quantized(keys, page_shape, jnp.bfloat16)
        a = np.asarray(pages, np.float32)
        b = np.asarray(back, np.float32)
        rel = np.linalg.norm(a - b) / np.linalg.norm(a)
        assert rel < 0.012, rel
        # Half the bytes of the bf16 page (+ scale sidecar).
        raw = int(np.prod(page_shape)) * 2
        assert kv_quant.packed_page_bytes(page_shape) < raw * 0.55
    finally:
        conn.close()


def test_decode_attention_on_quantized_pages():
    """Decode attention over dequantized int8 pages must stay close to
    attention over the original pages."""
    rng = np.random.default_rng(3)
    n_pages, page, n_kv, hd = 8, 16, 2, 64
    batch, n_heads = 2, 4
    k_pages = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.standard_normal((n_pages, page, n_kv, hd)), jnp.float32
    )
    q = jnp.asarray(rng.standard_normal((batch, n_heads, hd)), jnp.float32)
    page_table = jnp.asarray(
        rng.permutation(n_pages)[: 4 * batch].reshape(batch, 4), jnp.int32
    )
    seq_lens = jnp.asarray([50, 63], jnp.int32)

    ref = paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens)
    kq, ks = kv_quant.quantize_kv_pages(k_pages)
    vq, vs = kv_quant.quantize_kv_pages(v_pages)
    k_deq = kv_quant.dequantize_kv_pages(kq, ks, jnp.float32)
    v_deq = kv_quant.dequantize_kv_pages(vq, vs, jnp.float32)
    out = paged_decode_attention(q, k_deq, v_deq, page_table, seq_lens)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 0.05, err


# ---- int8 WEIGHT quantization (models/llama.quantize_params) ----


def test_quantize_params_roundtrip_and_bytes():
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=64, page_size=8, dtype="float32",
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    q = llama.quantize_params(params, cfg)
    # Quantized tree streams ~1/4 the bytes of the f32 tree (int8
    # weights + tiny scales + untouched norms).
    assert llama.param_bytes(q) < llama.param_bytes(params) / 3
    # Dequantized weights match the originals to int8 precision.
    w = params["layers"][0]["wq"]
    ql = q["layers"][0]["wq"]
    deq = ql["int8"].astype(jnp.float32) * ql["scale"][None, :]
    rel = float(jnp.max(jnp.abs(deq - w)) / jnp.max(jnp.abs(w)))
    assert rel < 0.01, rel


def test_quantized_model_paths_track_dense():
    """Prefill, paged decode and multi-token verify all run on the
    quantized tree and track the dense model closely (weight-only int8
    is ~0.4%/matmul; tiny 2-layer nets compound to a few percent)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=128, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=256, max_seq=128, page_size=8, dtype="float32",
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    qparams = llama.quantize_params(params, cfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 24)), jnp.int32
    )

    lf, kvs = llama.prefill(params, cfg, toks)
    lq, _ = llama.prefill(qparams, cfg, toks)
    rel = float(jnp.max(jnp.abs(lq - lf)) / jnp.max(jnp.abs(lf)))
    assert rel < 0.15, rel

    # Paged decode on the quantized tree: shapes/pytree structure flow
    # through decode_step unchanged.
    n_pages, max_pages = 3, 4
    k_pages = jnp.zeros((cfg.n_layers, 2 * max_pages, cfg.page_size,
                         cfg.n_kv_heads, cfg.head_dim), cfg.jdtype)
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        k_pages = k_pages.at[li, :n_pages].set(kp[0])
        v_pages = v_pages.at[li, :n_pages].set(vp[0])
    page_table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    logits_q, _, _ = llama.decode_step(
        qparams, cfg, jnp.asarray([5], jnp.int32),
        jnp.asarray([24], jnp.int32), k_pages, v_pages, page_table,
    )
    logits_f, _, _ = llama.decode_step(
        params, cfg, jnp.asarray([5], jnp.int32),
        jnp.asarray([24], jnp.int32), k_pages, v_pages, page_table,
    )
    rel = float(jnp.max(jnp.abs(logits_q - logits_f))
                / jnp.max(jnp.abs(logits_f)))
    assert rel < 0.15, rel


def test_init_params_quantized_never_materializes_dense():
    """Direct int8 init: bytes ~= n_params, and the engine can serve
    from the tree (the 8B-on-16GB flagship path)."""
    import jax
    import numpy as np

    from infinistore_tpu.models import llama
    from infinistore_tpu.serving import Request, ServingConfig, ServingEngine

    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=128, page_size=8, dtype="float32",
    )
    qp = llama.init_params_quantized(jax.random.PRNGKey(1), cfg)
    n_params = sum(
        int(np.prod(l["int8"].shape))
        for l in jax.tree_util.tree_leaves(
            qp, is_leaf=lambda x: isinstance(x, dict) and "int8" in x
        ) if isinstance(l, dict)
    )
    assert llama.param_bytes(qp) < n_params * 1.2  # int8 + small extras

    eng = ServingEngine(qp, cfg, ServingConfig(
        max_slots=2, total_pages=32, max_pages_per_seq=12))
    toks = []
    eng.submit(Request("q", list(range(10)), max_new_tokens=5,
                       on_token=lambda r, t: toks.append(int(t))))
    eng.run([])
    assert len(toks) == 5


def test_embed_quantization_is_per_row():
    """The embedding table is consumed by gather, so its quantization
    unit must be the row: a token whose embedding is 100x smaller than
    the vocab's loudest rows still dequantizes to ~int8 precision (a
    per-column scheme would collapse it to a few levels)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.models import decoder, llama

    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=64, max_seq=64, page_size=8, dtype="float32",
    )
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"].at[7].multiply(0.01)
    q = llama.quantize_params(params, cfg)
    assert q["embed"]["scale"].shape == (cfg.vocab_size,)
    toks = jnp.asarray([[7]], jnp.int32)
    ef = np.asarray(decoder.embed(params, toks))
    eq = np.asarray(decoder.embed(q, toks))
    rel = np.abs(eq - ef).max() / (np.abs(ef).max() + 1e-12)
    assert rel < 0.02, rel
