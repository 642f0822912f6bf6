"""Flagship paged-KV model tests: decode-vs-dense equivalence, store
round-trip of KV pages, and the sharded training step on the virtual
8-device mesh."""

import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.models import llama
from infinistore_tpu.ops import paged_attention as pa


@pytest.fixture(scope="module")
def cfg():
    return llama.LlamaConfig(
        vocab_size=128,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq=64,
        page_size=8,
        dtype="float32",  # exact-match tests need fp32
    )


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.PRNGKey(0), cfg)


def test_prefill_shapes(params, cfg):
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)),
        dtype=jnp.int32,
    )
    logits, kvs = llama.prefill(params, cfg, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert len(kvs) == cfg.n_layers
    assert kvs[0][0].shape == (2, 16, cfg.n_kv_heads, cfg.head_dim)


def test_paged_decode_matches_dense(params, cfg):
    """Decoding token s+1 with paged KV must reproduce the dense forward's
    logits for that position — paging is a layout change, not math."""
    rng = np.random.default_rng(1)
    s = 16  # two pages
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, s + 1)), dtype=jnp.int32
    )
    dense_logits, _ = llama.forward_dense(params, cfg, tokens)

    # Build the paged cache from the prefill of the first s tokens.
    _, kvs = llama.prefill(params, cfg, tokens[:, :s])
    n_pages_seq = s // cfg.page_size
    max_pages = 4
    total_pages = 8
    k_pages = jnp.zeros(
        (cfg.n_layers, total_pages, cfg.page_size, cfg.n_kv_heads,
         cfg.head_dim),
        dtype=cfg.jdtype,
    )
    v_pages = jnp.zeros_like(k_pages)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        k_pages = k_pages.at[li, :n_pages_seq].set(kp[0])
        v_pages = v_pages.at[li, :n_pages_seq].set(vp[0])
    page_table = jnp.zeros((1, max_pages), dtype=jnp.int32)
    page_table = page_table.at[0, :3].set(jnp.arange(3, dtype=jnp.int32))

    logits, _, _ = llama.decode_step(
        params,
        cfg,
        tokens[:, s],
        jnp.asarray([s], dtype=jnp.int32),
        k_pages,
        v_pages,
        page_table,
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]),
        np.asarray(dense_logits[0, s]),
        rtol=2e-4,
        atol=2e-4,
    )


def test_kv_pages_store_roundtrip(params, cfg, shm_conn):
    """Prefill → page out KV to the store → restore → decode works on the
    restored cache (the config-3 offload flow)."""
    from infinistore_tpu.tpu import TpuKVStore

    store = TpuKVStore(shm_conn)
    rng = np.random.default_rng(2)
    s = 16
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (1, s)), dtype=jnp.int32
    )
    _, kvs = llama.prefill(params, cfg, tokens)
    prefix = f"seq_{uuid.uuid4()}"
    n_pages = s // cfg.page_size

    # Offload every layer's pages.
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        store.put_kv_pages(llama.page_keys(prefix, li, "k", n_pages), kp[0])
        store.put_kv_pages(llama.page_keys(prefix, li, "v", n_pages), vp[0])
    shm_conn.sync()

    # Prefix-cache hit detection.
    keys_l0 = llama.page_keys(prefix, 0, "k", n_pages + 2)
    assert store.cached_prefix_len(keys_l0) == n_pages

    # Restore into fresh page arrays and verify bytes.
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        got_k = store.get_kv_pages(
            llama.page_keys(prefix, li, "k", n_pages),
            cfg.kv_page_shape(),
            cfg.jdtype,
        )
        got_v = store.get_kv_pages(
            llama.page_keys(prefix, li, "v", n_pages),
            cfg.kv_page_shape(),
            cfg.jdtype,
        )
        assert np.array_equal(np.asarray(got_k), np.asarray(kp[0]))
        assert np.array_equal(np.asarray(got_v), np.asarray(vp[0]))


def test_prefill_with_prefix_matches_full(params, cfg):
    """Suffix prefill over cached prefix KV must reproduce the full
    prefill's suffix logits AND suffix KV — the cache-hit path is a
    FLOP-saving identity, not an approximation."""
    rng = np.random.default_rng(3)
    p_len, s_new = 24, 16
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, p_len + s_new)), dtype=jnp.int32
    )
    full_logits, full_kvs = llama.prefill(params, cfg, tokens)

    _, prefix_kvs = llama.prefill(params, cfg, tokens[:, :p_len])
    tail_logits, tail_kvs = llama.prefill_with_prefix(
        params, cfg, tokens[:, p_len:], prefix_kvs
    )
    np.testing.assert_allclose(
        np.asarray(tail_logits),
        np.asarray(full_logits[:, p_len:]),
        rtol=2e-4, atol=2e-4,
    )
    for (tk, tv), (fk, fv) in zip(tail_kvs, full_kvs):
        np.testing.assert_allclose(
            np.asarray(tk), np.asarray(fk[:, p_len:]), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(tv), np.asarray(fv[:, p_len:]), rtol=2e-4, atol=2e-4
        )


def test_prefix_cache_hit_flow(params, cfg, shm_conn):
    """The full vLLM cache-HIT loop against a real store: prefill A,
    page out; a second request shares A's prefix — match → restore pages
    → pages_to_kv → suffix-only prefill — and must land on the same
    logits as prefilling from scratch."""
    from infinistore_tpu.tpu import TpuKVStore

    store = TpuKVStore(shm_conn)
    rng = np.random.default_rng(5)
    p_len = 16  # two pages — page-aligned prefix, as vLLM guarantees
    s_new = 8
    prefix_tokens = rng.integers(0, cfg.vocab_size, (1, p_len))
    tokens = jnp.asarray(
        np.concatenate(
            [prefix_tokens, rng.integers(0, cfg.vocab_size, (1, s_new))],
            axis=1,
        ),
        dtype=jnp.int32,
    )

    # Request 1: prefill the prefix, page it out to the store.
    seq = f"pfx_{uuid.uuid4()}"
    _, kvs = llama.prefill(params, cfg, tokens[:, :p_len])
    n_pages = p_len // cfg.page_size
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        store.put_kv_pages(llama.page_keys(seq, li, "k", n_pages), kp[0])
        store.put_kv_pages(llama.page_keys(seq, li, "v", n_pages), vp[0])
    shm_conn.sync()

    # Request 2: detect the hit, restore, suffix-prefill.
    want_pages = (p_len + s_new + cfg.page_size - 1) // cfg.page_size
    hit = store.cached_prefix_len(
        llama.page_keys(seq, 0, "k", want_pages)
    )
    assert hit == n_pages
    prefix_kvs = llama.restore_prefix_kvs(store, cfg, seq, hit)
    tail_logits, _ = llama.prefill_with_prefix(
        params, cfg, tokens[:, p_len:], prefix_kvs
    )

    full_logits, _ = llama.prefill(params, cfg, tokens)
    np.testing.assert_allclose(
        np.asarray(tail_logits),
        np.asarray(full_logits[:, p_len:]),
        rtol=2e-4, atol=2e-4,
    )


def test_verify_step_equals_sequential_decode(params, cfg):
    """verify_step must consume m tokens in one pass and reproduce m
    sequential decode_steps — logits at every position AND the final
    page contents (the invariant speculative decoding rests on)."""
    rng = np.random.default_rng(7)
    s, m = 12, 3
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, s)), dtype=jnp.int32
    )
    step_toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (2, m)), dtype=jnp.int32
    )
    _, kvs = llama.prefill(params, cfg, tokens)
    total_pages, max_pages = 8, 4
    shape = (cfg.n_layers, total_pages, cfg.page_size, cfg.n_kv_heads,
             cfg.head_dim)
    k_pages = jnp.zeros(shape, dtype=cfg.jdtype)
    v_pages = jnp.zeros_like(k_pages)
    # Batch row 0 owns pages 0-3, row 1 owns 4-7 (interleaved layout on
    # purpose — exercises the per-row page tables).
    pt = np.stack([np.arange(4), 4 + np.arange(4)]).astype(np.int32)
    for li, (k, v) in enumerate(kvs):
        kp, vp = llama.kv_to_pages(cfg, k, v)
        for bi in range(2):
            k_pages = k_pages.at[li, pt[bi, : kp.shape[1]]].set(kp[bi])
            v_pages = v_pages.at[li, pt[bi, : vp.shape[1]]].set(vp[bi])
    page_table = jnp.asarray(pt)
    seq_lens = jnp.asarray([s, s], dtype=jnp.int32)

    # Sequential reference: m single-token decode steps.
    ks, vs = k_pages, v_pages
    seq_logits = []
    for j in range(m):
        lg, ks, vs = llama.decode_step(
            params, cfg, step_toks[:, j], seq_lens + j, ks, vs, page_table
        )
        seq_logits.append(lg)

    ver_logits, kv2, vv2 = llama.verify_step(
        params, cfg, step_toks, seq_lens, k_pages, v_pages, page_table
    )
    for j in range(m):
        np.testing.assert_allclose(
            np.asarray(ver_logits[:, j]), np.asarray(seq_logits[j]),
            rtol=2e-4, atol=2e-4,
        )
    np.testing.assert_allclose(
        np.asarray(kv2), np.asarray(ks), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(vv2), np.asarray(vs), rtol=2e-5, atol=2e-5
    )


def test_scatter_kv_to_pages():
    pages = jnp.zeros((4, 8, 2, 4))
    new = jnp.ones((2, 1, 2, 4))
    out = pa.scatter_kv_to_pages(
        pages, new, jnp.asarray([1, 3]), jnp.asarray([0, 5])
    )
    assert float(out[1, 0].sum()) == 8.0
    assert float(out[3, 5].sum()) == 8.0
    assert float(out.sum()) == 16.0


# ---------------------------------------------------------------------------
# The pool stays one 5-D array through a step: updated in place, never
# sliced to a layer or stacked back.
# ---------------------------------------------------------------------------

def _pool_cfgs():
    from infinistore_tpu.models import moe

    kw = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4,
              n_kv_heads=2, d_ff=64, max_seq=64, page_size=8,
              dtype="float32")
    return {
        "llama": (llama, llama.LlamaConfig(**kw)),
        "moe": (moe, moe.MoEConfig(n_experts=4, top_k=2,
                                   capacity_factor=4.0, **kw)),
    }


def _fused_step(model, step):
    """The donated device program the engine runs for `step` (verify_step
    has none of its own there, so the test donates the model's)."""
    from infinistore_tpu import serving

    if step == "decode_step":
        return lambda p, cfg, tok, lens, kp, vp, rows: (
            serving._decode_fused.lower(p, cfg, tok, lens, kp, vp, rows,
                                        model=model))
    if step == "_decode_scan":
        return lambda p, cfg, tok, lens, kp, vp, rows: (
            serving._decode_scan.lower(p, cfg, tok, lens, kp, vp, rows,
                                       n_steps=4, model=model))
    verify = jax.jit(model.verify_step.__wrapped__,
                     static_argnames=("cfg",), donate_argnums=(4, 5))
    return lambda p, cfg, tok, lens, kp, vp, rows: verify.lower(
        p, cfg, jnp.zeros((tok.shape[0], 3), jnp.int32), lens, kp, vp, rows)


@pytest.mark.parametrize("step", ["decode_step", "verify_step",
                                  "_decode_scan"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_step_program_holds_no_layer_of_the_pool(family, step):
    """The counter that says the in-place mechanism engages, read off
    the compiled program: with a pool far larger than everything else,
    the donated step's temporaries stay under ONE layer-and-kind of it.
    Slicing `k_pages[li]` per layer and stacking the slices back (the
    formulation before) held more than the whole pool."""
    model, cfg = _pool_cfgs()[family]
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch, max_pages, n_pages = 2, 4, 8192
    pool = jnp.zeros((cfg.n_layers, n_pages, *cfg.kv_page_shape()),
                     cfg.jdtype)
    one_layer_and_kind = pool.nbytes // cfg.n_layers
    weights = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    assert one_layer_and_kind > 8 * weights
    lens = jnp.zeros((batch,), jnp.int32)
    rows = jnp.zeros((batch, max_pages), jnp.int32)
    compiled = _fused_step(model, step)(
        params, cfg, lens, lens, pool, pool, rows).compile()
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * pool.nbytes  # donated, aliased
    assert ma.temp_size_in_bytes < one_layer_and_kind, (
        ma.temp_size_in_bytes, one_layer_and_kind)


def _step_sliced(model, params, cfg, tokens, seq_lens, k_pages, v_pages,
                 page_table, valid_len=None):
    """The formulation decode_step / verify_step had before the pool
    stayed whole, kept here as the reference: slice a layer out, scatter
    the tokens' rows into the slice, attend over the slice, stack the
    slices back. tokens [batch, m]; m == 1 is a decode step."""
    b, m = tokens.shape
    x = llama._embed(params, tokens, cfg)
    positions = seq_lens[:, None] + jnp.arange(m)[None, :]
    target_page = jnp.take_along_axis(
        page_table, positions // cfg.page_size, axis=1)
    slot = positions % cfg.page_size
    ok = None
    if valid_len is not None:
        ok = jnp.arange(m)[None, :] < valid_len[:, None]
        target_page = jnp.where(ok, target_page, 0)
        slot = jnp.where(ok, slot, jnp.arange(m)[None, :] % cfg.page_size)
    new_k, new_v = [], []
    for li, layer in enumerate(params["layers"]):
        q, k, v = llama._qkv(layer, x, cfg, positions)
        kp = k_pages[li].at[target_page, slot].set(k, mode="drop")
        vp = v_pages[li].at[target_page, slot].set(v, mode="drop")
        attn = pa.multi_token_paged_attention(q, kp, vp, page_table,
                                              seq_lens, window=cfg.window)
        x = x + llama._attn_out(layer, attn.reshape(b, m, -1))
        if model is llama:
            x = x + llama._mlp(layer, x, cfg)
        else:
            valid = (seq_lens > 0)[:, None] if valid_len is None else ok
            x = x + model._moe_mlp(layer, x, cfg, valid)[0]
        new_k.append(kp)
        new_v.append(vp)
    return jnp.stack(new_k), jnp.stack(new_v)


@pytest.mark.parametrize("step", ["decode_step", "verify_step"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_pool_after_a_step_equals_slice_scatter_stack(family, step):
    """After a step the WHOLE pool is bit-identical to the sliced
    formulation's: the tokens' rows written in every layer, every other
    byte of every layer untouched, inactive slots' rows in scratch page
    0, and a page id equal to total_pages dropped."""
    model, cfg = _pool_cfgs()[family]
    params = model.init_params(jax.random.PRNGKey(1), cfg)
    total_pages = 12
    rng = np.random.default_rng(7)
    shape = (cfg.n_layers, total_pages, *cfg.kv_page_shape())
    k_pages = jnp.asarray(rng.standard_normal(shape), cfg.jdtype)
    v_pages = jnp.asarray(rng.standard_normal(shape), cfg.jdtype)
    # slot 0: mid-page; slot 1: inactive (length 0, table of zeros ->
    # scratch page 0); slot 2: its next page is not allocated yet (the
    # table pads with total_pages -> dropped); slot 3: last slot of a page.
    seq_lens = jnp.asarray([11, 0, 16, 7], jnp.int32)
    page_table = jnp.asarray([[3, 4, 9, total_pages],
                              [0, 0, 0, 0],
                              [5, 6, total_pages, total_pages],
                              [7, 8, total_pages, total_pages]], jnp.int32)
    if step == "decode_step":
        valid_len = None
        tokens = jnp.asarray([[5], [0], [9], [2]], jnp.int32)
        _, k_new, v_new = model.decode_step(
            params, cfg, tokens[:, 0], seq_lens, k_pages, v_pages,
            page_table)
        # position -> (page, slot) of the rows a step must have written
        written = {(3 + 1, 3), (0, 0), (8 - 1, 7)}
    else:
        valid_len = jnp.asarray([3, 0, 2, 3], jnp.int32)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 3)),
                             jnp.int32)
        _, k_new, v_new = model.verify_step(
            params, cfg, tokens, seq_lens, k_pages, v_pages, page_table,
            valid_len)
        written = {(4, 3), (4, 4), (4, 5),       # slot 0: 11, 12, 13
                   (0, 0), (0, 1), (0, 2),       # inactive + padding
                   (7, 7), (8, 0), (8, 1)}       # slot 3 crosses a page
        # slot 2's two real tokens (16, 17) sit on a dropped page id;
        # its padded third column goes to scratch slot 2 (listed above).
    k_ref, v_ref = jax.jit(_step_sliced, static_argnums=(0, 2))(
        model, params, cfg, tokens, seq_lens, k_pages, v_pages, page_table,
        valid_len)
    np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_ref))
    for before, after in ((k_pages, k_new), (v_pages, v_new)):
        diff = np.any(np.asarray(before) != np.asarray(after), axis=(3, 4))
        for li in range(cfg.n_layers):  # [total_pages, page] per layer
            assert {(int(p), int(s)) for p, s in zip(*np.nonzero(diff[li]))
                    } == written, li


def test_train_step_sharded_mesh(cfg):
    """Full training step jitted over the 8-device (dp=2, tp=4) mesh."""
    import optax

    from infinistore_tpu.parallel import mesh as pmesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = pmesh.make_mesh(pmesh.MeshConfig(dp=2, tp=4), jax.devices()[:8])
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    params = pmesh.shard_params(mesh, params)
    optimizer = optax.adamw(1e-3)
    opt_state = optimizer.init(params)
    tokens = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)),
            dtype=jnp.int32,
        ),
        NamedSharding(mesh, P("dp")),
    )

    def step(p, o, t):
        return llama.train_step(p, o, cfg, t, optimizer)

    p2, o2, loss = jax.jit(step)(params, opt_state, tokens)
    jax.block_until_ready(loss)
    assert np.isfinite(float(loss))
    # Parameters actually sharded: wq lives on the tp axis.
    wq_shard = p2["layers"][0]["wq"].sharding
    assert "tp" in (wq_shard.spec[1],)


def test_train_step_fsdp_matches_replicated(cfg):
    """FSDP/ZeRO placement (weights + Adam moments 1/dp per rank,
    collectives inserted by XLA) computes the identical loss to the
    megatron tp/dp placement — same math, different sharding."""
    import optax

    from infinistore_tpu.parallel import mesh as pmesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = pmesh.make_mesh(pmesh.MeshConfig(dp=2, tp=4), jax.devices()[:8])
    host_params = llama.init_params(jax.random.PRNGKey(0), cfg)
    optimizer = optax.adamw(1e-3)
    tokens = jax.device_put(
        jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)),
            dtype=jnp.int32,
        ),
        NamedSharding(mesh, P("dp")),
    )

    def step(p, o, t):
        return llama.train_step(p, o, cfg, t, optimizer)

    losses = {}
    for name, sh in (
        ("tp", pmesh.param_shardings(mesh, host_params)),
        ("fsdp", pmesh.fsdp_param_shardings(mesh, host_params)),
    ):
        p = jax.device_put(host_params, sh)
        o = optimizer.init(p)
        p2, o2, loss = jax.jit(step)(p, o, tokens)
        jax.block_until_ready(loss)
        losses[name] = float(loss)
        if name == "fsdp":
            # Every weight matrix (and its Adam moments, via
            # init-on-sharded) carries a dp-sharded axis.
            wq_spec = p2["layers"][0]["wq"].sharding.spec
            assert "dp" in tuple(wq_spec), wq_spec
            mu_spec = o2[0].mu["layers"][0]["wq"].sharding.spec
            assert "dp" in tuple(mu_spec), mu_spec
    assert abs(losses["fsdp"] - losses["tp"]) < 1e-3, losses


def test_graft_entry():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2 and np.isfinite(np.asarray(out)).all()


def test_graft_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)
