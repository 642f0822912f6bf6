"""Decoder-stack tests, one suite for every model family: decode-vs-dense
equivalence, prefix-cached prefill, the m-token step, the window, store
round-trip of KV pages; and the sharded training step on the virtual
8-device mesh."""

import contextlib
import dataclasses
import uuid
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.models import decoder, llama, moe
from infinistore_tpu.ops import paged_attention as pa


_CFG = dict(
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
# capacity_factor = n_experts / top_k: per-expert capacity equals the
# token count, so no token is ever dropped whatever T a pass routes over
# (a whole sequence in the dense forward, one batch in a paged step) and
# the paths compare exactly. GShard capacity is per forward pass; a
# config that drops in one and not in the other differs BY DESIGN.
_MOE = dict(n_experts=4, top_k=2, capacity_factor=2.0)


def _family(name, **kw):
    """(model module, config, params) of one family at the tiny width."""
    if name == "llama":
        model, cfg = llama, llama.LlamaConfig(**{**_CFG, **kw})
    else:
        model, cfg = moe, moe.MoEConfig(**{**_CFG, **_MOE, **kw})
    return SimpleNamespace(
        model=model, cfg=cfg,
        params=model.init_params(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module", params=["llama", "moe"])
def family(request):
    return _family(request.param)


@pytest.fixture(scope="module", params=["llama", "moe"])
def windowed(request):
    """A sliding window of two pages, far shorter than the sequences."""
    return _family(request.param, window=16)


@pytest.fixture(scope="module")
def cfg():
    return llama.LlamaConfig(**_CFG)


def _tokens(seed, cfg, shape):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, cfg.vocab_size, shape),
        dtype=jnp.int32,
    )


def _empty_pool(cfg, total_pages):
    k_pages = jnp.zeros((cfg.n_layers, total_pages, *cfg.kv_page_shape()),
                        dtype=cfg.jdtype)
    return k_pages, jnp.zeros_like(k_pages)


def _page_in(cfg, pools, kvs, row, page_ids):
    """Write batch row `row` of a prefill's per-layer KV into the pools
    at `page_ids`."""
    k_pages, v_pages = pools
    for li, (k, v) in enumerate(kvs):
        kp, vp = decoder.kv_to_pages(cfg, k, v)
        ids = jnp.asarray(page_ids[: kp.shape[1]])
        k_pages = k_pages.at[li, ids].set(kp[row])
        v_pages = v_pages.at[li, ids].set(vp[row])
    return k_pages, v_pages


def _dense_greedy(f, prompt, n_new):
    """Greedy generation by dense re-forward: the oracle of the engine's
    token stream that has no paged cache in it."""
    toks, out = list(prompt), []
    for _ in range(n_new):
        logits = f.model.forward_dense(
            f.params, f.cfg, jnp.asarray([toks], dtype=jnp.int32))[0]
        out.append(int(jnp.argmax(logits[0, -1])))
        toks.append(out[-1])
    return out


def test_prefill_shapes(family):
    model, cfg, params = family.model, family.cfg, family.params
    tokens = _tokens(0, cfg, (2, 16))
    logits, kvs, *aux = model.forward_dense(params, cfg, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert len(kvs) == cfg.n_layers
    assert kvs[0][0].shape == (2, 16, cfg.n_kv_heads, cfg.head_dim)
    assert np.isfinite(np.asarray(logits)).all()
    assert all(np.isfinite(float(a)) for a in aux)  # MoE: the aux loss
    p_logits, p_kvs = model.prefill(params, cfg, tokens)
    np.testing.assert_array_equal(np.asarray(p_logits), np.asarray(logits))
    assert len(p_kvs) == cfg.n_layers


def test_paged_decode_matches_dense(family):
    """Decoding token s+1 with paged KV must reproduce the dense forward's
    logits for that position — paging is a layout change, not math — and
    the engine's greedy stream over the paged cache is the dense one's."""
    from infinistore_tpu.serving import Request, ServingEngine

    model, cfg, params = family.model, family.cfg, family.params
    s = 16  # two pages
    tokens = _tokens(1, cfg, (1, s + 1))
    dense_logits = model.forward_dense(params, cfg, tokens)[0]

    # Build the paged cache from the prefill of the first s tokens.
    _, kvs = model.prefill(params, cfg, tokens[:, :s])
    k_pages, v_pages = _page_in(cfg, _empty_pool(cfg, 8), kvs, 0,
                                np.arange(3))
    page_table = jnp.zeros((1, 4), dtype=jnp.int32)
    page_table = page_table.at[0, :3].set(jnp.arange(3, dtype=jnp.int32))

    logits, _, _ = model.decode_step(
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

    prompt = [int(t) for t in np.asarray(_tokens(50, cfg, (11,)))]
    eng = ServingEngine(params, cfg, model=model)
    out = eng.run([Request("r", prompt, max_new_tokens=9)])
    assert out["r"] == _dense_greedy(family, prompt, 9)


def test_kv_pages_store_roundtrip(family, shm_conn):
    """Prefill → page out KV to the store → restore → decode works on the
    restored cache (the config-3 offload flow). Every family's pages are
    ordinary store blocks through the same helpers."""
    from infinistore_tpu.tpu import TpuKVStore

    model, cfg, params = family.model, family.cfg, family.params
    store = TpuKVStore(shm_conn)
    s = 16
    tokens = _tokens(2, cfg, (1, s))
    _, kvs = model.prefill(params, cfg, tokens)
    prefix = f"seq_{uuid.uuid4()}"
    n_pages = s // cfg.page_size

    # Offload every layer's pages.
    for li, (k, v) in enumerate(kvs):
        kp, vp = decoder.kv_to_pages(cfg, k, v)
        store.put_kv_pages(decoder.page_keys(prefix, li, "k", n_pages),
                           kp[0])
        store.put_kv_pages(decoder.page_keys(prefix, li, "v", n_pages),
                           vp[0])
    shm_conn.sync()

    # Prefix-cache hit detection.
    keys_l0 = decoder.page_keys(prefix, 0, "k", n_pages + 2)
    assert store.cached_prefix_len(keys_l0) == n_pages

    # Restore into fresh page arrays and verify bytes.
    for li, (k, v) in enumerate(kvs):
        kp, vp = decoder.kv_to_pages(cfg, k, v)
        got_k = store.get_kv_pages(
            decoder.page_keys(prefix, li, "k", n_pages),
            cfg.kv_page_shape(),
            cfg.jdtype,
        )
        got_v = store.get_kv_pages(
            decoder.page_keys(prefix, li, "v", n_pages),
            cfg.kv_page_shape(),
            cfg.jdtype,
        )
        assert np.array_equal(np.asarray(got_k), np.asarray(kp[0]))
        assert np.array_equal(np.asarray(got_v), np.asarray(vp[0]))


def test_prefill_with_prefix_matches_full(family):
    """Suffix prefill over cached prefix KV must reproduce the full
    prefill's suffix logits AND suffix KV — the cache-hit path is a
    FLOP-saving identity, not an approximation."""
    model, cfg, params = family.model, family.cfg, family.params
    p_len, s_new = 24, 16
    tokens = _tokens(3, cfg, (2, p_len + s_new))
    full_logits, full_kvs = model.prefill(params, cfg, tokens)

    _, prefix_kvs = model.prefill(params, cfg, tokens[:, :p_len])
    tail_logits, tail_kvs = model.prefill_with_prefix(
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


def test_prefix_cache_hit_flow(family, shm_conn):
    """The full vLLM cache-HIT loop against a real store: prefill A,
    page out; a second request shares A's prefix — match → restore pages
    → pages_to_kv → suffix-only prefill — and must land on the same
    logits as prefilling from scratch."""
    from infinistore_tpu.tpu import TpuKVStore

    model, cfg, params = family.model, family.cfg, family.params
    store = TpuKVStore(shm_conn)
    p_len = 16  # two pages — page-aligned prefix, as vLLM guarantees
    s_new = 8
    tokens = _tokens(5, cfg, (1, p_len + s_new))

    # Request 1: prefill the prefix, page it out to the store.
    seq = f"pfx_{uuid.uuid4()}"
    _, kvs = model.prefill(params, cfg, tokens[:, :p_len])
    n_pages = p_len // cfg.page_size
    for li, (k, v) in enumerate(kvs):
        kp, vp = decoder.kv_to_pages(cfg, k, v)
        store.put_kv_pages(decoder.page_keys(seq, li, "k", n_pages), kp[0])
        store.put_kv_pages(decoder.page_keys(seq, li, "v", n_pages), vp[0])
    shm_conn.sync()

    # Request 2: detect the hit, restore, suffix-prefill.
    want_pages = (p_len + s_new + cfg.page_size - 1) // cfg.page_size
    hit = store.cached_prefix_len(
        decoder.page_keys(seq, 0, "k", want_pages)
    )
    assert hit == n_pages
    prefix_kvs = decoder.restore_prefix_kvs(store, cfg, seq, hit)
    tail_logits, _ = model.prefill_with_prefix(
        params, cfg, tokens[:, p_len:], prefix_kvs
    )

    full_logits, _ = model.prefill(params, cfg, tokens)
    np.testing.assert_allclose(
        np.asarray(tail_logits),
        np.asarray(full_logits[:, p_len:]),
        rtol=2e-4, atol=2e-4,
    )


def _two_row_cache(f, s, seed):
    """Two sequences of `s` tokens prefilled and paged in: batch row 0
    owns pages 1-4, row 1 owns 5-8 (page 0 is the scratch page)."""
    cfg = f.cfg
    tokens = _tokens(seed, cfg, (2, s))
    _, kvs = f.model.prefill(f.params, cfg, tokens)
    pt = np.stack([1 + np.arange(4), 5 + np.arange(4)]).astype(np.int32)
    pools = _empty_pool(cfg, 9)
    for bi in range(2):
        pools = _page_in(cfg, pools, kvs, bi, pt[bi])
    return tokens, pools, jnp.asarray(pt)


def test_verify_step_equals_sequential_decode(family):
    """verify_step must consume m tokens in one pass and reproduce m
    sequential decode_steps — logits at every position AND the final
    page contents (the invariant speculative decoding rests on)."""
    model, cfg, params = family.model, family.cfg, family.params
    s, m = 12, 3
    _, (k_pages, v_pages), page_table = _two_row_cache(family, s, 7)
    step_toks = _tokens(8, cfg, (2, m))
    seq_lens = jnp.asarray([s, s], dtype=jnp.int32)

    # Sequential reference: m single-token decode steps.
    ks, vs = k_pages, v_pages
    seq_logits = []
    for j in range(m):
        lg, ks, vs = model.decode_step(
            params, cfg, step_toks[:, j], seq_lens + j, ks, vs, page_table
        )
        seq_logits.append(lg)

    ver_logits, kv2, vv2 = model.verify_step(
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


def test_verify_step_ragged_valid_len_equals_unpadded(family):
    """An m-token step whose rows hold 4, 2 and 0 real tokens: every
    valid position's logits and every live page equal what each row's
    own unpadded step gives. Padded columns and the row with nothing to
    add write only to scratch page 0."""
    model, cfg, params = family.model, family.cfg, family.params
    s, m = 12, 4
    _, (k0, v0), pt2 = _two_row_cache(family, s, 11)
    # Row 2 is a live sequence (row 1's cache again, on pages of its
    # own) that adds no token in this step.
    page_table = jnp.concatenate([pt2, pt2[1:]], axis=0)
    seq_lens = jnp.asarray([s, s, s], dtype=jnp.int32)
    valid_len = jnp.asarray([4, 2, 0], dtype=jnp.int32)
    step_toks = _tokens(12, cfg, (3, m))

    logits, k1, v1 = model.verify_step(
        params, cfg, step_toks, seq_lens, k0, v0, page_table, valid_len)

    k_ref, v_ref = k0, v0
    for row in (0, 1):
        n = int(valid_len[row])
        lg, k_ref, v_ref = model.verify_step(
            params, cfg, step_toks[row:row + 1, :n], seq_lens[row:row + 1],
            k_ref, v_ref, page_table[row:row + 1])
        np.testing.assert_allclose(
            np.asarray(logits[row, :n]), np.asarray(lg[0]),
            rtol=2e-4, atol=2e-4)
    # Every page but the scratch page: the live ones as the unpadded
    # steps left them (row 2 shares row 1's, which its 0 tokens must not
    # touch), the free ones untouched.
    np.testing.assert_allclose(np.asarray(k1[:, 1:]), np.asarray(k_ref[:, 1:]),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(v1[:, 1:]), np.asarray(v_ref[:, 1:]),
                               rtol=2e-5, atol=2e-5)


def test_windowed_paged_paths_match_dense_band_mask(windowed):
    """With a window of two pages, prefill + one paged decode step + an
    m-token paged step give the logits (and so the tokens) of the dense
    band-masked forward over the whole sequence. `window` reaches three
    kernels; this is the test that sets it for both families."""
    model, cfg, params = windowed.model, windowed.cfg, windowed.params
    s, m = 24, 3
    tokens = _tokens(21, cfg, (1, s + 1 + m))
    dense = model.forward_dense(params, cfg, tokens)[0]
    unwindowed = model.forward_dense(
        params, dataclasses.replace(cfg, window=0), tokens)[0]
    assert not np.allclose(np.asarray(dense[0, s:]),
                           np.asarray(unwindowed[0, s:]), atol=1e-3)

    _, kvs = model.prefill(params, cfg, tokens[:, :s])
    pages = 1 + np.arange(4)
    k_pages, v_pages = _page_in(cfg, _empty_pool(cfg, 6), kvs, 0, pages)
    page_table = jnp.asarray(pages[None], dtype=jnp.int32)
    lens = jnp.asarray([s], dtype=jnp.int32)
    one, k_pages, v_pages = model.decode_step(
        params, cfg, tokens[:, s], lens, k_pages, v_pages, page_table)
    many, _, _ = model.verify_step(
        params, cfg, tokens[:, s + 1:], lens + 1, k_pages, v_pages,
        page_table)
    paged = np.concatenate([np.asarray(one)[:, None], np.asarray(many)],
                           axis=1)
    np.testing.assert_allclose(paged, np.asarray(dense[:, s:]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(paged.argmax(-1),
                                  np.asarray(dense[:, s:]).argmax(-1))


def test_pos0_shifted_prefix_prefill_equals_unshifted(windowed):
    """A prefix trimmed to its in-window tail pages and prefilled at
    `pos0` = the trimmed length gives the suffix logits and suffix KV of
    the whole prefix at pos0 = 0: the same absolute rope positions, and
    a band mask that never reached the dropped page."""
    model, cfg, params = windowed.model, windowed.cfg, windowed.params
    p_len, s_new, cut = 24, 8, 8  # window 16: suffix sees keys >= 9
    tokens = _tokens(22, cfg, (2, p_len + s_new))
    _, prefix_kvs = model.prefill(params, cfg, tokens[:, :p_len])
    whole_logits, whole_kvs = model.prefill_with_prefix(
        params, cfg, tokens[:, p_len:], prefix_kvs)
    tail = [(k[:, cut:], v[:, cut:]) for k, v in prefix_kvs]
    cut_logits, cut_kvs = model.prefill_with_prefix(
        params, cfg, tokens[:, p_len:], tail, pos0=cut)
    np.testing.assert_allclose(np.asarray(cut_logits),
                               np.asarray(whole_logits),
                               rtol=2e-4, atol=2e-4)
    for (ck, cv), (wk, wv) in zip(cut_kvs, whole_kvs):
        np.testing.assert_allclose(np.asarray(ck), np.asarray(wk),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(cv), np.asarray(wv),
                                   rtol=2e-4, atol=2e-4)
    # pos0 matters: the same trimmed prefix at pos0 = 0 is another model.
    wrong, _ = model.prefill_with_prefix(
        params, cfg, tokens[:, p_len:], tail)
    assert not np.allclose(np.asarray(wrong), np.asarray(whole_logits),
                           atol=1e-3)


# ---------------------------------------------------------------------------
# One stack: the families differ in their config fields, their init and
# their feed-forward block, and in nothing else.
# ---------------------------------------------------------------------------

def _binding(model, name):
    f = getattr(model, "_forward_stack" if name == "forward_stack" else name)
    return getattr(f, "__wrapped__", f)  # under the jit, the partial


@pytest.mark.parametrize("loop", ["forward_stack", "decode_step",
                                  "verify_step"])
def test_families_bind_one_loop(loop):
    """Each family's program is decoder.py's loop with the family's
    block bound in: one function object, not a copy kept equal."""
    for model, block in ((llama, llama._mlp), (moe, moe._moe_mlp)):
        bound = _binding(model, loop)
        assert bound.func is getattr(decoder, loop)
        assert bound.args == (block,)


def test_moe_config_is_a_llama_config():
    assert issubclass(moe.MoEConfig, llama.LlamaConfig)
    base, ext = llama.LlamaConfig(), moe.MoEConfig()
    shared = [f.name for f in dataclasses.fields(llama.LlamaConfig)]
    assert {f.name for f in dataclasses.fields(moe.MoEConfig)} == set(
        shared) | {"n_experts", "top_k", "capacity_factor",
                   "aux_loss_weight",
                   # the sorted dispatch's router form and shared experts
                   "router", "route_scale", "n_shared", "shared_mean",
                   # one chip's share of the experts the router scores
                   "n_routed", "first_expert"}
    assert (ext.router, ext.route_scale, ext.n_shared) == ("softmax", 1.0,
                                                           0)
    assert (ext.n_routed, ext.first_expert, ext.holds_share,
            ext.shared_mean) == (0, 0, False, False)
    assert all(getattr(base, n) == getattr(ext, n) for n in shared)
    for name in ("head_dim", "jdtype", "kv_page_shape", "kv_page_bytes"):
        assert name not in vars(moe.MoEConfig), name  # inherited, not copied


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


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a DESCRIBED v5e host: the TPU's compiler is installed
    here and compiles for a chip that is not attached (nothing runs).
    Described inside the fixture, never at import: one process at a
    time may load the TPU's library, and every xdist worker imports
    this file."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        mp.setenv("TPU_SKIP_MDS_QUERY", "1")
        mp.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        mp.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _the_chips_branch(monkeypatch):
    """While a program is lowered and compiled for the described chip:
    `jax.default_backend` answers "tpu", so that the attention wrappers
    trace the branch the chip takes (the Pallas kernels), and the
    persistent compile cache is off (a compile for a described device
    is written to it but cannot be read back without a chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_hit_program_aliases_the_pools_and_holds_the_restored_pages_once(
        v5e_chip, monkeypatch):
    """The fused hit admission at mistral7b's widths and pool geometry,
    compiled for a described v5e as benchmark/tools/aot_memory.py
    compiles the other programs (the attention wrappers steered to the
    branch the chip takes): both pools are donated and aliased, and the
    temporaries stay under twice the restored bytes plus what the
    suffix prefill alone holds and returns. A scatter of the
    layer-major stacks as one `[:, ids]` update held 1.09 GB here, this
    form 0.21 GB; a pad to max_pages_per_seq, a pool layer sliced out or
    a pool copied would each show."""
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.lib import serve
    from infinistore_tpu import serving

    conf = serve.load_config("benchmark/configs/mistral7b.json")
    model, cfg = serve.model_config(conf)
    sc = conf["serving"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: model.init_params(k, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)))
    # The sessions mix's largest hit: 128 restored pages (134 MB), a
    # suffix of 352 tokens.
    s_pad, n = 352, 128
    i32 = jnp.int32
    pool = sds((cfg.n_layers, sc["total_pages"], *cfg.kv_page_shape()),
               cfg.jdtype)
    restored = sds((n * cfg.n_layers * 2, *cfg.kv_page_shape()), cfg.jdtype)
    kv = sds((1, n * cfg.page_size, cfg.n_kv_heads, cfg.head_dim),
             cfg.jdtype)
    with _the_chips_branch(monkeypatch):
        fused = serving._admit_fused_px.lower(
            params, cfg, sds((1, s_pad), i32), restored, pool, pool,
            sds((n,), i32), sds((sc["max_pages_per_seq"],), i32),
            sds((), i32), sds((), i32), model=model,
        ).compile().memory_analysis()
        alone = serving._prefill_px_jit.lower(
            params, cfg, sds((1, s_pad), i32), [(kv, kv)] * cfg.n_layers,
            sds((), i32), model=model,
        ).compile().memory_analysis()
    pool_bytes = 2 * int(np.prod(pool.shape)) * cfg.jdtype.itemsize
    restored_bytes = int(np.prod(restored.shape)) * cfg.jdtype.itemsize
    assert fused.alias_size_in_bytes >= pool_bytes  # donated, aliased
    # Its outputs beyond the pools: one logits row.
    assert fused.output_size_in_bytes - pool_bytes < 1 << 20
    bound = (2 * restored_bytes + alone.temp_size_in_bytes
             + alone.output_size_in_bytes)
    assert fused.temp_size_in_bytes < bound < pool_bytes // 4, (
        fused.temp_size_in_bytes, bound)


def _decode_program(family, slots=16):
    """(lower, pools' bytes, one layer-and-kind of the smallest pool,
    attention layers) of the engine's decode program for a family at
    the cells' attention widths (bf16, 128 lanes a cache row, pages of
    16) and a few narrow layers: `lower(sds)` lowers it over
    ShapeDtypeStructs, and `lower.layers` are its layers' weights as
    shapes."""
    import types

    from infinistore_tpu import serving
    from infinistore_tpu.models import cohere, hf, hybrid, smallthinker, xing

    total, table = 4096, 192
    if family == "llama":  # mistral7b, mixtral8x7b: 8 kv heads, group 4
        model = llama
        cfg = llama.LlamaConfig(
            vocab_size=256, d_model=256, n_layers=3, n_heads=32,
            n_kv_heads=8, head_dim_override=128, d_ff=256, page_size=16)
    elif family == "hybrid":  # granite4h-micro: head_dim 64, two a row
        model = hybrid
        cfg = hf.hybrid_config_from_hf(types.SimpleNamespace(
            vocab_size=256, hidden_size=2048, shared_intermediate_size=256,
            intermediate_size=256, num_hidden_layers=4,
            num_attention_heads=32, num_key_value_heads=8,
            layer_types=["mamba", "attention", "mamba", "attention"],
            mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
            mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
            mamba_chunk_size=256, mamba_conv_bias=True,
            mamba_proj_bias=False, num_local_experts=0,
            num_experts_per_tok=0, attention_multiplier=0.0625,
            embedding_multiplier=12, residual_multiplier=0.22,
            logits_scaling=8, position_embedding_type="nope",
            rope_scaling=None, attention_bias=False, hidden_act="silu",
            normalization_function="rmsnorm", tie_word_embeddings=True,
            rms_norm_eps=1e-5, max_position_embeddings=8192), page_size=16)
        assert cfg.kv_pack == 2
    elif family == "cohere":  # command-a-plus: 128 query heads in groups
        model = cohere       # of 16, window and full layers, a share of
        cfg = hf.cohere_moe_config_from_hf(types.SimpleNamespace(  # experts
            vocab_size=256, hidden_size=256, num_hidden_layers=4,
            num_attention_heads=128, num_key_value_heads=8, head_dim=128,
            intermediate_size=128, num_experts=2, num_experts_per_tok=2,
            num_shared_experts=4, layer_norm_eps=1e-5, rope_theta=50000,
            layer_types=["sliding_attention"] * 3 + ["full_attention"],
            sliding_window=4096, max_position_embeddings=16384,
            expert_share={"router_width": 8, "first_expert": 2}),
            page_size=16)
    elif family == "xing":  # xing4-29b: one latent row a token, 32 heads
        model = xing
        cfg = xing.XingConfig(
            vocab_size=256, d_model=256, n_layers=2, n_heads=32,
            q_lora_rank=768, kv_lora_rank=512, qk_nope=128, qk_rope=64,
            v_dim=128, d_ff=128, ffn_dense=256, n_experts=8, top_k=2,
            page_size=16)
    else:  # smallthinker21b: the group of 7, full and banded layers
        model = smallthinker
        cfg = hf.smallthinker_config_from_hf(types.SimpleNamespace(
            vocab_size=256, hidden_size=256, num_hidden_layers=4,
            num_attention_heads=28, num_key_value_heads=4, head_dim=128,
            moe_ffn_hidden_size=64, moe_num_primary_experts=8,
            moe_num_active_primary_experts=2,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True,
            rms_norm_eps=1e-6, rope_layout=[0, 1, 1, 1],
            sliding_window_layout=[0, 1, 1, 1], sliding_window_size=4096,
            rope_theta=1500000, rope_scaling=None,
            tie_word_embeddings=False, max_position_embeddings=16384),
            page_size=16)
    n_full = sum(pool == "full" for *_, pool, _ in decoder.attn_layers(cfg))
    n_win = sum(pool == "window" for *_, pool, _ in decoder.attn_layers(cfg))
    page = cfg.kv_page_shape()
    i32 = jnp.int32

    weights = jax.eval_shape(lambda k: model.init_params(k, cfg),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))

    def lower(sds):
        params = jax.tree_util.tree_map(
            lambda x: sds(x.shape, x.dtype), weights)
        pool = sds((n_full, total, *page), cfg.jdtype)
        lens, rows = sds((slots,), i32), sds((slots, table), i32)
        if family == "llama":
            return serving._decode_fused.lower(
                params, cfg, lens, lens, pool, pool, rows, model=model)
        if family == "xing":  # ONE pool
            return serving._decode_fused.lower(
                params, cfg, lens, lens, pool, None, rows, model=model,
                fetched=True)
        if family == "hybrid":
            state = jax.tree_util.tree_map(
                lambda x: sds(x.shape, x.dtype),
                jax.eval_shape(lambda: model.state_pools(cfg, slots)))
            return serving._decode_fused_st.lower(
                params, cfg, lens, lens, pool, pool, state, rows,
                model=model)
        wpool = sds((n_win, slots * 264 + 1, *page), cfg.jdtype)
        return serving._decode_fused_wf.lower(
            params, cfg, lens, lens, pool, pool, wpool, wpool,
            (rows, sds((slots, 264), i32), lens), model=model, fetched=True)

    lower.layers = weights["layers"]
    kind = total * int(np.prod(page)) * cfg.jdtype.itemsize
    # a paged-decode kernel a layer and, over routed experts, the
    # gathered expert kernel (ops/pallas_moe_decode.py: one function,
    # called by every layer); over state layers the state's update
    # (ops/ssm.py `step_kernel`: one function too)
    kernels = n_full + n_win + (family in ("smallthinker", "cohere",
                                           "hybrid"))
    return lower, 2 * (n_full + n_win) * kind, kind, kernels


@pytest.mark.parametrize("family", ["llama", "hybrid", "smallthinker",
                                    "cohere"])
def test_decode_program_for_the_chip_holds_no_layer_of_the_pool(
        family, v5e_chip, monkeypatch):
    """`_decode_fused`, `_decode_fused_st` and `_decode_fused_wf` with
    the Pallas decode kernel in them (compiled for a described v5e, the
    attention wrapper steered to the branch the chip takes): K and V
    reach every layer's kernel as the donated pools themselves. The
    kernel takes them in HBM as they lie and a page as [page * n_kv,
    hd] rows; if that view moved one tile, or the group of 7 or the
    packed rows made the wrapper slice a layer out, the program would
    hold a layer-and-kind of a pool for it (a relayout of the whole
    pool was 29.5 ms of a 48 ms step, PERF.md, PRs 25 and 31)."""
    lower, pool_bytes, one_layer_and_kind, n_calls = _decode_program(family)
    with _the_chips_branch(monkeypatch):
        lowered = lower(lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=v5e_chip))
        assert lowered.as_text().count("tpu_custom_call") == n_calls
        ma = lowered.compile().memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes * 0.99  # donated, aliased
    assert ma.temp_size_in_bytes < one_layer_and_kind // 2, (
        ma.temp_size_in_bytes, one_layer_and_kind)


def test_decode_program_of_three_kinds_for_the_chip_copies_no_pool(
        v5e_chip, monkeypatch):
    """`_decode_fused_wf_st` at Phi-4-mini-flash's attention and state
    widths (40 query heads over 10 packed rows of 128 lanes a token,
    a page as FLAT ROWS [160, 128]; a state of 16 x 5,120 a layer),
    compiled for a described v5e. 10 rows a token are no multiple of
    the 8 a tile holds: as a 5-D pool the page lay padded to 16 rows
    and every kernel call moved its layer whole (3.8 GB of temporaries
    in the real program, PERF.md PR 53). Held here: no `copy`,
    `copy-start` or `pad` of a page pool's shape in the text, one Pallas
    call an attention layer (banded, full and BORROWING alike: the
    cross layer reads the full pool where it lies) and one a state
    layer, the pools aliased, temporaries under a MB a slot."""
    import re

    from infinistore_tpu import serving
    from infinistore_tpu.models import hf, phi_flash

    cfg = hf.phi4flash_config_from_hf(SimpleNamespace(
        vocab_size=256, hidden_size=2560, intermediate_size=256,
        num_hidden_layers=8, num_attention_heads=40,
        num_key_value_heads=20, sliding_window=512, mb_per_layer=2,
        layer_norm_eps=1e-5, tie_word_embeddings=True,
        max_position_embeddings=16384), page_size=16, dtype="bfloat16")
    assert cfg.kv_page_shape() == (160, 128) and cfg.page_rows == 10
    slots, total, table, entries = 16, 4096, 192, 40
    weights = jax.eval_shape(lambda k: phi_flash.init_params(k, cfg),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    def described(tree):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)

    i32 = jnp.int32
    pool = sds((1, total, 160, 128), cfg.jdtype)
    wpool = sds((2, slots * entries + 1, 160, 128), cfg.jdtype)
    state = described(jax.eval_shape(
        lambda: phi_flash.state_pools(cfg, slots)))
    lens = sds((slots,), i32)
    with _the_chips_branch(monkeypatch):
        lowered = serving._decode_fused_wf_st.lower(
            described(weights), cfg, lens, lens, pool, pool, wpool, wpool,
            state, (sds((slots, table), i32), sds((slots, entries), i32),
                    lens), model=phi_flash)
        # a function a pool layer: 2 banded, 1 full, which the cross
        # layer's call IS (the same pool, layer and shapes); the 3 state
        # layers' one (ops/ssm.py `selective_step_kernel`)
        assert lowered.as_text().count("tpu_custom_call") == 3 + 1
        compiled = lowered.compile()
    text = compiled.as_text()
    for shape in (f"bf16[1,{total},160,128]", f"bf16[{total},160,128]",
                  f"bf16[2,{slots * entries + 1},160,128]",
                  f"f32[{slots},16,5120]"):
        moved = re.findall(r"= \(?" + re.escape(shape)
                           + r"[^=]*? (copy|copy-start|pad)\(", text)
        if shape.startswith("f32"):
            # a state pool is 5 MB: the compiler may stage it whole in
            # fast memory around its kernel (copy-start into and out of
            # memory space 1), which is no second copy in HBM
            moved = [m for m in moved if m != "copy-start"]
        assert not moved, (shape, moved)
    ma = compiled.memory_analysis()
    pools = 2 * (total + 2 * (slots * entries + 1)) * 160 * 128 * 2
    states = slots * 3 * (16 + 3) * 5120 * 4
    assert ma.alias_size_in_bytes >= (pools + states) * 0.99
    assert ma.temp_size_in_bytes < slots << 20, ma.temp_size_in_bytes


@pytest.mark.parametrize("form", ["rows", "planted"])
def test_decode_program_for_the_chip_copies_no_state_pool(form, v5e_chip,
                                                          monkeypatch):
    """`_decode_fused_st` at granite4h-micro's state widths (h [16, 64,
    64, 128] float32 a state layer, 33.5 MB), compiled for a described
    v5e: the program's text holds no `copy`, `copy-start` or scatter of
    a state pool's shape, and the state pools come back aliased to the
    donated ones (with the page pools: every byte of both). Each h
    pool reaches ONE Pallas call as it lies and the call moves the
    decoding slots' blocks alone. "planted" is the other form PR 52
    timed, the first slots of the order gathered, advanced and put back
    with `.at[rows].set` (at another batch, so that no cached trace of
    the real form answers): XLA scatters into a pool it first moves
    whole, and both must show, or the case above proves nothing."""
    import re

    from infinistore_tpu.ops import ssm

    if form == "planted":
        step = ssm.step

        def gathered(h, x, dt, A, B, C, rows):
            take = rows[1][:4]
            y, new = step(h[take], x[take], dt[take], A, B[take], C[take])
            return (jnp.zeros((h.shape[0], *y.shape[1:]), y.dtype)
                    .at[take].set(y), h.at[take].set(new))

        monkeypatch.setattr(ssm, "step", gathered)
    slots = 16 if form == "rows" else 12
    lower, pool_bytes, _, _ = _decode_program("hybrid", slots=slots)
    with _the_chips_branch(monkeypatch):
        compiled = lower(lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=v5e_chip)).compile()
    text = compiled.as_text()
    h = f"f32[{slots},64,64,128]"
    state_bytes = 2 * slots * (64 * 64 * 128 + 3 * 4352) * 4
    moved = re.findall(r"= \(?" + re.escape(h)
                       + r"[^=]*? (copy|copy-start|scatter)\(", text)
    if form == "planted":  # a scatter a state layer, and pools moved
        assert moved.count("scatter") == 2 and "copy-start" in moved, moved
        return
    assert not moved, moved
    assert text.count("tpu_custom_call") >= 4  # 2 attention, 2 state layers
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= (pool_bytes + state_bytes) * 0.99
    assert ma.temp_size_in_bytes < 1 << 24, ma.temp_size_in_bytes


@pytest.mark.parametrize("heads", [48, 12])
def test_state_kernel_compiles_for_the_chip_whatever_the_heads(
        heads, v5e_chip, monkeypatch):
    """`ssm.step_kernel` as the program calls it, over heads that are
    no multiple of 32, compiled for a described v5e: the tile
    `ssm.head_tile` picks (16 of 48; all 12 of 12) is one the chip's
    compiler takes, and the pool comes back aliased."""
    from infinistore_tpu.ops import ssm

    b, P, N = 16, 64, 128
    f32 = jnp.float32

    def arg(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    with _the_chips_branch(monkeypatch):
        compiled = jax.jit(ssm.step_kernel, donate_argnums=0).lower(
            arg((b, heads, P, N)), arg((b, heads)), arg((b, heads, P)),
            arg((b, N)), arg((b, N)), arg((b,), jnp.int32),
            arg((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= b * heads * P * N * 4


def _relaid(text):
    """[(instruction, dims, bytes)] of every `copy` and `transpose` in
    the ENTRY computation of a compiled program's text."""
    import re

    out = []
    for name, bits, dims in re.findall(
            r"%(\S+) = [a-z]+(\d+)\[([\d,]+)\]\{[^}]*\} (?:copy|transpose)\(",
            text[text.index("\nENTRY"):]):
        dims = tuple(int(d) for d in dims.split(","))
        out.append((name, dims, int(np.prod(dims)) * int(bits) // 8))
    return out


# The projections whose product goes, reshaped, to a decode kernel or
# a pool's scatter.
_PROJECTIONS = ("wq", "wk", "wv", "wqb")


@pytest.mark.parametrize("family", ["llama", "hybrid", "smallthinker",
                                    "cohere", "xing", "planted"])
def test_decode_program_for_the_chip_copies_no_projection_weight(
        family, v5e_chip, monkeypatch):
    """The decode program of each family, compiled for a described v5e:
    its entry computation holds no `copy` or `transpose` with the shape
    of a q, k or v projection's weight (xing: Wqb's), and nothing of
    more than 1 MB at all (the pools are donated and never copied; 1 MB
    is the 16 rows of 128 query heads re-laid in float32, the most any
    family's rows come to here). Before PR 43 every such weight was
    transposed anew in every step, because the kernel's operand layout
    reached back through the reshape into the dot (decoder.
    weight_where_it_lies; 604 MB a step at command-a-plus's widths, 805
    at mistral7b's). xing's Wkvb is still re-laid: the absorbed
    products are batched over the heads, which lie in the MIDDLE of
    Wkvb's [rank, heads, width] (PERF.md section 7); the case holds it
    to that one shape. "planted" is llama's program with the barrier
    taken out again (at another batch, so that no cached trace of the
    real form answers): the copies must show, or the cases above prove
    nothing."""
    planted = family == "planted"
    if planted:
        monkeypatch.setattr(decoder, "weight_where_it_lies", lambda p: p)
    lower = _decode_program("llama" if planted else family,
                            slots=8 if planted else 16)[0]
    with _the_chips_branch(monkeypatch):
        text = lower(lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=v5e_chip)).compile().as_text()
    layers = lower.layers
    weights = {tuple(sorted(layer[w].shape)) for layer in layers
               for w in _PROJECTIONS if w in layer}
    assert weights and all(int(np.prod(w)) * 2 >= 1 << 18 for w in weights)
    relaid = _relaid(text)
    of_weights = [r for r in relaid if tuple(sorted(r[1])) in weights]
    if planted:
        # a q, a k and a v weight a layer
        assert len(of_weights) == 3 * len(layers), of_weights
        return
    assert not of_weights, of_weights
    known = {tuple(sorted(layers[0]["wkvb"].shape))} \
        if family == "xing" else set()
    large = [r for r in relaid
             if r[2] > 1 << 20 and tuple(sorted(r[1])) not in known]
    assert not large, large


@pytest.mark.parametrize("rows", [1, 6, 64])
def test_selection_for_the_chip_is_one_kernel_and_no_sort(rows, v5e_chip,
                                                          monkeypatch):
    """`sparse_select.select` over rows of 35,072 scores at k 2,048,
    compiled for a described v5e at a decode step's batch (1, 6) and an
    admission's block (64): the threshold is ONE Pallas call (Mosaic
    takes the kernel at these shapes), and the program holds neither a
    sort nor a loop (a loop under a decode program's `attn.topk` would
    be counted beside its children: benchmark/metrics/_scoped_ops.py)."""
    from infinistore_tpu.ops import sparse_select

    with _the_chips_branch(monkeypatch):
        text = jax.jit(lambda s, n: sparse_select.select(s, n, 2048)).lower(
            jax.ShapeDtypeStruct((rows, 35072), jnp.float32,
                                 sharding=v5e_chip),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=v5e_chip),
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert " sort(" not in text and " while(" not in text


@pytest.mark.parametrize("rows", [4096, 35072])
def test_an_admissions_attention_for_the_chip_keeps_its_logits_on_it(
        rows, v5e_chip, monkeypatch):
    """`sparse_select.select_attend_seq` at keye-vl2-30b-a3b's widths
    (16 index heads of 128 lanes, 32 query heads over 4 kv heads of
    128, k 2,048), two blocks of 64 queries over `rows` contiguous rows
    (35,072: not a multiple of the kernel's key tile), compiled for a
    described v5e: Mosaic takes the attention's kernel at these shapes,
    the program's two Pallas calls are the bisection and the attention,
    and its temporaries are under ONE block's float32 logits [64, 32,
    rows] (what the XLA form writes and reads back a block)."""
    from infinistore_tpu.ops import sparse_select

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    n = 128
    with _the_chips_branch(monkeypatch):
        compiled = jax.jit(lambda *a: sparse_select.select_attend_seq(
            *a, k=2048, scale=128 ** -0.5)[1]
        ).lower(sds((n, 16, 128)), sds((n, 16), jnp.float32),
                sds((rows, 128)), sds((n,), jnp.int32), sds((n, 32, 128)),
                sds((rows, 4, 128)), sds((rows, 4, 128))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert " sort(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 32 * rows * 4


def _tool(name):
    """tools/<name>.py as a module."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(name, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", name + ".py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("family", ["llama", "moe", "hybrid", "smallthinker",
                                    "xing", "cohere"])
def test_decode_step_is_bit_equal_to_the_form_without_the_barrier(
        family, monkeypatch):
    """The engine's decode program of each family at its tiny
    configuration, jitted on the CPU over random pools (state, banded
    pools), both rows active: logits, next tokens and every pool the
    program returns are bit for bit what the form before PR 43 gives
    (decoder.weight_where_it_lies taken out). The barrier moves no
    value; where a backend fused the bias or a scale into the dot in
    another order, this would show it. The caches are cleared between
    the two forms and after them: `decode_step` is jitted inside the
    program, and a cached trace would answer for the other form."""
    fn, args = _tool("jaxpr_hashes").programs(family)["decode"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))

    def filled(x):
        if x is None or not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return jax.random.normal(next(keys), x.shape).astype(x.dtype)

    params, _, _, *pools, rows = args
    pools = jax.tree_util.tree_map(filled, pools)
    table = jnp.arange(1, 17, dtype=jnp.int32).reshape(2, 8)
    if isinstance(rows, tuple):  # (table, the banded pools' table, base)
        short = rows[1].shape[1]
        rows = (table, 1 + jnp.arange(2 * short, dtype=jnp.int32).reshape(
            2, short) % (pools[2].shape[1] - 1), rows[2])
    else:
        rows = table
    args = (params, jnp.asarray([3, 7], jnp.int32),
            jnp.asarray([5, 9], jnp.int32), *pools, rows)

    def run():
        jax.clear_caches()
        out = jax.jit(fn)(*args)
        return out, str(jax.make_jaxpr(fn)(*args))

    try:
        new, text = run()
        assert "optimization_barrier" in text
        monkeypatch.setattr(decoder, "weight_where_it_lies", lambda p: p)
        old, text = run()
        assert "optimization_barrier" not in text
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    new, old = (jax.tree_util.tree_leaves(o) for o in (new, old))
    assert len(new) == len(old) >= 3
    assert np.isfinite(np.asarray(new[0])).all() and np.asarray(new[0]).any()
    for a, b in zip(new, old):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("where, stage", [
    ("jit(_decode_fused)/jit(decode_step)/attn.qkv/dot_general fusion.3",
     "attn.qkv"),
    ("jit(_decode_fused_wf)/jit(decode_step)/attn.kernel.window/"
     "pallas_call custom-call.5", "attn.kernel.window"),
    ("jit(_decode_fused)/jit(decode_step)/moe.experts/jit(gathered_call)/"
     "while fusion.9", "moe.experts"),
    ("jit(f)/mlp/attn.out/dot_general fusion.1", "attn.out"),
    ("jit(_decode_fused)/jit(decode_step)/jit(_take)/gather fusion.2",
     "no scope"),
    (" copy.454", "no scope"),
])
def test_step_by_scope_files_an_operation_under_its_innermost_stage(
        where, stage):
    """tools/step_by_scope.py: the stage of a device operation is the
    innermost `jax.named_scope` stage name on its `op_name` path (what
    benchmark/metrics/_scoped_ops.py hands over: the path, a space,
    the instruction), and a weight's `copy`, which carries none, is
    named under "no scope". Only runs of the decode programs that lie
    whole inside the traced window count."""
    tool = _tool("step_by_scope")
    assert tool.stage_of(where) == stage
    ms = 10 ** 6
    modules = [("jit__decode_fused(1)", 0, 8 * ms),        # before it
               ("jit__decode_fused(1)", 10 * ms, 8 * ms),
               ("jit__admit_fused_px(2)", 20 * ms, 15 * ms),
               ("jit__decode_fused(1)", 40 * ms, 6 * ms)]
    ops = [(where, 1 * ms, 1 * ms), (where, 11 * ms, 2 * ms),
           (where, 21 * ms, 9 * ms), (where, 41 * ms, 1 * ms),
           ("jit(f)/lm_head/dot_general fusion.7", 45 * ms, ms // 2)]
    found = tool.by_stage(ops, modules, (9 * ms, 60 * ms), ["decode_fused"])
    assert found["runs"] == 2 and found["step_ms"] == 7.0
    assert found["stage_ms"] == {stage: 1.5, "lm_head": 0.25}
    assert found["unscoped_top_ms"] == (
        {where.rsplit(" ", 1)[-1]: 1.5} if stage == "no scope" else {})


def _step_sliced(model, params, cfg, tokens, seq_lens, k_pages, v_pages,
                 page_table, valid_len=None):
    """The formulation decode_step / verify_step had before the pool
    stayed whole, kept here as the reference: slice a layer out, scatter
    the tokens' rows into the slice, attend over the slice, stack the
    slices back. tokens [batch, m]; m == 1 is a decode step."""
    b, m = tokens.shape
    block = model.decode_step.__wrapped__.args[0]  # the family's own
    x = decoder.embed(params, tokens, cfg)
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
        q, k, v = decoder.qkv(layer, x, cfg, positions)
        kp = k_pages[li].at[target_page, slot].set(k, mode="drop")
        vp = v_pages[li].at[target_page, slot].set(v, mode="drop")
        attn = pa.multi_token_paged_attention(q, kp, vp, page_table,
                                              seq_lens, window=cfg.window)
        x = x + decoder.attn_out(layer, attn.reshape(b, m, -1))
        valid = (seq_lens > 0)[:, None] if valid_len is None else ok
        x = x + block(layer, x, cfg, valid)[0]
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


# ---------------------------------------------------------------------------
# The hit admission as one program (serving._admit_fused_px) against the
# composition of public pieces the engine dispatched one by one before.
# ---------------------------------------------------------------------------

_HIT_TOTAL_PAGES = 12
_HIT_ARITY = 8  # max_pages_per_seq of the `_pad_ids` form

# p_len prefix tokens were cached; `cut` leading pages of them lie below
# a windowed engine's band floor and are not restored (pos0 = cut pages).
_HIT_CASES = {
    "page_multiple_suffix": dict(p_len=24, s_real=16, cut=0, window=0),
    "ragged_suffix": dict(p_len=24, s_real=13, cut=0, window=0),
    "windowed_trimmed_prefix_pos0": dict(p_len=24, s_real=8, cut=1,
                                         window=16),
    "every_id_at_the_drop_sentinel": dict(p_len=16, s_real=11, cut=0,
                                          window=0, drop=True),
}


def _page_major(cfg, kvs, first_page):
    """A prefill's per-layer KV of batch row 0, from page `first_page`
    on, in the form one store call returns it: [n * L * 2, page, n_kv,
    hd], rows ordered page, layer, k then v."""
    per_layer = []
    for k, v in kvs:
        kp, vp = decoder.kv_to_pages(cfg, k[:1], v[:1])
        per_layer.append(jnp.stack([kp[0, first_page:],
                                    vp[0, first_page:]]))
    both = jnp.stack(per_layer)  # [L, 2, n, page, n_kv, hd]
    return jnp.moveaxis(both, 2, 0).reshape(-1, *cfg.kv_page_shape())


def _hit_composed(f, toks, restored, pools, restored_ids, suffix_ids,
                  s_real, pos0):
    """Today's composition, dispatch by dispatch: restore_prefix_pages
    -> pages_to_kv -> _prefill_px_jit -> kv_to_pages -> a pool write
    (twice, each padded to the fixed arity, the sentinel dropped)."""
    from infinistore_tpu import serving

    model, cfg, params = f.model, f.cfg, f.params
    n = len(restored_ids)
    kp, vp = decoder.restore_prefix_pages(
        None, cfg, lambda li, kind: [f"L{li}/{kind}/{p}" for p in range(n)],
        n, getter=lambda keys, shape, dtype: restored)
    prefix_kvs = [decoder.pages_to_kv(cfg, kp[li][None], vp[li][None],
                                      n * cfg.page_size)
                  for li in range(cfg.n_layers)]
    logits, kvs = serving._prefill_px_jit(
        params, cfg, toks, prefix_kvs, jnp.int32(pos0), model=model)

    def write(pools, ids, k_new, v_new):
        ids_p = np.full(_HIT_ARITY, _HIT_TOTAL_PAGES, np.int32)
        ids_p[:len(ids)] = ids
        pad = [(0, 0), (0, _HIT_ARITY - k_new.shape[1])] + [(0, 0)] * 3
        return tuple(pool.at[:, ids_p].set(jnp.pad(new, pad), mode="drop")
                     for pool, new in zip(pools, (k_new, v_new)))

    pools = write(pools, restored_ids, kp, vp)
    k_sfx, v_sfx = [], []
    for k, v in kvs:
        a, b = decoder.kv_to_pages(cfg, k[:, :s_real], v[:, :s_real])
        k_sfx.append(a[0])
        v_sfx.append(b[0])
    m = k_sfx[0].shape[0]
    pools = write(pools, suffix_ids[:m], jnp.stack(k_sfx), jnp.stack(v_sfx))
    return logits[0, s_real - 1], *pools


@pytest.mark.parametrize("case", list(_HIT_CASES))
@pytest.mark.parametrize("name", ["llama", "moe"])
def test_fused_hit_program_equals_the_composition(name, case):
    """serving._admit_fused_px on what one store call returned against
    the composition on the same restored pages: the restored pool pages
    bit-exact, the suffix pool pages (real positions) and the logits
    row within the cold-against-hit tolerance, the same argmax, every
    other pool page untouched. With every id at the drop sentinel (the
    `first_token_logits` form) the pool comes back as it went in."""
    from infinistore_tpu import serving

    c = _HIT_CASES[case]
    f = _family(name, window=c["window"])
    model, cfg, params = f.model, f.cfg, f.params
    page = cfg.page_size
    p_len, s_real, cut = c["p_len"], c["s_real"], c["cut"]
    s_pad = -(-s_real // page) * page
    tokens = _tokens(31, cfg, (1, p_len + s_real))
    _, prefix_kvs = model.prefill(params, cfg, tokens[:, :p_len])
    restored = _page_major(cfg, prefix_kvs, cut)
    n, m = p_len // page - cut, s_pad // page
    assert restored.shape[0] == n * cfg.n_layers * 2
    toks = jnp.zeros((1, s_pad), jnp.int32).at[:, :s_real].set(
        tokens[:, p_len:])
    if c.get("drop"):
        restored_ids = [_HIT_TOTAL_PAGES] * n
        suffix_ids = []
    else:
        restored_ids = [9, 2, 5][:n]
        suffix_ids = [7, 3][:m]
    rng = np.random.default_rng(32)
    shape = (cfg.n_layers, _HIT_TOTAL_PAGES, *cfg.kv_page_shape())
    k0 = rng.standard_normal(shape).astype(np.float32)
    v0 = rng.standard_normal(shape).astype(np.float32)
    pos0 = cut * page

    want_row, want_k, want_v = _hit_composed(
        f, toks, restored, (jnp.asarray(k0), jnp.asarray(v0)),
        restored_ids, suffix_ids, s_real, pos0)
    ids_p = np.full(_HIT_ARITY, _HIT_TOTAL_PAGES, np.int32)
    ids_p[:len(suffix_ids)] = suffix_ids
    row, got_k, got_v = serving._admit_fused_px(
        params, cfg, toks, restored, jnp.asarray(k0), jnp.asarray(v0),
        jnp.asarray(restored_ids, jnp.int32), jnp.asarray(ids_p),
        jnp.int32(s_real), jnp.int32(pos0), model=model)

    row, want_row = np.asarray(row), np.asarray(want_row)
    assert row.shape == (cfg.vocab_size,)
    np.testing.assert_allclose(row, want_row, rtol=2e-4, atol=2e-4)
    assert row.argmax() == want_row.argmax()
    # ... which is the dense forward's row over prefix + suffix.
    dense = model.forward_dense(params, cfg, tokens)[0]
    np.testing.assert_allclose(row, np.asarray(dense[0, -1]),
                               rtol=2e-4, atol=2e-4)
    tail = s_real - (m - 1) * page  # real slots of the last suffix page
    for got, want, before in ((got_k, want_k, k0), (got_v, want_v, v0)):
        got, want = np.asarray(got), np.asarray(want)
        if c.get("drop"):
            np.testing.assert_array_equal(got, before)
            np.testing.assert_array_equal(want, before)
            continue
        np.testing.assert_array_equal(got[:, restored_ids],
                                      want[:, restored_ids])
        assert not np.array_equal(got[:, restored_ids],
                                  before[:, restored_ids])
        np.testing.assert_allclose(got[:, suffix_ids[:-1]],
                                   want[:, suffix_ids[:-1]],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(got[:, suffix_ids[-1], :tail],
                                   want[:, suffix_ids[-1], :tail],
                                   rtol=2e-4, atol=2e-4)
        others = sorted(set(range(_HIT_TOTAL_PAGES))
                        - set(restored_ids) - set(suffix_ids))
        np.testing.assert_array_equal(got[:, others], before[:, others])


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
