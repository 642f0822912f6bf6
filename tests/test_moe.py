"""Sparse-MoE model family tests (8-device virtual CPU mesh).

Covers what is the family's own: routing conservation (dispatch/combine
algebra), capacity drops, the training step, expert-parallel sharded
execution matching the single-device result, and the engine's serving
modes under routing. The decoder stack it shares with the dense family
(shapes, paged decode against dense, pages through the store, prefix
hits, the m-token step, the window) is tests/test_model.py's, which
runs every such test for both families.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from infinistore_tpu.models import moe
from infinistore_tpu.utils import profiling


def tiny_cfg(**kw):
    d = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, n_experts=4, top_k=2, max_seq=64, page_size=8,
        dtype="float32",
    )
    d.update(kw)
    return moe.MoEConfig(**d)


def _steps_of_kind(eng, kind):
    """How many of the engine's steps were of `kind`, by the spans they
    left (utils/profiling.py)."""
    return sum(1 for s in profiling.spans()
               if s.name == "istpu.engine.step" and s.engine == eng.engine_id
               and s.fields["kind"] == kind)


def test_routing_dispatch_combine_algebra():
    """Every kept token occupies exactly one slot per selected expert,
    and combine weights per token sum to 1 (no capacity drops at this
    size)."""
    cfg = tiny_cfg()
    rng = jax.random.PRNGKey(0)
    params = moe.init_params(rng, cfg)
    h = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    dispatch, combine, aux = moe._route(params["layers"][0], h, cfg)
    T, E, C = dispatch.shape
    assert (T, E) == (32, cfg.n_experts)
    # Slot occupancy: each (e, c) slot holds at most one token.
    assert float(jnp.max(jnp.sum(dispatch, axis=0))) <= 1.0 + 1e-6
    # Each token dispatched to exactly top_k experts (capacity ample).
    per_token = jnp.sum(dispatch, axis=(1, 2))
    assert np.allclose(np.asarray(per_token), cfg.top_k)
    # Combine weights per token sum to 1 (renormalized top-k gates).
    np.testing.assert_allclose(
        np.asarray(jnp.sum(combine, axis=(1, 2))), 1.0, atol=1e-5
    )
    assert float(aux) > 0


def test_capacity_drop_is_bounded():
    """With a tight capacity factor, over-capacity tokens drop (standard
    switch semantics) but kept weights stay normalized per token."""
    cfg = tiny_cfg(capacity_factor=0.25, n_experts=2, top_k=1)
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (256, cfg.d_model))
    dispatch, combine, _ = moe._route(params["layers"][0], h, cfg)
    C = cfg.capacity(256)
    # No expert exceeds capacity.
    assert float(jnp.max(jnp.sum(dispatch, axis=(0, 2)))) <= C + 1e-6
    # Some tokens dropped, and dropped tokens contribute zero.
    kept = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
    assert kept.min() == 0 and kept.max() == 1


def test_train_step_reduces_loss():
    import optax

    cfg = tiny_cfg()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    optimizer = optax.adamw(3e-3)
    opt_state = optimizer.init(params)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (4, 32)),
        jnp.int32,
    )
    step = jax.jit(
        lambda p, o, t: moe.train_step(p, o, cfg, t, optimizer)
    )
    first = None
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens)
        if first is None:
            first = float(loss)
    assert float(loss) < first, (first, float(loss))


def test_expert_parallel_matches_single_device():
    """The ep-sharded train step must produce the same loss as the
    unsharded one — sharding changes placement, not math."""
    import optax

    assert len(jax.devices()) >= 8
    cfg = tiny_cfg()
    params = moe.init_params(jax.random.PRNGKey(0), cfg)
    optimizer = optax.adamw(1e-3)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (4, 32)),
        jnp.int32,
    )

    # Single-device reference.
    opt_state = optimizer.init(params)
    _, _, loss_ref = jax.jit(
        lambda p, o, t: moe.train_step(p, o, cfg, t, optimizer)
    )(params, opt_state, tokens)

    # (dp=2, ep=4) sharded run.
    mesh = moe.make_ep_mesh(dp=2, ep=4)
    sh_params = jax.device_put(params, moe.param_shardings(mesh, params))
    sh_opt = optimizer.init(sh_params)
    sh_tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp")))
    p2, _, loss_sh = jax.jit(
        lambda p, o, t: moe.train_step(p, o, cfg, t, optimizer)
    )(sh_params, sh_opt, sh_tokens)
    np.testing.assert_allclose(
        float(loss_sh), float(loss_ref), rtol=1e-4
    )
    # Expert weights actually live sharded over ep.
    e_gate_sh = p2["layers"][0]["e_gate"].sharding
    assert "ep" in (e_gate_sh.spec[0],), e_gate_sh


# ---- MoE serving (the engine's second model family) --------------------

@pytest.fixture(scope="module")
def serve_cfg():
    # capacity_factor=4 guarantees NO capacity drops at these sizes in
    # either path: GShard capacity is per-forward-pass (T = the whole
    # sequence in the dense oracle, T = the decode batch in the
    # engine), so a config that drops in one and not the other would
    # make exact parity impossible BY DESIGN, not by bug.
    return tiny_cfg(max_seq=128, capacity_factor=4.0)


@pytest.fixture(scope="module")
def serve_params(serve_cfg):
    return moe.init_params(jax.random.PRNGKey(3), serve_cfg)


@pytest.mark.parametrize("mode", ["spec", "pieces", "burst"])
def test_moe_serving_modes_token_parity(serve_params, serve_cfg, mode):
    """Speculation (verify_step), admission in pieces and multi-step
    bursts all serve the MoE family with the plain-engine token
    stream."""
    from infinistore_tpu.serving import Request, ServingConfig, ServingEngine

    rng = np.random.default_rng(51)
    # Repetitive prompt so prompt-lookup always drafts (spec mode must
    # actually exercise moe.verify_step, not fall through to plain
    # decode).
    prompt = [3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3, 7, 3]
    n_new = 8
    ref = ServingEngine(serve_params, serve_cfg, model=moe).run(
        [Request("x", prompt, max_new_tokens=n_new)]
    )["x"]
    sc = {
        "spec": ServingConfig(spec_k=2),
        "pieces": ServingConfig(admit_piece=serve_cfg.page_size),
        "burst": ServingConfig(host_steps=4),
    }[mode]
    eng = ServingEngine(serve_params, serve_cfg, sc, model=moe)
    out = eng.run([Request("r", prompt, max_new_tokens=n_new)])
    assert out["r"] == ref, mode
    if mode == "burst":
        assert _steps_of_kind(eng, "burst") > 0
    if mode == "spec":
        assert eng.stats["spec_proposed"] > 0
    if mode == "pieces":
        assert eng.stats["admit_pieces"] == 2


def test_moe_pieces_parity_at_default_capacity():
    """Admission in pieces at the DEFAULT capacity_factor (1.5): a
    piece changes the tokens a capacity is reckoned over, and its last
    one is mostly padding — pad tokens must not evict real tokens from
    expert capacity (the _route validity mask), so at a size where no
    real token is dropped pieces == one program exactly."""
    from infinistore_tpu.serving import Request, ServingConfig, ServingEngine

    cfg = tiny_cfg(max_seq=128)  # capacity_factor at its default
    params = moe.init_params(jax.random.PRNGKey(9), cfg)
    rng = np.random.default_rng(53)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 21)]
    ref = ServingEngine(params, cfg, ServingConfig(max_slots=8),
                        model=moe).run(
        [Request("x", prompt, max_new_tokens=6)]
    )["x"]
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=8, admit_piece=cfg.page_size), model=moe,
    )
    out = eng.run([Request("r", prompt, max_new_tokens=6)])
    assert out["r"] == ref
    assert eng.stats["admit_pieces"] == 3


def test_moe_multiturn_prefix_hit_through_store(serve_params, serve_cfg,
                                                shm_conn):
    """MoE pages ride the same store contract: turn 2 extending turn 1
    restores cached pages (prefix HIT) and matches the cold run."""
    from infinistore_tpu.serving import Request, ServingEngine
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(52)
    store = TpuKVStore(shm_conn)
    turn1 = [int(t) for t in rng.integers(0, serve_cfg.vocab_size, 16)]
    eng1 = ServingEngine(serve_params, serve_cfg, store=store, model=moe)
    out1 = eng1.run([Request("t1", turn1, max_new_tokens=8)])
    assert eng1.stats["offloaded_pages"] > 0

    convo = turn1 + out1["t1"]
    page = serve_cfg.page_size
    turn2 = convo[: (len(convo) // page) * page]
    turn2 = turn2 + [int(t) for t in rng.integers(0, serve_cfg.vocab_size,
                                                  5)]
    eng2 = ServingEngine(serve_params, serve_cfg, store=store, model=moe)
    out2 = eng2.run([Request("t2", turn2, max_new_tokens=6)])
    assert eng2.stats["prefix_hit_pages"] > 0
    ref = ServingEngine(serve_params, serve_cfg, model=moe).run(
        [Request("x", turn2, max_new_tokens=6)]
    )
    assert out2["t2"] == ref["x"]


# ---- the gathered expert kernel (ops/pallas_moe_decode.py) --------------
# One call over a decode step's rows that fetches only the experts some
# valid row chose; on the CPU in interpret mode, the same code the chip
# compiles.

# name: (experts, a token, d, f, rows): the three sparse families' forms
# at tiny widths (f of 256 over 128-wide tiles where the budget is cut)
FAMILY_SHAPES = {
    "mixtral8x7b": (8, 2, 64, 256, 16),
    "smallthinker21b": (64, 6, 32, 48, 16),
    "xing4-29b": (64, 4, 32, 128, 8),
}


def _experts(E, d, f, T, k, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + E * 1000 + T), 5)
    layer = {
        "e_gate": (jax.random.normal(ks[0], (E, d, f)) * d ** -0.5
                   ).astype(dtype),
        "e_up": (jax.random.normal(ks[1], (E, d, f)) * d ** -0.5
                 ).astype(dtype),
        "e_down": (jax.random.normal(ks[2], (E, f, d)) * f ** -0.5
                   ).astype(dtype)}
    u = jax.random.normal(ks[3], (T, d)).astype(dtype)
    router = jax.random.normal(ks[4], (d, E))
    _, top_idx, gates = moe.route_top_k(router, u, k)
    return layer, u, top_idx, gates


def _gathered(layer, u, top_idx, gates, valid, act):
    from infinistore_tpu.ops import pallas_moe_decode

    return pallas_moe_decode.gathered_experts(
        u, layer["e_gate"], layer["e_up"], layer["e_down"], top_idx, gates,
        valid, act, interpret=True)


@pytest.mark.parametrize("act", [jax.nn.silu, jax.nn.relu],
                         ids=["silu", "relu"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("family", list(FAMILY_SHAPES))
def test_gathered_experts_equal_the_dense_form(family, dtype, tol, act,
                                               monkeypatch):
    """`gathered_experts` against `experts_dense` over float32 copies
    of the same weights and rows, to the rows' own precision; the f
    axis in several tiles where the block budget is small."""
    from infinistore_tpu.ops import pallas_moe_decode

    E, k, d, f, T = FAMILY_SHAPES[family]
    layer, u, top_idx, gates = _experts(E, d, f, T, k, dtype)
    monkeypatch.setattr(pallas_moe_decode, "_WEIGHT_BLOCK_BYTES",
                        3 * d * 128 * jnp.dtype(dtype).itemsize)
    assert pallas_moe_decode._f_tile(d, f, jnp.dtype(dtype).itemsize) == (
        128 if f % 128 == 0 else f)
    got = _gathered(layer, u, top_idx, gates, None, act)
    assert got.dtype == u.dtype and got.shape == u.shape
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    want = moe.experts_dense(f32, u.astype(jnp.float32), top_idx, gates, act)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want)).max()
    assert err < tol * max(1.0, float(jnp.abs(want).max())), err


def test_rows_that_are_not_valid_fetch_nothing():
    """16 rows of which 2 hold a token, 2 experts a row: at most 4
    experts are fetched, the ids are those rows' own, and a row that is
    not valid comes back zero."""
    from infinistore_tpu.ops import pallas_moe_decode

    E, k, d, f, T = FAMILY_SHAPES["mixtral8x7b"]
    layer, u, top_idx, gates = _experts(E, d, f, T, k, "float32")
    valid = jnp.arange(T) < 2
    dense, ids, n = pallas_moe_decode.live_experts(top_idx, gates, valid, E)
    chosen = sorted(set(np.asarray(top_idx[:2]).ravel()))
    assert int(n) == len(chosen) <= 4
    assert list(np.asarray(ids[:int(n)])) == chosen
    assert set(np.asarray(ids[int(n):])) <= {chosen[-1]}  # the last, again
    assert ids.shape == (min(E, T * k),)
    assert not np.asarray(dense[2:]).any()
    got = _gathered(layer, u, top_idx, gates, valid, jax.nn.silu)
    want = moe.experts_dense(layer, u, top_idx, gates, jax.nn.silu)
    assert np.abs(np.asarray(got[:2] - want[:2])).max() < 1e-5
    assert not np.asarray(got[2:]).any()
    # no row valid: nothing fetched, nothing computed
    none = jnp.zeros(T, bool)
    assert int(pallas_moe_decode.live_experts(top_idx, gates, none, E)[2]) == 0
    assert not np.asarray(
        _gathered(layer, u, top_idx, gates, none, jax.nn.silu)).any()


@pytest.mark.parametrize("case", ["duplicates", "every_expert", "one_row"])
def test_gathered_experts_by_what_the_rows_chose(case):
    """Every row the same experts: n = k. Every expert chosen by some
    row: n = E. One row alone: n = k."""
    from infinistore_tpu.ops import pallas_moe_decode

    E, k, d, f = 8, 2, 32, 16
    T = 1 if case == "one_row" else 16
    layer, u, top_idx, gates = _experts(E, d, f, T, k, "float32", seed=7)
    if case == "duplicates":
        top_idx = jnp.broadcast_to(jnp.asarray([5, 1], jnp.int32), (T, k))
    if case == "every_expert":
        top_idx = (jnp.arange(T * k, dtype=jnp.int32) % E).reshape(T, k)
    n = int(pallas_moe_decode.live_experts(top_idx, gates, None, E)[2])
    assert n == {"duplicates": k, "every_expert": E, "one_row": k}[case]
    got = _gathered(layer, u, top_idx, gates, None, jax.nn.relu)
    want = moe.experts_dense(layer, u, top_idx, gates, jax.nn.relu)
    assert np.abs(np.asarray(got - want)).max() < 1e-5 * max(
        1.0, float(jnp.abs(want).max()))


def _capacity_form(layer, x, cfg, valid, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(moe, "GATHERED_EXPERTS_MAX_ROWS", 0)
        return moe._moe_mlp(layer, x, cfg, valid)


def test_moe_block_gathers_where_no_row_can_be_dropped(monkeypatch):
    """`_moe_mlp` over a decode batch: with a capacity that holds every
    row (the bridge's capacity_factor E / top_k) it takes the gathered
    kernel and equals its own capacity dispatch, aux loss and all, with
    rows that are not valid kept out; at capacity_factor 1.0, where a
    row can be dropped, it does not take it."""
    seen = []
    real = moe.experts_gathered
    monkeypatch.setattr(moe, "experts_gathered",
                        lambda *a: (seen.append(1), real(*a))[1])
    x = jax.random.normal(jax.random.PRNGKey(4), (16, 1, 32))
    valid = (jnp.arange(16) % 3 != 0)[:, None]
    for factor, gathers in ((4.0, True), (1.0, False)):
        cfg = tiny_cfg(n_experts=8, capacity_factor=factor)
        assert (cfg.capacity(16) >= 16) == gathers
        layer = moe.init_params(jax.random.PRNGKey(5), cfg)["layers"][0]
        for v in (None, valid):
            del seen[:]
            out, aux, fetched = moe._moe_mlp(layer, x, cfg, v)
            assert bool(seen) == gathers == (fetched is not None)
            want, want_aux, none = _capacity_form(layer, x, cfg, v,
                                                  monkeypatch)
            assert none is None
            np.testing.assert_allclose(float(aux), float(want_aux),
                                       rtol=1e-6)
            if gathers:
                assert np.abs(np.asarray(out - want)).max() < 1e-5
                assert 2 <= int(fetched) <= 8
    # above a decode batch the capacity dispatch stays, whatever it holds
    many = jnp.ones((17, 1, 32))
    del seen[:]
    assert moe._moe_mlp(layer, many, tiny_cfg(
        n_experts=8, capacity_factor=4.0), None)[2] is None and not seen


def test_decode_step_counts_the_experts_it_fetched(monkeypatch):
    """`decode_step(..., fetched=True)` returns, last, the experts its
    layers fetched: the host's own count of the distinct experts the
    valid rows chose, layer by layer, on a seeded step (run eagerly, so
    the routing can be read)."""
    cfg = tiny_cfg(n_experts=8, capacity_factor=4.0)
    params = moe.init_params(jax.random.PRNGKey(11), cfg)
    slots, pages = 6, 4
    shape = (cfg.n_layers, slots * pages + 1, *cfg.kv_page_shape())
    kp = jax.random.normal(jax.random.PRNGKey(12), shape) * 0.1
    table = 1 + jnp.arange(slots * pages, dtype=jnp.int32).reshape(
        slots, pages)
    seq_lens = jnp.asarray([5, 0, 9, 0, 0, 0], jnp.int32)  # 2 rows hold
    token = jnp.asarray([3, 1, 4, 1, 5, 9], jnp.int32)
    # without the flag the step returns what it always did
    assert len(moe.decode_step(params, cfg, token, seq_lens, kp, kp,
                               table)) == 3
    routed = []
    real = moe.experts_gathered

    def spy(layer, u, top_idx, gates, act, valid):
        routed.append(len(set(np.asarray(top_idx)[np.asarray(valid)].ravel())))
        return real(layer, u, top_idx, gates, act, valid)

    monkeypatch.setattr(moe, "experts_gathered", spy)
    with jax.disable_jit():
        *_, fetched = moe.decode_step(params, cfg, token, seq_lens, kp, kp,
                                      table, fetched=True)
    assert len(routed) == cfg.n_layers
    assert int(fetched) == sum(routed) <= 2 * cfg.top_k * cfg.n_layers


def test_engine_counts_experts_fetched_and_held(serve_params, serve_cfg):
    """The count rides in the array the host pulls for the tokens:
    `stats["moe_experts_fetched"]` of `moe_experts_held` (layers x
    experts a single decode step), and `experts_fetched` on the step's
    `istpu.model.decode` span (the one that holds the wait for the
    tokens: the `land` span of a step that ran ahead). One request:
    each layer fetches its row's top_k experts."""
    from infinistore_tpu.serving import Request, ServingEngine

    eng = ServingEngine(serve_params, serve_cfg, model=moe)
    eng.run([Request("c", [3, 7, 3, 9, 2], max_new_tokens=6)])
    steps = eng.stats["decode_steps"]
    held = serve_cfg.n_layers * serve_cfg.n_experts
    assert eng.stats["moe_experts_held"] == steps * held > 0
    assert eng.stats["moe_experts_fetched"] == (
        steps * serve_cfg.n_layers * serve_cfg.top_k)
    spans = [s for s in profiling.spans()
             if s.name == "istpu.model.decode" and s.engine == eng.engine_id]
    counted = [s.fields["experts_fetched"] for s in spans
               if "experts_fetched" in s.fields]
    assert len(counted) == steps
    assert sum(counted) == eng.stats["moe_experts_fetched"]
    # a family without routed experts counts nothing and pulls tokens alone
    from infinistore_tpu.models import llama

    lcfg = llama.LlamaConfig()
    dense = ServingEngine(llama.init_params(jax.random.PRNGKey(0), lcfg),
                          lcfg)
    dense.run([Request("d", [3, 7, 3], max_new_tokens=3)])
    assert dense.stats["moe_experts_held"] == 0
    assert all("experts_fetched" not in s.fields for s in profiling.spans()
               if s.name == "istpu.model.decode"
               and s.engine == dense.engine_id)
