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


@pytest.mark.parametrize("mode", ["spec", "chunk", "burst"])
def test_moe_serving_modes_token_parity(serve_params, serve_cfg, mode):
    """Speculation (verify_step), chunked prefill and multi-step bursts
    all serve the MoE family with the plain-engine token stream."""
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
        "chunk": ServingConfig(prefill_chunk=4),
        "burst": ServingConfig(host_steps=4),
    }[mode]
    eng = ServingEngine(serve_params, serve_cfg, sc, model=moe)
    out = eng.run([Request("r", prompt, max_new_tokens=n_new)])
    assert out["r"] == ref, mode
    if mode == "burst":
        assert _steps_of_kind(eng, "burst") > 0
    if mode == "spec":
        assert eng.stats["spec_proposed"] > 0
    if mode == "chunk":
        assert _steps_of_kind(eng, "unified") > 0


def test_moe_chunked_parity_at_default_capacity():
    """The reviewer's failure scenario: chunked prefill at the DEFAULT
    capacity_factor (1.5) with idle slots — pad/inactive tokens must
    not evict real tokens from expert capacity (the _route validity
    mask), so chunked == unchunked exactly."""
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
        params, cfg, ServingConfig(max_slots=8, prefill_chunk=4),
        model=moe,
    )
    out = eng.run([Request("r", prompt, max_new_tokens=6)])
    assert out["r"] == ref
    assert _steps_of_kind(eng, "unified") > 0


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
