"""The plain float32 references against models/llama.py and
models/moe.py at tiny widths, the near-tie rule and a dropped token."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import correct, serve


def tiny(name):
    conf = serve.load_config(f"benchmark/configs/{name}.json",
                             rehearsal=True)
    model, cfg = serve.model_config(conf)
    return conf, model, cfg


def both(name, seed, length, positions):
    conf, model, cfg = tiny(name)
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, length)
    ref, margins = serve.reference_module(conf).forward(
        params, conf, toks, positions)
    got = model.prefill(params, cfg, jnp.asarray(toks[None], jnp.int32))[0]
    return np.asarray(ref), np.asarray(got[0])[positions], margins


@pytest.mark.parametrize("name", ["mistral7b", "mixtral8x7b"])
@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 96)])
def test_reference_agrees_with_the_program_at_tiny_widths(name, seed,
                                                          length):
    pos = [0, length // 2, length - 1]
    ref, got, margins = both(name, seed, length, pos)
    assert ref.shape == got.shape == (3, 512)
    assert np.max(np.abs(ref - got)) < 2e-4
    assert (margins is None) == (name == "mistral7b")


def test_reference_padding_is_inert_for_earlier_positions():
    conf, model, cfg = tiny("mixtral8x7b")
    params = serve.init_weights(model, cfg, 3)
    ref = serve.reference_module(conf)
    toks = np.random.default_rng(3).integers(0, 512, 40)
    a, ma = ref.forward(params, conf, toks, [10, 39])
    b, mb = ref.forward(params, conf, np.concatenate(
        [toks, np.zeros(24, toks.dtype)]), [10, 39])
    assert np.allclose(a, b, atol=1e-5) and np.allclose(ma, mb, atol=1e-5)


def test_config_goes_through_the_repo_bridge_and_drops_no_token():
    _, _, cfg = tiny("mixtral8x7b")
    assert cfg.capacity_factor == 4.0 == cfg.n_experts / cfg.top_k
    assert cfg.capacity(96) >= 96  # every expert can take every token
    full = serve.load_config("benchmark/configs/mixtral8x7b.json")
    _, big = serve.model_config(full)
    assert (big.d_model, big.n_heads, big.n_kv_heads, big.head_dim,
            big.d_ff, big.n_experts, big.top_k, big.vocab_size,
            big.n_layers, big.dtype) == (4096, 32, 8, 128, 14336, 8, 2,
                                         32000, 3, "bfloat16")
    _, dense = serve.model_config(
        serve.load_config("benchmark/configs/mistral7b.json"))
    assert (dense.d_model, dense.n_heads, dense.n_kv_heads, dense.head_dim,
            dense.d_ff, dense.vocab_size, dense.n_layers, dense.window,
            dense.rope_theta) == (4096, 32, 8, 128, 14336, 32768, 16, 0,
                                  1e6)


def forced_router(params, cols):
    """Every layer's router replaced: `cols` maps expert -> bias column
    direction so that chosen experts win for every token."""
    out = dict(params)
    out["layers"] = []
    for layer in params["layers"]:
        r = np.zeros(layer["router"].shape, np.float32)
        for e, w in cols.items():
            r[:, e] = w
        out["layers"].append(dict(layer, router=jnp.asarray(r)))
    return out


def test_a_forced_near_tie_is_set_aside_by_the_margin_rule():
    conf, model, cfg = tiny("mixtral8x7b")
    params = serve.init_weights(model, cfg, 4)
    # experts 1 and 2 get IDENTICAL router columns: wherever they are
    # 2nd and 3rd the choice between them is a coin toss of rounding.
    rng = np.random.default_rng(0)
    col = rng.normal(size=cfg.d_model).astype(np.float32)
    tie = forced_router(params, {0: 3 * np.abs(col) + 5, 1: col, 2: col})
    toks = rng.integers(0, 512, 32)
    pos = list(range(24, 32))
    ref = serve.reference_module(conf)
    _, margins = ref.forward(tie, conf, toks, pos)
    margins = np.asarray(margins)
    aside = correct.set_aside(margins, 0.05)
    tied = np.abs(margins).min(axis=1) < 1e-5
    assert tied.any() and (aside[tied]).all()
    deficits = [9.9 if t else 0.0 for t in tied]  # a flip costs O(1)
    checked, failed, skipped, worst = correct.judge_tokens(
        deficits, aside, token_eps=0.5)
    assert failed == 0 and skipped >= tied.sum() and worst == 0.0
    # without the rule the same positions would fail the run
    assert correct.judge_tokens(deficits, None, 0.5)[1] == tied.sum()
    # an untouched router has real margins
    _, m2 = ref.forward(params, conf, toks, pos)
    assert not correct.set_aside(np.asarray(m2), 1e-6).any()


def test_a_dropped_token_is_caught():
    """capacity_factor 1.5 with every token routed to the same two
    experts drops the late tokens' FFN: the logits at the end of the
    prompt leave the reference by far more than any tolerance."""
    conf, model, cfg = tiny("mixtral8x7b")
    params = serve.init_weights(model, cfg, 5)
    same = forced_router(params, {0: np.full(cfg.d_model, 0.0) + 1.0,
                                  1: np.full(cfg.d_model, 0.0) + 0.9})
    for layer in same["layers"]:  # make 0 and 1 win for every token
        r = np.asarray(layer["router"]).copy()
        r[:, 2:] = -1.0
        layer["router"] = jnp.asarray(np.abs(r) * np.sign(r))
    toks = np.random.default_rng(5).integers(0, 512, 64)
    pos = list(range(24, 64))  # capacity is 24 slots: these are dropped
    ref, _ = serve.reference_module(conf).forward(same, conf, toks, pos)
    ref = np.asarray(ref)
    tok = jnp.asarray(toks[None], jnp.int32)
    keeps = np.asarray(model.prefill(same, cfg, tok)[0][0])[pos]
    drops_cfg = dataclasses.replace(cfg, capacity_factor=1.5)
    assert drops_cfg.capacity(64) == 24
    drops = np.asarray(model.prefill(same, drops_cfg, tok)[0][0])[pos]
    tol = correct.tolerances("moe")
    assert np.max(np.abs(keeps - ref)) < 1e-3
    assert np.max(np.abs(drops - ref)) > 4 * tol["logit_tol"]
    chosen = drops.argmax(axis=1)
    deficits = correct.token_deficits(ref, chosen)
    assert max(deficits) > tol["token_eps"]
    assert correct.judge_tokens(deficits, None, tol["token_eps"])[1] >= 1


@pytest.mark.parametrize("deficits,aside,eps,want", [
    ([0.0, 0.1, 0.2], None, 0.25, (3, 0, 0, 0.2)),
    ([0.0, 0.3, 0.2], None, 0.25, (3, 1, 0, 0.3)),
    ([0.0, 0.3, 0.2], [False, True, False], 0.25, (2, 0, 1, 0.2)),
    ([float("nan")], None, 0.25, (1, 1, 0, 0.0)),
    ([], None, 0.25, (0, 0, 0, 0.0)),
])
def test_judge_tokens(deficits, aside, eps, want):
    got = correct.judge_tokens(deficits, aside, eps)
    assert got[:3] == want[:3]
    if not np.isnan(deficits).any():
        assert got[3] == pytest.approx(want[3])


def test_token_deficits_pass_a_rounding_flip_and_fail_a_wrong_token():
    ref = np.array([[0.0, 4.0, 3.9, -1.0], [2.0, 0.0, 0.0, 0.0]])
    assert correct.token_deficits(ref, [1, 0]) == [0.0, 0.0]
    d = correct.token_deficits(ref, [2, 3])
    assert d[0] == pytest.approx(0.1) and d[1] == pytest.approx(2.0)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_tolerances_are_written_with_their_reason(family):
    tol = correct.tolerances(family)
    assert 0 < tol["token_eps"] < 2 and 0 < tol["logit_tol"] < 2
    assert len(tol["why"]) > 40
    if family == "moe":
        assert 0 < tol["router_margin"] < 1


def test_sample_sessions_are_seeded_one_per_class_and_replica():
    from benchmark.lib import traffic

    spec = traffic.load("benchmark/traffic/sessions-rr4.json")
    a = correct.sample_sessions(spec, 11, copies=4)
    b = correct.sample_sessions(spec, 11, copies=4)
    c = correct.sample_sessions(spec, 12, copies=4)
    assert a == b and len(a) == 16 and a != c
    assert [s.cls for s in a] == [i // 4 for i in range(16)]
    for turn in (1, 2, 3):  # every replica runs every turn of a class
        assert {traffic.replica_of(spec, s.index, turn)
                for s in a[:4]} == {0, 1, 2, 3}


def test_jit_weights_match_the_program_init_and_the_seed():
    _, model, cfg = tiny("mistral7b")
    a = serve.init_weights(model, cfg, 2 ** 31 + 9)
    b = serve.init_weights(model, cfg, 2 ** 31 + 9)
    c = serve.init_weights(model, cfg, 2 ** 31 + 10)
    la, lb, lc = (jax.tree_util.tree_leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert any(not np.array_equal(x, y) for x, y in zip(la, lc))
