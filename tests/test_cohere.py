"""A parallel block over window and full attention layers with routed
experts of which this chip holds a SHARE (models/cohere.py, Command
A+'s shape). Held to the plain float32 reference
(benchmark/reference/cohere_moe.py) by LOGITS, at a tiny preset on the
CPU with seeded weights: a router 8 wide of which 2 experts are held, a
band of 4 pages, sessions under the band and past it in one engine.

The engine's rows come from a recording engine (tests/
test_window_full.py has the pattern). A position whose router margin in
the reference is under MARGIN in some layer is a near-tie that float32
rounding may flip, and is left out (benchmark/lib/correct.py does the
same).

Mutations tried by hand, each failing the test named (the mutation is
in models/, the tests did not change):
- the block made sequential (`_block` normalising the stream after
  attention in place of reading `h_attn`):
  test_prefill_matches_the_reference;
- the shared mean made a sum (`shared_mean` False):
  test_prefill_matches_the_reference, test_the_shares_add_up;
- rotary half-split (`rope_adjacent` False):
  test_prefill_matches_the_reference,
  test_rotary_turns_adjacent_lanes;
- an absent pair kept (the chosen ids not counted from `first_expert`,
  so expert 0 of the router lands on the first held expert):
  test_the_shares_add_up, test_the_three_forms_agree_on_the_held_ids,
  test_prefill_matches_the_reference.
"""

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import cohere_moe as reference
from infinistore_tpu.models import cohere, decoder, hf, moe
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 8
BAND = 32            # 4 pages
B = BAND // PAGE
WIDTH, HELD, FIRST = 8, 2, 2     # the router's width, the experts held
CONF = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 32, "num_experts": HELD, "num_experts_per_tok": 2,
    "num_shared_experts": 4, "expert_selection_fn": "sigmoid",
    "norm_topk_prob": True, "shared_expert_combination_strategy": "average",
    "layer_norm_eps": 1e-5, "hidden_act": "silu",
    "use_gated_activation": True,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": BAND, "rope_theta": 50000, "rotary_pct": 1,
    "position_embedding_type": "rope_gptj", "use_parallel_block": True,
    "use_qk_norm": False, "attention_bias": False,
    "first_k_dense_replace": 0, "logit_scale": 1,
    "tie_word_embeddings": True, "max_position_embeddings": 4096,
    "expert_share": {"router_width": WIDTH, "first_expert": FIRST},
}
L_FULL, L_WIN = 1, 3
LAYERS = L_FULL + L_WIN
TOL = 2e-4
MARGIN = 1e-3


@pytest.fixture(autouse=True)
def sorted_dispatch_above_a_decode_batch(monkeypatch):
    """With the threshold at 24 tokens (counted over the router's 8
    experts) the prefills run the sorted dispatch over the held pairs,
    a suffix of 17-24 tokens the dense form and the decode steps the
    gathered kernel, as at the published widths."""
    monkeypatch.setattr(moe, "DENSE_EXPERTS_MAX_ROWS", 24 * WIDTH)


def _cfg(conf=CONF):
    return hf.cohere_moe_config_from_hf(types.SimpleNamespace(**conf),
                                        page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return cohere.init_params(jax.random.PRNGKey(0), cfg)


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CONF["vocab_size"], n)]


def _ref(params, seq, positions, conf=CONF):
    rows, margins = reference.forward(params, conf,
                                      np.asarray(seq, np.int32),
                                      list(positions))
    clear = np.asarray(margins).min(axis=1) >= MARGIN
    return np.asarray(rows), clear


class Recording(ServingEngine):
    """Keeps every logits row a request's tokens were picked from."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.rows = {}

    def _pick(self, work, row):
        self.rows.setdefault(work.req.request_id, []).append(
            np.array(row, np.float32))
        return int(np.argmax(row))


def _engine(params, cfg, conn=None, model_id="co", **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 96)
    sc.setdefault("max_pages_per_seq", 32)
    return Recording(params, cfg, ServingConfig(model_id=model_id, **sc),
                     store=None if conn is None else TpuKVStore(conn),
                     model=cohere)


def _req(rid, prompt, n):
    return Request(rid, prompt, max_new_tokens=n, temperature=1.0)


def _worst(eng, params, rid, prompt, out):
    """Worst |row - reference| over every token of one request whose
    routing is no near-tie; at least half must be left to compare."""
    seq = list(prompt) + list(out)
    want, clear = _ref(params, seq, range(len(prompt) - 1, len(seq) - 1))
    got = np.stack(eng.rows[rid])
    assert got.shape == want.shape
    assert clear.sum() * 2 >= len(clear), clear
    return float(np.abs(got - want)[clear].max())


def _spans(eng, t0):
    return [s for s in profiling.spans(since_ns=t0)
            if s.engine == eng.engine_id]


def first_live(p):
    return max(0, p * PAGE - BAND + 1) // PAGE


# -- the layer's parts, by hand --------------------------------------------
def test_the_norm_is_mean_centred():
    x = jnp.asarray([[1.0, 2.0, 3.0, 6.0]])
    w = jnp.asarray([1.0, 2.0, 1.0, 0.5])
    # mean 3, deviations -2 -1 0 3, variance 14 / 4 = 3.5
    want = np.array([-2.0, -1.0, 0.0, 3.0]) / np.sqrt(3.5 + 1e-5) \
        * np.array([1.0, 2.0, 1.0, 0.5])
    assert np.allclose(decoder.layer_norm(x, w, 1e-5)[0], want, atol=1e-6)
    assert np.allclose(reference.layer_norm(x, w, 1e-5)[0], want, atol=1e-6)
    # RMSNorm, which it is not: no mean taken out
    assert not np.allclose(decoder.rms_norm(x, w, 1e-5)[0], want, atol=1e-2)


def test_rotary_turns_adjacent_lanes():
    """Head of 4 lanes, theta 100, position 3: frequency 0 (1 rad a
    position) turns lanes (0, 1), frequency 1 (0.1 rad) lanes (2, 3);
    the half-split form would pair (0, 2) and (1, 3)."""
    x = jnp.asarray([1.0, 0.0, 0.0, 2.0]).reshape(1, 1, 1, 4)
    pos = jnp.asarray([[3]])
    got = decoder.rope(x, pos, 100.0, adjacent=True).reshape(4)
    want = [np.cos(3.0), np.sin(3.0), -2 * np.sin(0.3), 2 * np.cos(0.3)]
    assert np.allclose(got, want, atol=1e-6)
    assert np.allclose(
        reference.rope_adjacent(x[0], pos[0], 100.0).reshape(4), want,
        atol=1e-6)
    split = decoder.rope(x, pos, 100.0).reshape(4)
    assert not np.allclose(split, want, atol=1e-2)


def test_sigmoid_gates_by_hand():
    """Scores sigmoid(z); the 2 largest chosen; a gate its score over
    the two scores' sum; no bias."""
    z = np.array([[0.0, 2.0, -1.0, 1.0]], np.float32)
    h = jnp.asarray([[1.0]])
    scored, idx, gates = moe.route_sigmoid(jnp.asarray(z), None, h, 2, 1.0)
    s = 1 / (1 + np.exp(-z[0]))
    assert list(np.asarray(idx[0])) == [1, 3]
    assert np.allclose(scored[0], s, atol=1e-6)
    assert np.allclose(gates[0], [s[1] / (s[1] + s[3]), s[3] / (s[1] + s[3])],
                       atol=1e-6)


def test_bridge_gives_the_spec_and_the_share(cfg):
    assert cfg.layer_windows == (BAND, BAND, BAND, 0)
    assert cfg.layer_ropes == (True, True, True, False)
    assert cfg.two_kinds and cfg.window_band == BAND
    assert (cfg.n_routed, cfg.n_experts, cfg.first_expert) \
        == (WIDTH, HELD, FIRST)
    assert cfg.holds_share and cfg.shared_mean and cfg.n_shared == 4
    assert cfg.norm_center and cfg.rope_adjacent and cfg.router == "sigmoid"
    assert [a[2] for a in decoder.attn_layers(cfg)] \
        == ["window"] * 3 + ["full"]


def test_the_tree_is_the_share(cfg, params):
    layer = params["layers"][0]
    assert "ln2" not in layer and "lm_head" not in params
    assert layer["router"].shape == (64, WIDTH)
    assert layer["router"].dtype == jnp.float32
    assert layer["e_gate"].shape == (HELD, 64, 32)
    assert layer["s_gate"].shape == (64, 4 * 32)


def test_a_wider_query_projection_is_the_configuration_s(cfg, params):
    """Random weights: every matrix alike by default; a file's
    `random_init.query_gain` widens the query projection and nothing
    else."""
    assert cfg.q_init_gain == 1.0
    wide = cohere.init_params(jax.random.PRNGKey(7),
                              dataclasses.replace(cfg, q_init_gain=4.0))
    plain = cohere.init_params(jax.random.PRNGKey(7), cfg)
    for a, b in zip(wide["layers"], plain["layers"]):
        for name in a:
            np.testing.assert_array_equal(
                a[name], b[name] * (4.0 if name == "wq" else 1.0))
    np.testing.assert_array_equal(wide["embed"], plain["embed"])
    # the benchmark's configuration asks for 4, in its own file
    from benchmark.lib import serve
    conf = serve.load_config("benchmark/configs/command-a-plus.json")
    assert serve.model_config(conf)[1].q_init_gain \
        == conf["random_init"]["query_gain"] == 4.0


# -- the model against the reference -----------------------------------------
def test_prefill_matches_the_reference(cfg, params):
    """141 tokens (4.4 bands) through the sorted dispatch over the held
    pairs: every clear position's row, and the counts the program
    keeps."""
    seq = _prompt(1, 141)
    logits, kvs, counts = cohere.prefill(
        params, cfg, jnp.asarray(np.asarray(seq, np.int32)[None]))
    want, clear = _ref(params, seq, range(141))
    assert clear.sum() > 70
    assert np.abs(np.asarray(logits[0]) - want)[clear].max() < TOL
    assert len(kvs) == LAYERS
    # the pairs held: by the reference's own routing
    held = 0
    x = jnp.take(params["embed"], jnp.asarray(seq), axis=0)
    for i, layer in enumerate(params["layers"]):
        h = reference.layer_norm(x, layer["ln1"], 1e-5)
        gates, _ = reference._route(h, layer["router"], 2)
        held += int((np.asarray(gates)[:, FIRST:FIRST + HELD] > 0).sum())
        x, _ = reference.layer_forward(x, layer, CONF, i)
    assert int(counts["pairs_held"].sum()) == held
    # one pass a layer of a whole tile of rows: 141 x 2 x 2 / 8 x 1.25
    assert moe.held_rows(141, cfg) == moe.SORTED_ROW_TILE
    assert int(counts["rows"]) == LAYERS * moe.SORTED_ROW_TILE


def test_the_reference_is_a_parallel_block(cfg, params):
    """x' = x + a + m with ONE norm: a sequential block (the experts
    reading a norm of x + a) gives other rows, so the agreement above
    is no accident of small numbers."""
    seq = _prompt(2, 40)
    x = jnp.take(params["embed"], jnp.asarray(seq), axis=0)
    layer = params["layers"][0]
    par, _ = reference.layer_forward(x, layer, CONF, 0)
    h = reference.layer_norm(x, layer["ln1"], 1e-5)
    a = reference._attn(h, layer, reference._static(CONF), sliding=True)
    bare = {**layer, "e_gate": layer["e_gate"][:0],
            "e_up": layer["e_up"][:0], "e_down": layer["e_down"][:0]}
    only_attn, _ = reference.layer_forward(
        x, bare, {**CONF, "num_shared_experts": 0}, 0)
    assert np.allclose(only_attn, x + a, atol=1e-6)
    # the experts' half reads h, not a norm of x + a
    seq_in = reference.layer_norm(x + a, layer["ln1"], 1e-5)
    assert np.abs(np.asarray(seq_in - h)).max() > 0.1
    m = par - only_attn
    out, *_ = cohere._block(layer, (x + a)[None], cfg, None, h[None])
    assert np.abs(np.asarray(out[0]) - np.asarray(m)).max() < 1e-5


def _whole(params):
    """(the uncut model's file, config and layer 0) around `params`'
    layer 0: a router 8 wide and all 8 experts, of which the held two
    are `params`' own."""
    conf = {k: v for k, v in CONF.items() if k != "expert_share"}
    conf["num_experts"] = WIDTH
    cfg = _cfg(conf)
    assert not cfg.holds_share
    layer = dict(cohere.init_params(jax.random.PRNGKey(7), cfg)["layers"][0])
    mine = params["layers"][0]
    for name in ("ln1", "wq", "wk", "wv", "wo", "router", "s_gate", "s_up",
                 "s_down"):
        layer[name] = mine[name]
    for name in ("e_gate", "e_up", "e_down"):
        layer[name] = layer[name].at[FIRST:FIRST + HELD].set(mine[name])
    return conf, cfg, layer


@pytest.mark.parametrize("T", [5, 20, 141])
def test_the_shares_add_up(cfg, params, T):
    """Four chips of two experts each: their partial expert sums, plus
    the shared mean counted ONCE, plus x and the attention, are the
    uncut reference's layer; and the chip of this preset's share gives
    what the reference gives for that share. T chooses the form:
    gathered, dense, sorted."""
    conf, _, layer = _whole(params)
    x = jnp.take(params["embed"], jnp.asarray(_prompt(3, T)), axis=0)
    uncut, _ = reference.layer_forward(x, layer, conf, 0)
    h = reference.layer_norm(x, layer["ln1"], 1e-5)
    xa = x + reference._attn(h, layer, reference._static(conf), sliding=True)
    shared = moe.shared_expert(layer, h, jax.nn.silu, 4)
    total = xa + shared
    for chip in range(WIDTH // HELD):
        first = chip * HELD
        part = {**layer, **{n: layer[n][first:first + HELD]
                            for n in ("e_gate", "e_up", "e_down")}}
        c = dataclasses.replace(cfg, first_expert=first)
        out, _, _, counts = cohere._block(part, x[None], c, None, h[None])
        total = total + (out[0] - shared)
        mine, _ = reference.layer_forward(
            x, part, {**CONF, "expert_share": {"router_width": WIDTH,
                                               "first_expert": first}}, 0)
        assert np.abs(np.asarray(xa + out[0] - mine)).max() < 1e-5, chip
        assert counts["pairs_held"].shape == (1, T)
    assert np.abs(np.asarray(total - uncut)).max() < 2e-5
    # the shared mean counted four times would not be
    assert np.abs(np.asarray(total + 3 * shared - uncut)).max() > 1e-2


def _loop(layer, u, local, gates, act):
    """The held experts one at a time: the plain statement of what
    every form computes."""
    out = jnp.zeros_like(u)
    for e in range(layer["e_gate"].shape[0]):
        w = jnp.sum(jnp.where(local == e, gates, 0.0), axis=-1)
        a = act(u @ layer["e_gate"][e]) * (u @ layer["e_up"][e])
        out = out + (a @ layer["e_down"][e]) * w[:, None]
    return out


@pytest.mark.parametrize("skew", ["even", "all-here", "none-here"])
@pytest.mark.parametrize("T", [3, 16, 100, 700])
def test_the_three_forms_agree_on_the_held_ids(T, skew):
    """gathered (a decode step's rows), dense and sorted over the held
    pairs against the loop, 16 routed experts of which 4 are held from
    id 6 on: with routing that is even, that falls on the held experts
    alone (the sorted form needs more than one pass: T x 4 pairs over
    passes of 1.25 x T x 4 / 4 rows) and that never does (nothing
    computed, nothing fetched, zero out)."""
    rng = np.random.default_rng(T)
    d, f, E, first, k = 32, 16, 4, 6, 4
    layer = {n: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
             for n, s in (("e_gate", (E, d, f)), ("e_up", (E, d, f)),
                          ("e_down", (E, f, d)))}
    u = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    ids = {"even": np.arange(16), "all-here": np.arange(first, first + E),
           "none-here": np.r_[0:first, first + E:16]}[skew]
    top = np.stack([rng.choice(ids, k, replace=False) for _ in range(T)])
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (T, k)), jnp.float32)
    local = jnp.asarray(top - first, jnp.int32)
    act = jax.nn.silu
    want = np.asarray(_loop(layer, u, local, gates, act))
    if skew == "none-here":
        assert not want.any()
    cfg = moe.MoEConfig(n_experts=E, n_routed=16, first_expert=first,
                        top_k=k)
    rows = moe.held_rows(T, cfg)
    got, passes = moe.experts_sorted_held(layer, u, local, gates, act, rows)
    assert np.abs(np.asarray(got) - want).max() < 1e-5
    held = int(((top >= first) & (top < first + E)).sum())
    assert int(passes) == -(-held // rows)
    if skew == "all-here" and T == 700:
        assert int(passes) == 3  # 2,800 held pairs, 1,024 rows a pass
    dense = moe.experts_dense(layer, u, local, gates, act)
    assert np.abs(np.asarray(dense) - want).max() < 1e-5
    if T <= moe.GATHERED_EXPERTS_MAX_ROWS:
        valid = jnp.asarray(np.arange(T) != 1)
        out, n = moe.experts_gathered(layer, u, local, gates, act, valid)
        keep = np.asarray(valid)[:, None]
        assert np.abs(np.asarray(out) - want * keep).max() < 1e-5
        live = {int(e) for e in (top - first)[np.asarray(valid)].ravel()
                if 0 <= e < E}
        assert int(n) == len(live)


def test_a_step_where_no_row_chose_a_held_expert(cfg, params):
    """The router's columns of the held experts pushed far down: no
    pair falls here, the kernel fetches nothing, and the block is the
    shared mean alone: in a decode step's rows and in a prompt's."""
    layer = dict(params["layers"][0])
    layer["router"] = layer["router"].at[:, FIRST:FIRST + HELD].set(0.0)
    layer["router"] = layer["router"].at[0, FIRST:FIRST + HELD].set(-1e4)
    for T in (3, 20, 141):
        x = jnp.take(params["embed"], jnp.asarray(_prompt(5, T)), axis=0)
        h = decoder.layer_norm(x, layer["ln1"], 1e-5)
        h = h.at[:, 0].set(jnp.abs(h[:, 0]) + 1.0)  # so the push is felt
        valid = jnp.ones((1, T), bool)
        out, _, fetched, counts = cohere._block(layer, x[None], cfg, valid,
                                                h[None])
        shared = moe.shared_expert(layer, h, jax.nn.silu, 4)
        assert np.array_equal(np.asarray(out[0]), np.asarray(shared)), T
        assert int(counts["pairs_held"].sum()) == 0
        if T <= moe.GATHERED_EXPERTS_MAX_ROWS:
            assert int(fetched) == 0 and int(counts["rows"]) == 0
        elif T > 24:
            assert int(counts["rows"]) == 0  # the loop ran no pass


# -- through the engine: two kinds of page, the share's counters ------------
def test_cold_admission_and_24_decoded_tokens(cfg, params):
    """Prefill, then 24 tokens through the cache, a session of 4.4
    bands: every row agrees with the reference."""
    eng = _engine(params, cfg)
    prompt = _prompt(6, 141)
    out = eng.run([_req("a", prompt, 24)])["a"]
    assert len(out) == 24
    assert _worst(eng, params, "a", prompt, out) < TOL
    assert sorted(eng.wfree) == list(range(1, eng._wpool_pages))
    assert sorted(eng.free_pages) == list(range(1, 96))


def test_the_probe_asks_for_layer_zero_though_it_is_banded(cfg, params):
    """The first attention layers are banded here; the probed key of a
    page is layer 0's all the same (only a slot of three kinds probes
    its one full layer: serving.py `_probe_kinds`)."""
    assert cfg.layer_bands[0] > 0
    assert _engine(params, cfg)._probe_kinds == [(0, "k")]


def test_under_the_band_and_past_it_in_one_engine(cfg, params, shm_conn):
    """Two sessions side by side, one that never leaves the band (its
    window layers keep every page: first_live 0) and one 4 bands long
    (sub-floor writes, release): three turns each, turns 2 and 3 as
    hits, every row against the reference."""
    eng = _engine(params, cfg, shm_conn, model_id="co-mixed")
    short, long_ = _prompt(7, 9), _prompt(8, 130)
    t0 = time.time_ns()
    for turn in range(3):
        outs = eng.run([_req(f"s{turn}", short, 5),
                        _req(f"l{turn}", long_, 9)])
        assert _worst(eng, params, f"s{turn}", short, outs[f"s{turn}"]) < TOL
        assert _worst(eng, params, f"l{turn}", long_, outs[f"l{turn}"]) < TOL
        short = short + outs[f"s{turn}"] + _prompt(20 + turn, 4)
        long_ = long_ + outs[f"l{turn}"] + _prompt(30 + turn, 11)
    restores = [s.fields for s in _spans(eng, t0)
                if s.name == "istpu.cache.restore"]
    assert len(restores) == 4
    assert sorted(r["trimmed_pages"] == 0 for r in restores) \
        == [False, False, True, True]
    assert eng.stats["subfloor_pages_written"] > 0
    assert eng.stats["window_pages_released"] > 0
    assert eng.stats["restore_misses"] == 0


@pytest.mark.parametrize("ctx", [9, 20, 27, 33, 70, 141])
def test_a_hit_at_every_page_edge(cfg, params, shm_conn, ctx):
    """Turn 2 over a stored turn 1 whose length is under, at and past
    the band: the hit's rows agree with the reference, and the one
    store call brought each kind of layer what its band needs."""
    eng = _engine(params, cfg, shm_conn, model_id=f"co-hit-{ctx}")
    first = _prompt(9, ctx)
    history = first + eng.run([_req("t1", first, 12)])["t1"]
    P = (len(history) - 1) // PAGE
    prompt = history + _prompt(10, 13)
    before = dict(eng.stats)
    out = eng.run([_req("t2", prompt, 10)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    moved = {k: eng.stats[k] - before[k] for k in before}
    assert moved["prefix_hit_pages"] == P
    assert moved["restored_pages"] == 2 * (
        L_FULL * P + L_WIN * (P - first_live(P)))


def test_offload_evict_restore_bit_for_bit(cfg, params, shm_conn):
    """What a finish wrote is what a later hit reads: every page of
    every layer of a finished sequence, read back from the store,
    equals the dense forward's K and V bit for bit where the pools held
    it; with a window layer's page gone the next turn is a miss, not an
    error; written again, it hits."""
    eng = _engine(params, cfg, shm_conn, model_id="co-evict")
    prompt = _prompt(11, 70)
    out = eng.run([_req("a", prompt, 10)])["a"]
    seq = prompt + out
    n = (len(seq) - 1) // PAGE
    toks = jnp.asarray(np.asarray(seq[:n * PAGE], np.int32)[None])
    _, kvs, _ = cohere.prefill(params, cfg, toks)
    digests = eng._digests(seq, n)
    for layer in range(LAYERS):
        for which, kind in enumerate("kv"):
            keys = [f"cp/{d}/L{layer}/{kind}" for d in digests]
            got = eng.store.get_kv_pages_host(keys, cfg.kv_page_shape(),
                                              cfg.jdtype)
            want = np.asarray(kvs[layer][which][0]).reshape(got.shape)
            assert np.abs(got - want).max() < 1e-5, (layer, kind)
    shm_conn.delete_keys([f"cp/{digests[n - 1]}/L1/v"])
    second = seq + _prompt(12, 13)
    out2 = eng.run([_req("b", second, 6)])["b"]
    assert eng.stats["restore_misses"] == 1
    assert _worst(eng, params, "b", second, out2) < TOL
    third = second + out2 + _prompt(13, 5)
    hits = eng.stats["prefix_hit_pages"]
    out3 = eng.run([_req("c", third, 6)])["c"]
    assert eng.stats["prefix_hit_pages"] - hits \
        == (len(second) + len(out2) - 1) // PAGE
    assert _worst(eng, params, "c", third, out3) < TOL


def test_first_token_logits_on_both_paths(cfg, params, shm_conn):
    """What decides `correct` on the chip: the admission's programs,
    nothing admitted, the row without the program's counts."""
    eng = _engine(params, cfg, shm_conn, model_id="co-ftl")
    prompt = _prompt(14, 141)
    row, hit = eng.first_token_logits(prompt)
    want, clear = _ref(params, prompt, [140])
    assert hit == 0 and clear[0] and row.shape == (CONF["vocab_size"],)
    assert np.abs(row - want[0]).max() < TOL
    history = prompt + eng.run([_req("t1", prompt, 12)])["t1"]
    second = history + _prompt(15, 13)
    row, hit = eng.first_token_logits(second)
    want, clear = _ref(params, second, [len(second) - 1])
    assert hit == (len(history) - 1) // PAGE and clear[0]
    assert np.abs(row - want[0]).max() < TOL


def test_the_shares_counters_and_spans(cfg, params):
    """`pairs_held` on the admission's and every decode step's span,
    the three counters, and the experts fetched: a step of one token
    fetches, a layer, the held experts its 2 chosen pairs fell on."""
    eng = _engine(params, cfg)
    t0 = time.time_ns()
    prompt = _prompt(16, 141)
    out = eng.run([_req("a", prompt, 30)])["a"]
    spans = _spans(eng, t0)
    pre, = [s for s in spans if s.name == "istpu.model.prefill"]
    decodes = [s for s in spans if s.name == "istpu.model.decode"]
    assert len(decodes) == 29
    # by the reference's own routing over the whole sequence
    seq = prompt + out
    x = jnp.take(params["embed"], jnp.asarray(seq), axis=0)
    per_pos = np.zeros(len(seq), int)
    for i, layer in enumerate(params["layers"]):
        h = reference.layer_norm(x, layer["ln1"], 1e-5)
        gates, _ = reference._route(h, layer["router"], 2)
        per_pos += (np.asarray(gates)[:, FIRST:FIRST + HELD] > 0).sum(axis=1)
        x, _ = reference.layer_forward(x, layer, CONF, i)
    assert pre.fields["pairs_held"] == per_pos[:141].sum()
    assert [d.fields["pairs_held"] for d in decodes] \
        == list(per_pos[141:170])
    assert all(d.fields["experts_fetched"] <= d.fields["pairs_held"]
               for d in decodes)
    assert eng.stats["moe_pairs_routed"] == 170 * 2 * LAYERS
    assert eng.stats["moe_pairs_held"] == per_pos[:170].sum()
    assert eng.stats["moe_rows_computed"] == LAYERS * moe.SORTED_ROW_TILE
    assert eng.stats["moe_experts_held"] == 29 * LAYERS * HELD
    # an eighth of the router's experts a chip, two of eight here
    share = eng.stats["moe_pairs_held"] / eng.stats["moe_pairs_routed"]
    assert 0.1 < share < 0.45
