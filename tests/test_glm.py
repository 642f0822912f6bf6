"""A learned selection of cache rows over a latent cache (models/glm.py,
GLM-5.2's shape): index keys as a SECOND kind of page on the layers
that own an indexer, a selection borrowed by the layers above, the
share of the experts. Held to the plain float32 reference (benchmark/
reference/glm_dsa.py) by LOGITS and by the SELECTED SET, at a tiny
preset on the CPU with seeded weights (`index_topk` 32, so that a
256-token prompt is 8 x it). The recording engine and the near-tie rule
are tests/test_latent.py's.
"""

import dataclasses
import time
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_dsa as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, glm, hf, moe
from infinistore_tpu.ops import sparse_select
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 8
TOPK = 32
OWNERS = ["full", "shared", "shared", "shared", "full"]
CONF = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "index_head_dim": 16,
    "index_n_heads": 4, "index_topk": TOPK, "index_topk_pattern": None,
    "indexer_rope_interleave": True, "indexer_types": OWNERS,
    "intermediate_size": 128, "kv_lora_rank": 32,
    "max_position_embeddings": 4096,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "model_type": "glm_moe_dsa", "moe_intermediate_size": 32, "n_group": 1,
    "n_routed_experts": 2, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 5, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "q_lora_rank": 48,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 128,
    "expert_share": {"router_width": 16, "first_expert": 4},
}
# Float32 program against the float32 reference on the CPU: the worst
# row seen is 5e-6 at logits of 4; 2e-4 is what the other families' CPU
# comparisons hold (tolerances_glm.json, glm_cpu_f32).
TOL = 2e-4
MARGIN = 1e-3
GAP = 1e-5


@pytest.fixture(autouse=True)
def sorted_dispatch_above_a_decode_batch(monkeypatch):
    """As tests/test_latent.py: prefills run the sorted dispatch, decode
    steps the gathered kernel, as at the published widths."""
    monkeypatch.setattr(moe, "DENSE_EXPERTS_MAX_ROWS", 24 * 8)


def _cfg(conf=CONF):
    return hf.glm_dsa_config_from_hf(types.SimpleNamespace(**conf),
                                     page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return jax.jit(glm.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CONF["vocab_size"], n)]


def _ref(params, seq, positions, conf=CONF):
    """(the reference's rows, which of them are evidence): not where a
    router's choice is a near-tie in some layer, nor where an owner's
    SELECTION is: at `index_topk` 32 one row of 32 flipped between two
    float32 summation orders moves a logit by 0.3 (at the preset's
    vocabulary of 128 a prompt repeats tokens, whose index keys differ
    by their rotation alone)."""
    rows, margins, chosen = reference.forward_with_selection(
        params, conf, np.asarray(seq, np.int32), list(positions))
    clear = np.asarray(margins).min(axis=1) >= MARGIN
    for parts in chosen.values():
        clear &= parts[3] >= GAP
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


def _engine(params, cfg, conn=None, model_id="glm", cls=Recording, **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 160)
    sc.setdefault("max_pages_per_seq", 48)
    return cls(params, cfg, ServingConfig(model_id=model_id, **sc),
               store=None if conn is None else TpuKVStore(conn), model=glm)


def _req(rid, prompt, n):
    return Request(rid, prompt, max_new_tokens=n, temperature=1.0)


def _worst(eng, params, rid, prompt, out):
    seq = list(prompt) + list(out)
    want, clear = _ref(params, seq, range(len(prompt) - 1, len(seq) - 1))
    got = np.stack(eng.rows[rid])
    assert got.shape == want.shape
    assert clear.sum() * 2 >= len(clear), clear
    return float(np.abs(got - want)[clear].max())


def _sets(idx, taken):
    return [frozenset(np.asarray(i)[np.asarray(t)].tolist())
            for i, t in zip(idx, taken)]


# -- the model ---------------------------------------------------------------
def test_bridge_reads_every_shaping_key(cfg):
    assert cfg.layer_kinds == ("latent",) * 5 and cfg.n_kv_layers == 5
    assert cfg.page_kinds == "ci" and cfg.hc_mult == 1
    assert cfg.indexer_kinds == tuple(OWNERS) and cfg.index_layers == (0, 4)
    assert cfg.dense_layers == (True, False, False, False, False)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (4, 16, TOPK)
    assert cfg.rope_adjacent and cfg.index_rope_adjacent
    assert cfg.rope_theta == 8e6 and cfg.norm_eps == 1e-5
    assert (cfg.n_experts, cfg.n_routed, cfg.first_expert, cfg.top_k,
            cfg.n_shared, cfg.route_scale, cfg.router) == (
        2, 16, 4, 2, 1, 2.5, "sigmoid")
    assert cfg.holds_share
    # the page contract, by kind
    assert cfg.kv_page_shape() == cfg.page_shape("c") == (PAGE, 128)
    assert cfg.page_shape("i") == (PAGE, 16)
    assert cfg.page_layers("c") == (0, 1, 2, 3, 4)
    assert cfg.page_layers("i") == (0, 4)
    wide = dataclasses.replace(cfg, kv_lora_rank=512, qk_rope=64)
    assert wide.latent_width == 640       # 576 up to a lane tile


def test_prefill_matches_the_reference_and_selects_its_set(cfg, params):
    prompt = _prompt(1, 256)              # 8 x index_topk
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kvs, counts = glm.prefill(params, cfg, toks)
    want, clear = _ref(params, prompt, range(256))
    assert clear.sum() > 150
    assert np.abs(np.asarray(logits[0]) - want)[clear].max() < TOL
    # rows on every layer, index keys on the owners alone
    assert [(r.shape, None if k is None else k.shape) for r, k in kvs] == [
        ((1, 256, 128), (1, 256, 16))] + [((1, 256, 128), None)] * 3 + [
        ((1, 256, 128), (1, 256, 16))]
    assert not np.asarray(kvs[0][0][..., 40:]).any()     # the row's padding
    assert counts["pairs_held"].shape == (1, 256)
    # the SAME selected set as the reference's own top-k, both owners
    taps = jax.jit(glm.prefill_selections, static_argnums=1)(params, cfg,
                                                             toks)
    theirs = reference.selection(params, CONF, np.asarray(prompt, np.int32),
                                 list(range(256)))
    assert sorted(theirs) == [0, 4] and len(taps) == 2
    for (idx, taken), layer in zip(taps, (0, 4)):
        ours = _sets(idx[0], taken[0])
        want_sets = _sets(*theirs[layer][:2])
        sure = theirs[layer][3] >= GAP          # no near-tie at the edge
        assert sure.sum() > 200
        assert [o for o, ok in zip(ours, sure) if ok] == [
            w for w, ok in zip(want_sets, sure) if ok]
        assert [len(s) for s in ours] == [min(t + 1, TOPK)
                                          for t in range(256)]
        assert all(max(s) <= t for t, s in enumerate(ours))  # causal


def test_a_shared_layer_attends_its_owners_selection(cfg, params):
    """Layers 1-3 compute no selection and cache no index key; what
    they attend is layer 0's set: with layer 0's indexer changed,
    their attention changes; with their own (absent) indexer there is
    nothing to change."""
    assert all("wqi" not in params["layers"][i] for i in (1, 2, 3))
    assert all("wqi" in params["layers"][i] for i in (0, 4))
    prompt = _prompt(2, 96)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    seen = []
    real = decoder.latent_selected_prefill

    def spy(layer, cfg_, q_nope, q_pe, rows, sel):
        seen.append(sel)
        return real(layer, cfg_, q_nope, q_pe, rows, sel)

    decoder.latent_selected_prefill = spy
    try:
        glm.prefill(params, cfg, toks)
    finally:
        decoder.latent_selected_prefill = real
    assert len(seen) == 5
    assert all(seen[i] is seen[0] for i in (1, 2, 3))    # the same arrays
    assert seen[4] is not seen[0]
    assert _sets(seen[4][0][0], seen[4][1][0]) != _sets(seen[0][0][0],
                                                        seen[0][1][0])


@pytest.mark.parametrize("what,change", [
    ("every row attended (no selection)", {"index_topk": 0}),
    ("a selection twice as wide", {"index_topk": 2 * TOPK}),
    ("every layer its own indexer's... the last borrows",
     {"indexer_kinds": ("full", "shared", "shared", "shared", "shared")}),
    ("half-split rotary on the index lanes", {"index_rope_adjacent": False}),
    ("half-split rotary on the rope lanes", {"rope_adjacent": False}),
    ("gates summing to 1", {"route_scale": 1.0}),
    ("the shared expert left out", {"n_shared": 0}),
    ("an absent pair kept", {"first_expert": 0}),
])
def test_each_part_of_the_layer_is_seen_by_the_reference(cfg, params, what,
                                                         change):
    prompt = _prompt(3, 128)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    want, clear = _ref(params, prompt, range(64, 128))
    ours = np.asarray(glm.prefill(params, cfg, toks)[0][0, 64:])
    assert np.abs(ours - want)[clear].max() < TOL
    other = dataclasses.replace(cfg, **change)
    wrong = np.asarray(glm.prefill(params, other, toks)[0][0, 64:])
    assert np.abs(wrong - want)[clear].max() > 50 * TOL, what


def test_init_gains_widen_three_kinds_of_matrix_and_no_other(cfg):
    """`random_init`'s three gains (GlmConfig q_/o_/down_init_gain) are
    read by init_params alone: Wqb, Wo and every down projection of a
    feed-forward are the same draws at another width, every other leaf
    is the same bits."""
    init = jax.jit(glm.init_params, static_argnums=1)
    plain = init(jax.random.PRNGKey(5), cfg)
    other = init(jax.random.PRNGKey(5), dataclasses.replace(
        cfg, q_init_gain=4.0, o_init_gain=0.125, down_init_gain=0.25))
    gains = {"wqb": 4.0, "wo": 0.125, "w_down": 0.25, "e_down": 0.25,
             "s_down": 0.25}
    seen = set()
    for a, b in zip(plain["layers"], other["layers"]):
        assert a.keys() == b.keys()
        for name in a:
            want = np.asarray(a[name]) * gains.get(name, 1.0)
            np.testing.assert_allclose(np.asarray(b[name]), want,
                                       rtol=1e-6, atol=0)
            seen.add(name)
    assert set(gains) <= seen
    for name in ("embed", "lm_head", "final_ln"):
        assert np.array_equal(np.asarray(plain[name]),
                              np.asarray(other[name]))


@pytest.mark.parametrize("fault", ["recent_rows", "other_layer_keys",
                                   "stale_keys"])
def test_the_references_planted_selections_move_its_rows(cfg, params,
                                                         fault):
    """benchmark/tools/precision_reading_index.py's third reading: the
    reference under a planted wrong selection is another model's rows,
    and the hook leaves nothing behind."""
    prompt = _prompt(11, 256)
    at = list(range(200, 256))
    want, clear = _ref(params, prompt, at)
    reference.FAULT = fault
    try:
        wrong = np.asarray(reference.forward(
            params, CONF, np.asarray(prompt, np.int32), at)[0])
    finally:
        reference.FAULT = None
    assert np.abs(wrong - want)[clear].max() > 50 * TOL
    again = np.asarray(reference.forward(
        params, CONF, np.asarray(prompt, np.int32), at)[0])
    assert np.array_equal(again, want)


def _few_values(rng):
    """200 rows of 96 scores drawn from 6 values: ties everywhere."""
    return (rng.integers(0, 6, (200, 96)).astype(np.float32),
            rng.integers(1, 97, 200).astype(np.int32), TOPK)


def _ties_at_the_kth(rng):
    """Rows of 35,072 at k 2,048 whose 2,048th largest value is held by
    hundreds of positions, on both sides of the cut."""
    scores = rng.integers(0, 40, (8, 35072)).astype(np.float32)
    scores[1] = 3.0                                 # one value, all equal
    scores[2, rng.random(35072) < 0.05] = 50.0      # ~1,750 above the ties
    scores[3] = -scores[3]
    return scores, np.array([35072, 35072, 35072, 35072, 17000, 20001,
                             34000, 2300], np.int32), 2048


def _signs_and_specials(rng):
    """Negative scores, -0.0 beside +0.0 (ONE value: position decides),
    denormals of both signs, +inf and -inf."""
    scores = rng.standard_normal((48, 96)).astype(np.float32)
    scores[:, ::3] = np.round(scores[:, ::3])       # and ties among them
    for r in range(0, 48, 4):
        at = rng.permutation(96)
        scores[r, at[:20]] = -0.0
        scores[r, at[20:40]] = 0.0
        scores[r + 1, at[:15]] = 1e-42
        scores[r + 1, at[15:30]] = -1e-42
        scores[r + 1, at[30:45]] = 2e-42
        scores[r + 2, at[:10]] = np.inf
        scores[r + 2, at[10:20]] = -np.inf
        scores[r + 3] = -np.abs(scores[r + 3])
    return scores, rng.integers(1, 97, 48).astype(np.int32), TOPK


def _n_live_at_the_edges(rng):
    """n_live of 1, k - 1, k, k + 1 and S, over few values and many."""
    edges = np.array([1, TOPK - 1, TOPK, TOPK + 1, 96], np.int32)
    scores = np.concatenate([
        rng.integers(0, 3, (20, 96)), rng.standard_normal((20, 96))]
    ).astype(np.float32)
    return scores, np.tile(edges, 8), TOPK


SELECTION_ROWS = {"few_values": _few_values,
                  "ties_at_the_kth_of_35072": _ties_at_the_kth,
                  "signs_zeros_denormals_inf": _signs_and_specials,
                  "n_live_1_k_and_s": _n_live_at_the_edges}


@pytest.mark.parametrize("rows", list(SELECTION_ROWS))
@pytest.mark.parametrize("reordered", [False, True, 6])
def test_selection_is_top_ks_set_on_rows_with_ties(reordered, rows):
    """Score rows with ties everywhere (`rows`): the set is
    `jax.lax.top_k`'s, which takes the lower position of equals; a
    numpy stable sort says the same, and the slots `taken` are the
    first of a row. `reordered`: the rows as decode batches of 8 (or 6)
    slots of which some hold a sequence, selected through `over_active`
    (the valid slots first, a rung of the ladder, back in slot order): a
    valid row's set is the same, at every rung."""
    rng = np.random.default_rng(5)
    scores, n_live, k = SELECTION_ROWS[rows](rng)
    n, s = scores.shape
    picked = range(n)
    if reordered:
        b = 8 if reordered is True else reordered
        batches = max(n // b, b + 3)                # the rows, again
        scores, n_live = (np.resize(a, (batches * b, *a.shape[1:]))
                          for a in (scores, n_live))
        valid = rng.random((batches, b)) < 0.4
        valid[0], valid[1] = True, False
        for count in range(b + 1):              # every rung, every count
            valid[2 + count] = np.arange(b)[::-1] < count
        some = jax.jit(lambda v, s, n: sparse_select.over_active(
            partial(sparse_select.select, k=k),
            sparse_select.active_first(v), s, n))
        idx, taken = (jnp.concatenate(part) for part in zip(*(
            some(jnp.asarray(v), jnp.asarray(s), jnp.asarray(n))
            for v, s, n in zip(valid, scores.reshape(batches, b, s),
                               n_live.reshape(batches, b)))))
        picked = np.flatnonzero(valid)
        # no slot of the second batch is valid: the least rung runs one
        assert not np.asarray(taken[b + 1:2 * b]).any()
        assert {int(sparse_select.active_first(jnp.asarray(v))[1])
                for v in valid} == set(range(len(sparse_select.ladder(b))))
    else:
        idx, taken = jax.jit(partial(sparse_select.select, k=k))(
            jnp.asarray(scores), jnp.asarray(n_live))
    idx, taken = np.asarray(idx), np.asarray(taken)
    assert idx.shape[1:] == (min(k, s),) and idx.dtype == np.int32
    assert idx.min() >= 0 and idx.max() < s
    for r in picked:
        live = scores[r, :n_live[r]]
        kk = min(k, n_live[r])
        want = np.argsort(-live, kind="stable")[:kk]
        assert taken[r, :kk].all() and not taken[r, kk:].any()
        assert sorted(idx[r][taken[r]].tolist()) == sorted(want.tolist())
        if rows in ("few_values", "n_live_1_k_and_s"):
            # (`top_k` on the CPU ranks +0.0 above -0.0: not those rows)
            _, top = jax.lax.top_k(jnp.asarray(live), kk)
            assert sorted(np.asarray(top).tolist()) == sorted(want.tolist())


@pytest.mark.parametrize("rows", list(SELECTION_ROWS))
def test_the_chips_bisection_kernel_finds_the_loops_threshold(rows):
    """`_kth_key_kernel` (what a TPU backend runs, here in interpret
    mode) and `_kth_key_loop` at one bit a pass and at four: the same
    key of the kth largest on the rows with ties, and it IS the kth
    largest."""
    scores, n_live, k = SELECTION_ROWS[rows](np.random.default_rng(5))
    scores, n_live = scores[:24], n_live[:24]
    keys, edge, k_row = jax.jit(partial(sparse_select.threshold, k=k))(
        jnp.asarray(scores), jnp.asarray(n_live))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sparse_select, "_PASS_BITS", 4)
        assert np.array_equal(edge, sparse_select._kth_key_loop(keys, k_row))
    assert np.array_equal(edge, sparse_select._kth_key_kernel(
        keys, k_row, interpret=True))
    for r, row in enumerate(np.asarray(keys)):
        assert edge[r] == np.sort(row)[-k_row[r]]


def test_the_sum_of_all_shares_is_the_uncut_layer(cfg, params):
    """The guide's share test at the preset's scale (16 experts scored,
    2 held: 8 shares): the routed parts of all 8 shares plus the
    shared expert counted ONCE equal the layer with every expert held,
    which the uncut reference computes."""
    full = dataclasses.replace(cfg, n_experts=16, n_routed=0, first_expert=0)
    layer = glm.init_params(jax.random.PRNGKey(7), full)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 40, 64))
    whole, *_ = moe.sorted_moe_mlp(layer, x, full, None)
    u = decoder.rms_norm(x, layer["ln2"], cfg.norm_eps).reshape(40, 64)
    shared = moe.shared_expert(layer, u, jax.nn.silu).reshape(1, 40, 64)
    parts = jnp.zeros_like(whole)
    for first in range(0, 16, 2):
        held = dict(layer, **{k: layer[k][first:first + 2]
                              for k in ("e_gate", "e_up", "e_down")})
        share = dataclasses.replace(cfg, first_expert=first)
        out, _, _, counts = moe.sorted_moe_mlp(held, x, share, None)
        parts = parts + out - shared
    assert np.abs(np.asarray(parts + shared - whole)).max() < 1e-5
    assert np.abs(np.asarray(shared)).max() > 1e-2
    # ... and the reference's layer with every expert held is that sum
    uncut = dict(CONF, n_routed_experts=16, expert_share=None)
    y, _ = reference._experts(u, layer, 0, reference._static(uncut))
    assert np.abs(np.asarray(y) - np.asarray(whole[0])).max() < 1e-5


# -- decode over the pools ---------------------------------------------------
def _pools(cfg, kvs, n_tokens, pages):
    pool = jnp.zeros((5, pages, PAGE, cfg.latent_width))
    ipool = jnp.zeros((2, pages, PAGE, cfg.index_dim))
    n = n_tokens // PAGE
    owners = iter(range(2))
    for li, (rows, keys) in enumerate(kvs):
        pool = pool.at[li, 1:1 + n].set(
            rows[0, :n * PAGE].reshape(n, PAGE, -1))
        if keys is not None:
            ipool = ipool.at[next(owners), 1:1 + n].set(
                keys[0, :n * PAGE].reshape(n, PAGE, -1))
    return pool, ipool


def test_decode_through_the_cache_is_the_prefill(cfg, params):
    """A decode step over the paged rows and index keys selects and
    attends what the prefill's last position did: the same logits, the
    same set, and the new token's row and index key in their pools."""
    prompt = _prompt(7, 201)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kvs, _ = glm.prefill(params, cfg, toks)
    pool, ipool = _pools(cfg, kvs, 200, 40)
    table = jnp.asarray([list(range(1, 27)) + [0] * 6], jnp.int32)
    lens = jnp.asarray([200], jnp.int32)
    got, pool2, ipool2, _ = glm.decode_step(
        params, cfg, toks[:, 200], lens, pool, ipool, table, fetched=True)
    assert np.abs(np.asarray(got[0] - logits[0, 200])).max() < 1e-4
    for li, (rows, keys) in enumerate(kvs):
        assert np.allclose(np.asarray(pool2[li, 26, 0]),
                           np.asarray(rows[0, 200]), atol=1e-5)
    for j, li in enumerate((0, 4)):
        assert np.allclose(np.asarray(ipool2[j, 26, 0]),
                           np.asarray(kvs[li][1][0, 200]), atol=1e-5)
    taps = jax.jit(glm.decode_selections, static_argnums=1)(
        params, cfg, toks[:, 200], lens, pool, ipool, table)
    theirs = reference.selection(params, CONF, np.asarray(prompt, np.int32),
                                 [200])
    for (idx, taken), layer in zip(taps, (0, 4)):
        assert theirs[layer][3][0] >= GAP
        assert _sets(idx, taken) == _sets(*theirs[layer][:2])


@pytest.mark.parametrize("slots", [
    (), (0,), (5,), (1, 6), (0, 2, 3), (0, 2, 4, 5, 7), tuple(range(8))])
def test_a_decode_steps_selection_runs_over_the_decoding_slots(cfg, params,
                                                               slots):
    """A decode batch of 8 slots of which `slots` hold a sequence, at a
    table of 96 keys (3 x `index_topk`): for every valid slot the logits
    row and each owner layer's tapped (positions, taken) are what the
    same step gives with every slot valid, which is the parent's
    arithmetic (the full batch's branch runs `select_paged`, the gather
    and `attend` over the arrays as they come); the device's counts are
    the valid slots' rows taken and the least rung of 1, 2, 4, 8 that
    holds them."""
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal(
        (5, 97, PAGE, cfg.latent_width)), jnp.float32)
    ipool = jnp.asarray(rng.standard_normal(
        (2, 97, PAGE, cfg.index_dim)), jnp.float32)
    table = 1 + np.arange(8 * 12, dtype=np.int32).reshape(8, 12)
    lens = np.asarray([40, 95, 20, 64, 33, 71, 88, 50], np.int32)
    tok = jnp.asarray(rng.integers(0, CONF["vocab_size"], 8), jnp.int32)
    valid = np.isin(np.arange(8), slots)

    def step(valid):
        # as the engine hands an empty slot over: length 0, scratch page
        args = (params, cfg, tok, jnp.asarray(np.where(valid, lens, 0)),
                pool, ipool, jnp.asarray(np.where(valid[:, None], table, 0)))
        logits, _, _, counts = glm.decode_step(*args, fetched=True)
        taps = jax.jit(glm.decode_selections, static_argnums=1)(*args)
        return np.asarray(logits), taps, np.asarray(counts)

    want, want_taps, _ = step(np.ones(8, bool))
    got, taps, counts = step(valid)
    assert len(taps) == 2
    for i in slots:
        assert np.abs(got[i] - want[i]).max() < 1e-5
        for (idx, taken), (widx, wtaken) in zip(taps, want_taps):
            t = np.asarray(taken[i])
            assert np.array_equal(t, np.asarray(wtaken[i]))
            assert t.sum() == min(lens[i] + 1, TOPK)
            assert np.array_equal(np.asarray(idx[i])[t],
                                  np.asarray(widx[i])[t])
    assert np.isfinite(got).all()
    # [experts fetched, rows taken, slots run, pairs held]
    assert counts[1] == 5 * sum(min(lens[i] + 1, TOPK) for i in slots)
    assert counts[2] == next(n for n in (1, 2, 4, 8) if n >= len(slots))


def test_the_engine_counts_the_slots_a_steps_selection_ran(cfg, params):
    """Three requests of 6, 10 and 14 tokens into 4 slots (a table of
    48 pages, 12 x `index_topk`): the steps run 3, 2 and then 1
    sequence, their selections over 4, 2 and 1 slots, and the span of a
    step says so."""
    eng = _engine(params, cfg, max_slots=4, total_pages=200)
    eng._proven = lambda active: False     # a span a step, dispatch to land
    t0 = time.time_ns()
    eng.run([_req(r, _prompt(s, 40), n)
             for r, s, n in (("a", 1, 6), ("b", 2, 10), ("c", 3, 14))])
    steps = [(s.fields["select_rows_active"], s.fields["select_rows_run"])
             for s in profiling.spans(since_ns=t0)
             if s.name == "istpu.model.decode" and s.engine == eng.engine_id]
    assert steps == [(3, 4)] * 5 + [(2, 2)] * 4 + [(1, 1)] * 4
    assert eng.stats["select_rows_active"] == 15 + 8 + 4
    assert eng.stats["select_rows_run"] == 20 + 8 + 4
    assert eng.stats["attn_rows_selected"] == 5 * 27 * TOPK


def test_under_index_topk_plus_one_live_tokens_attention_is_dense(cfg,
                                                                  params):
    """A table that holds `index_topk` keys or fewer (a shape) runs the
    dense latent path itself: the same bits. Under a wider table a
    sequence of `index_topk` live tokens or fewer selects every live
    row: the dense path's numbers to float32's grain (the selected rows
    are summed in score order, not in position order)."""
    prompt = _prompt(9, TOPK)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    _, kvs, _ = glm.prefill(params, cfg, toks)
    pool, ipool = _pools(cfg, kvs, TOPK - PAGE, 12)
    lens = jnp.asarray([TOPK - PAGE], jnp.int32)           # 24 cached
    tok = toks[:, TOPK - PAGE]
    narrow = jnp.asarray([[1, 2, 3, 4]], jnp.int32)        # 32 keys
    wide = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]], jnp.int32)
    dense_cfg = dataclasses.replace(cfg, index_topk=0)
    a = glm.decode_step(params, cfg, tok, lens, pool, ipool, narrow)
    b = glm.decode_step(params, dense_cfg, tok, lens, pool, ipool, narrow)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    # the index keys are written all the same, for the context to come
    assert np.array_equal(np.asarray(a[2]), np.asarray(b[2]))
    assert np.asarray(a[2][0, 4, 0]).any()
    c = glm.decode_step(params, cfg, tok, lens, pool, ipool, wide)
    assert np.abs(np.asarray(c[0]) - np.asarray(a[0])).max() < 1e-5
    taps = jax.jit(glm.decode_selections, static_argnums=1)(
        params, cfg, tok, lens, pool, ipool, wide)
    assert _sets(*taps[0]) == [frozenset(range(25))]
    # an admission of index_topk tokens or fewer is the dense path too
    assert not decoder.indexed(cfg, TOPK) and decoder.indexed(cfg, TOPK + 1)


def test_paged_gather_reads_the_rows_the_selection_names():
    pool = jnp.arange(2 * 6 * 4 * 3, dtype=jnp.float32).reshape(2, 6, 4, 3)
    table = jnp.asarray([[5, 2, 0], [1, 3, 4]], jnp.int32)
    idx = jnp.asarray([[0, 7, 5], [11, 4, 1]], jnp.int32)
    got = np.asarray(sparse_select.gather_paged(pool, 1, table, idx))
    want = [[pool[1, 5, 0], pool[1, 2, 3], pool[1, 2, 1]],
            [pool[1, 4, 3], pool[1, 3, 0], pool[1, 1, 1]]]
    assert np.array_equal(got, np.asarray(want))


# -- the engine --------------------------------------------------------------
def test_cold_admission_and_decode_match_the_reference(cfg, params):
    eng = _engine(params, cfg)
    assert eng.k_pages.shape == (5, 160, PAGE, 128)
    assert eng.v_pages.shape == (2, 160, PAGE, 16)       # the index pool
    assert eng._page_objects == 7
    assert eng._page_bytes == (5 * 128 + 2 * 16) * PAGE * 4
    prompt = _prompt(11, 256)
    out = eng.run([_req("a", prompt, 16)])["a"]
    assert len(out) == 16
    assert _worst(eng, params, "a", prompt, out) < TOL
    assert eng.stats["decode_steps"] == 15
    # the counters of the selection: 15 steps at 257 .. 271 live rows,
    # 32 of them TAKEN in each of 5 layers (the device's count, pulled
    # with the tokens), and the 2 owners' scores run over every entry
    # of the 2 slots' page tables (48 pages of 8), live or not
    live = sum(range(257, 272))
    assert eng.stats["attn_rows_live"] == 5 * live
    assert eng.stats["attn_rows_selected"] == 5 * 15 * TOPK
    assert eng.stats["index_keys_scored"] == 15 * 2 * 2 * 48 * PAGE
    assert eng.stats["moe_pairs_routed"] == (256 + 15) * 2 * 4


def test_admission_in_pieces_is_the_admission_in_one(cfg, params):
    """Four pieces of 64 tokens: the later ones score and select over
    the index keys the earlier ones left in the pool."""
    eng = _engine(params, cfg, admit_piece=64)
    prompt = _prompt(13, 250)
    out = eng.run([_req("l", prompt, 6)])["l"]
    assert eng.stats["admit_pieces"] == 4
    assert _worst(eng, params, "l", prompt, out) < TOL
    one = _engine(params, cfg)
    one.run([_req("l", prompt, 6)])
    assert np.abs(np.stack(one.rows["l"])
                  - np.stack(eng.rows["l"])).max() < 1e-4
    free = sorted(eng.free_pages)
    row, hit = eng.first_token_logits(prompt)
    assert hit == 0 and sorted(eng.free_pages) == free
    assert np.abs(row - eng.rows["l"][0]).max() < 1e-4


def test_hit_restores_both_kinds_of_page(cfg, params, shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="glm-hit")
    base = _prompt(21, 248)
    eng.run([_req("base", base, 9)])       # 256 tokens in pages: 32 full
    grown = base + eng.outputs["base"]
    assert eng.stats["offloaded_pages"] == 32
    assert eng.stats["latent_pages_written"] == 5 * 32
    assert eng.stats["index_pages_offloaded"] == 2 * 32
    for n_hit in (1, 4, 5, 17, 32):        # under, at and over index_topk
        tail = _prompt(100 + n_hit, 5)
        prompt = grown[:n_hit * PAGE] + tail
        rid = f"h{n_hit}"
        before = dict(eng.stats)
        out = eng.run([_req(rid, prompt, 8)])[rid]
        assert eng.stats["prefix_hit_pages"] - before["prefix_hit_pages"] \
            == n_hit
        assert eng.stats["latent_pages_restored"] \
            - before["latent_pages_restored"] == 5 * n_hit
        assert eng.stats["index_pages_restored"] \
            - before["index_pages_restored"] == 2 * n_hit
        assert _worst(eng, params, rid, prompt, out) < TOL
    assert eng.stats["store_errors"] == 0
    # ... and in pieces' company: the restored keys go in with the
    # first piece, the later pieces read them from the pool
    prompt = grown[:17 * PAGE] + _prompt(42, 100)
    row_cold, hit0 = _engine(params, cfg).first_token_logits(prompt)
    pieces = _engine(params, cfg, shm_conn, model_id="glm-hit",
                     admit_piece=32)
    row_hit, hit = pieces.first_token_logits(prompt)
    assert hit0 == 0 and hit == 17
    assert np.abs(row_hit - row_cold).max() < 1e-4


def _page_keys(eng, tokens, n, kind):
    digests = serving.content_page_digests(tokens, PAGE, n, eng._ns)
    return serving.content_page_keys_by_page(
        digests, eng.cfg.page_layers(kind), kind)


def test_store_round_trip_of_both_kinds_is_bit_exact(cfg, params, shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="glm-bits")
    prompt = _prompt(31, 40)
    eng.submit(_req("r", prompt, 2))
    eng.step()
    slot = eng.slots[0]
    rows = np.asarray(eng.k_pages[:, slot.page_ids[:5]])    # [5, 5, 8, 128]
    keys = np.asarray(eng.v_pages[:, slot.page_ids[:5]])    # [2, 5, 8, 16]
    assert keys.any()
    eng.run()
    for kind, held, n_layers in (("c", rows, 5), ("i", keys, 2)):
        names = _page_keys(eng, prompt, 5, kind)
        assert len(names) == 5 * n_layers
        back = eng.store.get_kv_pages_host(names, cfg.page_shape(kind),
                                           cfg.jdtype)
        back = np.asarray(back).reshape(5, n_layers, *cfg.page_shape(kind))
        assert np.array_equal(back.swapaxes(0, 1).view(np.uint8),
                              held.view(np.uint8))
    assert _page_keys(eng, prompt, 1, "i") == [
        k.replace("/c", "/i") for k in _page_keys(eng, prompt, 1, "c")
        if "/L0/" in k or "/L4/" in k]


@pytest.mark.parametrize("eviction", [True, False])
def test_a_hit_without_an_index_page_is_trimmed_to_whole_pages(cfg, params,
                                                               eviction):
    """Pages 0-5 of a sequence are in the store with both kinds, pages
    6-7 with their rows alone (their index keys never arrived): a
    prompt that extends all 8 is a hit of the 6 pages that have both.
    The ONE probe asks for both kinds' keys page by page; a store with
    eviction on (the benchmark's and a deployment's) scans to the first
    hole. One without searches by halves, as if presence fell off once,
    and may name a depth whose index page is absent: the restore then
    finds the hole and the admission runs cold, never over a page it
    does not have."""
    from infinistore_tpu import (ClientConfig, InfiniStoreServer,
                                 InfinityConnection, ServerConfig, TYPE_SHM)
    srv = InfiniStoreServer(ServerConfig(
        service_port=0, prealloc_size=0.0625, minimal_allocate_size=16,
        enable_eviction=eviction))
    srv.start()
    conn = InfinityConnection(ClientConfig(
        host_addr="127.0.0.1", service_port=srv.service_port,
        connection_type=TYPE_SHM))
    conn.connect()
    try:
        eng = _engine(params, cfg, conn, model_id="glm-trim")
        prompt = _prompt(51, 70)
        real = eng.store.put_kv_pages

        def drop_late_index_pages(keys, pages, sync=False):
            if keys[0].endswith("/i"):
                keys, pages = keys[:2 * 6], pages[:2 * 6]
            return real(keys, pages, sync=sync)

        eng._put_pages = drop_late_index_pages
        eng.run([_req("w", prompt, 2)])    # 71 cached tokens: 8 full pages
        grown = prompt + eng.outputs["w"]
        assert eng.stats["offloaded_pages"] == 8
        again = grown[:64] + _prompt(52, 9)
        row_cold, _ = _engine(params, cfg).first_token_logits(again)
        row, hit = eng.first_token_logits(again)
        assert hit == 6 if eviction else hit in (0, 6)
        assert np.abs(row - row_cold).max() < 1e-4
        assert eng.stats["store_errors"] == 0
        eng.close()
    finally:
        conn.close()
        srv.stop()


@pytest.mark.parametrize("name,sc,change", [
    ("spec_k", {"spec_k": 2}, {}),
    ("host_steps", {"host_steps": 4}, {}),
    ("quantized_store", {"quantized_store": True}, {}),
    ("first layer that borrows", {},
     {"indexer_kinds": ("shared", "full", "shared", "shared", "full")}),
    ("one entry a layer", {}, {"indexer_kinds": ("full", "shared")}),
    ("hc_mult", {}, {"hc_mult": 2}),
])
def test_what_is_not_built_over_an_index_pool_is_refused(cfg, params, name,
                                                         sc, change):
    with pytest.raises(ValueError, match=name):
        _engine(params, dataclasses.replace(cfg, **change), **sc)


def test_the_reference_in_blocks_is_the_reference_whole(cfg, params,
                                                        monkeypatch):
    """The reference holds the stream as blocks of tokens, scores a
    block of queries and a group of index heads at a time, gathers and
    attends a smaller block at a time and runs an expert over padded
    rows, so that 35k tokens fit beside an engine: with blocks far
    smaller than the prompt it gives the rows it gives whole."""
    prompt = _prompt(61, 200)
    at = [40, 120, 199]
    whole, _ = reference.forward(params, CONF, np.asarray(prompt, np.int32),
                                 at)
    for name, value in (("TOKEN_BLOCK", 64), ("QUERY_BLOCK", 16),
                        ("GATHER_BLOCK", 8), ("ROW_PAD", 8),
                        ("INDEX_HEAD_GROUP", 2)):
        monkeypatch.setattr(reference, name, value)
    jax.clear_caches()
    blocks, _ = reference.forward(params, CONF,
                                  np.asarray(prompt, np.int32), at)
    assert np.abs(np.asarray(blocks) - np.asarray(whole)).max() < 2e-5


def test_the_reference_computes_only_what_the_positions_need(params):
    """Blocks that begin after the last position asked for are never
    run and the last layer runs at the positions alone: a longer
    stream, padded or not, gives the same rows."""
    prompt = _prompt(62, 256)
    at = [40, 100, 127]
    short, m0 = reference.forward(params, CONF,
                                  np.asarray(prompt[:128], np.int32), at)
    padded, m1 = reference.forward(params, CONF,
                                   np.asarray(prompt, np.int32), at)
    assert np.abs(np.asarray(padded) - np.asarray(short)).max() < 2e-5
    assert np.abs(np.asarray(m1) - np.asarray(m0)).max() < 1e-5


def test_the_references_attention_is_every_heads_own_k_and_v_under_a_mask(
        params):
    """The reference attends gathered rows in the absorbed form. The
    published description's form, every head's own K_h = [c Wkb,h |
    k_pe] and V_h = c Wvb,h of EVERY position under the selection's
    mask, gives the same output."""
    rng = np.random.default_rng(5)
    n, s, k = 24, 96, 16
    static = reference._static(CONF)
    layer = params["layers"][1]
    h = jnp.asarray(rng.normal(size=(n, 64)), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(s, 64)), jnp.float32)
    qpos = jnp.asarray(rng.integers(k, s, n), jnp.int32)
    idx = jnp.asarray(np.stack([rng.permutation(int(p) + 1)[:k]
                                for p in qpos]), jnp.int32)
    taken = jnp.asarray(rng.random((n, k)) < 0.8).at[:, 0].set(True)
    c, k_pe = reference._latents(ctx, layer["wkva"], layer["kv_ln"], 0,
                                 static)
    got = reference._attend(h, jnp.concatenate([c, k_pe], axis=-1), idx,
                            taken, layer, qpos, static, 8)
    f32 = jnp.float32
    heads, nope, rope, vd = 4, 16, 8, 16
    cq = reference.common.rms_norm(h @ layer["wqa"].astype(f32),
                                   layer["q_ln"], CONF["rms_norm_eps"])
    q = (cq @ layer["wqb"].astype(f32)).reshape(n, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], reference._rope(
        q[..., nope:], qpos, 8000000)], axis=-1)
    wkvb = layer["wkvb"].astype(f32).reshape(32, heads, nope + vd)
    keys = jnp.concatenate(
        [jnp.einsum("sr,rhd->shd", c, wkvb[..., :nope]),
         jnp.broadcast_to(k_pe[:, None], (s, heads, rope))], axis=-1)
    values = jnp.einsum("sr,rhd->shd", c, wkvb[..., nope:])
    mask = jnp.zeros((n, s), bool).at[jnp.arange(n)[:, None], idx].max(taken)
    sc = jnp.einsum("thd,shd->hts", q, keys) * (nope + rope) ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
    want = jnp.einsum("hts,shd->thd", p, values).reshape(n, heads * vd) \
        @ layer["wo"].astype(f32)
    assert np.abs(np.asarray(want)).max() > 1e-3
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# -- the page contract, by kind, of every family -----------------------------
# family (tools/jaxpr_hashes.py's tiny configurations): per kind of
# page, (the layers that keep it, one page's shape, its bytes in the
# family's default bfloat16), and the store keys of ONE page in the
# order an offload writes them. A change to a family's keys or bytes
# orphans every page a store holds of it.
PAGE_CONTRACT = {
    "llama": ({"k": ((0, 1), (16, 2, 32), 2048),
               "v": ((0, 1), (16, 2, 32), 2048)},
              ["L0/k", "L0/v", "L1/k", "L1/v"]),
    "moe": ({"k": ((0, 1), (16, 2, 32), 2048),
             "v": ((0, 1), (16, 2, 32), 2048)},
            ["L0/k", "L0/v", "L1/k", "L1/v"]),
    "hybrid": ({"k": ((0,), (16, 2, 32), 2048),
                "v": ((0,), (16, 2, 32), 2048)}, ["L0/k", "L0/v"]),
    "smallthinker": ({"k": ((0, 1, 2, 3), (16, 2, 32), 2048),
                      "v": ((0, 1, 2, 3), (16, 2, 32), 2048)},
                     ["L0/k", "L0/v", "L1/k", "L1/v", "L2/k", "L2/v",
                      "L3/k", "L3/v"]),
    "xing": ({"c": ((0, 1, 2), (16, 128), 4096)},
             ["L0/c", "L1/c", "L2/c"]),
    "cohere": ({"k": ((0, 1, 2, 3), (16, 2, 32), 2048),
                "v": ((0, 1, 2, 3), (16, 2, 32), 2048)},
               ["L0/k", "L0/v", "L1/k", "L1/v", "L2/k", "L2/v", "L3/k",
                "L3/v"]),
    "glm": ({"c": ((0, 1, 2, 3, 4), (16, 128), 4096),
             "i": ((0, 4), (16, 16), 512)},
            ["L0/c", "L1/c", "L2/c", "L3/c", "L4/c", "L0/i", "L4/i"]),
    "keye": ({"k": ((0, 1, 2), (16, 2, 32), 2048),
              "v": ((0, 1, 2), (16, 2, 32), 2048),
              "i": ((0, 1, 2), (16, 128), 4096)},
             ["L0/k", "L1/k", "L2/k", "L0/v", "L1/v", "L2/v", "L0/i",
              "L1/i", "L2/i"]),
}


@pytest.mark.parametrize("family", list(PAGE_CONTRACT))
def test_a_pages_keys_and_bytes_by_kind_are_pinned(family):
    import importlib
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "tools"))
    try:
        import jaxpr_hashes
    finally:
        sys.path.pop(0)
    module, config, kw = jaxpr_hashes.FAMILIES[family]
    model = importlib.import_module("infinistore_tpu.models." + module)
    fam = getattr(model, config)(**kw)
    kinds, keys = PAGE_CONTRACT[family]
    assert fam.page_kinds == "".join(kinds)
    assert fam.kv_page_shape() == fam.page_shape(fam.page_kinds[0])
    for kind, (layers, shape, nbytes) in kinds.items():
        assert fam.page_layers(kind) == layers
        assert fam.page_shape(kind) == shape
        assert int(np.prod(shape)) * fam.jdtype.itemsize == nbytes
    # a kind of its own shape or layers is a call of its own, behind
    # the kinds before it; kinds of one shape interleave layer by layer
    first = next(iter(kinds.values()))
    if all(v[:2] == first[:2] for v in kinds.values()):
        got = serving.content_page_keys_by_page(["d"], first[0],
                                                fam.page_kinds)
    else:
        got = [k for kind in fam.page_kinds
               for k in serving.content_page_keys_by_page(
                   ["d"], fam.page_layers(kind), kind)]
    assert got == ["cp/d/" + k for k in keys]


def test_restore_prefix_pages_makes_a_call_a_kind(cfg):
    """decoder.restore_prefix_pages over a family whose kinds differ:
    one store call a kind, page-major over that kind's layers, each
    kind's stack [its layers, pages, *its shape] back."""
    calls = []

    class Store:
        def get_kv_pages(self, keys, shape, dtype):
            calls.append((list(keys), shape))
            n = len(keys)
            return jnp.arange(n * int(np.prod(shape)), dtype=dtype).reshape(
                n, *shape)

    rows, keys = decoder.restore_prefix_pages(
        Store(), cfg, lambda li, kind: decoder.page_keys("s", li, kind, 3),
        3)
    assert [shape for _, shape in calls] == [(PAGE, 128), (PAGE, 16)]
    assert calls[0][0][:6] == [f"s/L{li}/c/p0" for li in range(5)] + [
        "s/L0/c/p1"]
    assert calls[1][0] == [f"s/L{li}/i/p{p}" for p in range(3)
                           for li in (0, 4)]
    assert rows.shape == (5, 3, PAGE, 128) and keys.shape == (2, 3, PAGE, 16)
    # page 1 of the second owner is row 1 * 2 + 1 of the index call
    assert float(keys[1, 1, 0, 0]) == 3 * PAGE * 16
