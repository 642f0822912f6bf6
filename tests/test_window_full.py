"""Full and banded attention layers in one model (models/smallthinker.py,
SmallThinker's shape): two kinds of page with lives of their own in the
serving engine, a prefix hit that restores each kind only what its band
needs, many small experts through the sorted dispatch, the router
before attention. Held to the plain float32 reference
(benchmark/reference/smallthinker_moe.py) by LOGITS, at a tiny preset
on the CPU with seeded weights: a band of 4 pages, contexts that pass
it several times.

The engine's rows come from a recording engine (tests/
test_hybrid_state.py has the pattern): every request samples, so every
token goes through `_pick`, which keeps the row and answers with its
argmax. A position whose router margin in the reference is under
MARGIN in some layer is a near-tie that float32 rounding may flip, and
is left out of the comparison (benchmark/lib/correct.py does the
same).
"""

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker_moe as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, hf, llama, moe
from infinistore_tpu.models import smallthinker as st
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 8
BAND = 32            # 4 pages
B = BAND // PAGE
CONF = {
    "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 2,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": BAND,
    "rope_theta": 1500000, "rope_scaling": None,
    "tie_word_embeddings": False, "max_position_embeddings": 4096,
}
L_FULL, L_WIN = 1, 3
TOL = 2e-4
MARGIN = 1e-3


@pytest.fixture(autouse=True)
def sorted_dispatch_above_a_decode_batch(monkeypatch):
    """At this preset's 8 experts the measured threshold would send
    every prompt here through the dense form; with it at 24 tokens the
    prefills run the sorted dispatch, a suffix of 17-24 tokens the
    dense form and the decode steps the gathered kernel, as at the
    published widths."""
    monkeypatch.setattr(moe, "DENSE_EXPERTS_MAX_ROWS", 24 * 8)


@pytest.fixture(scope="module")
def cfg():
    return hf.smallthinker_config_from_hf(types.SimpleNamespace(**CONF),
                                          page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return st.init_params(jax.random.PRNGKey(0), cfg)


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
    """Keeps every logits row a request's tokens were picked from, and
    the most pages a banded layer's short table ever held."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.rows = {}
        self.most_wpages = 0

    def _pick(self, work, row):
        self.rows.setdefault(work.req.request_id, []).append(
            np.array(row, np.float32))
        return int(np.argmax(row))

    def step(self):
        n = super().step()
        for s in self.slots:
            if s is not None:
                self.most_wpages = max(self.most_wpages, len(s.wpage_ids))
        return n


def _engine(params, cfg, conn=None, model_id="wf", cls=Recording, **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 96)
    sc.setdefault("max_pages_per_seq", 32)
    return cls(params, cfg, ServingConfig(model_id=model_id, **sc),
               store=None if conn is None else TpuKVStore(conn), model=st)


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


# -- the model: per-layer spec, early router, the dispatch -----------------
def test_bridge_gives_the_per_layer_spec(cfg):
    assert cfg.layer_windows == (0, BAND, BAND, BAND)
    assert cfg.layer_ropes == (False, True, True, True)
    assert cfg.two_kinds and cfg.window == 0 and cfg.window_band == BAND
    assert [(band, pool, li) for band, _, pool, li in
            decoder.attn_layers(cfg)] == [
        (0, "full", 0), (BAND, "window", 0), (BAND, "window", 1),
        (BAND, "window", 2)]
    # one band on every layer is LlamaConfig.window, one pool
    same = dict(CONF, sliding_window_layout=[1, 1, 1, 1])
    one = hf.smallthinker_config_from_hf(types.SimpleNamespace(**same),
                                         page_size=PAGE)
    assert one.window == BAND and not one.two_kinds and not one.layer_bands
    assert llama.LlamaConfig(window=5).layer_windows == (5, 5)


def test_prefill_matches_the_reference(cfg, params):
    prompt = _prompt(1, 150)            # passes the band 4 times
    toks = np.zeros((1, 152), np.int32)
    toks[0, :150] = prompt
    logits, kvs = st.prefill(params, cfg, jnp.asarray(toks))
    want, clear = _ref(params, prompt, range(150))
    assert clear.sum() > 100
    assert np.abs(np.asarray(logits[0, :150]) - want)[clear].max() < TOL
    assert len(kvs) == 4


@pytest.mark.parametrize("what,change", [
    ("router fed the feed-forward's own input", {"early_router": False}),
    ("rotary on every layer", {"layer_rope": (True,) * 4}),
    ("no rotary", {"layer_rope": (False,) * 4}),
    ("every layer full", {"layer_bands": (0,) * 4, "window": 0}),
    ("every layer banded", {"layer_bands": (BAND,) * 4}),
    ("silu on the gate branch", {"act": "silu"}),
])
def test_each_part_of_the_layer_is_seen_by_the_reference(cfg, params, what,
                                                         change):
    """The comparison is tight enough to see each of the family's own
    parts: the program with that part changed leaves the reference."""
    prompt = _prompt(2, 96)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    want, clear = _ref(params, prompt, range(96))
    ours = np.asarray(st.prefill(params, cfg, toks)[0][0])
    assert np.abs(ours - want)[clear].max() < TOL
    other = dataclasses.replace(cfg, **change)
    wrong = np.asarray(st.prefill(params, other, toks)[0][0])
    assert np.abs(wrong - want)[clear].max() > 100 * TOL, what


def _loop(layer, u, top_idx, gates, act):
    """The per-expert loop: every expert over every token, gated."""
    out = jnp.zeros_like(u)
    for e in range(layer["e_gate"].shape[0]):
        g = jnp.sum(jnp.where(top_idx == e, gates, 0.0), axis=1)
        a = act(u @ layer["e_gate"][e]) * (u @ layer["e_up"][e])
        out = out + (a @ layer["e_down"][e]) * g[:, None]
    return out


@pytest.mark.parametrize("skewed", [False, True])
@pytest.mark.parametrize("T", [1, 16, 1000])
@pytest.mark.parametrize("E,k", [(8, 2), (64, 6)])
def test_sorted_dispatch_equals_the_per_expert_loop(E, k, T, skewed):
    """Both forms of the dispatch against the loop; with a skewed
    router every token goes to the same k experts and nothing is
    dropped (there is no capacity)."""
    d, f = 32, 16
    ks = jax.random.split(jax.random.PRNGKey(E * 1000 + T), 5)
    layer = {"e_gate": jax.random.normal(ks[0], (E, d, f)) * d ** -0.5,
             "e_up": jax.random.normal(ks[1], (E, d, f)) * d ** -0.5,
             "e_down": jax.random.normal(ks[2], (E, f, d)) * f ** -0.5}
    u = jax.random.normal(ks[3], (T, d))
    router = jax.random.normal(ks[4], (d, E))
    if skewed:
        router = jnp.zeros((d, E)).at[:, :k].set(1e3 * jnp.abs(router[:, :k]))
        u = jnp.abs(u)
    _, top_idx, gates = moe.route_top_k(router, u, k)
    if skewed:
        assert set(np.asarray(top_idx).ravel()) == set(range(k))
    want = _loop(layer, u, top_idx, gates, jax.nn.relu)
    for form in (moe.experts_sorted, moe.experts_dense):
        got = form(layer, u, top_idx, gates, jax.nn.relu)
        assert np.abs(np.asarray(got - want)).max() < 1e-4 * max(
            1.0, float(jnp.abs(want).max())), form.__name__


def test_the_token_count_chooses_the_form(cfg, params, monkeypatch):
    seen = []
    for name in ("experts_gathered", "experts_dense", "experts_sorted"):
        form = getattr(moe, name)
        monkeypatch.setattr(moe, name, lambda *a, _f=form, _n=name: (
            seen.append(_n), _f(*a))[1])
    layer = params["layers"][0]
    few_rows = moe.GATHERED_EXPERTS_MAX_ROWS
    assert few_rows == 16 and moe.DENSE_EXPERTS_MAX_ROWS == 24 * cfg.n_experts
    for rows in (few_rows, few_rows + 1, 24, 25):
        x = jnp.ones((1, rows, cfg.d_model))
        out, aux, fetched = moe.sorted_moe_mlp(layer, x, cfg, None, x,
                                               early_router=True)
        assert aux is None and (fetched is None) == (rows > few_rows)
    assert seen == [
        "experts_gathered", "experts_dense", "experts_dense",
        "experts_sorted"]


# -- the engine: two kinds of page -------------------------------------------
def test_two_pools_sized_from_the_band(cfg, params):
    eng = _engine(params, cfg, max_slots=3)
    assert eng.k_pages.shape[:2] == (L_FULL, 96)
    entries = 8                                   # 4 + 2, rounded to 8
    assert eng.wtable.shape == (3, entries)
    assert eng.wk_pages.shape[:2] == (L_WIN, 3 * entries + 1)
    # a hit's probe asks for layer 0's key of a page, as every family
    # of one or two kinds of page did before a third kind came
    assert eng._probe_kinds == [(0, "k")]
    # a model of one kind holds one pool and no short table
    one = ServingEngine(llama.init_params(jax.random.PRNGKey(0),
                                          llama.LlamaConfig()),
                        llama.LlamaConfig())
    assert one.wk_pages is None and one.k_pages.shape[0] == 2


@pytest.mark.parametrize("field,value", [
    ("spec_k", 2), ("host_steps", 4), ("admit_piece", 16),
    ("quantized_store", True)])
def test_what_is_not_built_over_two_kinds_is_refused(cfg, params, field,
                                                     value):
    with pytest.raises(ValueError, match="full and banded"):
        _engine(params, cfg, **{field: value})


def test_cold_admission_and_decode_across_release(cfg, params):
    """A prompt of 4.4 bands, then 70 tokens: the banded layers' table
    fills and sheds twice, and every row agrees with the reference."""
    eng = _engine(params, cfg)
    prompt = _prompt(3, 141)
    out = eng.run([_req("a", prompt, 70)])["a"]
    assert _worst(eng, params, "a", prompt, out) < TOL
    assert eng.stats["window_pages_released"] >= 8
    assert eng.most_wpages <= eng._wtable_w
    assert sorted(eng.wfree) == list(range(1, eng._wpool_pages))
    assert sorted(eng.free_pages) == list(range(1, 96))


def test_two_sequences_side_by_side(cfg, params):
    eng = _engine(params, cfg)
    pa, pb = _prompt(4, 37), _prompt(5, 120)
    outs = eng.run([_req("a", pa, 50), _req("b", pb, 30)])
    assert _worst(eng, params, "a", pa, outs["a"]) < TOL
    assert _worst(eng, params, "b", pb, outs["b"]) < TOL


def test_slots_shed_together_and_not_a_page_an_edge(cfg, params, shm_conn):
    """With room in the short table (a band of 4 pages in a table of 16
    entries: 11 spare, so nothing is shed before some slot has 5 pages
    below its band), two sequences that run side by side shed in ONE
    offload whenever either is due."""
    import dataclasses

    wide = dataclasses.replace(cfg, page_size=4)  # band 8 pages, table 16
    eng = _engine(params, wide, shm_conn, model_id="wf-batched",
                  total_pages=192, max_pages_per_seq=64)
    assert (eng._band_pages, eng._wtable_w, eng._shed_pages) == (8, 16, 3)
    t0 = time.time_ns()
    pa, pb = _prompt(21, 77), _prompt(22, 90)
    eng.run([_req("a", pa, 40), _req("b", pb, 40)])
    shed = [s.fields for s in _spans(eng, t0)
            if s.name == "istpu.cache.offload"
            and s.fields["reason"] == "window"]
    assert shed and all(f["slots"] == 2 for f in shed)
    assert all(f["pages"] >= 3 for f in shed)
    assert len(shed) <= 40 // (3 * 4) + 1
    assert eng.most_wpages <= 16


def _stored(store, eng, tokens, layer, lo, hi):
    """Pages [lo, hi) of `layer` (k then v) from the store, host."""
    digests = eng._digests(tokens, hi)
    out = []
    for kind in "kv":
        keys = [f"cp/{d}/L{layer}/{kind}" for d in digests[lo:hi]]
        out.append(store.get_kv_pages_host(keys, eng.cfg.kv_page_shape(),
                                           eng.cfg.jdtype))
    return out


def test_finish_writes_every_page_of_every_layer_once(cfg, params,
                                                      shm_conn):
    """The store's contract does not change: after a finish every full
    page of EVERY layer is in the store, whichever way it went (a
    banded layer's below the band at admission, shed during decode, or
    at the finish), equal to the dense forward's K and V; and what the
    pools still held went bit for bit."""
    eng = _engine(params, cfg, shm_conn, model_id="wf-contract")
    prompt = _prompt(6, 141)
    eng.submit(_req("a", prompt, 60))
    while len(eng.slots[0].generated if eng.slots[0] else []) < 59:
        eng.step()
    slot = eng.slots[0]
    n_full = slot.seq_len // PAGE
    held_f = np.asarray(eng.k_pages)[0, slot.page_ids[:n_full]]
    w_lo = slot.wbase
    held_w = np.asarray(eng.wv_pages)[2, slot.wpage_ids[:n_full - w_lo]]
    out = eng.run()["a"]
    seq = prompt + out
    assert eng.stats["subfloor_pages_written"] == 18 - B - 1
    assert eng.stats["window_pages_offloaded"] > 0
    toks = jnp.asarray(np.asarray(seq[:n_full * PAGE], np.int32)[None])
    _, kvs = st.prefill(params, cfg, toks)
    for layer in range(4):
        k, v = _stored(eng.store, eng, seq, layer, 0, n_full)
        want_k = np.asarray(kvs[layer][0][0]).reshape(k.shape)
        want_v = np.asarray(kvs[layer][1][0]).reshape(v.shape)
        assert np.abs(k - want_k).max() < 1e-5, layer
        assert np.abs(v - want_v).max() < 1e-5, layer
    k0, _ = _stored(eng.store, eng, seq, 0, 0, n_full)
    assert np.array_equal(k0, held_f)
    _, v3 = _stored(eng.store, eng, seq, 3, w_lo, n_full)
    assert np.array_equal(v3, held_w)


def _first_turn(eng, prompt, n):
    out = eng.run([_req("t1", prompt, n)])["t1"]
    return prompt + out


@pytest.mark.parametrize("ctx,name", [(20, "below"), (27, "at"),
                                      (70, "above"), (141, "far-above")])
def test_a_hit_restores_each_kind_what_its_band_needs(cfg, params,
                                                      shm_conn, ctx, name):
    """Turn 2 over a stored turn 1, whose length is below, at and above
    the band: the hit's rows agree with the reference, and the one
    store call brought exactly 2 x (L_full x P + L_win x (P -
    first_live(P))) blocks."""
    eng = _engine(params, cfg, shm_conn, model_id=f"wf-hit-{name}")
    history = _first_turn(eng, _prompt(7, ctx), 12)
    P = (len(history) - 1) // PAGE  # the last token's K and V never were
    prompt = history + _prompt(8, 13)
    before = dict(eng.stats)
    t0 = time.time_ns()
    out = eng.run([_req("t2", prompt, 40)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    moved = {k: eng.stats[k] - before[k] for k in before}
    assert moved["prefix_hit_pages"] == P
    assert moved["restored_pages"] == 2 * (
        L_FULL * P + L_WIN * (P - first_live(P)))
    assert moved["restore_trimmed_pages"] == first_live(P)
    span, = [s for s in _spans(eng, t0) if s.name == "istpu.cache.restore"]
    assert (span.fields["full_pages"], span.fields["window_pages"],
            span.fields["trimmed_pages"]) == (P, P - first_live(P),
                                              first_live(P))
    assert span.fields["bytes"] == moved["restored_pages"] \
        * cfg.kv_page_bytes()
    assert eng.most_wpages <= eng._wtable_w


def test_a_hit_at_a_boundary_shorter_than_what_was_stored(cfg, params,
                                                          shm_conn):
    """A prompt that shares only the first 9 pages of a stored
    sequence of 19: the hit is 9 pages, valid (a hit at ANY page
    boundary of a stored prefix is), and restores [first_live(9), 9) of
    the banded layers, which the longer sequence wrote at its
    admission without a pool page."""
    eng = _engine(params, cfg, shm_conn, model_id="wf-shorter")
    history = _first_turn(eng, _prompt(9, 141), 12)
    prompt = history[:9 * PAGE] + _prompt(10, 30)
    before = eng.stats["restored_pages"]
    out = eng.run([_req("t2", prompt, 20)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    assert eng.stats["restored_pages"] - before == 2 * (
        L_FULL * 9 + L_WIN * (9 - first_live(9)))


def test_a_long_suffix_over_a_short_hit(cfg, params, shm_conn):
    """A hit of 3 pages under a suffix of 4 bands: the banded layers'
    suffix pages below the band go to the store from the hit program,
    and a third turn hits on them."""
    eng = _engine(params, cfg, shm_conn, model_id="wf-long-suffix")
    history = _first_turn(eng, _prompt(11, 24), 4)
    prompt = history[:24] + _prompt(12, 130)
    before = dict(eng.stats)
    out = eng.run([_req("t2", prompt, 10)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    assert eng.stats["prefix_hit_pages"] - before["prefix_hit_pages"] == 3
    n_pages = -(-len(prompt) // PAGE)
    assert eng.stats["subfloor_pages_written"] \
        - before["subfloor_pages_written"] == n_pages - B - 1 - 3
    third = prompt + out + _prompt(13, 9)
    out3 = eng.run([_req("t3", third, 10)])["t3"]
    assert _worst(eng, params, "t3", third, out3) < TOL
    assert eng.stats["prefix_hit_pages"] - before["prefix_hit_pages"] \
        == 3 + (len(prompt) + len(out) - 1) // PAGE


def test_first_token_logits_on_both_paths(cfg, params, shm_conn):
    """What decides `correct` on the chip dispatches what an admission
    does and admits nothing."""
    eng = _engine(params, cfg, shm_conn, model_id="wf-ftl")
    prompt = _prompt(14, 141)
    row, hit = eng.first_token_logits(prompt)
    want, clear = _ref(params, prompt, [140])
    assert hit == 0 and clear[0]
    assert np.abs(row - want[0]).max() < TOL
    assert eng.stats["subfloor_pages_written"] == 0
    history = _first_turn(eng, prompt, 12)
    second = history + _prompt(15, 13)
    pools = [np.asarray(p) for p in (eng.k_pages, eng.wk_pages)]
    row, hit = eng.first_token_logits(second)
    want, clear = _ref(params, second, [len(second) - 1])
    assert hit == (len(history) - 1) // PAGE and clear[0]
    assert np.abs(row - want[0]).max() < TOL
    assert np.array_equal(pools[0], np.asarray(eng.k_pages))
    assert np.array_equal(pools[1], np.asarray(eng.wk_pages))
    assert sorted(eng.wfree) == list(range(1, eng._wpool_pages))


def test_preemption_and_resume_over_two_kinds(cfg, params, shm_conn):
    """The full pools run out mid-decode: one sequence is swapped out
    through the store (both kinds of page) and resumes as a hit; every
    row of both agrees with the reference."""
    eng = _engine(params, cfg, shm_conn, model_id="wf-preempt",
                  total_pages=28, max_pages_per_seq=24)
    pa, pb = _prompt(16, 90), _prompt(17, 90)
    outs = eng.run([_req("a", pa, 40), _req("b", pb, 40)])
    assert eng.stats["preemptions"] >= 1
    assert len(outs["a"]) == len(outs["b"]) == 40
    assert _worst(eng, params, "a", pa, outs["a"]) < TOL
    assert _worst(eng, params, "b", pb, outs["b"]) < TOL
    assert sorted(eng.wfree) == list(range(1, eng._wpool_pages))


def test_an_evicted_banded_page_is_a_miss_not_an_error(cfg, params,
                                                       shm_conn):
    """The probe walks layer 0's chain; a banded layer's page gone from
    the store turns the hit into a cold admission."""
    eng = _engine(params, cfg, shm_conn, model_id="wf-evicted")
    history = _first_turn(eng, _prompt(18, 70), 10)
    P = (len(history) - 1) // PAGE
    gone = f"cp/{eng._digests(history, P)[P - 1]}/L2/v"
    shm_conn.delete_keys([gone])
    prompt = history + _prompt(19, 13)
    out = eng.run([_req("t2", prompt, 10)])["t2"]
    assert eng.stats["restore_misses"] == 1
    assert _worst(eng, params, "t2", prompt, out) < TOL
    assert sorted(eng.wfree) == list(range(1, eng._wpool_pages))


def test_pages_of_both_pools_survive_the_next_admission(cfg, params,
                                                       shm_conn,
                                                       gated_transfers):
    """One slot, pools with room for one sequence: request a's
    sub-floor pages, the pages it sheds and its finish are on the
    upload queue, and b is admitted into a's slot and a's pages of
    BOTH pools and runs to its own finish, before the upload thread
    has waited for a single transfer. Every page of every layer the
    store then holds of a equals, bit for bit, what an engine that ran
    a alone wrote."""
    pa, pb = _prompt(40, 70), _prompt(41, 60)
    sizes = dict(max_slots=1, total_pages=12)
    eng = _engine(params, cfg, shm_conn, model_id="wf-reuse", **sizes)
    eng.submit(_req("a", pa, 10))
    eng.submit(_req("b", pb, 4))
    full = {"a": set(), "b": set()}
    banded = {"a": set(), "b": set()}
    while eng.finished < 2:
        eng.step()
        slot = eng.slots[0]
        if slot is not None:
            full[slot.work.req.request_id] |= set(slot.page_ids)
            banded[slot.work.req.request_id] |= set(slot.wpage_ids)
        assert eng.outputs == {}
    assert len(full["a"] & full["b"]) >= 6
    assert len(banded["a"] & banded["b"]) >= 2
    assert eng.uploads_pending >= 4  # sub-floor and finish of each
    assert eng.stats["offloaded_pages"] == 0
    gated_transfers.set()
    eng.drain_uploads()
    ref = _engine(params, cfg, shm_conn, model_id="wf-reuse-alone", **sizes)
    out = ref.run([_req("a", pa, 10)])["a"]
    assert out == eng.outputs["a"]
    seq = pa + out
    n = (len(seq) - 1) // PAGE
    assert n == 9
    for layer in range(L_FULL + L_WIN):
        got = _stored(eng.store, eng, seq, layer, 0, n)
        want = _stored(eng.store, ref, seq, layer, 0, n)
        for g, w in zip(got, want):
            assert np.abs(w).max() > 0
            assert np.array_equal(np.asarray(g).view(np.uint8),
                                  np.asarray(w).view(np.uint8)), layer


def test_spans_and_counters_of_two_kinds(cfg, params, shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="wf-spans")
    t0 = time.time_ns()
    prompt = _prompt(20, 141)
    eng.run([_req("a", prompt, 70)])
    spans = _spans(eng, t0)
    admit, = [s for s in spans if s.name == "istpu.sched.admit"]
    assert admit.fields["subfloor_pages"] == 18 - B - 1
    by_reason = {}
    for s in spans:
        if s.name == "istpu.cache.offload":
            by_reason.setdefault(s.fields["reason"], []).append(s)
    assert set(by_reason) == {"subfloor", "window", "finish"}
    # Every writer goes through the one queue: an upload an offload, in
    # the order they were put, with its bytes and its store batches,
    # all on one other thread.
    offs = sorted((s for v in by_reason.values() for s in v),
                  key=lambda s: s.t0_ns)
    ups = sorted((s for s in spans if s.name == "istpu.cache.upload"),
                 key=lambda s: s.t0_ns)
    assert [(u.fields["reason"], u.fields["bytes"], u.fields["puts"])
            for u in ups] == [(o.fields["reason"], o.fields["bytes"],
                               o.fields["puts"]) for o in offs]
    assert len({u.tid for u in ups}) == 1 and ups[0].tid != offs[0].tid
    assert eng.stats["uploads"] == len(ups)
    assert not [s for s in spans if s.parent in {o.id for o in offs}]
    shed = by_reason["window"][0].fields
    assert shed["slots"] == 1 and shed["pages"] >= eng._shed_pages == 1
    assert shed["bytes"] == shed["pages"] * 2 * L_WIN * cfg.kv_page_bytes()
    assert eng.stats["window_pages_offloaded"] == sum(
        s.fields["pages"] for s in by_reason["window"])
    assert eng.stats["window_pages_released"] \
        >= eng.stats["window_pages_offloaded"]
    # What the paged-decode kernel walks, per kind of pool: a full
    # layer every page of the sequence, a banded layer the band's (its
    # short table's lengths count from the table's base).
    decodes = [s for s in spans if s.name == "istpu.model.decode"]
    assert len(decodes) == eng.stats["decode_steps"] == 69

    def live(i):  # decode step i attends n keys
        n = len(prompt) + i + 1
        return (L_FULL * ((n - 1) // PAGE + 1)
                + L_WIN * ((n - 1) // PAGE - (n - BAND) // PAGE + 1))

    assert [d.fields["live_pages"] for d in decodes] == \
        [live(i) for i in range(69)]
    assert eng.stats["attn_pages_live"] == sum(live(i) for i in range(69))
    assert eng.stats["attn_pages_table"] == 69 * eng.sc.max_slots * (
        L_FULL * eng.sc.max_pages_per_seq + L_WIN * eng.wtable.shape[1])
    # What the expert kernel fetches: one row holds a token, so every
    # layer fetches that row's top_k of the experts it holds.
    layers = L_FULL + L_WIN
    assert [d.fields["experts_fetched"] for d in decodes] == \
        [layers * cfg.top_k] * 69
    assert eng.stats["moe_experts_fetched"] == 69 * layers * cfg.top_k
    assert eng.stats["moe_experts_held"] == 69 * layers * cfg.n_experts
