"""models/evabyte.py and the engine over a folded cache (PR 55): a
window of positions attended exactly, every finished window folded
into one summary row a chunk that takes its pages' place.

Tiny preset on the CPU, float32, seeded weights: window 256, chunk =
page = 16 (16 chunks = ONE summary page a window; the published model
folds 128 pages into 8), 4 heads, 2 layers, 40 "bytes", 2 heads of
logits. The plain reference is benchmark/reference/evabyte_eva.py:
the equations over the whole sequence, no cache, no fold.
"""

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import evabyte_eva as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, evabyte, hf
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 16
W = 256          # the window
PER_W = W // PAGE  # 16 pages of positions a window, ONE of summary rows
CONF = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 4096, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "num_pred_heads": 2,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 40, "window_size": W,
    "random_init": {"phi_gain": 8.0, "mu_gain": 8.0},
}
# Float32 program against the float32 reference on the CPU: the worst
# row seen is 6e-6 at logits of 5 (tolerances_evabyte.json,
# evabyte_cpu_f32); every planted fault reads over 0.07.
TOL = 2e-4


@pytest.fixture(scope="module")
def cfg():
    return hf.evabyte_config_from_hf(types.SimpleNamespace(**CONF),
                                     page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return evabyte.init_params(jax.random.PRNGKey(0), cfg)


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CONF["vocab_size"], n)]


def _ref(params, seq, positions, **kw):
    rows, _ = reference.forward(params, CONF, np.asarray(seq, np.int32),
                                list(positions), **kw)
    return np.asarray(rows)


class Recording(ServingEngine):
    """Keeps every logits row a request's tokens were picked from."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.rows = {}

    def _pick(self, work, row):
        self.rows.setdefault(work.req.request_id, []).append(
            np.array(row, np.float32))
        return int(np.argmax(row))


def _engine(params, cfg, conn=None, model_id="eva", cls=Recording, **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 96)
    sc.setdefault("max_pages_per_seq", 24)
    sc.setdefault("admit_piece", W)
    return cls(params, cfg, ServingConfig(model_id=model_id, **sc),
               store=None if conn is None else TpuKVStore(conn),
               model=evabyte)


def _req(rid, prompt, n, sampled=True):
    """`sampled`: the engine pulls every row (`Recording._pick`) and
    steps synchronously; greedy requests run one step ahead."""
    return Request(rid, prompt, max_new_tokens=n,
                   temperature=1.0 if sampled else 0.0)


def _worst(eng, params, rid, prompt, out):
    seq = list(prompt) + list(out)
    want = _ref(params, seq, range(len(prompt) - 1, len(seq) - 1))
    got = np.stack(eng.rows[rid])
    assert got.shape == want.shape
    return float(np.abs(got - want).max())


def _spans(eng, t0, name):
    return [s for s in profiling.spans(since_ns=t0)
            if s.engine == eng.engine_id and s.name == name]


# -- the model ---------------------------------------------------------------
def test_bridge_reads_every_shaping_key(cfg):
    assert (cfg.fold_window, cfg.fold_chunk, cfg.n_pred_heads) == (W, 16, 2)
    assert cfg.vocab_size == 40 and cfg.head_width == 80
    assert cfg.n_kv_heads == cfg.n_heads == 4 and cfg.norm_plus_one
    assert cfg.fp32_stream and cfg.rope_theta == 100000.0
    assert (cfg.phi_gain, cfg.mu_gain) == (8.0, 8.0)
    assert cfg.page_kinds == "kv" and cfg.kv_page_shape() == (16, 4, 16)
    assert [decoder.cache_rows(cfg, p) for p in (0, 255, 256, 300, 512)] \
        == [0, 255, 16, 60, 32]


@pytest.mark.parametrize("change,word", [
    ({"attention_class": "flash"}, "attention_class"),
    ({"chunk_size": 8}, "chunk_size"),
    ({"window_size": 64}, "window_size"),
    ({"num_key_value_heads": 2}, "kv heads"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_scaling": {"type": "linear"}}, "rope_scaling"),
])
def test_bridge_refuses_what_the_family_does_not_implement(change, word):
    with pytest.raises(NotImplementedError, match=word):
        hf.evabyte_config_from_hf(
            types.SimpleNamespace(**{**CONF, **change}), page_size=PAGE)


@pytest.fixture(scope="module")
def five_windows(cfg, params):
    """The stack window by window over 5 windows and a tail, as an
    admission in pieces runs it, and the reference's one pass: every
    position's logits of BOTH heads."""
    seq = _prompt(1, 5 * W + 23)
    got, _ = evabyte.forward_folded(params, cfg, jnp.asarray([seq]))
    want = _ref(params, seq, range(len(seq)), all_heads=True)
    return np.asarray(got[0]), want


@pytest.mark.parametrize("window", range(6))
def test_the_stack_in_pieces_is_the_reference_at_every_position(
        five_windows, window):
    got, want = five_windows
    assert got.shape == want.shape == (5 * W + 23, 80)
    sl = slice(window * W, (window + 1) * W)
    assert np.abs(got[sl] - want[sl]).max() < TOL
    assert np.abs(want[sl]).max() > 1.0


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_part_of_the_attention_is_seen_by_the_reference(params, fault):
    """Every planted fault moves the last position's logits by far
    more than the comparison's tolerance."""
    seq = _prompt(2, 3 * W + 40)
    want = _ref(params, seq, [len(seq) - 1])
    got = _ref(params, seq, [len(seq) - 1], fault=fault)
    assert np.abs(got - want).max() > 300 * TOL


def test_the_float32_stream_and_the_unit_offset_are_the_models(cfg, params):
    """Both move the logits: a stack that adds its residuals in the
    model's type at bfloat16 differs from the float32 stream, and the
    norm's weight is (1 + g)."""
    seq = jnp.asarray([_prompt(3, 64)])
    row = np.asarray(evabyte.forward_dense(params, cfg, seq)[0][0, -1])
    plain = dataclasses.replace(cfg, norm_plus_one=False)
    other = np.asarray(evabyte.forward_dense(params, plain, seq)[0][0, -1])
    assert np.abs(row - other).max() > 0.1
    bf = dataclasses.replace(cfg, dtype="bfloat16")
    pb = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    wide = evabyte.forward_dense(pb, bf, seq)[0][0, -1]
    narrow = evabyte.forward_dense(
        pb, dataclasses.replace(bf, fp32_stream=False), seq)[0][0, -1]
    assert wide.dtype == narrow.dtype == jnp.float32
    assert float(jnp.abs(wide - narrow).max()) > 0


def test_the_fold_of_a_window_is_the_references_summaries_bit_for_bit(
        cfg, params):
    """Window 1's pages folded in the pools against the reference's
    summaries of those 16 chunks, computed from the sequence's own K
    and V: the same bits in float32, and no other page is touched."""
    shape = (cfg.n_layers, 40, PAGE, cfg.n_kv_heads, cfg.head_dim)
    rng = np.random.default_rng(0)
    k_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=shape), jnp.float32)
    ids = jnp.asarray(rng.permutation(np.arange(1, 40))[:PER_W], jnp.int32)
    static = reference._static(CONF, None)
    k0, v0 = np.asarray(k_pages), np.asarray(v_pages)
    k1, v1 = serving._fold_window(params, cfg, k_pages, v_pages, ids,
                                  model=evabyte)
    k1, v1 = np.asarray(k1), np.asarray(v1)
    for li, layer in enumerate(params["layers"]):
        k = k0[li, np.asarray(ids)].reshape(W, 4, 16)
        v = v0[li, np.asarray(ids)].reshape(W, 4, 16)
        ks, vs = reference.summaries(jnp.asarray(k), jnp.asarray(v),
                                     layer["fold_phi"], layer["fold_mu"],
                                     static)
        assert np.array_equal(k1[li, int(ids[0])],
                              np.asarray(ks).reshape(PAGE, 4, 16))
        assert np.array_equal(v1[li, int(ids[0])],
                              np.asarray(vs).reshape(PAGE, 4, 16))
    keep = np.ones(40, bool)
    keep[int(ids[0])] = False
    assert np.array_equal(k1[:, keep], k0[:, keep])
    assert np.array_equal(v1[:, keep], v0[:, keep])


def _decode_rows(cfg, params, seq, n_prompt, rows_of=None):
    """Window 0 prefilled and folded by hand, then `seq[n_prompt:]`
    decoded a row at a time through the pools; the rows' logits."""
    assert n_prompt > W
    pool = (cfg.n_layers, 64, PAGE, cfg.n_kv_heads, cfg.head_dim)
    k_pages, v_pages = jnp.zeros(pool), jnp.zeros(pool)
    _, kvs = evabyte._forward_stack(params, cfg, jnp.asarray([seq[:W]]))[:2]
    sums = [evabyte.fold(cfg, layer, k, v)
            for layer, (k, v) in zip(params["layers"], kvs)]
    tail = -(-(n_prompt - W) // PAGE) * PAGE
    toks = np.zeros((1, tail), np.int32)
    toks[0, :n_prompt - W] = seq[W:n_prompt]
    _, kv2 = evabyte.prefill_with_prefix(params, cfg, jnp.asarray(toks),
                                         sums, pos0=W - PAGE)
    table = np.zeros((1, 24), np.int32)
    table[0, :1 + tail // PAGE + 2] = np.arange(1, 4 + tail // PAGE)
    for li in range(cfg.n_layers):
        rows = [sums[li]] + [kv2[li]]
        k = jnp.concatenate([r[0][0] for r in rows]).reshape(
            -1, PAGE, cfg.n_kv_heads, cfg.head_dim)
        v = jnp.concatenate([r[1][0] for r in rows]).reshape(k.shape)
        k_pages = k_pages.at[li, 1:1 + k.shape[0]].set(k)
        v_pages = v_pages.at[li, 1:1 + v.shape[0]].set(v)
    out = []
    for p in range(n_prompt, len(seq)):
        lens = jnp.asarray([p if rows_of is None else rows_of(p)],
                           jnp.int32)
        logits, k_pages, v_pages = evabyte.decode_step(
            params, cfg, jnp.asarray([seq[p]], jnp.int32), lens, k_pages,
            v_pages, jnp.asarray(table))
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def test_a_decode_row_is_rotated_at_its_position_not_its_row(
        cfg, params, monkeypatch):
    """Decode steps in window 1 over window 0's summary rows: the new
    row is WRITTEN at its cache row (16 + its offset in the window) and
    ROTATED at its position. The planted fault (the stack handed the
    row as the position, the table as it is) fails by a wide margin."""
    seq = _prompt(5, W + 40)
    want = _ref(params, seq, range(W + 30, W + 40))
    got = _decode_rows(cfg, params, seq, W + 30)
    assert np.abs(got - want).max() < TOL
    monkeypatch.setattr(decoder, "cache_rows", lambda cfg, pos: pos)
    bad = _decode_rows(cfg, params, seq, W + 30,
                       rows_of=lambda p: p - (W - PAGE))
    assert np.abs(bad - want).max() > 300 * TOL


# -- the engine --------------------------------------------------------------
@pytest.mark.parametrize("sampled", [True, False],
                         ids=["synchronous", "one-step-ahead"])
def test_prefill_then_decode_across_a_windows_edge(cfg, params, sampled):
    """A prompt that ends 5 positions short of window 1's end, 12
    tokens decoded: rows before the fold, the fold behind the step
    that wrote the window's last row, rows after it, against the
    reference's full pass."""
    t0 = time.time_ns()
    eng = _engine(params, cfg)
    prompt = _prompt(6, 2 * W - 5)
    out = eng.run([_req("a", prompt, 12, sampled)])["a"]
    assert len(out) == 12
    seq = prompt + out
    want = _ref(params, seq, range(len(prompt) - 1, len(seq) - 1))
    if sampled:
        assert np.abs(np.stack(eng.rows["a"]) - want).max() < TOL
    else:
        assert eng.stats["decode_steps_ahead"] > 0
        assert out == [int(t) for t in want.argmax(-1)]
    folds = _spans(eng, t0, "istpu.cache.fold")
    assert [(s.fields["window"], s.fields["during"], s.fields["pages_in"],
             s.fields["pages_out"]) for s in folds] == [
        (0, "piece", 16, 1), (1, "decode", 16, 1)]
    assert eng.stats["windows_folded"] == 2
    assert eng.stats["fold_pages_freed"] == 30
    assert eng.stats["summary_pages_written"] == 2
    pieces = _spans(eng, t0, "istpu.sched.admit_piece")
    assert [(s.fields["tokens"], s.fields["prefix_pages"],
             s.fields["piece"], s.fields["of"]) for s in pieces] == [
        (W, 0, 1, 2), (W - 5, 1, 2, 2)]
    # rows read against positions live, over the 11 steps and 2 layers
    steps = [s for s in _spans(eng, t0, "istpu.model.decode")
             if "cache_rows" in s.fields]
    assert [s.fields["positions"] for s in steps] == list(
        range(2 * W - 4, 2 * W + 7))
    assert [s.fields["cache_rows"] for s in steps] == [
        PAGE + W - 4 + i for i in range(5)] + [
        2 * PAGE + 1 + i for i in range(6)]
    assert eng.stats["attn_rows_read"] == 2 * sum(
        s.fields["cache_rows"] for s in steps)
    assert eng.stats["attn_positions_live"] == 2 * sum(
        s.fields["positions"] for s in steps)


def test_a_slots_table_after_k_folds_and_the_free_list(cfg, params):
    """After k folds a slot holds k summary pages and its window's
    live pages; every id is the slot's or free, exactly once."""
    eng = _engine(params, cfg, total_pages=64)
    everything = sorted(eng.free_pages)
    eng.submit(_req("a", _prompt(7, 3 * W + 40), 30))
    seen = set()
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        slot = eng.slots[0]
        if slot is None:
            continue
        k = slot.folded
        rows = decoder.cache_rows(cfg, slot.seq_len)
        assert k == slot.seq_len // W
        assert len(slot.page_ids) in (-(-rows // PAGE),
                                      -(-(rows + 1) // PAGE))
        assert len(slot.page_ids) <= k + PER_W
        assert list(eng.page_table[0, :len(slot.page_ids)]) == slot.page_ids
        assert not eng.page_table[0, len(slot.page_ids):].any()
        assert sorted(eng.free_pages + slot.page_ids) == everything
        seen.add(k)
    assert seen == {1, 2, 3} and sorted(eng.free_pages) == everything
    assert eng.stats["windows_folded"] == 3
    assert eng.stats["fold_pages_freed"] == 3 * (PER_W - 1)


def _chain_keys(eng, tokens, n_pages):
    digests = serving.content_page_digests(tokens, PAGE, n_pages, eng._ns)
    return digests


def test_a_hit_after_a_finish_is_the_cold_path_with_both_kinds_restored(
        cfg, params, shm_conn):
    """Turn 1 finishes in window 2: the store gets 2 summary pages and
    the exact pages of window 2 alone. Turn 2 restores both kinds
    through one probe and one restore and gives the cold path's tokens
    and rows."""
    t0 = time.time_ns()
    eng = _engine(params, cfg, shm_conn, model_id="eva-hit")
    base = _prompt(8, 2 * W + 70)
    eng.run([_req("t1", base, 20)])
    n_full = (2 * W + 70 + 19) // PAGE          # 37 pages of positions
    assert eng.stats["summary_pages_offloaded"] == 2
    assert eng.stats["offloaded_pages"] == n_full - 2 * PER_W == 5
    off, = _spans(eng, t0, "istpu.cache.offload")
    assert (off.fields["summary_pages"], off.fields["exact_pages"],
            off.fields["pages"], off.fields["kinds"]) == (2, 5, 7, 2)
    # a folded window's exact pages were never written
    digests = _chain_keys(eng, base + eng.outputs["t1"], n_full)
    exact = serving.content_page_keys_by_page(digests, [0], "k")
    held = [eng.store.cached_prefix_len([k]) for k in exact]
    assert held == [0] * (2 * PER_W) + [1] * 5
    sums = serving.content_page_keys_by_page(
        [digests[PER_W - 1], digests[2 * PER_W - 1]], 2, ("sk", "sv"))
    assert eng.store.cached_prefix_len(sums) == 8

    prompt = base + eng.outputs["t1"] + _prompt(9, 25)
    cold = _engine(params, cfg)
    want = cold.run([_req("t2", prompt, 10)])["t2"]
    t1 = time.time_ns()
    out = eng.run([_req("t2", prompt, 10)])["t2"]
    assert out == want
    assert np.abs(np.stack(eng.rows["t2"])
                  - np.stack(cold.rows["t2"])).max() < 1e-4
    assert _worst(eng, params, "t2", prompt, out) < TOL
    assert eng.stats["prefix_hit_pages"] == n_full
    assert eng.stats["summary_pages_restored"] == 2
    assert eng.stats["exact_pages_restored"] == 5
    assert len(_spans(eng, t1, "istpu.cache.probe")) == 1
    rest, = _spans(eng, t1, "istpu.cache.restore")
    assert (rest.fields["summary_pages"], rest.fields["exact_pages"],
            rest.fields["pages"]) == (2, 5, 7)
    admit, = _spans(eng, t1, "istpu.sched.admit")
    assert admit.fields["hit_pages"] == n_full
    assert admit.fields["cut_to_window_edge"] is False
    # first_token_logits runs the hit an admission runs and gives the
    # pool back
    free = sorted(eng.free_pages)
    row, hit = eng.first_token_logits(prompt)
    assert hit >= n_full and sorted(eng.free_pages) == free
    assert np.abs(row - eng.rows["t2"][0]).max() < 1e-4
    assert eng.stats["store_errors"] == 0


def test_a_hit_across_a_windows_edge_folds_between_its_pieces(
        cfg, params, shm_conn):
    """Turn 1 ends 10 positions short of window 1's end; turn 2's
    suffix is a piece to the edge, the fold, a piece beyond it."""
    t0 = time.time_ns()
    eng = _engine(params, cfg, shm_conn, model_id="eva-cross")
    base = _prompt(10, 2 * W - 30)
    eng.run([_req("t1", base, 21)])              # 2 W - 10 positions held
    prompt = base + eng.outputs["t1"] + _prompt(11, 40)
    t1 = time.time_ns()
    out = eng.run([_req("t2", prompt, 6)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    hit = (2 * W - 10) // PAGE                   # 31 pages of positions
    pieces = _spans(eng, t1, "istpu.sched.admit_piece")
    assert [(s.fields["tokens"], s.fields["prefix_pages"])
            for s in pieces] == [(2 * W - hit * PAGE, 1 + hit - PER_W),
                                 (len(prompt) - 2 * W, 2)]
    fold, = _spans(eng, t1, "istpu.cache.fold")
    assert (fold.fields["window"], fold.fields["during"]) == (1, "piece")
    assert len(_spans(eng, t0, "istpu.cache.fold")) == 2
    # the second finish wrote window 1's summary page and window 2's
    # exact pages; window 1's exact pages beyond the hit, none
    assert eng.stats["summary_pages_offloaded"] == 2
    assert eng.stats["offloaded_pages"] == (hit - PER_W) + (
        len(prompt) + 5 - 2 * W) // PAGE


def _grown(eng, params, cfg, conn, model_id, seed):
    """An engine whose store holds a finished sequence of 2 W + 89
    positions (2 summary pages, 5 exact), and a prompt that extends
    it."""
    eng = _engine(params, cfg, conn, model_id=model_id)
    base = _prompt(seed, 2 * W + 70)
    eng.run([_req("t1", base, 20)])
    seq = base + eng.outputs["t1"]
    return eng, seq, seq + _prompt(seed + 1, 25)


def test_a_hit_whose_exact_pages_were_evicted_is_cut_to_the_windows_edge(
        cfg, params, shm_conn):
    eng, seq, prompt = _grown(eng=None, params=params, cfg=cfg,
                              conn=shm_conn, model_id="eva-cut", seed=20)
    digests = _chain_keys(eng, seq, 37)
    gone = serving.content_page_keys_by_page(digests[2 * PER_W:37], 2, "kv")
    assert shm_conn.delete_keys(gone) == len(gone)
    t1 = time.time_ns()
    out = eng.run([_req("t2", prompt, 6)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    admit, = _spans(eng, t1, "istpu.sched.admit")
    assert admit.fields["hit_pages"] == 2 * PER_W
    assert admit.fields["cut_to_window_edge"] is True
    assert eng.stats["hits_cut_to_window_edge"] == 1
    assert eng.stats["summary_pages_restored"] == 2
    assert eng.stats["exact_pages_restored"] == 0


def test_a_hit_whose_summaries_were_evicted_is_cold(cfg, params, shm_conn):
    eng, seq, prompt = _grown(eng=None, params=params, cfg=cfg,
                              conn=shm_conn, model_id="eva-cold", seed=30)
    digests = _chain_keys(eng, seq, 37)
    gone = serving.content_page_keys_by_page([digests[PER_W - 1]], 2,
                                             ("sk", "sv"))
    assert shm_conn.delete_keys(gone) == len(gone)
    t1 = time.time_ns()
    out = eng.run([_req("t2", prompt, 6)])["t2"]
    assert _worst(eng, params, "t2", prompt, out) < TOL
    admit, = _spans(eng, t1, "istpu.sched.admit")
    assert admit.fields["hit_pages"] == 0
    assert admit.fields["cut_to_window_edge"] is False
    assert eng.stats["prefix_hit_pages"] == 0
    assert eng.stats["hits_cut_to_window_edge"] == 0


def test_a_prompt_longer_than_the_table_in_positions_is_admitted_by_rows(
        cfg, params):
    """24 table entries are 384 positions; a prompt of 1,100 holds at
    most 3 + 16 pages of rows on its way and is admitted; one whose
    ROWS do not fit is refused, and the message says rows."""
    eng = _engine(params, cfg, max_pages_per_seq=24, total_pages=40)
    prompt = _prompt(40, 4 * W + 76)
    assert len(prompt) > 24 * PAGE
    out = eng.run([_req("long", prompt, 5)])["long"]
    assert _worst(eng, params, "long", prompt, out) < TOL
    small = _engine(params, cfg, max_pages_per_seq=15)
    with pytest.raises(ValueError, match="pages of cache rows"):
        small.submit(_req("no", _prompt(41, W + 10), 5))
    # by positions where rows are positions
    from infinistore_tpu.models import llama
    lcfg = llama.LlamaConfig(vocab_size=40, d_model=64, n_layers=1,
                             dtype="float32")
    plain = ServingEngine(llama.init_params(jax.random.PRNGKey(0), lcfg),
                          lcfg, ServingConfig(max_pages_per_seq=4))
    with pytest.raises(ValueError, match="pages of positions"):
        plain.submit(_req("no", _prompt(42, 80), 5))


@pytest.mark.parametrize("name,sc,change", [
    ("spec_k", {"spec_k": 2}, {}),
    ("host_steps", {"host_steps": 4}, {}),
    ("quantized_store", {"quantized_store": True}, {}),
    ("kv_pack", {}, {"kv_pack": 2}),
    ("window", {}, {"window": 64}),
    ("window", {}, {"layer_bands": (64, 0)}),
    ("an admit_piece longer than the window", {"admit_piece": 2 * W}, {}),
])
def test_what_is_not_built_over_a_folded_cache_is_refused_by_name(
        cfg, params, name, sc, change):
    with pytest.raises(ValueError, match=f"{name} is not supported for a "
                                         "model with a folded cache"):
        _engine(params, dataclasses.replace(cfg, **change), **sc)


@pytest.mark.parametrize("change,word", [
    ({"fold_chunk": 8}, "fold_chunk"),
    ({"fold_window": 64}, "fold_window"),
])
def test_a_chunk_that_is_not_the_page_is_refused(cfg, change, word):
    with pytest.raises(ValueError, match=word):
        dataclasses.replace(cfg, **change)


def test_preemption_through_the_store_resumes_a_folded_sequence(
        cfg, params, shm_conn):
    """A pool too small for two sequences: one is swapped out through
    the store (its summary pages and its window's exact pages) and
    resumes as a hit; both match the reference."""
    eng = _engine(params, cfg, shm_conn, model_id="eva-swap",
                  total_pages=21, max_pages_per_seq=20)
    a, b = _prompt(50, W + 20), _prompt(51, W + 10)
    out = eng.run([_req("a", a, 150), _req("b", b, 150)])
    assert eng.stats["preemptions"] >= 1
    assert _worst(eng, params, "a", a, out["a"]) < TOL
    assert _worst(eng, params, "b", b, out["b"]) < TOL
    assert sorted(eng.free_pages) == list(range(1, 21))
