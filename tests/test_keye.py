"""A learned selection over K and V pages (models/keye.py, the language
model of Keye-VL-2.0-30B-A3B): grouped queries with a per-head norm on
q and k, an indexer on EVERY layer, its index keys a THIRD kind of
page, all the experts held. Held to the plain float32 reference
(benchmark/reference/keye_dsa.py) by LOGITS and by the SELECTED SET,
at a tiny preset on the CPU with seeded weights (`topk` 32, so that a
256-token prompt is 8 x it). The recording engine and the near-tie rule
are tests/test_glm.py's.
"""

import dataclasses
import time
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import test_glm
from test_admit_one_row import _equations

from benchmark.reference import keye_dsa as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, hf, keye, moe
from infinistore_tpu.ops import pallas_masked_attention, sparse_select
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 8
TOPK = 32
LAYERS = 3
CONF = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 4096, "max_window_layers": LAYERS,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": LAYERS, "num_key_value_heads": 2,
    "num_local_experts": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": TOPK},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 128,
}
# Float32 program against the float32 reference on the CPU: the worst
# row seen is 2e-6 at logits of 3; 2e-4 is what the other families' CPU
# comparisons hold (tolerances_keye.json, keye_cpu_f32).
TOL = 2e-4
MARGIN = 1e-3
GAP = 1e-5


@pytest.fixture(autouse=True)
def sorted_dispatch_above_a_decode_batch(monkeypatch):
    """As tests/test_glm.py: prefills run the sorted dispatch, decode
    steps the gathered kernel, as at the published widths."""
    monkeypatch.setattr(moe, "DENSE_EXPERTS_MAX_ROWS", 24 * 8)


def _cfg(conf=CONF):
    return hf.keye_config_from_hf(types.SimpleNamespace(**conf),
                                  page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return jax.jit(keye.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CONF["vocab_size"], n)]


def _ref(params, seq, positions, conf=CONF):
    """(the reference's rows, which of them are evidence): not where a
    router's choice is a near-tie in some layer, nor where a layer's
    SELECTION is (tests/test_glm.py has the arithmetic)."""
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


def _engine(params, cfg, conn=None, model_id="keye", cls=Recording, **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 160)
    sc.setdefault("max_pages_per_seq", 48)
    return cls(params, cfg, ServingConfig(model_id=model_id, **sc),
               store=None if conn is None else TpuKVStore(conn), model=keye)


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
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (LAYERS, 64, 8, 2, 16)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.router,
            cfg.n_shared, cfg.holds_share) == (8, 2, 32, "softmax", 0, False)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.index_rope, cfg.index_width) == (4, 16, TOPK, 16, 128)
    assert cfg.indexer_kinds == ("full",) * LAYERS
    assert not cfg.rope_adjacent and not cfg.index_rope_adjacent
    assert cfg.rope_theta == 1e7 and cfg.norm_eps == 1e-6
    assert cfg.layer_kinds == ("attention",) * LAYERS
    assert cfg.page_kinds == "kvi"
    assert [cfg.page_shape(k) for k in "kvi"] == [
        (PAGE, 2, 16), (PAGE, 2, 16), (PAGE, 128)]
    assert all(cfg.page_layers(k) == (0, 1, 2) for k in "kvi")


@pytest.mark.parametrize("n", [TOPK, 200])
def test_prefill_matches_the_reference_and_selects_its_set(cfg, params, n):
    """Under `topk` + 1 tokens (the dense flash path, no selection
    made) and past it (every layer's own selection, as a mask over the
    contiguous rows): the reference's logits; past it, the reference's
    sets at every position clear of a near-tie."""
    prompt = _prompt(5, n)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kvs = jax.jit(keye.prefill, static_argnums=1)(params, cfg, toks)
    assert [tuple(a.shape[2:] for a in kv) for kv in kvs] == [
        ((2, 16), (2, 16), (128,))] * LAYERS
    # an index key is Di lanes of a tile; the others are zero
    assert not np.asarray(kvs[0][2][..., 16:]).any()
    positions = list(range(0, n, 7)) + [n - 1]
    want, clear = _ref(params, prompt, positions)
    assert clear.sum() * 2 >= len(clear)
    assert np.abs(np.asarray(logits)[0, positions] - want)[clear].max() < TOL
    if n <= TOPK:
        assert not decoder.indexed(cfg, n)
        return
    taps = jax.jit(keye.prefill_selections, static_argnums=1)(
        params, cfg, toks)
    theirs = reference.selection(params, CONF, np.asarray(prompt, np.int32),
                                 positions)
    assert len(taps) == LAYERS and sorted(theirs) == list(range(LAYERS))
    checked = 0
    for layer, (idx, taken) in enumerate(taps):
        mine = _sets(idx[0], taken[0])
        for j, p in enumerate(positions):
            if theirs[layer][3][j] < GAP:
                continue
            checked += 1
            assert len(mine[p]) == min(p + 1, TOPK)
            assert mine[p] == _sets(theirs[layer][0][j:j + 1],
                                    theirs[layer][1][j:j + 1])[0]
    assert checked > 2 * len(positions)


@pytest.mark.parametrize("what,change", [
    ("q_norm", lambda layer: [layer.pop(k) for k in ("q_norm", "k_norm")]),
    ("wiw", lambda layer: layer.update(wiw=-layer["wiw"])),
    ("ki_ln_b", lambda layer: layer.update(
        ki_ln_b=layer["ki_ln_b"] + jnp.linspace(-2.0, 2.0, 16))),
    ("router", lambda layer: layer.update(router=layer["router"][:, ::-1])),
])
def test_each_part_of_the_layer_is_seen_by_the_reference(cfg, params, what,
                                                         change):
    """The program with one part of layer 1 changed (its q and k norms
    taken away: `_qkv` applies them where the leaves are; its index
    weights negated; its key norm's bias moved; its router's columns
    reversed) no longer gives the reference's rows of the sound
    weights: the comparison sees each."""
    prompt = _prompt(6, 120)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    # the norms' weights differ from 1, or taking them away moves
    # nothing but the scale a norm would have taken out
    layers = [dict(layer, q_norm=layer["q_norm"] * 1.5) for layer in
              params["layers"]]
    sound = dict(params, layers=layers)
    broken = dict(params, layers=[dict(layer) for layer in layers])
    change(broken["layers"][1])
    positions = [60, 100, 119]
    want, _, _ = reference._run(sound, CONF, np.asarray(prompt, np.int32),
                                positions)
    run = jax.jit(keye.prefill, static_argnums=1)
    got = np.asarray(run(sound, cfg, toks)[0])[0, positions]
    bad = np.asarray(run(broken, cfg, toks)[0])[0, positions]
    assert np.abs(got - np.asarray(want)).max() < 1e-3
    assert np.abs(bad - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("fault", ["recent_rows", "other_layer_keys",
                                   "stale_keys", "half_rows", "twice_rows",
                                   "no_qk_norm"])
def test_the_references_planted_faults_move_its_rows(cfg, params, fault,
                                                     monkeypatch):
    """What benchmark/tools/precision_reading_kvi.py plants in the
    reference (a wrong selection, rows dropped from it or added to it,
    q and k left unnormalised) moves its own rows: a fault that moved
    nothing would be a reading of nothing."""
    prompt = np.asarray(_prompt(8, 160), np.int32)
    layers = [dict(layer, k_norm=layer["k_norm"] * 1.5) for layer in
              params["layers"]]
    weights = dict(params, layers=layers)
    sound, _ = reference.forward(weights, CONF, prompt, [120, 159])
    monkeypatch.setattr(reference, "FAULT", fault)
    planted, _ = reference.forward(weights, CONF, prompt, [120, 159])
    assert np.abs(np.asarray(planted) - np.asarray(sound)).max() > 1e-3


def test_init_gains_reach_three_kinds_of_leaf_and_no_other(cfg):
    wide = dataclasses.replace(cfg, q_init_gain=4.0, o_init_gain=0.25,
                               down_init_gain=0.125)
    key = jax.random.PRNGKey(3)
    a, b = keye.init_params(key, cfg), keye.init_params(key, wide)
    for name, gain in (("q_norm", 4.0), ("wo", 0.25), ("e_down", 0.125)):
        assert np.allclose(np.asarray(b["layers"][1][name]),
                           np.asarray(a["layers"][1][name]) * gain)
    for name in set(a["layers"][1]) - {"q_norm", "wo", "e_down"}:
        assert np.array_equal(np.asarray(a["layers"][1][name]),
                              np.asarray(b["layers"][1][name]))


def test_the_router_is_the_softmax_over_the_chosen_logits():
    """Top-8 of the softmax over 128, renormalised over the 8
    (`norm_topk_prob`), equals the softmax over the 8 largest logits,
    which is what models/moe.py's `route_top_k` computes."""
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    _, top_idx, gates = moe.route_top_k(router, h, 8)
    probs = jax.nn.softmax(h @ router, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, 8)
    assert np.array_equal(np.asarray(top_idx), np.asarray(top_i))
    assert np.allclose(np.asarray(gates),
                       np.asarray(top_p / top_p.sum(-1, keepdims=True)),
                       atol=1e-6)
    assert np.allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)


# -- the selection as a mask -------------------------------------------------
@pytest.mark.parametrize("rows", list(test_glm.SELECTION_ROWS))
def test_the_mask_is_top_ks_set_on_rows_with_ties(rows):
    """Score rows with ties everywhere (tests/test_glm.py's `rows`; the
    few values here under weights of either sign): `taken_mask` names
    exactly the positions `select` takes of the same scores, which are
    a stable sort's (ties to the lower position), and `select` hands
    them over in ascending position."""
    rng = np.random.default_rng(5)
    scores, n_live, k = test_glm.SELECTION_ROWS[rows](rng)
    if rows == "few_values":
        scores *= rng.choice([-1.0, 1.0], (len(scores), 1))
    mask, (idx, taken) = jax.jit(lambda s, n: (
        sparse_select.taken_mask(s, n, k), sparse_select.select(s, n, k))
    )(jnp.asarray(scores), jnp.asarray(n_live))
    mask, idx, taken = np.asarray(mask), np.asarray(idx), np.asarray(taken)
    for r in range(len(scores)):
        want = np.argsort(-scores[r, :n_live[r]],
                          kind="stable")[:min(k, n_live[r])]
        assert np.flatnonzero(mask[r]).tolist() == sorted(
            want.tolist()) == idx[r][taken[r]].tolist()


def test_an_admissions_selection_is_found_without_a_sort(cfg, monkeypatch):
    """No program of the engine's admissions under a selection over K
    and V rows holds a `sort` or a `top_k` (nobody reads an order), nor
    positions; with the tap open the positions are made, of the same
    mask and still without a sort. A decode step's selection (and no
    loop under its `attn.topk`: benchmark/metrics/_scoped_ops.py would
    count a loop's own event beside its children's) and a latent
    admission's: the same."""
    rng = np.random.default_rng(3)
    s, n = 96, 80
    args = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for shape in [(1, n, 4, 16), (1, n, 4), (1, s, 16),
                          (1, n, 8, 16), (1, s, 2, 16), (1, s, 2, 16)]]
    at = jnp.arange(s - n, s)[None]

    def admit(qi, wi, keys, q, k_all, v_all):
        return decoder.kv_selected_prefill(cfg, q, k_all, v_all, qi, wi,
                                           keys, at)

    def primitives(jaxpr):
        return {eqn.primitive.name for eqn in _equations(jaxpr.jaxpr)}

    # (a fresh function a trace: the tap is read while tracing)
    closed = primitives(jax.make_jaxpr(lambda *a: admit(*a))(*args))
    assert not closed & {"sort", "top_k", "scatter", "scatter-add",
                         "cumsum"}
    with decoder.selection_tap([]) as taps:
        opened = primitives(jax.make_jaxpr(lambda *a: admit(*a))(*args))
        (idx, taken), = taps
    assert idx.shape == taken.shape == (1, n, TOPK)
    assert not opened & {"sort", "top_k", "scatter", "scatter-add"}
    for backend in ("cpu", "tpu"):      # the loop's form, the kernel's
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        step = primitives(jax.make_jaxpr(partial(
            sparse_select.select_paged, layer=0, k=TOPK))(
                args[0][0], args[1][0], jnp.zeros((n, 12), jnp.int32),
                jnp.full(n, 90), jnp.zeros((1, 4, PAGE, 16))))
        assert not step & {"sort", "top_k", "scatter", "scatter-add",
                           "while"}
        assert ("pallas_call" in step) == (backend == "tpu")
    monkeypatch.undo()
    seq = primitives(jax.make_jaxpr(partial(sparse_select.select_seq, k=TOPK))(
        args[0][0], args[1][0], args[2][0], at[0]))
    assert not seq & {"sort", "top_k", "scatter", "scatter-add"}


def test_attention_under_the_mask_is_attention_over_the_gathered_rows():
    """The two forms an admission could take (PERF.md, PR 49): every
    row under the selection's mask, and the selected rows gathered.
    The same sums."""
    rng = np.random.default_rng(9)
    s, n, h, g, hd = 96, 10, 8, 2, 16
    q = jnp.asarray(rng.standard_normal((n, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, g, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, g, hd)), jnp.float32)
    scores = jnp.asarray(rng.standard_normal((n, s)), jnp.float32)
    n_live = jnp.asarray(rng.integers(1, s + 1, n), jnp.int32)
    idx, taken = sparse_select.select(scores, n_live, TOPK)
    a = sparse_select.attend_masked(
        q, k, v, sparse_select.taken_mask(scores, n_live, TOPK), 0.25)
    b = sparse_select.attend_grouped(q, k[idx], v[idx], taken, 0.25)
    assert np.abs(np.asarray(a)).max() > 0.1
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5


def _first_tiles_empty(rng):
    """Scores that rise with the position: a query at position t takes
    the LAST 32 of its t + 1 live rows. Rows 0-31 stand at 300 or
    later, so none keeps a key of the first two tiles of 128 (their
    running maximum is NEG through both); rows 32-63 stand under 128
    and keep nothing after the first tile."""
    pos = np.concatenate([rng.integers(300, 512, 32),
                          rng.integers(0, 128, 32)]).astype(np.int32)
    return np.tile(np.arange(512, dtype=np.float32), (64, 1)), pos + 1, 128


def _rows_not_a_tile(rng):
    """300 rows at 128 a tile: the third tile lies across the end."""
    return (rng.standard_normal((64, 300)).astype(np.float32),
            rng.integers(1, 301, 64).astype(np.int32), 128)


def _under_topk(rng):
    """24 queries (not a multiple of a tile of the mask's sublanes) at
    positions under `topk`: every live row is kept."""
    return (rng.standard_normal((24, 200)).astype(np.float32),
            rng.permutation(TOPK)[:24].astype(np.int32) + 1, 128)


MASKED_BLOCKS = {
    "rows_not_a_multiple_of_the_tile": _rows_not_a_tile,
    "first_tiles_hold_nothing_selected": _first_tiles_empty,
    "queries_under_topk_positions": _under_topk,
    "few_values": lambda rng: (
        *(a[:64] for a in test_glm.SELECTION_ROWS["few_values"](rng)[:2]),
        128),
    "ties_at_the_kth_of_35072": lambda rng: (
        *test_glm.SELECTION_ROWS["ties_at_the_kth_of_35072"](rng)[:2], 4096),
}


@pytest.mark.parametrize("block", list(MASKED_BLOCKS))
def test_the_chips_flash_kernel_sums_what_attend_masked_sums(block):
    """`masked_flash_attention` (what `block_attention` runs on a TPU
    backend, here in interpret mode) against `attend_masked` in XLA
    over one block of queries under `taken_mask`'s mask of `block`'s
    scores: the same rows attended, the same sums, and the key tiles
    past the block's last position not run."""
    rng = np.random.default_rng(5)
    scores, n_live, block_k = MASKED_BLOCKS[block](rng)
    k_sel = 2048 if scores.shape[1] > 4096 else TOPK
    n, s = scores.shape
    h, g, hd = 8, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for shape in [(n, h, hd), (s, g, hd), (s, g, hd)])
    mask = sparse_select.taken_mask(jnp.asarray(scores), jnp.asarray(n_live),
                                    k_sel)
    assert np.array_equal(np.asarray(mask).sum(-1),
                          np.minimum(n_live, k_sel))
    want = sparse_select.attend_masked(q, k, v, mask, 0.25)
    by_head = pallas_masked_attention.by_head
    got = pallas_masked_attention.masked_flash_attention(
        q, by_head(k), by_head(v), mask, jnp.asarray(n_live.max()),
        scale=0.25, block_k=block_k, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    run, of = pallas_masked_attention.tiles_run(s, int(n_live.max()), block_k)
    assert run == -(-int(n_live.max()) // block_k) <= of == -(-s // block_k)
    # rows of V past the last live tile are never read: poison them
    if run < of:
        poisoned = v.at[run * block_k:].set(jnp.nan)
        again = pallas_masked_attention.masked_flash_attention(
            q, by_head(k), by_head(poisoned), mask,
            jnp.asarray(n_live.max()), scale=0.25, block_k=block_k,
            interpret=True)
        assert np.array_equal(np.asarray(again), np.asarray(got))


def test_a_padded_block_through_the_kernel_is_the_xla_admission(
        cfg, monkeypatch):
    """`kv_selected_prefill` over 80 queries (a block of 64 and one of
    16 padded up with copies of query 0, `_blocked`) of which the first
    stand under `topk`: the program a chip traces, its kernels
    interpreted, gives what the XLA forms give; the block's count of
    live rows is its LAST query's position + 1."""
    rng = np.random.default_rng(3)
    s, n = 150, 80
    args = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for shape in [(1, n, 4, 16), (1, n, 4), (1, s, 16),
                          (1, n, 8, 16), (1, s, 2, 16), (1, s, 2, 16)]]
    at = jnp.arange(s - 70 - n, s - 70)[None]      # 0 .. 79: under 150

    def admit(qi, wi, keys, q, k_all, v_all):
        return decoder.kv_selected_prefill(cfg, q, k_all, v_all, qi, wi,
                                           keys, at)

    want = np.asarray(admit(*args))
    # the forms a TPU backend traces, their kernels interpreted (steered
    # here: the program has no option for it)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for name in ("masked_flash_attention", "_kth_key_kernel"):
        monkeypatch.setattr(sparse_select, name, partial(
            getattr(sparse_select, name), interpret=True))
    got = np.asarray(admit(*args))
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 1e-5


def test_an_admission_on_a_chip_keeps_its_logits_in_the_kernel(
        cfg, monkeypatch):
    """The jaxpr of `kv_selected_prefill` as a TPU backend traces it
    holds the attention's `pallas_call` under `attn.kernel` and, outside
    it, no float32 array as large as a block's logits [block, H, S]
    (the index scores [block, Hi, S] are half of that here); off the
    chip the logits are there and no kernel is."""
    rng = np.random.default_rng(3)
    s, n = 384, 128
    args = [jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for shape in [(1, n, 4, 16), (1, n, 4), (1, s, 16),
                          (1, n, 8, 16), (1, s, 2, 16), (1, s, 2, 16)]]
    at = jnp.arange(s - n, s)[None]
    logits = sparse_select.QUERY_BLOCK * 8 * s

    def walk(jaxpr, scope, kernels, wide):
        # (an equation's name stack is counted from its own jaxpr's)
        for eqn in jaxpr.eqns:
            here = f"{scope}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                kernels.append(here.rstrip("/").rsplit("/", 1)[-1])
                continue
            wide += [out.aval.shape for out in eqn.outvars
                     if getattr(out.aval, "dtype", None) == jnp.float32
                     and out.aval.size >= logits]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here, kernels, wide)
        return kernels, wide

    def traced():
        return walk(jax.make_jaxpr(
            lambda qi, wi, keys, q, k_all, v_all: decoder.kv_selected_prefill(
                cfg, q, k_all, v_all, qi, wi, keys, at))(*args).jaxpr,
            "", [], [])

    kernels, wide = traced()
    assert not kernels and (64, 2, 4, s) in wide
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernels, wide = traced()
    assert not wide
    assert kernels == ["attn.topk", "attn.kernel"]  # bisection, attention


# -- a decode step -----------------------------------------------------------
def _pools(cfg, kvs, n_tokens, pages):
    kp = jnp.zeros((LAYERS, pages, PAGE, 2, 16))
    vp = jnp.zeros((LAYERS, pages, PAGE, 2, 16))
    ip = jnp.zeros((LAYERS, pages, PAGE, 128))
    n = n_tokens // PAGE
    for li, (k, v, ki) in enumerate(kvs):
        kp = kp.at[li, 1:1 + n].set(k[0, :n * PAGE].reshape(n, PAGE, 2, 16))
        vp = vp.at[li, 1:1 + n].set(v[0, :n * PAGE].reshape(n, PAGE, 2, 16))
        ip = ip.at[li, 1:1 + n].set(ki[0, :n * PAGE].reshape(n, PAGE, 128))
    return kp, (vp, ip)


def test_decode_through_the_cache_is_the_prefill(cfg, params):
    """A decode step over the paged K, V and index keys selects and
    attends what the prefill's last position did: the same logits, the
    same sets, and the new token's three rows in their pools."""
    prompt = _prompt(7, 201)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kvs = keye.prefill(params, cfg, toks)
    kp, vi = _pools(cfg, kvs, 200, 40)
    table = jnp.asarray([list(range(1, 27)) + [0] * 6], jnp.int32)
    lens = jnp.asarray([200], jnp.int32)
    got, kp2, (vp2, ip2), counts = keye.decode_step(
        params, cfg, toks[:, 200], lens, kp, vi, table, fetched=True)
    assert np.abs(np.asarray(got[0] - logits[0, 200])).max() < 1e-4
    for li, (k, v, ki) in enumerate(kvs):
        for pool, new in ((kp2, k), (vp2, v), (ip2, ki)):
            assert np.allclose(np.asarray(pool[li, 26, 0]),
                               np.asarray(new[0, 200]), atol=1e-5)
    # [experts fetched, rows taken, slots run]
    assert list(np.asarray(counts)[1:]) == [LAYERS * TOPK, 1]
    taps = jax.jit(keye.decode_selections, static_argnums=1)(
        params, cfg, toks[:, 200], lens, kp, vi, table)
    theirs = reference.selection(params, CONF, np.asarray(prompt, np.int32),
                                 [200])
    assert len(taps) == LAYERS
    for layer, (idx, taken) in enumerate(taps):
        assert theirs[layer][3][0] >= GAP
        assert _sets(idx, taken) == _sets(*theirs[layer][:2])


@pytest.mark.parametrize("slots", [
    (), (0,), (5,), (1, 6), (0, 2, 3), (0, 2, 4, 5, 7), tuple(range(8))])
def test_every_rung_of_the_ladder_over_k_and_v(cfg, params, slots):
    """A decode batch of 8 slots of which `slots` hold a sequence, at a
    table of 96 keys (3 x `topk`): for every valid slot the logits row
    and each layer's tapped (positions, taken) are what the same step
    gives with every slot valid (the full batch's branch runs
    `select_paged`, the two gathers and `attend_grouped` over the arrays
    as they come); the device's counts are the valid slots' rows taken
    and the least rung of 1, 2, 4, 8 that holds them."""
    rng = np.random.default_rng(3)
    kp = jnp.asarray(rng.standard_normal((LAYERS, 97, PAGE, 2, 16)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((LAYERS, 97, PAGE, 2, 16)),
                     jnp.float32)
    ip = jnp.asarray(rng.standard_normal((LAYERS, 97, PAGE, 128)),
                     jnp.float32)
    table = 1 + np.arange(8 * 12, dtype=np.int32).reshape(8, 12)
    lens = np.asarray([40, 95, 20, 64, 33, 71, 88, 50], np.int32)
    tok = jnp.asarray(rng.integers(0, CONF["vocab_size"], 8), jnp.int32)
    valid = np.isin(np.arange(8), slots)

    def step(valid):
        # as the engine hands an empty slot over: length 0, scratch page
        args = (params, cfg, tok, jnp.asarray(np.where(valid, lens, 0)),
                kp, (vp, ip), jnp.asarray(np.where(valid[:, None], table, 0)))
        logits, _, _, counts = keye.decode_step(*args, fetched=True)
        taps = jax.jit(keye.decode_selections, static_argnums=1)(*args)
        return np.asarray(logits), taps, np.asarray(counts)

    want, want_taps, _ = step(np.ones(8, bool))
    got, taps, counts = step(valid)
    assert len(taps) == LAYERS
    for i in slots:
        assert np.abs(got[i] - want[i]).max() < 1e-5
        for (idx, taken), (widx, wtaken) in zip(taps, want_taps):
            t = np.asarray(taken[i])
            assert np.array_equal(t, np.asarray(wtaken[i]))
            assert t.sum() == min(lens[i] + 1, TOPK)
            assert np.array_equal(np.asarray(idx[i])[t],
                                  np.asarray(widx[i])[t])
    assert np.isfinite(got).all()
    assert counts[1] == LAYERS * sum(min(lens[i] + 1, TOPK) for i in slots)
    assert counts[2] == next(n for n in (1, 2, 4, 8) if n >= len(slots))


def test_under_topk_plus_one_keys_attention_is_the_dense_kernel(cfg, params):
    """A table that holds `topk` keys or fewer (a shape) runs the dense
    paged kernel itself: the same bits as a model without a selection,
    and the index keys are written all the same. Under a wider table a
    sequence of `topk` live tokens or fewer selects every live row."""
    prompt = _prompt(9, TOPK)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    _, kvs = keye.prefill(params, cfg, toks)
    kp, vi = _pools(cfg, kvs, TOPK - PAGE, 12)
    lens = jnp.asarray([TOPK - PAGE], jnp.int32)           # 24 cached
    tok = toks[:, TOPK - PAGE]
    narrow = jnp.asarray([[1, 2, 3, 4]], jnp.int32)        # 32 keys
    wide = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]], jnp.int32)
    dense_cfg = dataclasses.replace(cfg, index_topk=0)
    a = keye.decode_step(params, cfg, tok, lens, kp, vi, narrow)
    b = keye.decode_step(params, dense_cfg, tok, lens, kp, vi, narrow)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert np.asarray(a[2][1][0, 4, 0]).any()
    c = keye.decode_step(params, cfg, tok, lens, kp, vi, wide)
    assert np.abs(np.asarray(c[0]) - np.asarray(a[0])).max() < 1e-5
    taps = jax.jit(keye.decode_selections, static_argnums=1)(
        params, cfg, tok, lens, kp, vi, wide)
    assert _sets(*taps[0]) == [frozenset(range(25))]


def test_paged_gather_reads_k_rows_by_head():
    pool = jnp.arange(2 * 6 * 4 * 2 * 3, dtype=jnp.float32).reshape(
        2, 6, 4, 2, 3)
    table = jnp.asarray([[5, 2, 0], [1, 3, 4]], jnp.int32)
    idx = jnp.asarray([[0, 7, 5], [11, 4, 1]], jnp.int32)
    got = np.asarray(sparse_select.gather_paged(pool, 1, table, idx))
    want = [[pool[1, 5, 0], pool[1, 2, 3], pool[1, 2, 1]],
            [pool[1, 4, 3], pool[1, 3, 0], pool[1, 1, 1]]]
    assert got.shape == (2, 3, 2, 3)
    assert np.array_equal(got, np.asarray(want))


# -- the engine --------------------------------------------------------------
def test_cold_admission_and_decode_match_the_reference(cfg, params):
    eng = _engine(params, cfg)
    v_pool, i_pool = eng.v_pages
    assert eng.k_pages.shape == v_pool.shape == (LAYERS, 160, PAGE, 2, 16)
    assert i_pool.shape == (LAYERS, 160, PAGE, 128)      # the third pool
    assert eng._page_objects == 3 * LAYERS
    assert eng._kind_bytes == [LAYERS * PAGE * 32 * 4] * 2 + [
        LAYERS * PAGE * 128 * 4]
    assert eng._page_bytes == LAYERS * (2 * 32 + 128) * PAGE * 4
    assert eng._probe_kinds == [(2, "k"), (2, "v"), (2, "i")]
    prompt = _prompt(11, 256)
    out = eng.run([_req("a", prompt, 16)])["a"]
    assert len(out) == 16
    assert _worst(eng, params, "a", prompt, out) < TOL
    assert eng.stats["decode_steps"] == 15
    # the counters of the selection: 15 steps at 257 .. 271 live rows,
    # 32 of them TAKEN in each of 3 layers (the device's count, pulled
    # with the tokens), and the 3 indexers' scores run over every entry
    # of the 2 slots' page tables (48 pages of 8), live or not
    live = sum(range(257, 272))
    assert eng.stats["attn_rows_live"] == LAYERS * live
    assert eng.stats["attn_rows_selected"] == LAYERS * 15 * TOPK
    assert eng.stats["index_keys_scored"] == 15 * LAYERS * 2 * 48 * PAGE
    assert eng.stats["select_rows_run"] == eng.stats["select_rows_active"] \
        == 15
    assert eng.stats["moe_experts_held"] == 15 * LAYERS * 8
    assert 15 * LAYERS <= eng.stats["moe_experts_fetched"] \
        <= 15 * LAYERS * 2


def test_the_engine_counts_the_slots_a_steps_selection_ran(cfg, params):
    eng = _engine(params, cfg, max_slots=4, total_pages=200)
    eng._proven = lambda active: False     # a span a step, dispatch to land
    t0 = time.time_ns()
    eng.run([_req(r, _prompt(s, 40), n)
             for r, s, n in (("a", 1, 6), ("b", 2, 10), ("c", 3, 14))])
    steps = [(s.fields["select_rows_active"], s.fields["select_rows_run"],
              s.fields["rows_selected"])
             for s in profiling.spans(since_ns=t0)
             if s.name == "istpu.model.decode" and s.engine == eng.engine_id]
    assert steps == [(3, 4, 3 * LAYERS * TOPK)] * 5 \
        + [(2, 2, 2 * LAYERS * TOPK)] * 4 + [(1, 1, LAYERS * TOPK)] * 4


def test_admission_in_pieces_is_the_admission_in_one(cfg, params):
    """Four pieces of 64 tokens: the later ones score and select over
    the index keys, and attend the K and V rows, the earlier ones left
    in the three pools."""
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


def test_hit_restores_all_three_kinds_of_page(cfg, params, shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="keye-hit")
    base = _prompt(21, 248)
    t0 = time.time_ns()
    eng.run([_req("base", base, 9)])       # 256 tokens in pages: 32 full
    grown = base + eng.outputs["base"]
    assert eng.stats["offloaded_pages"] == 32
    assert eng.stats["index_pages_offloaded"] == LAYERS * 32
    assert eng.stats["latent_pages_written"] == 0
    for n_hit in (1, 4, 5, 17, 32):        # under, at and over topk
        tail = _prompt(100 + n_hit, 5)
        prompt = grown[:n_hit * PAGE] + tail
        rid = f"h{n_hit}"
        before = dict(eng.stats)
        out = eng.run([_req(rid, prompt, 8)])[rid]
        assert eng.stats["prefix_hit_pages"] - before["prefix_hit_pages"] \
            == n_hit
        # a third of the kind-summed count is the index keys'
        assert eng.stats["restored_pages"] - before["restored_pages"] \
            == 3 * LAYERS * n_hit
        assert eng.stats["index_pages_restored"] \
            - before["index_pages_restored"] == LAYERS * n_hit
        assert _worst(eng, params, rid, prompt, out) < TOL
    assert eng.stats["store_errors"] == 0
    spans = {s.name: s.fields for s in profiling.spans(since_ns=t0)
             if s.engine == eng.engine_id}
    assert spans["istpu.cache.offload"]["kinds"] == 3
    assert spans["istpu.cache.restore"]["kinds"] == 3
    assert spans["istpu.cache.restore"]["bytes"] == 32 * eng._page_bytes
    # ... and in pieces' company: the restored pages go in with the
    # first piece, the later pieces read them from the pools
    prompt = grown[:17 * PAGE] + _prompt(42, 100)
    row_cold, hit0 = _engine(params, cfg).first_token_logits(prompt)
    pieces = _engine(params, cfg, shm_conn, model_id="keye-hit",
                     admit_piece=32)
    row_hit, hit = pieces.first_token_logits(prompt)
    assert hit0 == 0 and hit == 17
    assert np.abs(row_hit - row_cold).max() < 1e-4


def _page_keys(eng, tokens, n, kind):
    digests = serving.content_page_digests(tokens, PAGE, n, eng._ns)
    return serving.content_page_keys_by_page(
        digests, eng.cfg.page_layers(kind), kind)


def test_store_round_trip_of_three_kinds_is_bit_exact(cfg, params, shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="keye-bits")
    prompt = _prompt(31, 40)
    eng.submit(_req("r", prompt, 2))
    eng.step()
    slot = eng.slots[0]
    held = {kind: np.asarray(pool[:, slot.page_ids[:5]]) for kind, pool in
            zip("kvi", serving._kind_pools(eng.k_pages, eng.v_pages))}
    assert all(a.any() for a in held.values())
    assert not np.array_equal(held["k"], held["v"])
    eng.run()
    for kind in "kvi":
        names = _page_keys(eng, prompt, 5, kind)
        assert len(names) == 5 * LAYERS
        back = eng.store.get_kv_pages_host(names, cfg.page_shape(kind),
                                           cfg.jdtype)
        back = np.asarray(back).reshape(5, LAYERS, *cfg.page_shape(kind))
        assert np.array_equal(back.swapaxes(0, 1).view(np.uint8),
                              held[kind].view(np.uint8))
    assert "/index16@0.1.2w128" in eng._ns


@pytest.mark.parametrize("kind", ["k", "v", "i"])
def test_a_hit_without_one_kind_of_a_page_is_cut_back(cfg, params, kind):
    """Pages 0-5 of a sequence are in the store with all three kinds,
    pages 6-7 lack `kind` (those never arrived): a prompt that extends
    all 8 is a hit of the 6 pages that are whole. The ONE probe asks
    for the last-written key of every kind page by page, and a store
    with eviction on (the benchmark's and a deployment's) scans to the
    first hole."""
    from infinistore_tpu import (ClientConfig, InfiniStoreServer,
                                 InfinityConnection, ServerConfig, TYPE_SHM)
    srv = InfiniStoreServer(ServerConfig(
        service_port=0, prealloc_size=0.0625, minimal_allocate_size=16,
        enable_eviction=True))
    srv.start()
    conn = InfinityConnection(ClientConfig(
        host_addr="127.0.0.1", service_port=srv.service_port,
        connection_type=TYPE_SHM))
    conn.connect()
    try:
        eng = _engine(params, cfg, conn, model_id="keye-trim")
        prompt = _prompt(51, 70)
        real = eng.store.put_kv_pages

        def drop_late_pages(keys, pages, sync=False):
            if keys[0].endswith("/" + kind):
                keys, pages = keys[:LAYERS * 6], pages[:LAYERS * 6]
            return real(keys, pages, sync=sync)

        eng._put_pages = drop_late_pages
        eng.run([_req("w", prompt, 2)])    # 71 cached tokens: 8 full pages
        grown = prompt + eng.outputs["w"]
        assert eng.stats["offloaded_pages"] == 8
        again = grown[:64] + _prompt(52, 9)
        row_cold, _ = _engine(params, cfg).first_token_logits(again)
        row, hit = eng.first_token_logits(again)
        assert hit == 6
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
    ("kv_pack", {}, {"kv_pack": 2}),
    ("window", {}, {"window": 64}),
    ("hc_mult", {}, {"hc_mult": 2}),
])
def test_what_is_not_built_over_three_pools_is_refused(cfg, params, name, sc,
                                                       change):
    with pytest.raises(ValueError, match=name):
        _engine(params, dataclasses.replace(cfg, **change), **sc)


def test_the_reference_in_blocks_is_the_reference_whole(cfg, params,
                                                        monkeypatch):
    """The reference holds the stream as blocks of tokens, scores a
    block of queries at a time, gathers and attends a smaller one and
    pads an expert's rows: the same rows at other block sizes."""
    prompt = np.asarray(_prompt(17, 150), np.int32)
    positions = [40, 99, 149]
    whole, margins = reference.forward(params, CONF, prompt, positions)
    for name, size in (("TOKEN_BLOCK", 128), ("QUERY_BLOCK", 16),
                       ("GATHER_BLOCK", 8), ("ROW_PAD", 8)):
        monkeypatch.setattr(reference, name, size)
    parts, margins2 = reference.forward(params, CONF, prompt, positions)
    assert np.abs(np.asarray(parts) - np.asarray(whole)).max() < 1e-5
    assert np.abs(np.asarray(margins2) - np.asarray(margins)).max() < 1e-5


def test_the_references_attention_is_plain_gqa_under_a_mask(cfg, params):
    """The reference's gathered attention against every head's scores
    over ALL positions under the selection's mask, written out here."""
    f32 = jnp.float32
    prompt = np.asarray(_prompt(19, 90), np.int32)
    layer = {k: v.astype(f32) for k, v in params["layers"][0].items()}
    static = reference._static(CONF)
    x = reference.common.embed(params, jnp.asarray(prompt))
    with jax.default_matmul_precision("highest"):
        h = reference.common.rms_norm(x, layer["ln1"], 1e-6)
        pos = jnp.arange(90)
        kv = reference._kv(h, layer, 0, static)
        k, v = kv[..., :16], kv[..., 16:]
        ki = reference._index_keys(h, layer["wki"], layer["ki_ln"],
                                   layer["ki_ln_b"], 0, static)
        idx, live, _ = reference._select(h, ki, layer, pos, static, 32)
        assert idx.shape == (90, TOPK)
        got = reference._attend(h, kv, idx, live, layer, pos, static, 16)
        q = (h @ layer["wq"]).reshape(90, 8, 16)
        q = reference._rope(reference.common.rms_norm(
            q, layer["q_norm"], 1e-6), pos, 1e7)
        mask = jnp.zeros((90, 90), bool).at[
            jnp.arange(90)[:, None], idx].max(live)
        assert bool((mask.sum(1) == jnp.minimum(pos + 1, TOPK)).all())
        kk, vv = jnp.repeat(k, 4, axis=1), jnp.repeat(v, 4, axis=1)
        sc = jnp.einsum("thd,shd->hts", q, kk) * 16 ** -0.5
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        want = jnp.einsum("hts,shd->thd", p, vv).reshape(90, 128) \
            @ layer["wo"]
    assert np.abs(np.asarray(want)).max() > 1e-3
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
