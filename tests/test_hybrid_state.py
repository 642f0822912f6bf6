"""Recurrent state beside pages: the hybrid family (models/hybrid.py,
granite-4.0-h's shape) through the decoder stack, the ops of
ops/ssm.py, and the serving engine's second kind of cache, held to the
plain float32 reference (benchmark/reference/granite_hybrid.py) by
LOGITS, at a tiny preset on the CPU with seeded weights.

The engine's rows come from a recording engine: every request samples
(temperature 1), so every token goes through `_pick`, which here keeps
the logits row and answers with its argmax.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_hybrid as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, hf, hybrid
from infinistore_tpu.ops import ssm
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
KINDS = ("mamba", "attention", "mamba", "mamba")
# The published keys at tiny widths: what the bridge and the reference
# both read.
CONF = {
    "vocab_size": 128, "hidden_size": 64, "shared_intermediate_size": 128,
    "intermediate_size": 128, "num_hidden_layers": len(KINDS),
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_types": list(KINDS), "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "num_local_experts": 0,
    "num_experts_per_tok": 0, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 8, "position_embedding_type": "nope",
    "rope_scaling": None, "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "tie_word_embeddings": True,
    "rms_norm_eps": 1e-5, "max_position_embeddings": 4096,
}
with open(os.path.join(
        ROOT, "benchmark/reference/tolerances_granite_hybrid.json")) as f:
    TOL = json.load(f)["granite_hybrid_cpu_f32"]["logit_tol"]


@pytest.fixture(scope="module")
def cfg():
    return hf.hybrid_config_from_hf(types.SimpleNamespace(**CONF),
                                    page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return hybrid.init_params(jax.random.PRNGKey(0), cfg)


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CONF["vocab_size"], n)]


def _ref_rows(params, seq, positions):
    rows, _ = reference.forward(params, CONF, np.asarray(seq, np.int32),
                                list(positions))
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


def _engine(params, cfg, conn=None, model_id="hyb", **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 64)
    sc.setdefault("max_pages_per_seq", 16)
    return Recording(params, cfg, ServingConfig(model_id=model_id, **sc),
                     store=None if conn is None else TpuKVStore(conn),
                     model=hybrid)


def _req(rid, prompt, n):
    return Request(rid, prompt, max_new_tokens=n, temperature=1.0)


def _worst(eng, params, rid, prompt, out):
    """Worst |row - reference| over every token of one request."""
    seq = list(prompt) + list(out)
    want = _ref_rows(params, seq, range(len(prompt) - 1, len(seq) - 1))
    got = np.stack(eng.rows[rid])
    assert got.shape == want.shape
    worst = float(np.abs(got - want).max())
    if os.environ.get("HYBRID_READINGS"):
        print(f"reading: {rid} worst |logit diff| {worst:.3e}")
    return worst


# -- the ops ---------------------------------------------------------------
@pytest.mark.parametrize("s,chunk", [(8, 8), (19, 8), (40, 16), (5, 256)])
def test_chunked_scan_equals_the_sequential_recurrence(s, chunk):
    """ssm.scan against ssm.step token by token, from a state that is
    not zero, over chunk edges and a ragged last chunk."""
    rng = np.random.default_rng(s)
    b, H, P, N = 2, 4, 8, 16
    x = jnp.asarray(rng.normal(size=(b, s, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(b, s, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, size=H), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, N)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, H, P, N)), jnp.float32)
    y, h_end = ssm.scan(h0, x, dt, A, B, C, chunk)
    h, ys = h0, []
    for t in range(s):
        yt, h = ssm.step(h, x[:, t], dt[:, t], A, B[:, t], C[:, t])
        ys.append(yt)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), atol=2e-5)
    np.testing.assert_allclose(h_end, h, atol=2e-5)


def test_a_position_with_dt_zero_leaves_the_state_alone():
    rng = np.random.default_rng(0)
    h0 = jnp.asarray(rng.normal(size=(1, 2, 4, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 6, 2, 4)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(1, 6, 8)), jnp.float32)
    _, h = ssm.scan(h0, x, jnp.zeros((1, 6, 2)), -jnp.ones(2), B, B, 4)
    np.testing.assert_array_equal(h, h0)


def test_conv_step_continues_conv_seq():
    rng = np.random.default_rng(1)
    xbc = jnp.asarray(rng.normal(size=(1, 9, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 12)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=12), jnp.float32)
    whole, _ = ssm.conv_seq(jnp.zeros((1, 3, 12)), xbc, w, bias)
    part, full = ssm.conv_seq(jnp.zeros((1, 3, 12)), xbc[:, :6], w, bias)
    tail = ssm.conv_tail(full, 6, 4)
    np.testing.assert_allclose(part, whole[:, :6], atol=1e-6)
    for t in range(6, 9):
        out, tail = ssm.conv_step(tail, xbc[:, t], w, bias)
        np.testing.assert_allclose(out, whole[:, t], atol=1e-6)


# -- the model against the reference --------------------------------------
def test_dense_forward_matches_the_reference(params, cfg):
    toks = _prompt(0, 50)  # 6 chunks of 8 and a ragged one
    got, kvs = hybrid.forward_dense(params, cfg, jnp.asarray([toks]))
    want = _ref_rows(params, toks, range(50))
    assert len(kvs) == cfg.n_kv_layers == 1
    assert float(np.abs(np.asarray(got[0]) - want).max()) < TOL


def test_bridge_matches_transformers():
    """The equations against the published modeling code itself."""
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    if not hasattr(tr, "GraniteMoeHybridForCausalLM"):
        pytest.skip("transformers has no GraniteMoeHybrid")
    hc = tr.GraniteMoeHybridConfig(**CONF)
    torch.manual_seed(0)
    model = tr.GraniteMoeHybridForCausalLM(hc).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if any(k in name for k in ("A_log", "dt_bias", "norm",
                                       "conv1d.bias")) or name.endswith(".D"):
                p.add_(torch.randn_like(p) * 0.1)
    cfg, params = hf.load_hf_hybrid(model, page_size=PAGE, dtype="float32")
    toks = np.asarray([_prompt(3, 37)])
    with torch.no_grad():
        want = model(torch.tensor(toks, dtype=torch.long)).logits.numpy()
    got, _ = hybrid.forward_dense(params, cfg, jnp.asarray(toks, jnp.int32))
    assert float(np.abs(np.asarray(got) - want).max()) < 1e-5
    ref = _ref_rows(params, toks[0], range(37))
    assert float(np.abs(ref - want[0]).max()) < 1e-5


@pytest.mark.parametrize("n", [13, 16, 17, 31])
def test_padded_prompt_equals_unpadded(params, cfg, n):
    """Padded positions do not advance the state: logits at the last
    real position, the state after it and the state at the last page
    edge are those of the unpadded prompt."""
    toks = _prompt(n, n)
    pad = -(-n // PAGE) * PAGE
    padded = np.zeros((1, pad), np.int32)
    padded[0, :n] = toks
    padded[0, n:] = 77  # a token whose input would move the state
    lp, _, sp = hybrid.prefill(params, cfg, jnp.asarray(padded),
                               s_real=jnp.int32(n))
    lu, _, su = hybrid.prefill(params, cfg, jnp.asarray([toks]))
    np.testing.assert_allclose(lp[0, n - 1], lu[0, n - 1], atol=2e-5)
    edge = n // PAGE * PAGE
    _, _, se = hybrid.prefill(params, cfg, jnp.asarray([toks[:edge]])) \
        if edge else (None, None, None)
    for j in range(cfg.n_state_layers):
        for key in ("h", "conv"):
            np.testing.assert_allclose(sp[j][key], su[j][key], atol=2e-5)
            want = se[j][key] if edge else jnp.zeros_like(sp[j][key])
            np.testing.assert_allclose(sp[j][key + "_b"], want, atol=2e-5)
    # ... and without the mask they would have.
    lw, _, sw = hybrid.prefill(params, cfg, jnp.asarray(padded))
    if pad != n:
        assert float(jnp.abs(sw[0]["h"] - su[0]["h"]).max()) > 1e-3


def test_prefill_then_decode_through_the_state(params, cfg):
    """Model level: prefill, then paged decode steps over the page and
    state pools, against the reference's full forward."""
    toks = _prompt(5, 24)
    n0, slots = 11, 2
    logits, kvs, states = hybrid.prefill(params, cfg,
                                         jnp.asarray([toks[:n0]]))
    assert cfg.kv_pack == 2 and cfg.kv_page_shape() == (PAGE, 1, 32)
    shape = (cfg.n_kv_layers, 8, *cfg.kv_page_shape())
    kp, vp = jnp.zeros(shape), jnp.zeros(shape)
    for li, (k, v) in enumerate(kvs):
        k2, v2 = decoder.kv_to_pages(cfg, k, v)  # [1, pages, page, kv, hd]
        n = k2.shape[1]
        kp = kp.at[li, 1:1 + n].set(k2[0].reshape(n, *cfg.kv_page_shape()))
        vp = vp.at[li, 1:1 + n].set(v2[0].reshape(n, *cfg.kv_page_shape()))
    state = hybrid.state_pools(cfg, slots)
    for j, st in enumerate(states):
        state["h"][j] = state["h"][j].at[1].set(st["h"][0])
        state["conv"][j] = state["conv"][j].at[1].set(st["conv"][0])
    table = np.zeros((slots, 8), np.int32)
    table[1, :4] = [1, 2, 3, 4]
    want = _ref_rows(params, toks, range(n0 - 1, 23))
    worst = float(np.abs(np.asarray(logits[0, -1]) - want[0]).max())
    for i, pos in enumerate(range(n0, 23)):
        logits, kp, vp, state = hybrid.decode_step(
            params, cfg, jnp.asarray([0, toks[pos]], jnp.int32),
            jnp.asarray([0, pos], jnp.int32), kp, vp, jnp.asarray(table),
            state)
        worst = max(worst, float(np.abs(
            np.asarray(logits[1]) - want[i + 1]).max()))
    assert worst < TOL


def test_a_bfloat16_state_would_fail_the_tolerance(params, cfg):
    """The tolerance is tight enough to tell the state's precision: the
    same model with the state kept in bfloat16 leaves it."""
    import dataclasses

    low = dataclasses.replace(cfg, state_dtype="bfloat16")
    toks = _prompt(6, 40)
    _, _, states = hybrid.prefill(params, low, jnp.asarray([toks[:24]]))
    state = [(st["h"].astype(jnp.bfloat16).astype(jnp.float32), st["conv"])
             for st in states]
    _, kvs, _ = hybrid.prefill(params, cfg, jnp.asarray([toks[:24]]))
    got, _, _ = hybrid.prefill_with_prefix(
        params, cfg, jnp.asarray([toks[24:]]), kvs, state=state)
    want = _ref_rows(params, toks, range(24, 40))
    assert float(np.abs(np.asarray(got[0]) - want).max()) > TOL


# -- the engine: two kinds of cache in one manager ------------------------
def test_cold_admission_and_decode_match_the_reference(params, cfg):
    eng = _engine(params, cfg)
    reqs = {"a": _prompt(10, 13), "b": _prompt(11, 24), "c": _prompt(12, 5)}
    out = eng.run([_req(r, p, 12) for r, p in reqs.items()])
    for rid, prompt in reqs.items():
        assert _worst(eng, params, rid, prompt, out[rid]) < TOL
    assert eng.stats["boundary_copies"] > 0
    assert eng.k_pages.shape[0] == 1  # pages for the attention layer alone


def test_hit_is_snapshot_plus_pages_and_equals_the_cold_run(
        params, cfg, shm_conn):
    turn1 = _prompt(20, 21)
    e1 = _engine(params, cfg, shm_conn, "hit")
    out1 = e1.run([_req("t1", turn1, 9)])
    assert e1.stats["offloaded_pages"] == 3  # 29 tokens in cache
    assert e1.stats["snapshots_written"] == 1
    turn2 = turn1 + out1["t1"] + _prompt(21, 6)
    e2 = _engine(params, cfg, shm_conn, "hit")
    out2 = e2.run([_req("t2", turn2, 10)])
    assert e2.stats["prefix_hit_pages"] == 3
    assert e2.stats["snapshots_restored"] == 1
    assert e2.stats["prefill_tokens"] == len(turn2) - 3 * PAGE
    assert _worst(e2, params, "t2", turn2, out2["t2"]) < TOL
    cold = _engine(params, cfg)
    ref = cold.run([_req("t2", turn2, 10)])
    assert out2["t2"] == ref["t2"]
    diff = np.abs(np.stack(e2.rows["t2"]) - np.stack(cold.rows["t2"]))
    assert float(diff.max()) < TOL
    # first_token_logits goes through the same two programs and says
    # the depth that ran.
    turn3 = turn2 + out2["t2"] + _prompt(22, 5)
    row, hit = e2.first_token_logits(turn3)
    assert hit == 5  # e2's own finish wrote pages and a snapshot at 5
    want = _ref_rows(params, turn3, [len(turn3) - 1])[0]
    assert float(np.abs(row - want).max()) < TOL
    row, hit = cold.first_token_logits(turn3)
    assert hit == 0 and float(np.abs(row - want).max()) < TOL
    # Turn 2 asked again: its pages match to depth 4, the snapshots lie
    # at 3 and 5, so the hit is 3 (one probe walked back) and page 4 is
    # prefilled again.
    row, hit = e2.first_token_logits(turn2)
    assert hit == 3 and e2.stats["snapshot_walkbacks"] == 1
    want = _ref_rows(params, turn2, [len(turn2) - 1])[0]
    assert float(np.abs(row - want).max()) < TOL


def test_finish_offload_then_restore_is_bit_exact(params, cfg, shm_conn):
    """What the store hands back is, bit for bit, the boundary copy and
    the pages the engine held."""
    prompt = _prompt(30, 19)
    eng = _engine(params, cfg, shm_conn, "bits")
    # Keep the slot's pools as they were at the finish.
    kept = {}
    finish = eng._finish

    def spy(slot_idx, slot):
        kept["rows"] = np.asarray(serving._state_rows(
            cfg, eng.bstate, jnp.int32(slot_idx)))
        kept["pages"] = [np.asarray(eng.k_pages[:, slot.page_ids[:3]]),
                         np.asarray(eng.v_pages[:, slot.page_ids[:3]])]
        kept["digests"] = list(eng._slot_digests(slot, 3))
        finish(slot_idx, slot)

    eng._finish = spy
    eng.run([_req("r", prompt, 8)])  # 26 tokens in cache: 3 full pages
    store = TpuKVStore(shm_conn)
    rows = store.get_kv_pages_host(
        serving.snapshot_keys(kept["digests"][-1], 0, cfg.n_state_layers),
        (eng._snapshot_row,), np.float32)
    assert rows.tobytes() == kept["rows"].tobytes()
    assert eng._snapshot_bytes == rows.nbytes
    pages = store.get_kv_pages_host(
        serving.content_page_keys_by_page(kept["digests"], 1),
        cfg.kv_page_shape(), np.float32).reshape(3, 1, 2, *cfg.kv_page_shape())
    for kind in (0, 1):
        want = np.moveaxis(kept["pages"][kind], 0, 1)  # [page, layer, ...]
        assert pages[:, :, kind].tobytes() == want.tobytes()
    # A row is whole K pages, so rows lie back to back in the pool.
    assert (eng._snapshot_row * 4) % cfg.kv_page_bytes() == 0


def test_pages_without_their_snapshot_are_no_hit(params, cfg, shm_conn):
    """The snapshot evicted and the pages not: admitted cold and
    counted, never a wrong answer."""
    turn1 = _prompt(40, 17)
    e1 = _engine(params, cfg, shm_conn, "evict")
    out1 = e1.run([_req("t1", turn1, 8)])
    turn2 = turn1 + out1["t1"] + _prompt(41, 4)
    probe = _engine(params, cfg, shm_conn, "evict")
    digests = probe._digests(turn2, 3)
    shm_conn.delete_keys(serving.snapshot_keys(digests[2], 2, 3))
    assert TpuKVStore(shm_conn).cached_prefix_len(
        serving.content_page_keys_by_page(digests, 1)) == 6
    e2 = _engine(params, cfg, shm_conn, "evict")
    out2 = e2.run([_req("t2", turn2, 6)])
    assert e2.stats["snapshot_misses"] == 1
    assert e2.stats["prefix_hit_pages"] == 0
    assert e2.stats["snapshots_restored"] == 0
    assert _worst(e2, params, "t2", turn2, out2["t2"]) < TOL


def test_preempt_and_resume_through_the_store(params, cfg, shm_conn):
    """Out through the store (new full pages + the boundary snapshot),
    back in by the hit path; decoding resumes with the same logits."""
    reqs = {f"r{i}": _prompt(50 + i, 16) for i in range(2)}
    eng = _engine(params, cfg, shm_conn, "preempt", total_pages=8,
                  max_pages_per_seq=8)
    out = eng.run([_req(r, p, 24) for r, p in reqs.items()])
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["snapshots_restored"] >= 1
    assert eng.stats["prefix_hit_pages"] > 0
    for rid, prompt in reqs.items():
        assert len(out[rid]) == 24
        assert _worst(eng, params, rid, prompt, out[rid]) < TOL
    assert sorted(eng.free_pages) == list(range(1, 8))


@pytest.mark.parametrize("option", [
    {"spec_k": 2}, {"host_steps": 4}, {"admit_piece": 16},
    {"quantized_store": True},
])
def test_what_is_not_built_over_state_is_refused(params, cfg, option):
    with pytest.raises(ValueError, match="state layers"):
        ServingEngine(params, cfg, ServingConfig(**option), model=hybrid)


def test_verify_step_refuses_state_layers(params, cfg):
    with pytest.raises(NotImplementedError):
        hybrid.verify_step(params, cfg, jnp.zeros((1, 2), jnp.int32),
                           jnp.zeros(1, jnp.int32), None, None, None)


def test_namespace_names_every_cache_kind(params, cfg):
    import dataclasses

    def ns(c):
        return ServingEngine(params, c, ServingConfig(model_id="m"),
                             model=hybrid)._ns

    base = ns(cfg)
    assert base != ns(dataclasses.replace(cfg, state_dtype="bfloat16"))
    assert "st3x8x16x16+3x160/float32" in base and "/kvl1/" in base


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("position_embedding_type", "rope"),
    ("mamba_n_groups", 2), ("attention_bias", True),
    ("mamba_proj_bias", True), ("tie_word_embeddings", False),
    ("layer_types", ["mamba", "window", "mamba", "mamba"]),
    ("normalization_function", "layernorm"),
])
def test_bridge_refuses_what_it_does_not_implement(key, value):
    conf = dict(CONF, **{key: value})
    with pytest.raises(NotImplementedError):
        hf.hybrid_config_from_hf(types.SimpleNamespace(**conf))


# -- the upload thread and who owns a finished slot's pages ---------------
def test_pages_and_snapshot_of_a_finished_slot_survive_the_next_admission(
        params, cfg, shm_conn, gated_transfers):
    """One slot, a pool with room for one sequence: request a finishes
    and b is admitted into ITS slot, ITS pool pages and ITS row of the
    boundary copies, and runs to its own finish, all before the upload
    thread has waited for a single transfer of a's offload. What the
    store then holds of a (pages and snapshot) equals, bit for bit,
    what an engine that ran a alone wrote."""
    pa, pb = _prompt(70, 3 * PAGE + 2), _prompt(71, 4 * PAGE + 1)
    eng = _engine(params, cfg, shm_conn, "reuse", max_slots=1,
                  total_pages=8)
    eng.submit(_req("a", pa, PAGE))
    eng.submit(_req("b", pb, 4))
    held_by = {"a": set(), "b": set()}
    while eng.finished < 2:
        eng.step()
        if eng.slots[0] is not None:
            held_by[eng.slots[0].work.req.request_id] |= set(
                eng.slots[0].page_ids)
        assert eng.outputs == {}
    assert len(held_by["a"] & held_by["b"]) >= 3
    assert eng.uploads_pending == 2 and not eng.stats["snapshots_written"]
    gated_transfers.set()
    eng.drain_uploads()
    assert eng.stats["snapshots_written"] == 2
    ref = _engine(params, cfg, shm_conn, "reuse-alone", max_slots=1,
                  total_pages=8)
    out = ref.run([_req("a", pa, PAGE)])["a"]
    assert out == eng.outputs["a"]
    seq = pa + out
    n = (len(seq) - 1) // PAGE
    assert n == 4
    store = eng.store
    read = {}
    for e in (eng, ref):
        digests = e._digests(seq, n)
        pages = store.get_kv_pages_host(
            serving.content_page_keys_by_page(digests, cfg.n_kv_layers),
            cfg.kv_page_shape(), cfg.jdtype)
        snap = store.get_kv_pages_host(
            serving.snapshot_keys(digests[-1], 0, cfg.n_state_layers),
            (e._snapshot_row,), cfg.state_jdtype)
        read[e] = (np.asarray(pages), np.asarray(snap))
    assert np.abs(read[ref][0]).max() > 0 and np.abs(read[ref][1]).max() > 0
    for got, want in zip(read[eng], read[ref]):
        assert got.shape == want.shape
        assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


# -- spans and counters ----------------------------------------------------
def test_spans_and_counters_of_the_state_cache(params, cfg, shm_conn):
    turn1 = _prompt(60, 23)
    t0 = profiling.time.time_ns()
    e1 = _engine(params, cfg, shm_conn, "spans")
    out1 = e1.run([_req("t1", turn1, 12)])
    e2 = _engine(params, cfg, shm_conn, "spans")
    e2.run([_req("t2", turn1 + out1["t1"] + _prompt(61, 3), 4)])
    ring = [s for s in profiling.spans(t0)
            if s.engine in (e1.engine_id, e2.engine_id)]
    by = {}
    for s in ring:
        by.setdefault(s.name, []).append(s)
    snap = e1._snapshot_bytes
    assert snap == 3 * e1._snapshot_row * 4
    off = [s for s in by["istpu.cache.offload"] if s.engine == e1.engine_id]
    assert off[0].fields["snapshot_bytes"] == snap
    assert off[0].fields["bytes"] == off[0].fields["pages"] \
        * e1._page_bytes + snap
    out = by["istpu.cache.state_out"][0]
    assert out.parent == off[0].id and out.fields["bytes"] == snap
    # The engine thread dispatches the two gathers (pages, snapshot)
    # and waits for neither; the upload thread has the two transfers
    # and store batches and the one sync.
    assert off[0].fields["puts"] == 2
    assert not [s for s in ring if s.parent == out.id]
    upl = [s for s in by["istpu.cache.upload"] if s.engine == e1.engine_id]
    assert len(upl) == 1 and upl[0].tid != off[0].tid
    assert upl[0].fields["bytes"] == off[0].fields["bytes"]
    kids = sorted((s for s in ring if s.parent == upl[0].id),
                  key=lambda s: s.t0_ns)
    assert [k.name for k in kids] == [
        "istpu.xfer.d2h", "istpu.store.allocate", "istpu.store.write"] * 2 \
        + ["istpu.cache.offload_sync"]
    assert kids[3].fields["bytes"] == snap  # the snapshot's transfer
    assert kids[4].fields["keys"] == cfg.n_state_layers
    rest = by["istpu.cache.restore"][0]
    assert rest.fields["snapshot_bytes"] == snap
    assert rest.fields["bytes"] == rest.fields["pages"] * e2._page_bytes \
        + snap
    assert by["istpu.cache.state_in"][0].parent == rest.id
    copies = by["istpu.cache.snapshot"]
    assert {s.fields["reason"] for s in copies} == {"boundary"}
    assert all(s.fields["pos"] % PAGE == 0 for s in copies)
    assert len([s for s in copies if s.engine == e1.engine_id]) \
        == e1.stats["boundary_copies"] == 2  # 23 -> 34 tokens: 24 and 32
    prefills = {s.fields["program"]: s for s in by["istpu.model.prefill"]}
    assert prefills["cold"].fields["chunks"] == 3   # 24 padded / chunk 8
    assert prefills["prefix"].fields["chunks"] == 1
    assert (e1.stats["snapshots_written"], e2.stats["snapshots_restored"],
            e2.stats["snapshot_misses"]) == (1, 1, 0)
    # _page_bytes comes from the pools the engine holds.
    assert e1._page_bytes == 2 * 1 * PAGE * 2 * 16 * 4  # packed or not
