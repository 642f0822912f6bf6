"""A latent cache, several residual streams, a sigmoid router with a
shared expert (models/xing.py, Xing4.0's shape): ONE page a layer in
the serving engine, admission in pieces, the absorbed decode kernel.
Held to the plain float32 reference (benchmark/reference/
xing_latent.py) by LOGITS, at a tiny preset on the CPU with seeded
weights. The recording engine and the near-tie rule are
tests/test_window_full.py's.
"""

import dataclasses
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing_latent as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, hf, moe, xing
from infinistore_tpu.ops import pallas_latent_attention as pla
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 8
CONF = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "max_position_embeddings": 4096,
    "model_type": "xing4_0", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 128,
}
# Float32 program against the float32 reference on the CPU: the worst
# row seen is 6e-6 at logits of 4; 2e-4 is what the other families'
# CPU comparisons hold (tolerances_xing.json, xing_cpu_f32).
TOL = 2e-4
MARGIN = 1e-3


@pytest.fixture(autouse=True)
def sorted_dispatch_above_a_decode_batch(monkeypatch):
    """As tests/test_window_full.py: prefills run the sorted dispatch,
    a suffix of 17-24 tokens the dense form, decode steps the gathered
    kernel, as at the published widths."""
    monkeypatch.setattr(moe, "DENSE_EXPERTS_MAX_ROWS", 24 * 8)


@pytest.fixture(scope="module")
def cfg():
    return hf.xing_config_from_hf(types.SimpleNamespace(**CONF),
                                  page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return xing.init_params(jax.random.PRNGKey(0), cfg)


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


def _engine(params, cfg, conn=None, model_id="lat", cls=Recording, **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 96)
    sc.setdefault("max_pages_per_seq", 32)
    return cls(params, cfg, ServingConfig(model_id=model_id, **sc),
               store=None if conn is None else TpuKVStore(conn), model=xing)


def _req(rid, prompt, n):
    return Request(rid, prompt, max_new_tokens=n, temperature=1.0)


def _worst(eng, params, rid, prompt, out):
    seq = list(prompt) + list(out)
    want, clear = _ref(params, seq, range(len(prompt) - 1, len(seq) - 1))
    got = np.stack(eng.rows[rid])
    assert got.shape == want.shape
    assert clear.sum() * 2 >= len(clear), clear
    return float(np.abs(got - want)[clear].max())


def _spans(eng, t0, name):
    return [s for s in profiling.spans(since_ns=t0)
            if s.engine == eng.engine_id and s.name == name]


# -- the model ---------------------------------------------------------------
def test_bridge_reads_every_shaping_key(cfg):
    assert cfg.layer_kinds == ("latent",) * 3 and cfg.n_kv_layers == 3
    assert cfg.page_kinds == "c" and cfg.hc_mult == 4 and cfg.hc_iters == 20
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope, cfg.qk_rope,
            cfg.v_dim) == (48, 32, 16, 8, 16)
    assert cfg.latent_width == 128 and cfg.kv_page_shape() == (PAGE, 128)
    assert cfg.kv_page_bytes() == PAGE * 128 * 4
    assert cfg.yarn == (8.0, 32, 32.0, 1.0, 1.0, 1.0)
    assert (cfg.n_dense_lead, cfg.ffn_dense, cfg.n_experts, cfg.top_k,
            cfg.n_shared, cfg.route_scale, cfg.router) == (
        1, 128, 8, 2, 1, 2.0, "sigmoid")
    wide = dataclasses.replace(cfg, kv_lora_rank=512, qk_rope=64)
    assert wide.latent_width == 640       # 576 up to a lane tile
    assert [(band, pool, li) for band, _, pool, li in
            decoder.attn_layers(cfg)] == [(0, "full", i) for i in range(3)]


def test_prefill_matches_the_reference(cfg, params):
    prompt = _prompt(1, 150)
    toks = np.zeros((1, 152), np.int32)
    toks[0, :150] = prompt
    logits, kvs = xing.prefill(params, cfg, jnp.asarray(toks))
    want, clear = _ref(params, prompt, range(150))
    assert clear.sum() > 100
    assert np.abs(np.asarray(logits[0, :150]) - want)[clear].max() < TOL
    assert len(kvs) == 3 and kvs[0][0].shape == (1, 152, 128)
    assert kvs[0][1] is None            # ONE page a layer, no V
    # lanes past kv_lora_rank + qk_rope are zero: the row's padding
    assert not np.asarray(kvs[0][0][..., 40:]).any()


@pytest.mark.parametrize("what,change", [
    ("2 Sinkhorn iterations", {"hc_iters": 2}),
    ("no clamp to speak of", {"hc_clamp": 0.5}),
    ("the shared expert left out", {"n_shared": 0}),
    ("gates summing to 1", {"route_scale": 1.0}),
    ("softmax over the chosen logits", {"router": "softmax"}),
    ("plain rotary", {"yarn": ()}),
    ("no mscale on the softmax scale",
     {"yarn": (8.0, 32, 32.0, 1.0, 1.0, 0.0)}),
])
def test_each_part_of_the_layer_is_seen_by_the_reference(cfg, params, what,
                                                         change):
    """The comparison is tight enough to see each of the family's own
    terms: the program with that term shortened or left out leaves the
    reference."""
    prompt = _prompt(2, 96)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    # coefficients far from the identity's logit (a = 1, not 0.01), so
    # that the stream mixer has work to do
    params = dict(params, layers=[
        {k: dict(v, a=jnp.ones(3)) if k.startswith("hc_") else v
         for k, v in layer.items()} for layer in params["layers"]])
    want, clear = _ref(params, prompt, range(96))
    ours = np.asarray(xing.prefill(params, cfg, toks)[0][0])
    assert np.abs(ours - want)[clear].max() < TOL
    other = dataclasses.replace(cfg, **change)
    wrong = np.asarray(xing.prefill(params, other, toks)[0][0])
    assert np.abs(wrong - want)[clear].max() > 100 * TOL, what


def test_hres_is_doubly_stochastic_after_20_iterations_not_after_2(cfg,
                                                                   params):
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 4, 64)) * 3.0
    # logits spread by the streams alone (a = 0.5, no bias): a dense
    # positive matrix, which 20 iterations balance to float32's grain.
    # (Near the identity's logit, where `init_params` starts, Sinkhorn
    # is slow: 20 iterations leave the columns 3e-4 off, and the
    # reference runs the same 20.)
    hc = dict(params["layers"][1]["hc_attn"], a=jnp.full((3,), 0.5),
              bias=jnp.zeros(24))
    pre, post, res = decoder.hc_coef(hc, x, cfg)
    assert res.shape == (1, 40, 4, 4) and res.dtype == jnp.float32
    assert np.abs(np.asarray(res).sum(-1) - 1).max() < 1e-4
    assert np.abs(np.asarray(res).sum(-2) - 1).max() < 1e-4
    assert (np.asarray(pre) > 0).all() and (np.asarray(pre) < 1).all()
    assert (np.asarray(post) > 0).all() and (np.asarray(post) < 2).all()
    _, _, short = decoder.hc_coef(hc, x, dataclasses.replace(cfg,
                                                             hc_iters=2))
    assert np.abs(np.asarray(short).sum(-2) - 1).max() > 1e-3


def test_one_stream_is_the_residual_every_family_has():
    one = xing.XingConfig(hc_mult=1)
    x = jnp.ones((1, 3, 8))
    assert decoder.stream_open(one, x) is x
    assert decoder.stream_in(one, {}, x, "attn") == (x, None)
    assert decoder.stream_close(one, x) is x
    assert np.array_equal(decoder.residual(one, x, 2 * x), 3 * x)


def test_sigmoid_router_by_hand():
    """8 experts, 2 a token. The bias changes the choice and not the
    gate; the gates sum to the scale."""
    h = jnp.eye(8, dtype=jnp.float32)[:2] * 4.0     # token t excites row t
    router = jnp.asarray(np.array([
        [2.0, 1.0, 0.5, 0, 0, 0, 0, -1.0],
        [0.0, 0.0, 0.0, 1.5, 1.0, -2.0, 0, 0]] + [[0.0] * 8] * 6,
        np.float32))
    sig = lambda z: 1 / (1 + np.exp(-z))            # noqa: E731
    _, idx, gates = moe.route_sigmoid(router, jnp.zeros(8), h, 2, 2.0)
    assert sorted(np.asarray(idx[0])) == [0, 1]     # logits 8 and 4
    assert sorted(np.asarray(idx[1])) == [3, 4]     # logits 6 and 4
    g0 = 2 * sig(8.0) / (sig(8.0) + sig(4.0))
    assert np.allclose(sorted(np.asarray(gates[0]))[::-1],
                       [g0, 2 - g0], atol=1e-6)
    assert np.allclose(np.asarray(gates).sum(-1), 2.0, atol=1e-6)
    # a bias on expert 7 (score sigmoid(-4)) makes token 0 choose it;
    # its gate is its SCORE's share, not the biased score's
    bias = jnp.zeros(8).at[7].set(1.0)
    _, idx_b, gates_b = moe.route_sigmoid(router, bias, h, 2, 2.0)
    assert sorted(np.asarray(idx_b[0])) == [0, 7]
    g7 = 2 * sig(-4.0) / (sig(8.0) + sig(-4.0))
    assert np.allclose(sorted(np.asarray(gates_b[0])), [g7, 2 - g7],
                       atol=1e-6)
    # ... and token 1 too (score 0.5 + 1 over sigmoid(4)), beside 3
    assert sorted(np.asarray(idx_b[1])) == [3, 7]


def test_shared_expert_is_counted_once(cfg, params):
    layer = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    both, *_ = moe.sorted_moe_mlp(layer, x, cfg, None)         # dense form
    routed, *_ = moe.sorted_moe_mlp(layer, x, dataclasses.replace(
        cfg, n_shared=0), None)
    u = decoder.rms_norm(x, layer["ln2"], cfg.norm_eps).reshape(24, 64)
    shared = moe.shared_expert(layer, u, jax.nn.silu).reshape(1, 24, 64)
    assert np.abs(np.asarray(both - routed - shared)).max() < 1e-5
    assert np.abs(np.asarray(shared)).max() > 1e-2
    # the forms of the dispatch agree with the shared expert on: the
    # gathered kernel over two rows (which fetches their 2 x top_k
    # experts at most, and never counts the shared one), the sorted
    # dispatch over 25
    few, _, fetched = moe.sorted_moe_mlp(layer, x[:, :2], cfg, None)
    assert np.abs(np.asarray(few - both[:, :2])).max() < 1e-5
    assert cfg.top_k <= int(fetched) <= 2 * cfg.top_k
    more = jnp.concatenate([x, x[:, :1]], axis=1)
    many, *_ = moe.sorted_moe_mlp(layer, more, cfg, None)
    assert np.abs(np.asarray(many[:, :24] - both)).max() < 1e-5


# -- the decode kernel and the two attention paths ---------------------------
@pytest.mark.parametrize("lens", [(37, 5, 0, 64), (1, 16, 8, 33)])
def test_kernel_matches_its_xla_form(lens):
    """Ragged lengths, an empty slot (length 0 attends position 0 of
    the scratch page), a table longer than the live pages, blocks of
    several pages."""
    rank, width, page, heads = 128, 256, 8, 8
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(key[0], (2, 40, page, width), jnp.float32)
    q = jax.random.normal(key[1], (4, heads, width), jnp.float32) * 0.2
    table = jnp.asarray(np.random.default_rng(1).permutation(40)[:32]
                        .reshape(4, 8).astype(np.int32))
    sl = jnp.asarray(lens, jnp.int32)
    for layer in (0, 1):
        want = pla.latent_decode_xla(q, pool, table, sl, rank, layer)
        got = pla.latent_flash_decode(q, pool, table, sl, rank=rank,
                                      layer=layer, interpret=True)
        assert got.shape == (4, heads, rank)
        assert np.abs(np.asarray(got - want)).max() < 2e-5
    # a table entry beyond a row's live pages is never read
    far = table.at[0, 7].set(10 ** 6)
    got = pla.latent_flash_decode(q, pool, far, sl, rank=rank, layer=0,
                                  interpret=True)
    want = pla.latent_decode_xla(q, pool, table, sl, rank, 0)
    assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_absorbed_decode_is_the_unabsorbed_prefill(cfg, params):
    """Decode and prefill are different paths on purpose (absorbed over
    the pool's rows; K and V expanded per head through the flash
    kernel): the same numbers."""
    prompt = _prompt(7, 41)
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    logits, kvs = xing.prefill(params, cfg, toks)
    pool = jnp.zeros((3, 9, PAGE, cfg.latent_width))
    for li, (rows, _) in enumerate(kvs):
        pool = pool.at[li, 1:6].set(rows[0, :40].reshape(5, PAGE, -1))
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 0, 0]], jnp.int32)
    got, pool2, none = xing.decode_step(
        params, cfg, toks[:, 40], jnp.asarray([40], jnp.int32), pool, None,
        table)
    assert none is None
    assert np.abs(np.asarray(got[0] - logits[0, 40])).max() < 1e-4
    # the new token's row went to page 6, slot 0, of every layer
    for li, (rows, _) in enumerate(kvs):
        assert np.allclose(np.asarray(pool2[li, 6, 0]),
                           np.asarray(rows[0, 40]), atol=1e-5)


# -- the engine --------------------------------------------------------------
def test_cold_admission_and_decode_match_the_reference(cfg, params):
    eng = _engine(params, cfg)
    assert eng.v_pages is None and eng.k_pages.shape == (3, 96, PAGE, 128)
    prompt = _prompt(11, 75)
    out = eng.run([_req("a", prompt, 24)])["a"]
    assert len(out) == 24
    assert _worst(eng, params, "a", prompt, out) < TOL
    assert eng.stats["decode_steps"] == 23 and eng.stats["admit_pieces"] == 0
    # the leading layer is dense; an expert layer fetches the one
    # row's top_k of its experts (the shared expert is not counted)
    routed = sum("e_gate" in layer for layer in params["layers"])
    assert 0 < routed < cfg.n_layers
    assert eng.stats["moe_experts_fetched"] == 23 * routed * cfg.top_k
    assert eng.stats["moe_experts_held"] == 23 * routed * cfg.n_experts


def test_admission_in_pieces_is_the_admission_in_one(cfg, params):
    """Three pieces (32 + 32 + 11 tokens), another slot decoding
    between them; the same rows as the one program."""
    t0 = time.time_ns()
    eng = _engine(params, cfg, admit_piece=32)
    short, long_ = _prompt(12, 20), _prompt(13, 75)
    eng.submit(_req("s", short, 12))
    eng.step()
    eng.submit(_req("l", long_, 6))
    decoded = []
    while eng.queue or any(s is not None for s in eng.slots):
        decoded.append(eng.step())
    eng.drain_uploads()
    assert eng.stats["admit_pieces"] == 3
    pieces = _spans(eng, t0, "istpu.sched.admit_piece")
    assert [(s.fields["tokens"], s.fields["prefix_pages"],
             s.fields["piece"], s.fields["of"]) for s in pieces] == [
        (32, 0, 1, 3), (32, 4, 2, 3), (11, 8, 3, 3)]
    # the short request decoded in the steps that ran pieces 1 and 2
    assert decoded[:2] == [1, 1]
    progs = [s.fields["program"] for s in _spans(eng, t0,
                                                 "istpu.model.prefill")]
    assert progs == ["cold", "cold", "prefix", "prefix"]
    assert _worst(eng, params, "l", long_, eng.outputs["l"]) < TOL
    assert _worst(eng, params, "s", short, eng.outputs["s"]) < TOL
    one = _engine(params, cfg)
    one.run([_req("l", long_, 6)])
    assert np.abs(np.stack(one.rows["l"])
                  - np.stack(eng.rows["l"])).max() < 1e-4
    # first_token_logits runs what an admission runs, pieces included,
    # and leaves the pool's free list as it found it
    free = sorted(eng.free_pages)
    row, hit = eng.first_token_logits(long_)
    assert hit == 0 and sorted(eng.free_pages) == free
    assert eng.stats["admit_pieces"] == 6
    assert np.abs(row - eng.rows["l"][0]).max() < 1e-4


def test_hit_restores_latent_pages_at_every_page_edge(cfg, params,
                                                      shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="lat-hit")
    base = _prompt(21, 64)
    eng.run([_req("base", base, 9)])       # 72 tokens in pages: 9 full
    grown = base + eng.outputs["base"]
    assert eng.stats["offloaded_pages"] == 9
    assert eng.stats["latent_pages_written"] == 27     # 9 pages x 3 layers
    keys = serving.content_page_keys_by_page(
        eng._slot_digests(types.SimpleNamespace(
            digests=[], digest_h=None, work=types.SimpleNamespace(
                prompt=grown), generated=[]), 9), 3, cfg.page_kinds)
    assert len(keys) == 27 and keys[0].endswith("/L0/c")
    for n_hit in range(1, 10):             # a hit of 1 .. 9 pages
        tail = _prompt(100 + n_hit, 5)
        prompt = grown[:n_hit * PAGE] + tail
        rid = f"h{n_hit}"
        before = eng.stats["prefix_hit_pages"]
        out = eng.run([_req(rid, prompt, 3)])[rid]
        assert eng.stats["prefix_hit_pages"] - before == n_hit
        assert _worst(eng, params, rid, prompt, out) < TOL
    assert eng.stats["latent_pages_restored"] == 3 * sum(range(1, 10))
    assert eng.stats["store_errors"] == 0


def test_store_round_trip_of_a_latent_page_is_bit_exact(cfg, params,
                                                        shm_conn):
    eng = _engine(params, cfg, shm_conn, model_id="lat-bits")
    prompt = _prompt(31, 40)
    eng.submit(_req("r", prompt, 2))
    eng.step()
    slot = eng.slots[0]
    held = np.asarray(eng.k_pages[:, slot.page_ids[:5]])    # [3, 5, 8, 128]
    eng.run()
    digests = serving.content_page_digests(prompt, PAGE, 5, eng._ns)
    keys = serving.content_page_keys_by_page(digests, 3, "c")
    back = eng.store.get_kv_pages_host(keys, cfg.kv_page_shape(),
                                       cfg.jdtype)
    back = np.asarray(back).reshape(5, 3, PAGE, 128).swapaxes(0, 1)
    assert np.array_equal(back.view(np.uint8), held.view(np.uint8))


def test_offload_evict_restore_gives_the_same_logits(cfg, params, shm_conn):
    """Offload, lose the slot, restore in pieces' company: a hit whose
    tail is longer than a piece restores with its first piece."""
    eng = _engine(params, cfg, shm_conn, model_id="lat-evict",
                  admit_piece=16)
    base = _prompt(41, 48)
    eng.run([_req("b", base, 9)])
    grown = base + eng.outputs["b"]
    prompt = grown[:56] + _prompt(42, 39)      # 7 pages hit, 39 to admit
    row_cold, hit0 = _engine(params, cfg).first_token_logits(prompt)
    out = eng.run([_req("again", prompt, 4)])["again"]
    assert hit0 == 0 and eng.stats["prefix_hit_pages"] == 7
    # the base prompt's 48 tokens were 3 pieces, then 16 + 16 + 7
    assert eng.stats["admit_pieces"] == 6
    assert np.abs(eng.rows["again"][0] - row_cold).max() < 1e-4
    assert _worst(eng, params, "again", prompt, out) < TOL
    row_hit, hit = eng.first_token_logits(prompt)
    assert hit >= 7 and np.abs(row_hit - row_cold).max() < 1e-4


@pytest.mark.parametrize("name,sc,change", [
    ("spec_k", {"spec_k": 2}, {}),
    ("host_steps", {"host_steps": 4}, {}),
    ("quantized_store", {"quantized_store": True}, {}),
    ("kv_pack", {}, {"kv_pack": 2}),
    ("window", {}, {"window": 32}),
])
def test_what_is_not_built_over_a_latent_pool_is_refused(cfg, params, name,
                                                         sc, change):
    with pytest.raises(ValueError, match=name):
        _engine(params, dataclasses.replace(cfg, **change), **sc)


def test_verify_step_and_a_ragged_piece_are_refused(cfg, params):
    with pytest.raises(NotImplementedError, match="latent"):
        xing.verify_step.__wrapped__(
            params, cfg, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), None, None,
            jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="admit_piece"):
        _engine(params, cfg, admit_piece=12)


@pytest.mark.parametrize("window", [0, 32])
def test_admission_in_pieces_serves_a_family_with_k_and_v_pages(window):
    """The pieces are the engine's, not the family's: a dense model's
    long prompt in pieces gives the tokens of the one program, under
    one sliding window too (a piece attends the slot's pages from its
    band's floor on, and what fell below is freed between pieces)."""
    from infinistore_tpu.models import llama
    lcfg = llama.LlamaConfig(dtype="float32", page_size=8, max_seq=512,
                             window=window)
    lp = llama.init_params(jax.random.PRNGKey(2), lcfg)
    prompt = _prompt(51, 70)
    outs = []
    for piece in (0, 24):
        eng = ServingEngine(lp, lcfg, ServingConfig(
            max_slots=2, total_pages=64, max_pages_per_seq=16,
            admit_piece=piece))
        outs.append(eng.run([Request("r", prompt, 8)])["r"])
        assert eng.stats["admit_pieces"] == (3 if piece else 0)
    assert outs[0] == outs[1]


def test_the_reference_in_blocks_is_the_reference_whole(cfg, params,
                                                        monkeypatch):
    """The reference holds the streams as blocks of tokens, hands a
    block of queries a bucket of keys and runs the experts a few blocks
    at a time, so that 33k tokens fit beside an engine: with blocks far
    smaller than the sequence it gives what it gives whole."""
    toks = np.asarray(_prompt(61, 300), np.int32)
    pos = list(range(0, 300, 13))
    want, m0 = reference.forward(params, CONF, toks, pos)
    for name, small in (("TOKEN_BLOCK", 64), ("QUERY_BLOCK", 32),
                        ("KEY_BUCKET", 128), ("MOE_BLOCKS", 2),
                        ("ROW_PAD", 16), ("VOCAB_BLOCK", 48)):
        monkeypatch.setattr(reference, name, small)
    got, m1 = reference.forward(params, CONF, toks, pos)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    assert np.abs(np.asarray(m0) - np.asarray(m1)).max() < 1e-5
