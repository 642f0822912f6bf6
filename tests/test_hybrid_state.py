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


# -- a decode step's update over the rows that decode -----------------------
def _step_operands(b=16, H=8, P=16, N=128, K=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    f32 = jnp.float32
    C = H * P + 2 * N
    return {
        "h": jax.random.normal(ks[0], (b, H, P, N), f32),
        "x": jax.random.normal(ks[1], (b, H, P), f32),
        "dt": jax.nn.softplus(jax.random.normal(ks[2], (b, H), f32)),
        "A": -jnp.exp(jax.random.normal(ks[3], (H,), f32)),
        "B": jax.random.normal(ks[4], (b, N), f32),
        "C": jax.random.normal(ks[5], (b, N), f32),
        "tail": jax.random.normal(ks[6], (b, K - 1, C), f32),
        "xbc": jax.random.normal(ks[7], (b, C), f32),
        "w": jax.random.normal(ks[8], (K, C), f32),
        "bias": jnp.zeros((C,), f32),
    }


def _scattered(n, b=16):
    """`n` of `b` slots, scattered: the decoding rows' order is not the
    identity (unless all decode)."""
    valid = np.zeros(b, bool)
    valid[np.random.default_rng(n).permutation(b)[:n]] = True
    rows = ssm.decoding(jnp.asarray(valid))
    if 0 < n < b:
        assert list(np.asarray(rows[1])) != list(range(b))
    assert int(rows[2][0]) == n
    assert sorted(np.asarray(rows[1])[:n]) == list(np.flatnonzero(valid))
    return valid, rows


def _step_by(form, o, rows):
    """(y, h) of the update over `rows` by "xla" (what `ssm.step` runs
    off the chip) or "kernel.T" (the Pallas call the chip runs, in
    interpret mode, T heads a block)."""
    if form == "xla":
        return ssm.step(o["h"], o["x"], o["dt"], o["A"], o["B"], o["C"],
                        rows)
    return ssm.step_kernel(
        o["h"], jnp.exp(o["dt"] * o["A"]), o["x"] * o["dt"][..., None],
        o["B"], o["C"], rows[1], rows[2], tile=int(form.split(".")[1]),
        interpret=True)


@pytest.mark.parametrize("form", ["xla", "kernel.8", "kernel.2"])
@pytest.mark.parametrize("n", [1, 3, 4, 16])
def test_step_over_the_decoding_rows_is_the_whole_batchs_on_them(n, form):
    """`ssm.step` with `rows`: a decoding row's y and state are what the
    update over the whole batch gives it, a row that does not decode
    keeps its state BIT FOR BIT and reads y = 0; by the form that runs
    off the chip and by the chip's kernel (interpret mode), whose grid
    is the n decoding slots x the head tiles."""
    o = _step_operands()
    valid, rows = _scattered(n)
    y_all, h_all = ssm.step(o["h"], o["x"], o["dt"], o["A"], o["B"], o["C"])
    y, h = _step_by(form, o, rows)
    if form != "xla":  # the kernel leaves the other rows' y unwritten
        y = jnp.where(valid[:, None, None], y, 0.0)
    np.testing.assert_allclose(np.asarray(h)[valid], np.asarray(h_all)[valid],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[valid], np.asarray(y_all)[valid],
                               rtol=1e-5, atol=1e-4)
    assert np.array_equal(np.asarray(h)[~valid].view(np.uint32),
                          np.asarray(o["h"])[~valid].view(np.uint32))
    assert not np.asarray(y)[~valid].any()


@pytest.mark.parametrize("n", [1, 3, 4, 16])
def test_conv_step_over_the_decoding_rows_is_the_whole_batchs_on_them(n):
    o = _step_operands()
    valid, rows = _scattered(n)
    out_all, tail_all = ssm.conv_step(o["tail"], o["xbc"], o["w"], o["bias"])
    out, tail = ssm.conv_step(o["tail"], o["xbc"], o["w"], o["bias"], rows)
    assert np.array_equal(np.asarray(out), np.asarray(out_all))
    assert np.array_equal(np.asarray(tail)[valid], np.asarray(tail_all)[valid])
    assert np.array_equal(np.asarray(tail)[~valid].view(np.uint32),
                          np.asarray(o["tail"])[~valid].view(np.uint32))


@pytest.mark.parametrize("tile", [8, 2])
@pytest.mark.parametrize("n", [0, 1, 5])
def test_the_kernels_grid_ends_at_the_count(n, tile):
    """The kernel's grid is (count, head tiles): were it to run one
    slot of the order more, a row that does not decode would advance,
    and were its last step to run again, the LAST decoding row twice
    (decay^2). Two steps from the same state, each a kernel call, equal
    the sequential recurrence on every decoding row; with nothing
    decoding no step runs and the pool comes back bit for bit."""
    o = _step_operands(seed=n)
    valid, rows = _scattered(n)
    want = o["h"]
    got = dict(o)
    for _ in range(2):
        _, want = ssm.step(want, o["x"], o["dt"], o["A"], o["B"], o["C"])
        _, got["h"] = _step_by(f"kernel.{tile}", got, rows)
    last = np.asarray(rows[1])[max(n - 1, 0)]
    np.testing.assert_allclose(np.asarray(got["h"])[last],
                               np.asarray(want if n else o["h"])[last],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["h"])[valid],
                               np.asarray(want)[valid], rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(got["h"])[~valid].view(np.uint32),
                          np.asarray(o["h"])[~valid].view(np.uint32))


@pytest.mark.parametrize("H,tile", [(64, 32), (48, 16), (40, 8), (12, 12),
                                    (8, 8), (100, 100)])
def test_the_kernels_tile_divides_the_heads(H, tile):
    """Every head lies in exactly one block of the kernel's grid: a
    family whose heads are no multiple of 32 gets a tile that divides
    them, a multiple of 8 or all of them (the chip's rule for a block's
    second-to-last dimension)."""
    assert ssm.head_tile(H) == tile
    assert H % tile == 0 and (tile % 8 == 0 or tile == H)


@pytest.mark.parametrize("H", [48, 40, 12])
@pytest.mark.parametrize("n", [2, 4])
def test_the_kernel_advances_every_head_whatever_their_number(n, H):
    """The kernel as the PROGRAM calls it (no tile given), in interpret
    mode, over heads that are no multiple of 32 heads a block: every
    head of a decoding row is what the update over the whole batch
    gives it (a grid of H // 32 tiles would leave the heads past the
    last whole tile as they were and their y unwritten)."""
    o = _step_operands(b=4, H=H, seed=H)
    valid, rows = _scattered(n, b=4)
    y_all, h_all = ssm.step(o["h"], o["x"], o["dt"], o["A"], o["B"], o["C"])
    y, h = ssm.step_kernel(
        o["h"], jnp.exp(o["dt"] * o["A"]), o["x"] * o["dt"][..., None],
        o["B"], o["C"], rows[1], rows[2], interpret=True)
    np.testing.assert_allclose(np.asarray(h)[valid], np.asarray(h_all)[valid],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[valid], np.asarray(y_all)[valid],
                               rtol=1e-5, atol=1e-4)
    assert not np.array_equal(np.asarray(h)[valid][:, -1],
                              np.asarray(o["h"])[valid][:, -1])
    assert np.array_equal(np.asarray(h)[~valid].view(np.uint32),
                          np.asarray(o["h"])[~valid].view(np.uint32))


def test_the_kernel_refuses_a_tile_that_does_not_divide_the_heads():
    o = _step_operands(b=4, H=12)
    _, rows = _scattered(2, b=4)
    with pytest.raises(ValueError, match="does not divide"):
        ssm.step_kernel(o["h"], o["dt"], o["x"], o["B"], o["C"], rows[1],
                        rows[2], tile=8, interpret=True)


def test_decode_step_leaves_the_rows_that_do_not_decode(params, cfg):
    """`hybrid.decode_step` over 4 slots of which slots 3 and 1 hold a
    sequence: their logits and state are what a step over them alone
    gives, and the two others' state pools' rows are bit for bit what
    they were."""
    b = 4
    state = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.size), a.shape,
                                    a.dtype),
        hybrid.state_pools(cfg, b))
    page = cfg.kv_page_shape()
    kp = jnp.zeros((cfg.n_kv_layers, 16, *page), cfg.jdtype)
    table = jnp.asarray(np.arange(b * 3).reshape(b, 3) % 15 + 1, jnp.int32)
    tok = jnp.asarray([5, 9, 2, 77], jnp.int32)
    lens = jnp.asarray([0, 6, 0, 3], jnp.int32)
    logits, _, _, new = hybrid.decode_step(params, cfg, tok, lens, kp, kp,
                                           table, state)
    held = np.asarray(lens) > 0
    for kind in state:
        for old, got in zip(state[kind], new[kind]):
            assert np.array_equal(np.asarray(got)[~held].view(np.uint32),
                                  np.asarray(old)[~held].view(np.uint32))
            assert not np.array_equal(np.asarray(got)[held],
                                      np.asarray(old)[held])
    # ... and the two that decode, as in a step where every slot does
    # (a step before PR 52: every row advanced)
    every = jnp.where(lens > 0, lens, 1)
    want, _, _, wnew = hybrid.decode_step(params, cfg, tok, every, kp, kp,
                                          table, state)
    np.testing.assert_allclose(np.asarray(logits)[held],
                               np.asarray(want)[held], rtol=1e-5, atol=1e-5)
    for kind in state:
        for w, got in zip(wnew[kind], new[kind]):
            np.testing.assert_allclose(np.asarray(got)[held],
                                       np.asarray(w)[held], rtol=1e-6,
                                       atol=1e-6)


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
    # the snapshot's store call is the restore thread's, behind the
    # pages' under the hit's stage span (since PR 56)
    stage = [s for s in by["istpu.cache.stage"] if s.request == "t2"][0]
    assert rest.tid != stage.tid
    assert by["istpu.cache.state_in"][0].parent == stage.id
    assert stage.fields["bytes"] == rest.fields["bytes"]
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


def test_a_decode_steps_span_counts_the_slots_whose_state_it_moved(
        params, cfg):
    """Three requests of 3, 6 and 9 tokens into 4 slots: the steps
    decode 3, 2 and then 1 sequence and move exactly their slots'
    state (the decoding slots' count bounds the kernel's grid: the
    engine writes it for both fields); the span a step lands in says
    so and the counters sum it. A family without state runs the
    program it ran and its spans carry the fields they carried."""
    from infinistore_tpu.models import llama

    eng = _engine(params, cfg, max_slots=4)
    eng._proven = lambda active: False     # a span a step, dispatch to land
    t0 = profiling.time.time_ns()
    eng.run([Request(r, _prompt(s, 11), max_new_tokens=n)
             for r, s, n in (("a", 1, 3), ("b", 2, 6), ("c", 3, 9))])
    steps = [(s.fields["state_rows_active"], s.fields["state_rows_run"])
             for s in profiling.spans(since_ns=t0)
             if s.name == "istpu.model.decode" and s.engine == eng.engine_id]
    assert steps == [(3, 3)] * 2 + [(2, 2)] * 3 + [(1, 1)] * 3
    assert eng.stats["state_rows_active"] == eng.stats["state_rows_run"] \
        == 6 + 6 + 3
    assert eng.stats["decode_steps"] == len(steps)

    lcfg = llama.LlamaConfig()
    dense = ServingEngine(llama.init_params(jax.random.PRNGKey(0), lcfg),
                          lcfg)
    dense._proven = lambda active: False
    dense.run([Request("d", [3, 7, 3], max_new_tokens=3)])
    assert dense.stats["state_rows_run"] == 0
    fields = [set(s.fields) for s in profiling.spans(since_ns=t0)
              if s.name == "istpu.model.decode"
              and s.engine == dense.engine_id]
    assert fields and all(
        f == {"program", "live_pages", "dispatch_ns", "waiting"}
        for f in fields), fields
