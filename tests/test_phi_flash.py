"""Phi-4-mini-flash's decoder-hybrid-decoder (models/phi_flash.py): Mamba-1
state layers, differential attention over a band and over the whole
context, Gated Memory Units and cross layers that borrow another
layer's scan output and pages, through the decoder stack, the ops of
ops/ssm.py and the serving engine's slot of THREE kinds of cache, held
to the plain float32 reference (benchmark/reference/phi4_flash.py) by
LOGITS, at a tiny preset on the CPU with seeded weights.

The engine's rows come from a recording engine (tests/
test_hybrid_state.py's): every request samples, so every token goes
through `_pick`, which keeps the logits row and answers its argmax.
"""

import dataclasses
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4_flash as reference
from infinistore_tpu import serving
from infinistore_tpu.models import decoder, hf, phi_flash
from infinistore_tpu.ops import ssm
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.tpu import TpuKVStore

PAGE = 8
BAND = 16
# The published keys at tiny widths: what the bridge and the reference
# both read. 8 layers: Mamba-1 at 0, 2, 4 (4 is the memory's source),
# banded attention at 1, 3, full at 5 (the shared K and V), a Gated
# Memory Unit at 6, a cross layer at 7.
CONF = {
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "sliding_window": BAND, "mb_per_layer": 2,
    "layer_norm_eps": 1e-5, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4, "tie_word_embeddings": True,
    "hidden_act": "silu", "max_position_embeddings": 4096,
    "mlp_bias": False, "lm_head_bias": False, "embd_pdrop": 0,
    "resid_pdrop": 0,
}
KINDS = ("mamba1", "attention", "mamba1", "attention", "mamba1",
         "attention", "gmu", "cross")
# float32 program against the float32 reference, max |logit| about 2:
# the worst row read 6e-6 (my CPU runs, PR 53); a state rounded to
# bfloat16 reads 3e-3 (test_a_bfloat16_state_would_fail_the_tolerance).
TOL = 1e-4


@pytest.fixture(scope="module")
def cfg():
    return hf.phi4flash_config_from_hf(types.SimpleNamespace(**CONF),
                                       page_size=PAGE, dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded, with every norm weight, bias and float32 vector moved
    off its initial 1 or 0, so that each matters to the comparison."""
    params = phi_flash.init_params(jax.random.PRNGKey(0), cfg)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        if leaf.ndim == 1 else leaf for leaf, k in zip(leaves, keys)])


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, CONF["vocab_size"], n)]


def _ref_rows(params, seq, positions, conf=CONF):
    rows, _ = reference.forward(params, conf, np.asarray(seq, np.int32),
                                list(positions))
    return np.asarray(rows)


# -- the config and the layout ---------------------------------------------
def test_the_bridge_derives_the_layout(cfg):
    assert cfg.layer_kinds == KINDS
    assert [k for k, _ in reference.layer_kinds(CONF)] == list(KINDS)
    assert cfg.layer_windows == (0, BAND, 0, BAND, 0, 0, 0, 0)
    assert cfg.two_kinds and cfg.n_kv_layers == 3 and cfg.n_state_layers == 3
    # a cross layer gets no pool layer; the full pool holds ONE layer
    assert decoder.attn_layers(cfg) == [
        (BAND, False, "window", 0), (BAND, False, "window", 1),
        (0, False, "full", 0)]
    assert decoder.rows_cut(cfg) == 5
    assert cfg.state_shapes() == {"h": (16, 128), "conv": (3, 128)}
    # a page as flat rows: 2 kv heads of 16 lanes packed into ONE row
    assert cfg.page_rows == 1 and cfg.kv_page_shape() == (PAGE, 32)


def test_the_published_layout():
    conf = dict(CONF, hidden_size=2560, num_attention_heads=40,
                num_key_value_heads=20, num_hidden_layers=32,
                sliding_window=512, mamba_dt_rank=160)
    big = hf.phi4flash_config_from_hf(types.SimpleNamespace(**conf))
    kinds = big.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == "mamba1"] == list(
        range(0, 17, 2))
    assert [i for i, k in enumerate(kinds) if k == "attention"] == list(
        range(1, 18, 2))
    assert [i for i, k in enumerate(kinds) if k == "gmu"] == list(
        range(18, 32, 2))
    assert [i for i, k in enumerate(kinds) if k == "cross"] == list(
        range(19, 32, 2))
    assert [w for w in big.layer_windows if w] == [512] * 8
    assert big.layer_windows[17] == 0 and decoder.rows_cut(big) == 17
    assert big.kv_page_shape() == (160, 128) and big.page_rows == 10
    assert big.state_shapes() == {"h": (16, 5120), "conv": (3, 5120)}
    assert decoder.stack_rows(big, 12416) == (12416 * 17 + 15, 12416 * 32)


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", False), ("mlp_bias", True),
    ("lm_head_bias", True), ("hidden_act", "gelu"), ("mb_per_layer", 1),
    ("sliding_window", 12), ("num_key_value_heads", 1),
    ("num_attention_heads", 1), ("resid_pdrop", 0.1),
])
def test_bridge_refuses_what_it_does_not_implement(key, value):
    with pytest.raises(NotImplementedError):
        hf.phi4flash_config_from_hf(
            types.SimpleNamespace(**dict(CONF, **{key: value})),
            page_size=PAGE)


# -- the ops ---------------------------------------------------------------
def _scan_inputs(seed, b, s, n, c):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, n, c)),
            jax.random.normal(k[1], (b, s, c)),
            jax.nn.softplus(jax.random.normal(k[2], (b, s, c))),
            -jnp.exp(jax.random.normal(k[3], (n, c))),
            jax.random.normal(k[4], (b, s, n)),
            jax.random.normal(k[5], (b, s, n)))


def _recurrence(h0, x, dt, A, B, C):
    """The reference's form: a scan over time of one sequence, the
    state [C, N]."""
    def one(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * A.T) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    s, y = jax.lax.scan(one, h0.T, (x, dt, B, C))
    return y, s.T


@pytest.mark.parametrize("s", [1, 8, 19, 40])
@pytest.mark.parametrize("form", ["scan", "kernel", "steps"])
def test_selective_scan_equals_the_sequential_recurrence(s, form):
    """`selective_scan` (its XLA form and its Pallas kernel, interpreted,
    over ragged last chunks) and `selective_step` iterated, from a
    carried-in state that is not zero, against the reference's scan."""
    h0, x, dt, A, B, C = _scan_inputs(s, 2, s, 16, 256)
    if form == "scan":
        y, h = ssm.selective_scan(h0, x, dt, A, B, C)
    elif form == "kernel":
        pad = -s % 8
        xs, dts, Bs, Cs = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                           for a in (x, dt, B, C))
        y, h = ssm.selective_scan_kernel(h0, xs, dts, A, Bs, Cs, chunk=8,
                                         tile=128, interpret=True)
        y = y[:, :s]
    else:
        h, ys = h0, []
        for t in range(s):
            y_t, h = ssm.selective_step(h, x[:, t], dt[:, t], A, B[:, t],
                                        C[:, t])
            ys.append(y_t)
        y = jnp.stack(ys, axis=1)
    for i in range(2):
        yw, hw = _recurrence(h0[i], x[i], dt[i], A, B[i], C[i])
        np.testing.assert_allclose(y[i], yw, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h[i], hw, rtol=2e-4, atol=2e-4)


def test_a_position_with_dt_zero_leaves_the_selective_state_alone():
    h0, x, dt, A, B, C = _scan_inputs(3, 1, 12, 16, 128)
    dt = dt.at[:, 7:].set(0.0)
    _, h = ssm.selective_scan(h0, x, dt, A, B, C)
    _, want = ssm.selective_scan(h0, x[:, :7], dt[:, :7], A, B[:, :7],
                                 C[:, :7])
    np.testing.assert_array_equal(h, want)


@pytest.mark.parametrize("n", [0, 1, 3, 4])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_selective_step_moves_the_decoding_rows_alone(n, form):
    """With `rows`, the decoding slots' state is the whole batch's on
    them and every other row stays bit for bit what it was; the kernel
    (interpreted) runs a grid of the count."""
    h0, x, dt, A, B, C = _scan_inputs(n, 4, 1, 16, 128)
    valid = jnp.asarray(np.random.default_rng(n).permutation(4) < n)
    rows = ssm.decoding(valid)
    yw, hw = ssm.selective_step(h0, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0])
    if form == "xla":
        y, h = ssm.selective_step(h0, x[:, 0], dt[:, 0], A, B[:, 0],
                                  C[:, 0], rows)
    else:
        y, h = ssm.selective_step_kernel(h0, x[:, 0], dt[:, 0], A, B[:, 0],
                                         C[:, 0], *rows[1:], interpret=True)
    for i in range(4):
        if valid[i]:
            np.testing.assert_allclose(h[i], hw[i], rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(y[i], yw[i], rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(h[i], h0[i])


def test_differential_attention_of_equal_maps_is_plain_attention(cfg):
    """With q2 = q1 and k2 = k1 both maps are one, so a pair's output
    is (1 - lam) softmax(q k^T) [v1 | v2]: `diff_combine` over the
    widened queries against plain attention, normed."""
    rng = jax.random.split(jax.random.PRNGKey(5), 4)
    s, hd = 12, cfg.head_dim
    q1 = jax.random.normal(rng[0], (1, s, 2, hd))       # pairs 0, 1
    k1 = jax.random.normal(rng[1], (1, s, 1, hd))       # one kv pair
    v = jax.random.normal(rng[2], (1, s, 1, 2 * hd))
    layer = {"lam_q1": jnp.full(hd, 0.1), "lam_k1": jnp.full(hd, 0.2),
             "lam_q2": jnp.full(hd, 0.3), "lam_k2": jnp.full(hd, -0.1),
             "sub_ln": 1.0 + 0.1 * jax.random.normal(rng[3], (2 * hd,))}
    q = jnp.concatenate([q1, q1], axis=2)     # heads: map 1 x 2, map 2 x 2
    k = jnp.concatenate([k1, k1], axis=2)     # kv heads: k1, k2
    qp, kp, _ = decoder.pack_heads(cfg, q, k, k)
    attn = decoder.flash_prefill(qp, kp, v, causal=True)
    got = decoder.diff_combine(layer, cfg, attn, depth=3)
    lam, lam0 = decoder.diff_lambda(layer, 3)
    assert lam0 == pytest.approx(0.8 - 0.6 * np.exp(-0.9))
    plain = decoder.flash_prefill(q1, k1, v, causal=True)   # [1, s, 2, 2hd]
    want = decoder.rms_norm((1.0 - lam) * plain, layer["sub_ln"],
                            cfg.norm_eps) * (1.0 - lam0)
    np.testing.assert_allclose(got, want.reshape(1, s, -1), rtol=2e-5,
                               atol=2e-5)


def test_paged_decode_over_flat_rows_of_ten(cfg):
    """The decode kernel (interpreted) over a pool that holds a page as
    flat rows, 10 packed kv rows a token under 40 widened queries (the
    published widths: 40 query rows are padded to 48 at the tail, the
    pool goes in as it lies), against the XLA path over the 5-D pool."""
    from infinistore_tpu.ops import paged_attention as xla
    from infinistore_tpu.ops import pallas_paged_attention as kernels

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    b, heads, rows, d, page, pages = 3, 40, 10, 128, 16, 12
    q = jax.random.normal(k[0], (b, heads, d), jnp.bfloat16)
    kp = jax.random.normal(k[1], (2, pages, page, rows, d), jnp.bfloat16)
    vp = jax.random.normal(k[2], (2, pages, page, rows, d), jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(0).permutation(pages)[
        :b * 4].reshape(b, 4), jnp.int32)
    lens = jnp.asarray([50, 1, 64], jnp.int32)
    flat = (2, pages, page * rows, d)
    for window in (0, 32):
        got = kernels.paged_flash_decode(
            q, kp.reshape(flat), vp.reshape(flat), table, lens,
            interpret=True, window=window, layer=1, rows=rows)
        want = xla.paged_decode_attention(q, kp, vp, table, lens,
                                          window=window, layer=1)
        np.testing.assert_allclose(got.astype(np.float32),
                                   want.astype(np.float32), atol=2e-2)


# -- the stack ---------------------------------------------------------------
def test_dense_forward_matches_the_reference_at_every_position(params, cfg):
    toks = _prompt(1, 3 * BAND + 5)
    logits, _ = phi_flash.forward_dense(params, cfg, jnp.asarray([toks]))
    want = _ref_rows(params, toks, range(len(toks)))
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < TOL


@pytest.mark.parametrize("block,n", [(16, 43), (16, 48), (32, 41)])
def test_the_reference_in_blocks_of_queries_is_the_reference(
        params, cfg, block, n, monkeypatch):
    """The reference's attention runs in blocks of queries at the
    published lengths: a length that is no multiple of the block, and
    one under block + band, against the program's every-row pass (its
    last block once lost the first keys of a full layer)."""
    monkeypatch.setattr(reference, "Q_BLOCK", block)
    toks = _prompt(block, n)
    logits, _ = phi_flash.forward_dense(params, cfg, jnp.asarray([toks]))
    want = _ref_rows(params, toks, range(n))
    assert float(np.abs(np.asarray(logits[0]) - want).max()) < TOL


def test_the_cross_layers_output_gain_is_the_initialisation_alone(cfg):
    """`random_init.cross_out_gain` of a configuration file reaches
    `init_params` through the bridge and scales the cross layers' `wo`,
    nothing else: every other leaf is what gain 1 draws."""
    gained = hf.phi4flash_config_from_hf(
        types.SimpleNamespace(**CONF, random_init={"cross_out_gain": 8.0}),
        page_size=PAGE, dtype="float32")
    assert gained.cross_out_gain == 8.0 and cfg.cross_out_gain == 1.0
    one = phi_flash.init_params(jax.random.PRNGKey(3), cfg)
    eight = phi_flash.init_params(jax.random.PRNGKey(3), gained)
    for kind, a, b in zip(KINDS, one["layers"], eight["layers"]):
        for name in a:
            scale = 8.0 if (kind, name) == ("cross", "wo") else 1.0
            np.testing.assert_allclose(b[name], scale * a[name], rtol=1e-6)
    np.testing.assert_array_equal(one["embed"], eight["embed"])


@pytest.mark.parametrize("n,pad", [(13, 16), (40, 40), (33, 40), (1, 8)])
def test_one_row_admission_equals_that_row_of_the_every_row_pass(
        params, cfg, n, pad):
    """`last_only`: the cross-decoder on the kept row alone gives that
    row's logits, and pages, states and boundary states are what the
    every-row pass gives; padded positions do not advance a state."""
    toks = _prompt(n, n) + [0] * (pad - n)
    one, kvs1, st1 = phi_flash.prefill(params, cfg, jnp.asarray([toks]),
                                       s_real=jnp.int32(n), last_only=True)
    every, kvs, st = phi_flash.prefill(params, cfg, jnp.asarray([toks]),
                                       s_real=jnp.int32(n))
    assert one.shape == (1, 1, CONF["vocab_size"])
    np.testing.assert_allclose(one[0, 0], every[0, n - 1], atol=2e-5)
    want = _ref_rows(params, toks[:n], [n - 1])[0]
    assert float(np.abs(np.asarray(one[0, 0]) - want).max()) < TOL
    for (k1, v1), (k, v) in zip(kvs1, kvs):
        np.testing.assert_array_equal(k1, k)
        np.testing.assert_array_equal(v1, v)
    _, _, unpadded = phi_flash.prefill(params, cfg,
                                       jnp.asarray([toks[:n]]))
    for a, b_, u in zip(st1, st, unpadded):
        for key in a:
            np.testing.assert_array_equal(a[key], b_[key])
        np.testing.assert_allclose(a["h"], u["h"], atol=2e-5)
        np.testing.assert_allclose(a["conv"], u["conv"], atol=2e-5)


def _weight_products(cfg, params, last_only, s=32):
    """Rows a sequence of every product with a weight (a `dot_general`
    whose second operand is a matrix) in the jaxpr of an admission's
    prefill, as tests/test_admit_one_row.py walks one."""
    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    jaxpr = jax.make_jaxpr(lambda p, t: phi_flash.prefill(
        p, cfg, t, s_real=jnp.int32(s - 2), last_only=last_only)[0])(
            params, jnp.zeros((1, s), jnp.int32))
    return [int(np.prod(eqn.outvars[0].aval.shape[1:-1]))
            for eqn in equations(jaxpr.jaxpr)
            if eqn.primitive.name == "dot_general"
            and eqn.invars[1].aval.ndim == 2]


def test_the_admission_program_runs_the_cross_decoder_on_one_row(params,
                                                                 cfg):
    """The program's products with weights, by their rows: with
    `last_only` those of the cut layer's query, output projection and
    MLP (its K and V stay every row's), of every layer above it and of
    the head have ONE row; without it none has."""
    s = 32
    one = _weight_products(cfg, params, True, s)
    every = _weight_products(cfg, params, False, s)
    assert len(one) == len(every) and set(every) == {s}
    # layer 5: wq, wo and the MLP's three; the gmu layer: its two and
    # the MLP's three; the cross layer: wq, wo and the MLP's three; the
    # head
    assert sorted(set(one)) == [1, s] and one.count(1) == 5 + 5 + 5 + 1
    assert decoder.stack_rows(cfg, s) == (s * 5 + 3, s * 8)


def _pools(cfg, slots, pages):
    shape = (1, pages, *cfg.kv_page_shape())
    wshape = (2, pages, *cfg.kv_page_shape())
    return (jnp.zeros(shape), jnp.zeros(shape), jnp.zeros(wshape),
            jnp.zeros(wshape), phi_flash.state_pools(cfg, slots))


@pytest.mark.parametrize("n0", [11, 2 * BAND, 2 * BAND + 3])
def test_prefill_then_decode_through_three_kinds(params, cfg, n0):
    """Model level: prefill, then paged decode steps over the full
    pool (through the page table), the banded pool (through a short
    table) and the state pools, against the reference's full forward,
    across a band's edge and several page edges."""
    toks = _prompt(5, n0 + 22)
    slots, pages = 2, 16
    logits, kvs, states = phi_flash.prefill(params, cfg,
                                            jnp.asarray([toks[:n0]]))
    kp, vp, wk, wv, state = _pools(cfg, slots, pages)
    n = -(-n0 // PAGE)
    ids = np.arange(1, n + 1)

    def paged(a):
        a = jnp.pad(a[0], ((0, n * PAGE - n0), (0, 0), (0, 0)))
        return a.reshape(n, *cfg.kv_page_shape())

    for (band, _, pool, li), (k, v) in zip(decoder.attn_layers(cfg), kvs):
        if pool == "full":
            kp, vp = kp.at[li, ids].set(paged(k)), vp.at[li, ids].set(
                paged(v))
        else:
            wk, wv = wk.at[li, ids].set(paged(k)), wv.at[li, ids].set(
                paged(v))
    for j, st in enumerate(states):
        state["h"][j] = state["h"][j].at[1].set(st["h"][0])
        state["conv"][j] = state["conv"][j].at[1].set(st["conv"][0])
    table = np.zeros((slots, pages), np.int32)
    table[1, :pages - 1] = np.arange(1, pages)
    # the banded layers' short table starts at page 1 of the sequence
    wtable = np.zeros((slots, pages), np.int32)
    wtable[1, :pages - 2] = np.arange(2, pages)
    wbase = jnp.asarray([0, PAGE], jnp.int32)
    assert n0 - BAND + 1 >= PAGE or n0 <= BAND  # the band spares page 0
    if n0 <= BAND:  # ... or the short table holds it too
        wtable[1, :pages - 1] = np.arange(1, pages)
        wbase = jnp.asarray([0, 0], jnp.int32)
    want = _ref_rows(params, toks, range(n0 - 1, len(toks) - 1))
    worst = float(np.abs(np.asarray(logits[0, -1]) - want[0]).max())
    for i, pos in enumerate(range(n0, len(toks) - 1)):
        logits, kp, vp, state, wk, wv = phi_flash.decode_step(
            params, cfg, jnp.asarray([0, toks[pos]], jnp.int32),
            jnp.asarray([0, pos], jnp.int32), kp, vp, jnp.asarray(table),
            state, win=(wk, wv, jnp.asarray(wtable), wbase))
        worst = max(worst, float(np.abs(
            np.asarray(logits[1]) - want[i + 1]).max()))
    assert worst < TOL


def test_a_bfloat16_state_would_fail_the_tolerance(params, cfg):
    """The tolerance tells the state's precision: the same program
    continuing from a state rounded to bfloat16 leaves it."""
    toks = _prompt(6, 40)
    _, kvs, states = phi_flash.prefill(params, cfg, jnp.asarray([toks[:24]]))
    low = [(st["h"].astype(jnp.bfloat16).astype(jnp.float32), st["conv"])
           for st in states]
    spec = decoder.attn_layers(cfg)
    prefix = [(k[:, 24 - BAND:] if band else k, v[:, 24 - BAND:] if band
               else v) for (band, *_), (k, v) in zip(spec, kvs)]
    got, _, _ = phi_flash.prefill_with_prefix(
        params, cfg, jnp.asarray([toks[24:]]), prefix, state=low)
    exact, _, _ = phi_flash.prefill_with_prefix(
        params, cfg, jnp.asarray([toks[24:]]), prefix,
        state=[(st["h"], st["conv"]) for st in states])
    want = _ref_rows(params, toks, range(24, 40))
    assert float(np.abs(np.asarray(exact[0]) - want).max()) < TOL
    assert float(np.abs(np.asarray(got[0]) - want).max()) > TOL


@pytest.mark.parametrize("fault", ["band", "lam", "memory_after_gate",
                                   "a_cross_layer_skips_the_cache"])
def test_a_planted_fault_leaves_the_tolerance(params, cfg, fault,
                                              monkeypatch):
    """What the tolerance must tell from the program: a band one page
    short, `lam` left out, the memory taken after the gate, a cross
    layer that does not read the shared cache."""
    toks = _prompt(8, 3 * BAND)
    want = _ref_rows(params, toks, range(len(toks)))
    bad = cfg
    if fault == "band":
        bad = dataclasses.replace(cfg, layer_bands=tuple(
            w - PAGE if w else 0 for w in cfg.layer_bands))
    elif fault == "lam":
        monkeypatch.setattr(decoder, "diff_lambda", lambda layer, depth: (
            jnp.float32(0.0), 0.8 - 0.6 * float(np.exp(-0.3 * depth))))
    elif fault == "memory_after_gate":
        real = decoder._mamba1_out

        def gated(layer, y, xs, z):
            out, mem = real(layer, y, xs, z)
            return out, mem * jax.nn.silu(z)

        monkeypatch.setattr(decoder, "_mamba1_out", gated)
    else:
        # a cross layer's call (the one without a band) attends keys of
        # zeros: every position alike, whatever the cache holds
        real = decoder.flash_prefill
        monkeypatch.setattr(
            decoder, "flash_prefill", lambda q, k, v, causal=True, **kw:
            real(q, k if "window" in kw else jnp.zeros_like(k), v, causal,
                 **kw))
    logits, _ = phi_flash.forward_dense(params, bad, jnp.asarray([toks]))
    assert float(np.abs(np.asarray(logits[0]) - want).max()) > 10 * TOL


# -- the engine: three kinds of cache in one slot ---------------------------
class Recording(ServingEngine):
    """Keeps every logits row a request's tokens were picked from."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.rows = {}

    def _pick(self, work, row):
        self.rows.setdefault(work.req.request_id, []).append(
            np.array(row, np.float32))
        return int(np.argmax(row))


def _engine(params, cfg, conn=None, model_id="phi", **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 64)
    sc.setdefault("max_pages_per_seq", 16)
    return Recording(params, cfg, ServingConfig(model_id=model_id, **sc),
                     store=None if conn is None else TpuKVStore(conn),
                     model=phi_flash)


def _req(rid, prompt, n):
    return Request(rid, prompt, max_new_tokens=n, temperature=1.0)


def _worst(eng, params, rid, prompt, out):
    seq = list(prompt) + list(out)
    want = _ref_rows(params, seq, range(len(prompt) - 1, len(seq) - 1))
    got = np.stack(eng.rows[rid])
    assert got.shape == want.shape
    return float(np.abs(got - want).max())


def test_the_engine_holds_three_kinds(params, cfg):
    eng = _engine(params, cfg)
    assert eng._three_kinds()
    assert eng.k_pages.shape == (1, 64, PAGE, 32)        # ONE full layer
    assert eng.wk_pages.shape[0] == 2 and eng.wk_pages.shape[2:] == (PAGE,
                                                                    32)
    assert len(eng.state["h"]) == 3 and eng.state["h"][0].shape == (
        2, 16, 128)
    assert eng._borrowers == 1 and eng._probe_kinds == [(2, "k")]
    # the state is float32 wherever it lies: no comparison with a
    # float32 reference can tell ONE rounding to bfloat16 from what
    # bfloat16 activations already carry (the benchmark's tolerance
    # file says so), so its dtype is what holds the stated precision
    assert cfg.state_jdtype == jnp.float32
    for held in (eng.state, eng.bstate):
        assert all(a.dtype == jnp.float32 for kind in held.values()
                   for a in kind)
    assert "/band16@0.1" in eng._ns and "/st3x16x128+3x128/float32" in eng._ns


def test_cold_admission_and_decode_match_the_reference(params, cfg):
    eng = _engine(params, cfg)
    reqs = {"a": _prompt(10, 13), "b": _prompt(11, 45), "c": _prompt(12, 5)}
    out = eng.run([_req(r, p, 20) for r, p in reqs.items()])
    for rid, prompt in reqs.items():
        assert _worst(eng, params, rid, prompt, out[rid]) < TOL
    assert eng.stats["boundary_copies"] > 0
    assert eng.stats["window_pages_released"] > 0
    assert eng.stats["shared_kv_rows_read"] > 0
    assert 0 < eng.stats["stack_rows_run"] < eng.stats["stack_rows_all"]


def test_a_hit_restores_three_kinds_and_equals_the_cold_run(params, cfg,
                                                            shm_conn):
    turn1 = _prompt(20, 45)
    e1 = _engine(params, cfg, shm_conn, "hit")
    out1 = e1.run([_req("t1", turn1, 20)])
    assert e1.stats["offloaded_pages"] == 8       # 64 tokens in cache
    assert e1.stats["snapshots_written"] == 1
    # what the banded layers computed below the admission's band is NOT
    # written: no snapshot lies at its end (serving._prefill_two)
    assert e1.stats["subfloor_pages_written"] == 0
    assert e1.stats["window_pages_offloaded"] > 0
    turn2 = turn1 + out1["t1"] + _prompt(21, 7)
    e2 = _engine(params, cfg, shm_conn, "hit")
    out2 = e2.run([_req("t2", turn2, 12)])
    assert e2.stats["prefix_hit_pages"] == 8
    assert e2.stats["snapshots_restored"] == 1
    # 8 full pages x (k, v) + the last band's 2 pages x 2 layers x (k, v)
    assert e2.stats["restored_pages"] == 8 * 2 + 2 * 2 * 2
    assert e2.stats["restore_trimmed_pages"] == 6
    assert e2.stats["prefill_tokens"] == len(turn2) - 8 * PAGE
    assert _worst(e2, params, "t2", turn2, out2["t2"]) < TOL
    cold = _engine(params, cfg)
    ref = cold.run([_req("t2", turn2, 12)])
    assert out2["t2"] == ref["t2"]
    diff = np.abs(np.stack(e2.rows["t2"]) - np.stack(cold.rows["t2"]))
    assert float(diff.max()) < TOL
    # first_token_logits goes through the same two programs
    turn3 = turn2 + out2["t2"] + _prompt(22, 5)
    row, hit = e2.first_token_logits(turn3)
    assert hit == (len(turn2) + 11) // PAGE
    want = _ref_rows(params, turn3, [len(turn3) - 1])[0]
    assert float(np.abs(row - want).max()) < TOL
    row, hit = cold.first_token_logits(turn3)
    assert hit == 0 and float(np.abs(row - want).max()) < TOL


def _keys_of(eng, prompt, n_pages, what):
    """Store keys of a stored prefix of `prompt`: its snapshot at depth
    `n_pages`, a banded layer's last page, or the full layer's."""
    digests = eng._digests(prompt, n_pages)
    if what == "snapshot":
        return serving.snapshot_keys(digests[-1], 2, 3)
    layer = eng._win_layers[0] if what == "band" else eng._full_layers[0]
    return serving.content_page_keys_by_page(digests[-1:], [layer])


@pytest.mark.parametrize("what", ["snapshot", "band", "full"])
def test_a_prefix_that_lacks_a_kind_is_cut_back_or_cold(params, cfg,
                                                        shm_conn, what):
    """The snapshot, a page of the band or a page of the full layer
    evicted and the rest not: the request is admitted cold (or its hit
    cut back), counted, never answered wrongly."""
    turn1 = _prompt(40, 45)
    e1 = _engine(params, cfg, shm_conn, "evict-" + what)
    out1 = e1.run([_req("t1", turn1, 20)])
    turn2 = turn1 + out1["t1"] + _prompt(41, 4)
    e2 = _engine(params, cfg, shm_conn, "evict-" + what)
    shm_conn.delete_keys(_keys_of(e2, turn2, 8, what))
    out2 = e2.run([_req("t2", turn2, 6)])
    assert _worst(e2, params, "t2", turn2, out2["t2"]) < TOL
    assert e2.stats["prefix_hit_pages"] < 8
    assert e2.stats["snapshots_restored"] == 0
    if what == "snapshot":
        assert e2.stats["snapshot_misses"] == 1
    elif what == "band":
        assert e2.stats["restore_misses"] == 1
    else:  # the probed chain itself ends a page short: no snapshot there
        assert e2.stats["snapshot_misses"] == 1


def test_preempt_and_resume_through_the_store(params, cfg, shm_conn):
    reqs = {f"r{i}": _prompt(50 + i, 16) for i in range(2)}
    eng = _engine(params, cfg, shm_conn, "preempt", total_pages=8,
                  max_pages_per_seq=8)
    out = eng.run([_req(r, p, 24) for r, p in reqs.items()])
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["snapshots_restored"] >= 1
    for rid, prompt in reqs.items():
        assert len(out[rid]) == 24
        assert _worst(eng, params, rid, prompt, out[rid]) < TOL
    assert sorted(eng.free_pages) == list(range(1, 8))


@pytest.mark.parametrize("option,name", [
    ({"spec_k": 2}, "spec_k"), ({"host_steps": 4}, "host_steps"),
    ({"admit_piece": 16}, "admit_piece"),
    ({"quantized_store": True}, "quantized_store"),
])
def test_what_is_not_built_over_three_kinds_is_refused(params, cfg, option,
                                                       name):
    with pytest.raises(ValueError, match=name):
        ServingEngine(params, cfg, ServingConfig(**option), model=phi_flash)


def test_packed_rows_and_state_beside_two_kinds_stay_refused_elsewhere(
        params, cfg):
    """The rule admits exactly this family's combination: the same
    layers with rows that are no pairs are refused by name, for the
    state layers and for the packed rows."""
    other = dataclasses.replace(cfg, diff_attn=False)
    with pytest.raises(ValueError, match="kv_pack"):
        ServingEngine(params, other, ServingConfig(), model=phi_flash)
    unpacked = dataclasses.replace(cfg, diff_attn=False, kv_pack=1)
    with pytest.raises(ValueError, match="state layers"):
        ServingEngine(params, unpacked, ServingConfig(), model=phi_flash)


def test_verify_step_refuses_the_family(params, cfg):
    with pytest.raises(NotImplementedError):
        phi_flash.verify_step(params, cfg, jnp.zeros((1, 2), jnp.int32),
                              jnp.zeros(1, jnp.int32), None, None, None)
