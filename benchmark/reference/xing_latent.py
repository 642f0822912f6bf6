"""Plain float32 reference of the Xing4.0 sparse decoder: latent
attention, four residual streams mixed by manifold-constrained
hyper-connections, a sigmoid router with a selection bias and a shared
expert, YaRN rotary. jax.numpy only: no kernels, no cache, no pages, no
pieces, no batching; nothing of the program is imported.
Hyper-parameters are read from the configuration FILE (the published
keys).

One layer, X its input [T, n, C] (n = hc_mult streams), F the sublayer
(attention, then feed-forward; each has coefficients of its own):

    xt     = RMSNorm(vec(X); hc.norm)                       [T, nC]
    Hpre   = sigmoid(a0 (xt P)[:n] + b[:n])                 [T, n]
    Hpost  = 2 sigmoid(a1 (xt P)[n:2n] + b[n:2n])           [T, n]
    M      = exp(clamp(a2 mat((xt P)[2n:]) + b[2n:]))       [T, n, n]
    hc_sinkhorn_iters times: M /= colsum(M) + eps; M /= rowsum(M) + eps
    x_in   = sum_i Hpre[i] X[i];   y = F(x_in)
    X'[i]  = sum_j M[i, j] X[j] + Hpost[i] y
    X_0[i] = embed(token);  logits = RMSNorm(sum_i X_L[i]) Whead

    attention (h = RMSNorm(x_in; ln1)):
    q = RMSNorm(h Wqa) Wqb -> q_nope [H, nope], q_pe [H, rope]
    c = RMSNorm((h Wkva)[:R]);  k_pe = (h Wkva)[R:]      ONE for all heads
    q_pe, k_pe = RoPE with YaRN's frequencies (half-split pairs)
    k[h] = [c Wkvb_k[h]; k_pe], v[h] = c Wkvb_v[h], q[h] = [q_nope; q_pe]
    A_ij ~ exp(s q_i . k_j), j <= i, s = (nope + rope) ** -0.5 m ** 2,
    m = 0.1 mscale_all_dim ln(factor) + 1;   out = concat_h(A v[h]) Wo

    feed-forward (u = RMSNorm(x_in; ln2)): the first
    `first_k_dense_replace` layers SwiGLU `intermediate_size` wide; the
    others sc = sigmoid(u Wr), S = the k largest of sc + bias,
    g_e = routed_scaling_factor sc_e / sum_S sc, and
    out = sum_S g_e Wdown_e(silu(Wgate_e u) * Wup_e u) + shared(u).

Departures and assumptions, each also under `assumed` in the
configuration's file: the stack's entry and exit (embedding copied to
the streams, streams summed before the final norm), `hc_eps` in both
Sinkhorn denominators and the clamp before exp, the rotary's lane
pairing (half-split), no multi-token-prediction module.

Attention is UNABSORBED over the whole sequence (K and V of every head
are built from c). To fit 33k tokens beside a serving engine the
streams are held as blocks of TOKEN_BLOCK tokens, attention runs a
block of queries and a group of heads at a time, the experts run over
MOE_BLOCKS blocks at a time, and an expert runs over the tokens that
chose it (their indices found on the host, padded
to a multiple of ROW_PAD with gate 0): no capacity, nothing dropped.

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins [P, layers] float32): margins[p, l] is the gap between the k-th
and (k+1)-th largest of sc + bias of position p in layer l (a dense
layer: a large constant): a small gap is a near-tie that rounding can
flip, and correct.py sets such positions aside.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = common.F32
TOKEN_BLOCK = 4096
QUERY_BLOCK = 512
HEAD_GROUP = 4
MOE_BLOCKS = 4      # token blocks the experts run over at once
KEY_BUCKET = 4 * TOKEN_BLOCK  # keys a block of queries is handed, up to
ROW_PAD = 1024
VOCAB_BLOCK = 16384
NO_ROUTER = 1e9
KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "rope_theta", "num_experts_per_tok", "routed_scaling_factor",
        "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
        "mhc_h_res_clamp_max")


def _static(conf):
    rs = conf.get("rope_scaling") or {}
    return tuple((k, conf[k]) for k in KEYS) + (
        ("yarn", tuple(sorted(rs.items()))),)


def yarn_inv_freq(dim, theta, rs):
    """Rotary frequencies [dim / 2] under YaRN (numpy, float64)."""
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not rs:
        return inv

    def turns_dim(turns):
        return dim * np.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(turns_dim(rs["beta_fast"])), 0)
    high = min(np.ceil(turns_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inv / rs["factor"] * ramp + inv * (1 - ramp)


def _mscale(rs, key):
    if not rs or rs["factor"] <= 1:
        return 1.0
    return 0.1 * rs.get(key, 0) * np.log(rs["factor"]) + 1.0


def _rope(x, pos, conf):
    """x: [T, heads, dim] at positions pos [T]; half-split pairs."""
    rs = dict(conf["yarn"])
    dim = x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(dim, conf["rope_theta"], rs), F32)
    mult = _mscale(rs, "mscale") / _mscale(rs, "mscale_all_dim")
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * mult)[:, None, :]
    sin = (jnp.sin(ang) * mult)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _coefficients(x, hc, conf):
    """(Hpre [T, n], Hpost [T, n], Hres [T, n, n]) of streams x
    [T, n, C]."""
    t, n, c = x.shape
    xt = common.rms_norm(x.reshape(t, n * c), hc["norm"],
                         conf["rms_norm_eps"])
    z = xt @ hc["proj"].astype(F32)
    a, b = hc["a"].astype(F32), hc["bias"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    m = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    m = jnp.exp(jnp.clip(m, conf["mhc_h_res_clamp_min"],
                         conf["mhc_h_res_clamp_max"]))
    for _ in range(conf["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + conf["hc_eps"])
        m = m / (jnp.sum(m, axis=2, keepdims=True) + conf["hc_eps"])
    return pre, post, m


@partial(jax.jit, static_argnames=("static",))
def _sublayer_in(x, hc, ln, static):
    """(RMSNorm(x_in; ln), Hpost, Hres) of one block of streams."""
    conf = dict(static)
    pre, post, res = _coefficients(x, hc, conf)
    x_in = sum(pre[:, i, None] * x[:, i] for i in range(x.shape[1]))
    return common.rms_norm(x_in, ln, conf["rms_norm_eps"]), post, res


@partial(jax.jit, donate_argnums=(0,))
def _sublayer_out(x, y, post, res):
    """X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y, stream by stream (a
    4 x 4 product a token is no matrix product worth the name)."""
    n = x.shape[1]
    return jnp.stack([
        sum(res[:, i, j, None] * x[:, j] for j in range(n))
        + post[:, i, None] * y for i in range(n)], axis=1)


@partial(jax.jit, static_argnames=("static",))
def _latents(h, wkva, kv_ln, pos0, static):
    """(c [T, R], k_pe [T, rope] rotated) of one block."""
    conf = dict(static)
    r = conf["kv_lora_rank"]
    ckv = h @ wkva.astype(F32)
    pos = pos0 + jnp.arange(h.shape[0])
    k_pe = _rope(ckv[:, None, r:], pos, conf)[:, 0]
    return common.rms_norm(ckv[:, :r], kv_ln, conf["rms_norm_eps"]), k_pe


@partial(jax.jit, static_argnames=("static",))
def _attend(h, c, k_pe, layer, pos0, static):
    """Wo . attention of one block of queries (h [tb, C], at positions
    pos0 ..) over the keys c, k_pe of positions 0 .. S - 1."""
    conf = dict(static)
    tb = h.shape[0]
    n_h = conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    vd, r = conf["v_head_dim"], conf["kv_lora_rank"]
    cq = common.rms_norm(h @ layer["wqa"].astype(F32), layer["q_ln"],
                         conf["rms_norm_eps"])
    q = (cq @ layer["wqb"].astype(F32)).reshape(tb, n_h, nope + rope)
    qpos = pos0 + jnp.arange(tb)
    q_pe = _rope(q[..., nope:], qpos, conf)
    q_nope = q[..., :nope]
    m = _mscale(dict(conf["yarn"]), "mscale_all_dim")
    scale = (nope + rope) ** -0.5 * m * m
    wkvb = layer["wkvb"].astype(F32).reshape(r, n_h, nope + vd)
    kpos = jnp.arange(c.shape[0])
    qb = math.gcd(tb, QUERY_BLOCK)

    def blocks(a):
        return a.reshape(tb // qb, qb, *a.shape[1:])

    groups = []
    for g in range(0, n_h, HEAD_GROUP):
        w = wkvb[:, g:g + HEAD_GROUP]
        k_nope = jnp.einsum("sr,rhd->shd", c, w[..., :nope])
        v = jnp.einsum("sr,rhd->shd", c, w[..., nope:])

        def one(args, k_nope=k_nope, v=v):
            qn, qp, pos = args             # one block of queries
            s = jnp.einsum("thd,shd->hts", qn, k_nope)
            s = s + jnp.einsum("thd,sd->hts", qp, k_pe)
            mask = kpos[None, :] <= pos[:, None]
            p = jax.nn.softmax(jnp.where(mask[None], s * scale, -jnp.inf),
                               axis=-1)
            return jnp.einsum("hts,shd->thd", p, v)

        out = jax.lax.map(one, (blocks(q_nope[:, g:g + HEAD_GROUP]),
                                blocks(q_pe[:, g:g + HEAD_GROUP]),
                                blocks(qpos)))
        groups.append(out.reshape(tb, *out.shape[2:]))
    attn = jnp.concatenate(groups, axis=1).reshape(tb, n_h * vd)
    return attn @ layer["wo"].astype(F32)


@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    a = jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
    return a @ w_down.astype(F32)


@partial(jax.jit, static_argnames=("static",))
def _route(u, router, bias, static):
    """(gates [T, E] with zeros off the chosen, margin [T])."""
    conf = dict(static)
    k = conf["num_experts_per_tok"]
    sc = jax.nn.sigmoid(u @ router.astype(F32))
    biased = sc + bias.astype(F32)
    _, top_i = jax.lax.top_k(biased, k)
    chosen = jnp.sum(jax.nn.one_hot(top_i, sc.shape[-1], dtype=F32), axis=1)
    picked = sc * chosen
    gates = conf["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    zs = jnp.sort(biased, axis=-1)
    return gates, zs[:, -k] - zs[:, -k - 1]


@partial(jax.jit, donate_argnums=(0,))
def _expert_into(out, u, idx, gate, w_gate, w_up, w_down):
    """out[idx] += gate * expert(u[idx])."""
    y = _swiglu(u[idx], w_gate, w_up, w_down) * gate[:, None]
    return out.at[idx].add(y)


def _experts(u, layer, static):
    """(shared(u) + the routed experts' sum [T, C], margin [T])."""
    gates, margin = _route(u, layer["router"], layer["router_bias"], static)
    out = _swiglu(u, layer["s_gate"], layer["s_up"], layer["s_down"])
    g_host = np.asarray(gates)
    for e in range(g_host.shape[1]):
        idx = np.nonzero(g_host[:, e])[0]
        if not len(idx):
            continue
        pad = -len(idx) % ROW_PAD
        gate = np.pad(g_host[idx, e], (0, pad))      # gate 0: adds nothing
        idx = np.pad(idx, (0, pad)).astype(np.int32)
        out = _expert_into(out, u, jnp.asarray(idx), jnp.asarray(gate),
                           layer["e_gate"][e], layer["e_up"][e],
                           layer["e_down"][e])
    return out, margin


def forward(params, conf, tokens, positions):
    static = _static(conf)
    n = conf["hc_mult"]
    positions = np.asarray(positions, np.int64)
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    starts = list(range(0, t, TOKEN_BLOCK))
    margins = []
    with jax.default_matmul_precision("highest"):
        x = []
        for a in starts:
            e = common.embed(params, tokens[a:a + TOKEN_BLOCK])
            x.append(jnp.repeat(e[:, None], n, axis=1))
        for i, layer in enumerate(params["layers"]):
            # the attention sublayer: every block's latents first (its
            # normalised input is made again in the second pass: the
            # coefficients are cheap, 0.5 GB of inputs held are not)
            cs, pes = [], []
            for a, xb in zip(starts, x):
                h, _, _ = _sublayer_in(xb, layer["hc_attn"], layer["ln1"],
                                       static)
                c, k_pe = _latents(h, layer["wkva"], layer["kv_ln"], a,
                                   static)
                cs.append(c), pes.append(k_pe)
            c_all, pe_all = jnp.concatenate(cs), jnp.concatenate(pes)
            del cs, pes, h
            attn = {k: layer[k] for k in ("wqa", "q_ln", "wqb", "wkvb",
                                          "wo")}
            for b, a in enumerate(starts):
                h, post, res = _sublayer_in(x[b], layer["hc_attn"],
                                            layer["ln1"], static)
                # keys up to the end of the block's bucket of KEY_BLOCKS
                # blocks (the mask cuts at the query's own position): a
                # few shapes to compile, not one a block
                end = min(t, -(-(a + h.shape[0]) // KEY_BUCKET) * KEY_BUCKET)
                y = _attend(h, c_all[:end], pe_all[:end], attn, a, static)
                x[b] = _sublayer_out(x[b], y, post, res)
            del c_all, pe_all, h, y
            # the feed-forward sublayer, MOE_BLOCKS blocks at a time
            margin = []
            for g in range(0, len(x), MOE_BLOCKS):
                us, mixes = [], []
                for xb in x[g:g + MOE_BLOCKS]:
                    u, post, res = _sublayer_in(xb, layer["hc_ffn"],
                                                layer["ln2"], static)
                    us.append(u), mixes.append((post, res))
                if i < conf["first_k_dense_replace"]:
                    ys = [_swiglu(u, layer["w_gate"], layer["w_up"],
                                  layer["w_down"]) for u in us]
                else:
                    y, m = _experts(jnp.concatenate(us), layer, static)
                    ys = [y[a:a + TOKEN_BLOCK]
                          for a in range(0, y.shape[0], TOKEN_BLOCK)]
                    margin.append(np.asarray(m))
                    del y
                del us, u
                for k, (yb, mix) in enumerate(zip(ys, mixes)):
                    x[g + k] = _sublayer_out(x[g + k], yb, *mix)
                del ys, mixes
            margins.append(np.concatenate(margin)[positions] if margin
                           else np.full(len(positions), NO_ROUTER,
                                        np.float32))
        rows = jnp.stack([
            jnp.sum(x[p // TOKEN_BLOCK][p % TOKEN_BLOCK], axis=0)
            for p in positions])
        del x
        xs = common.rms_norm(rows, params["final_ln"], conf["rms_norm_eps"])
        head = params["lm_head"]
        logits = jnp.concatenate([
            xs @ head[:, v:v + VOCAB_BLOCK].astype(F32)
            for v in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)
    return logits, jnp.asarray(np.stack(margins, axis=1))
