"""Plain float32 reference of Phi-4-mini-flash-reasoning (HF `phi4flash`;
the SambaY decoder-hybrid-decoder of arXiv:2507.06607 with the
differential attention of arXiv:2410.05258): every row through every
layer, no cache, no paging, no skipping; the selective scan a plain
`lax.scan` over time. Layer l of L, `LN` a LayerNorm with weight and
bias, no positional encoding anywhere:

  every layer: x += mixer_l(LN1(x)); x += W_down (silu(g) * u),
               [g, u] = [W_gate, W_up] LN2(x)
  l even, l <= L/2       Mamba-1: [x', z] = W_in h;
      x' = silu(conv_K(x') + b_c); [r, B, C] = W_x x';
      dt = softplus(W_dt r + b_dt); A = -exp(A_log);
      S_t = exp(dt_t (x) A) S_{t-1} + (dt_t x'_t) (x) B_t;
      y_t = S_t C_t + D x'_t; out = W_out (y * silu(z)).
      Layer L/2's y (with the D term, BEFORE the gate) is the memory M.
  l odd, l < L/2         differential attention over a band of
      `sliding_window` positions; l = L/2 + 1 the same, full causal,
      and its K and V are what the cross layers attend:
      pair j of H/2: q1 = q[j], q2 = q[H/2 + j]; kv pair g = j // (H/G)
      of G/2: k1 = k[g], k2 = k[G/2 + g], V = [v[g] | v[G/2 + g]];
      o_j = (softmax(q1 k1^T / sqrt(hd) + mask)
             - lam softmax(q2 k2^T / sqrt(hd) + mask)) V,
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),
      lam0(l) = 0.8 - 0.6 exp(-0.3 l);
      o_j <- RMSNorm_{2 hd}(o_j; one weight a layer) (1 - lam0(l));
      out = W_o concat_j(o_j) + b_o
  l even, l >= L/2 + 2   Gated Memory Unit: out = W_2 (silu(W_1 h) * M_t)
  l odd,  l >= L/2 + 3   cross-attention: q = W_q h + b; K and V are
      layer L/2 + 1's; differential attention as above with the
      layer's own lam, norm weight and W_o, full causal mask
  logits = LN(x) E^T (tied)

Departures from the published code, none of which changes a value:
the program's parameters hold the projections' columns in the order of
its cache rows, not by halves: published q[j] is column block 4 (j //
2) + j % 2 of `wq`, q[H/2 + j] block 4 (j // 2) + 2 + j % 2; published
k[g] / v[g] is block 2 g of `wk` / `wv`, k[G/2 + g] / v[G/2 + g] block
2 g + 1 (a permutation of columns; `_by_halves` undoes it, and the
equations above run as written). `A_log` is held [N, C] and the state
[C, N] here. W_qkv is held as its three parts, W_gate_up as its two,
`conv1d.weight` [C, 1, K] as [K, C]. The layer layout is derived from
`num_hidden_layers`, `mb_per_layer` and `sliding_window` as the
configuration file's `assumed` says.

Computed in blocks of rows so that it fits beside the served weights:
queries in blocks of `Q_BLOCK` (a banded layer's block sees the keys
its band can reach and no other), the MLP in blocks of `ROW_BLOCK`,
the head in blocks of `V_BLOCK` rows of the vocabulary; weights are
upcast layer by layer inside the jitted layer functions.

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins None). `tokens` is a 1-D int array; its tail may be padding
(every layer is causal). Imports nothing of the program.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = common.F32
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "layer_norm_eps", "sliding_window", "mamba_d_state", "mamba_d_conv",
        "mamba_expand", "mamba_dt_rank")
Q_BLOCK = 256
ROW_BLOCK = 2048
V_BLOCK = 16384


def layer_kinds(conf):
    """Per layer (kind, band): the layout `mb_per_layer` and
    `sliding_window` give."""
    n = conf["num_hidden_layers"]
    half = n // 2
    out = []
    for l in range(n):
        mamba = l % conf["mb_per_layer"] == 0
        if l >= half + 2:
            out.append(("gmu" if mamba else "cross", 0))
        elif mamba:
            out.append(("mamba1", 0))
        else:
            out.append(("attention",
                        conf["sliding_window"] if l < half else 0))
    return out


def _static(conf):
    return tuple((k, conf[k]) for k in KEYS)


def layer_norm(x, w, b, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * w.astype(F32) + b.astype(F32)


def _blocks(fn, x, block):
    """fn over row blocks of x [T, ...] (T padded up to whole blocks)."""
    t = x.shape[0]
    pad = -t % block
    xb = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, xb.reshape(-1, block, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:t]


def _mlp(x, layer, conf):
    def rows(xb):
        h = layer_norm(xb, layer["ln2"], layer["ln2_b"],
                       conf["layer_norm_eps"])
        gate = jax.nn.silu(h @ layer["w_gate"].astype(F32))
        return (gate * (h @ layer["w_up"].astype(F32))) \
            @ layer["w_down"].astype(F32)

    return x + _blocks(rows, x, min(ROW_BLOCK, x.shape[0]))


def _mamba(h, layer, conf):
    """(out, the memory y [T, C])."""
    t = h.shape[0]
    c = conf["mamba_expand"] * conf["hidden_size"]
    n, k, r = conf["mamba_d_state"], conf["mamba_d_conv"], \
        conf["mamba_dt_rank"]
    xz = h @ layer["in_proj"].astype(F32)
    xs, z = xz[:, :c], xz[:, c:]
    padded = jnp.concatenate([jnp.zeros((k - 1, c), F32), xs])
    w = layer["conv_w"].astype(F32)                       # [K, C]
    conv = sum(padded[j:j + t] * w[j] for j in range(k))
    xs = jax.nn.silu(conv + layer["conv_b"].astype(F32))
    sel = xs @ layer["x_proj"].astype(F32)
    dt = jax.nn.softplus(sel[:, :r] @ layer["dt_proj"].astype(F32)
                         + layer["dt_bias"].astype(F32))   # [T, C]
    B, C = sel[:, r:r + n], sel[:, r + n:]
    A = -jnp.exp(layer["A_log"].astype(F32)).T             # [C, N]

    def one(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(one, jnp.zeros((c, n), F32), (xs, dt, B, C))
    y = y + layer["D"].astype(F32) * xs
    return (y * jax.nn.silu(z)) @ layer["out_proj"].astype(F32), y


def _by_halves(heads):
    """Column blocks of the program's `wq` in the published order:
    first the map-1 half, then the map-2 half (the program's query
    heads come in groups of 4: two pairs, map 1 then map 2)."""
    half = heads // 2
    return np.array([4 * (j // 2) + j % 2 for j in range(half)]
                    + [4 * (j // 2) + 2 + j % 2 for j in range(half)])


def _kv(h, layer, conf):
    """K and V of every position in the published order [T, G, hd]
    (the program's kv heads come in pairs k1, k2)."""
    t = h.shape[0]
    g = conf["num_key_value_heads"]
    hd = conf["hidden_size"] // conf["num_attention_heads"]
    order = np.array(list(range(0, g, 2)) + list(range(1, g, 2)))
    k = (h @ layer["wk"].astype(F32) + layer["bk"].astype(F32))
    v = (h @ layer["wv"].astype(F32) + layer["bv"].astype(F32))
    return (k.reshape(t, g, hd)[:, order], v.reshape(t, g, hd)[:, order])


def _diff_attention(h, layer, conf, lam0, k, v, band):
    """Differential attention of every position of h over k, v [T, G,
    hd] (this layer's own or, for a cross layer, the shared ones)."""
    t = h.shape[0]
    n_h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["hidden_size"] // n_h
    pairs, kv_pairs = n_h // 2, g // 2
    q = (h @ layer["wq"].astype(F32) + layer["bq"].astype(F32))
    q = q.reshape(t, n_h, hd)[:, _by_halves(n_h)]
    lam = jnp.exp(jnp.sum(layer["lam_q1"] * layer["lam_k1"])) \
        - jnp.exp(jnp.sum(layer["lam_q2"] * layer["lam_k2"])) + lam0
    of = np.arange(pairs) // (pairs // kv_pairs)          # pair -> kv pair
    k1, k2 = k[:, :kv_pairs][:, of], k[:, kv_pairs:][:, of]   # [T, P, hd]
    vv = jnp.concatenate([v[:, :kv_pairs], v[:, kv_pairs:]],
                         axis=-1)[:, of]                       # [T, P, 2hd]
    # a block of queries sees the keys [start, start + span): every
    # key up to its own end (of the rows padded up to whole blocks,
    # `tp`: counted from `t`, the last block of a length that is no
    # multiple of the block lost the first keys), or what its band
    # can reach
    block = min(Q_BLOCK, t)
    tp = t + (-t % block)
    span = tp if not band else min(tp, block + band)
    front = span - block  # keys before the block's first query
    pad = ((front, tp - t), (0, 0), (0, 0))
    k1, k2, vv = (jnp.pad(a, pad) for a in (k1, k2, vv))

    def rows(inp):
        q0, qb = inp                                      # [block, H, hd]
        ks = [jax.lax.dynamic_slice_in_dim(a, q0, span) for a in
              (k1, k2, vv)]
        qpos = q0 + jnp.arange(block)[:, None]
        kpos = q0 - front + jnp.arange(span)[None, :]
        mask = (kpos <= qpos) & (kpos >= 0)
        if band:
            mask &= qpos - kpos < band

        def soft(qm, km):
            s = jnp.einsum("tph,sph->pts", qm, km) * hd ** -0.5
            return jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)

        p = soft(qb[:, :pairs], ks[0]) - lam * soft(qb[:, pairs:], ks[1])
        return jnp.einsum("pts,spd->tpd", p, ks[2])

    qb = jnp.pad(q, ((0, tp - t), (0, 0), (0, 0))).reshape(
        -1, block, n_h, hd)
    o = jax.lax.map(rows, (jnp.arange(0, tp, block), qb))
    o = o.reshape(tp, pairs, 2 * hd)[:t]
    o = common.rms_norm(o, layer["sub_ln"], conf["layer_norm_eps"]) \
        * (1.0 - lam0)
    return o.reshape(t, pairs * 2 * hd) @ layer["wo"].astype(F32) \
        + layer["bo"].astype(F32)


@partial(jax.jit, static_argnames=("static", "kind", "band"))
def _layer(x, layer, shared, lam0, static, kind, band):
    """One layer. `shared`: (memory, k, v) as the layers below left
    them; `lam0`: lam0(l) of its depth. Returns (x, shared)."""
    conf = dict(static)
    memory, k, v = shared
    h = layer_norm(x, layer["ln1"], layer["ln1_b"], conf["layer_norm_eps"])
    if kind == "mamba1":
        out, memory = _mamba(h, layer, conf)
    elif kind == "gmu":
        out = (jax.nn.silu(h @ layer["gmu_in"].astype(F32)) * memory) \
            @ layer["gmu_out"].astype(F32)
    else:
        if kind == "attention":
            own = _kv(h, layer, conf)
            if not band:
                k, v = own  # the whole-context cache the cross layers read
        else:
            own = (k, v)
        out = _diff_attention(h, layer, conf, lam0, *own, band)
    return _mlp(x + out, layer, conf), (memory, k, v)


@partial(jax.jit, static_argnames=("eps",))
def _head(xs, w, b, embed, eps):
    """The final norm and the tied head, the embedding upcast a block
    of `V_BLOCK` rows at a time inside ONE program (upcast whole and
    transposed, eagerly, it stood twice in float32 beside the served
    weights: 2 x 2.05 GB at 200,064 rows of 2,560)."""
    xs = layer_norm(xs, w, b, eps)
    return jnp.concatenate(
        [xs @ embed[i:i + V_BLOCK].astype(F32).T
         for i in range(0, embed.shape[0], V_BLOCK)], axis=1)


def forward(params, conf, tokens, positions):
    static = _static(conf)
    with jax.default_matmul_precision("highest"):
        x = common.embed(params, jnp.asarray(tokens, jnp.int32))
        shared = (None, None, None)
        for depth, (layer, (kind, band)) in enumerate(
                zip(params["layers"], layer_kinds(conf))):
            lam0 = 0.8 - 0.6 * float(np.exp(-0.3 * depth))
            x, shared = _layer(x, layer, shared, F32(lam0), static, kind,
                               band)
        logits = _head(x[jnp.asarray(positions, jnp.int32)],
                       params["final_ln"], params["final_ln_b"],
                       params["embed"], conf["layer_norm_eps"])
    return logits, None
