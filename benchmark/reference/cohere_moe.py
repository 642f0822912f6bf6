"""Plain float32 reference of the Command A+ sparse decoder
(`model_type: cohere2_moe`) as ONE CHIP'S SHARE of it: a parallel block
over window and full attention layers, 128 query heads on 8 kv heads,
a sigmoid router over all the published experts of which this chip
holds some, four averaged shared experts, a tied head. jax.numpy only,
no kernels, no cache, no paging, no batching; nothing of the program is
imported. Hyper-parameters are read from the configuration FILE (the
published keys and its `expert_share` group).

One layer, with x its input and T tokens (ISSUE 42 has the derivation
from the catalog row):

    h   = LayerNorm(x; ln1)     (x - mean) / sqrt(var + layer_norm_eps)
                                times the weight, no bias, float32
    q, k, v = h Wq, h Wk, h Wv                          (no bias, no q/k norm)
    layer_types[i] = "sliding_attention":
        q, k rotated in ADJACENT pairs (rope_gptj, rope_theta, the whole
        head: rotary_pct 1); A_ij over j <= i and j > i - sliding_window
    layer_types[i] = "full_attention":
        NO positional embedding; A_ij over j <= i
    A_ij ~ exp(q_i . k_j / sqrt(head_dim))
    a   = (A v) Wo
    s   = sigmoid(h Wr)          over ALL `router_width` experts
    S   = the num_experts_per_tok largest of s (no selection bias)
    g_e = s_e / sum_{e' in S} s_e'                      (norm_topk_prob)
    m   = sum_{e in S, e HELD HERE} g_e Wdown_e(silu(Wgate_e h) * (Wup_e h))
          + (1 / num_shared_experts) sum_j Wdown_j(silu(Wgate_j h) * (Wup_j h))
    out = x + a + m              the block is PARALLEL: one norm a layer

    logits = logit_scale * LayerNorm(x_L; final_ln) E^T   (E: the embedding)

The share: `params` hold the experts with ids first_expert ..
first_expert + num_experts - 1 of the `router_width` the router scores
(`expert_share` in the file; without it every expert is held). A chosen
expert that is absent adds nothing and nothing stands in for it; the
gates are normalised over ALL the chosen, so the eight chips' parts,
with the shared mean counted once, add up to the uncut layer
(tests/test_cohere.py). E is the rows of the vocabulary held here.

Departures from the published description, each also under `assumed` in
the configuration's file: (1) the shared experts' outputs are AVERAGED
(`shared_expert_combination_strategy: "average"`, `described_as`:
"shared experts averaged"); the other reading, (routed + shared) / 2,
is not taken; (2) a query of a sliding layer sees at most the last
`sliding_window` positions including itself (transformers' and this
repo's band); (3) `intermediate_size` is one expert's width, routed and
shared alike (the catalog's note); (4) the vision tower is no part of
the language model's config and is left out.

Attention runs in blocks of queries, projected block by block, so that
neither T x T scores nor T x 16,384 queries are held; experts and
shared experts are upcast one at a time, and every held expert runs
over every token with the gate of a token that did not choose it at
zero (no capacity, nothing dropped).

forward(params, conf, tokens, positions) -> (logits [P, vocab held]
float32, margins [P, layers] float32): margins[p, l] is the gap between
the k-th and (k+1)-th largest router SCORE of position p in layer l,
over the WHOLE router: a small gap is a near-tie that rounding can
flip, and correct.py sets such positions aside.
"""

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "layer_norm_eps", "rope_theta", "sliding_window",
        "num_experts_per_tok")


def _static(conf):
    return tuple((k, conf[k]) for k in KEYS)


def layer_norm(x, w, eps):
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope_adjacent(x, pos, theta):
    """x: [T, heads, hd] at positions `pos` [T]; frequency i turns the
    lanes (2 i, 2 i + 1)."""
    t, n, hd = x.shape
    half = hd // 2
    inv = jnp.exp(-jnp.log(F32(theta)) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(t, n, hd)


@partial(jax.jit, static_argnames=("static", "sliding"))
def _attn(h, layer, static, sliding):
    """attention(h) Wo for the whole sequence, a block of queries at a
    time."""
    conf = dict(static)
    t = h.shape[0]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    group = n_h // n_kv
    pos = jnp.arange(t)
    k = (h @ layer["wk"].astype(F32)).reshape(t, n_kv, hd)
    v = (h @ layer["wv"].astype(F32)).reshape(t, n_kv, hd)
    if sliding:
        k = rope_adjacent(k, pos, conf["rope_theta"])
    wq, wo = layer["wq"].astype(F32), layer["wo"].astype(F32)
    outs = []
    for a in range(0, t, QUERY_BLOCK):
        qi = pos[a:a + QUERY_BLOCK]
        q = (h[a:a + QUERY_BLOCK] @ wq).reshape(-1, n_h, hd)
        mask = pos[None, :] <= qi[:, None]
        if sliding:
            q = rope_adjacent(q, qi, conf["rope_theta"])
            mask &= pos[None, :] > qi[:, None] - conf["sliding_window"]
        heads = []
        for g in range(n_kv):               # one KV head at a time
            qg = q[:, g * group:(g + 1) * group]
            s = jnp.einsum("tgh,sh->gts", qg, k[:, g]) * (hd ** -0.5)
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            heads.append(jnp.einsum("gts,sh->tgh", p, v[:, g]))
        outs.append(jnp.concatenate(heads, axis=1).reshape(-1, n_h * hd)
                    @ wo)
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnames=("k",))
def _route(h, router, k):
    """(gates [T, router width] with zeros off the chosen, margin [T])
    of the sigmoid router over h."""
    s = jax.nn.sigmoid(h @ router.astype(F32))
    top_s, top_i = jax.lax.top_k(s, k)
    gates = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    dense = jnp.sum(jax.nn.one_hot(top_i, s.shape[-1], dtype=F32)
                    * gates[..., None], axis=1)
    ss = jnp.sort(s, axis=-1)
    return dense, ss[:, -k] - ss[:, -k - 1]


@jax.jit
def _expert(h, w_gate, w_up, w_down, weight):
    """weight[:, None] * Wdown(silu(Wgate h) * (Wup h))."""
    a = jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))
    return (a @ w_down.astype(F32)) * weight[:, None]


def layer_forward(x, layer, conf, i):
    """(x + a + m, the router's margins [T]) of layer i."""
    static = _static(conf)
    share = conf.get("expert_share") or {}
    first = int(share.get("first_expert", 0))
    h = layer_norm(x, layer["ln1"], conf["layer_norm_eps"])
    out = x + _attn(h, layer, static,
                    sliding=conf["layer_types"][i] == "sliding_attention")
    gates, margin = _route(h, layer["router"], conf["num_experts_per_tok"])
    for e in range(layer["e_gate"].shape[0]):       # the experts held
        out = out + _expert(h, layer["e_gate"][e], layer["e_up"][e],
                            layer["e_down"][e], gates[:, first + e])
    n_s = conf.get("num_shared_experts", 0)
    if n_s:
        ff = layer["s_gate"].shape[1] // n_s
        mean = jnp.full((x.shape[0],), 1.0 / n_s, F32)
        for j in range(n_s):
            cols = slice(j * ff, (j + 1) * ff)
            out = out + _expert(h, layer["s_gate"][:, cols],
                                layer["s_up"][:, cols],
                                layer["s_down"][cols], mean)
    return out, margin


def forward(params, conf, tokens, positions):
    positions = jnp.asarray(positions, jnp.int32)
    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens, jnp.int32),
                     axis=0).astype(F32)
        for i, layer in enumerate(params["layers"]):
            x, margin = layer_forward(x, layer, conf, i)
            margins.append(margin[positions])
        xs = layer_norm(x[positions], params["final_ln"],
                        conf["layer_norm_eps"])
        logits = F32(conf.get("logit_scale", 1)) \
            * (xs @ params["embed"].astype(F32).T)
    return logits, jnp.stack(margins, axis=1)
