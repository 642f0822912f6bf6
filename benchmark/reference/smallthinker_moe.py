"""Plain float32 reference of the SmallThinker sparse decoder: full and
banded attention layers in one model, 64 small ReGLU experts of which a
token uses 6, the router placed before attention. jax.numpy only, no
kernels, no cache, no paging, no batching; nothing of the program is
imported. Hyper-parameters are read from the configuration FILE (the
published keys).

One layer, with x its input and T tokens (ISSUE 35 has the derivation
from the catalog row):

    h   = RMSNorm(x; ln1)
    q, k, v = h Wq, h Wk, h Wv                         (no bias)
    q, k = RoPE(q, k; rope_theta, rotate-half)   where rope_layout[i] = 1
    A_ij ~ exp(q_i . k_j / sqrt(head_dim)) over j <= i, and j > i - W
          where sliding_window_layout[i] = 1     (W = sliding_window_size)
    y   = x + (A v) Wo
    z   = h Wr                     <- the router reads h, not RMSNorm(y)
    S   = the k largest of z;  g = softmax(z_S)
    u   = RMSNorm(y; ln2)
    out = y + sum_{e in S} g_e Wdown_e( relu(Wgate_e u) * (Wup_e u) )

Departures from the published description, each also under `assumed`
in the configuration's file: (1) the router's input is h (the catalog
row dropped `moe_enable_early_router`; `described_as.moe`: "router
placed before attention"); (2) the gate branch's activation is ReLU
(`described_as.moe`: "sparse ReGLU"; `config` has no `hidden_act`);
(3) no "secondary" experts (no key counts any); (4) no attention bias.
softmax over the chosen logits equals softmax over all 64 renormalised
on the chosen (`moe_primary_router_apply_softmax` with
`norm_topk_prob`). A query sees at most the last W positions including
itself (transformers' and this repo's band).

Attention is computed in blocks of queries, so that T x T scores are
never held; experts are upcast one at a time and every expert runs
over every token with the gate of a token that did not choose it at
zero (no capacity, nothing dropped).

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins [P, layers] float32): margins[p, l] is the gap between the
k-th and (k+1)-th largest router LOGIT of position p in layer l, as
benchmark/reference/moe_decoder.py has it: a small gap is a near-tie
that rounding can flip, and correct.py sets such positions aside.
"""

from functools import partial

import jax
import jax.numpy as jnp

from . import common

F32 = common.F32
QUERY_BLOCK = 512
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "rms_norm_eps", "rope_theta", "sliding_window_size",
        "moe_num_active_primary_experts")


def _static(conf):
    return tuple((k, conf[k]) for k in KEYS)


@partial(jax.jit, static_argnames=("static", "rotates", "banded"))
def _attn(x, layer, static, rotates, banded):
    """(x + Wo . attention(h), h) with h = RMSNorm(x; ln1)."""
    conf = dict(static)
    t = x.shape[0]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    h = common.rms_norm(x, layer["ln1"], conf["rms_norm_eps"])
    q = (h @ layer["wq"].astype(F32)).reshape(t, n_h, hd)
    k = (h @ layer["wk"].astype(F32)).reshape(t, n_kv, hd)
    v = (h @ layer["wv"].astype(F32)).reshape(t, n_kv, hd)
    if rotates:
        q = common.rope(q, conf["rope_theta"])
        k = common.rope(k, conf["rope_theta"])
    group = n_h // n_kv
    pos = jnp.arange(t)
    outs = []
    for a in range(0, t, QUERY_BLOCK):      # blocks of queries
        qi = pos[a:a + QUERY_BLOCK]
        mask = pos[None, :] <= qi[:, None]
        if banded:
            mask &= pos[None, :] > qi[:, None] - conf["sliding_window_size"]
        heads = []
        for g in range(n_kv):               # one KV head at a time
            qg = q[a:a + QUERY_BLOCK, g * group:(g + 1) * group]
            s = jnp.einsum("tgh,sh->gts", qg, k[:, g]) * (hd ** -0.5)
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            heads.append(jnp.einsum("gts,sh->tgh", p, v[:, g]))
        outs.append(jnp.concatenate(heads, axis=1))
    attn = jnp.concatenate(outs, axis=0).reshape(t, n_h * hd)
    return x + attn @ layer["wo"].astype(F32), h


@partial(jax.jit, static_argnames=("static",))
def _route(h, y, ln2, router, static):
    """(u = RMSNorm(y; ln2), gates [T, E] with zeros off the chosen,
    margin [T]) from the router over h."""
    conf = dict(static)
    k = conf["moe_num_active_primary_experts"]
    z = h @ router.astype(F32)                               # [T, E]
    top_z, top_i = jax.lax.top_k(z, k)
    gates = jax.nn.softmax(top_z, axis=-1)
    dense = jnp.sum(jax.nn.one_hot(top_i, z.shape[-1], dtype=F32)
                    * gates[..., None], axis=1)
    zs = jnp.sort(z, axis=-1)
    return (common.rms_norm(y, ln2, conf["rms_norm_eps"]), dense,
            zs[:, -k] - zs[:, -k - 1])


@jax.jit
def _expert(u, w_gate, w_up, w_down, gate_col):
    a = jax.nn.relu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
    return (a @ w_down.astype(F32)) * gate_col[:, None]


def forward(params, conf, tokens, positions):
    static = _static(conf)
    positions = jnp.asarray(positions, jnp.int32)
    margins = []
    with jax.default_matmul_precision("highest"):
        x = common.embed(params, jnp.asarray(tokens, jnp.int32))
        for i, layer in enumerate(params["layers"]):
            y, h = _attn(x, layer, static,
                         rotates=bool(conf["rope_layout"][i]),
                         banded=bool(conf["sliding_window_layout"][i]))
            u, gates, margin = _route(h, y, layer["ln2"], layer["router"],
                                      static)
            margins.append(margin[positions])
            x = y
            for e in range(layer["e_gate"].shape[0]):
                x = x + _expert(u, layer["e_gate"][e], layer["e_up"][e],
                                layer["e_down"][e], gates[:, e])
        logits = common.logits_at(params, x, positions, dict(static))
    return logits, jnp.stack(margins, axis=1)
