"""Shared pieces of the plain float32 references: RMSNorm, half-split
RoPE, grouped-query causal attention. jax.numpy only: no kernels, no
cache, no paging, no batching. Hyper-parameters are read from the
configuration FILE (the published keys), never from the program's
config object, so the references do not depend on the program's bridge.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope(x, theta):
    """x: [T, heads, hd]; positions 0..T-1; HF rotate_half convention
    (first half / second half of the head)."""
    t, _, hd = x.shape
    half = hd // 2
    inv = jnp.exp(-jnp.log(F32(theta)) * jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention_block(x, layer, conf):
    """x + Wo . causal GQA attention(RMSNorm(x)), all in float32."""
    t = x.shape[0]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // n_h
    h = rms_norm(x, layer["ln1"], conf["rms_norm_eps"])
    q = (h @ layer["wq"].astype(F32)).reshape(t, n_h, hd)
    k = (h @ layer["wk"].astype(F32)).reshape(t, n_kv, hd)
    v = (h @ layer["wv"].astype(F32)).reshape(t, n_kv, hd)
    q, k = rope(q, conf["rope_theta"]), rope(k, conf["rope_theta"])
    group = n_h // n_kv
    mask = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for g in range(n_kv):  # one KV head at a time keeps scores small
        qg = q[:, g * group:(g + 1) * group]           # [T, group, hd]
        s = jnp.einsum("tgh,sh->gts", qg, k[:, g]) * (hd ** -0.5)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("gts,sh->tgh", p, v[:, g]))
    attn = jnp.concatenate(outs, axis=1).reshape(t, n_h * hd)
    return x + attn @ layer["wo"].astype(F32)


def embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(F32)


def logits_at(params, x, positions, conf):
    """Final norm and head at `positions` only: [len(positions), vocab]."""
    xs = rms_norm(x[positions], params["final_ln"], conf["rms_norm_eps"])
    return xs @ params["lm_head"].astype(F32)
