"""Plain float32 reference of the granite-4.0-h family (HF
`granitemoehybrid` with `num_local_experts` 0): Mamba-2 layers with an
attention layer where `layer_types` says, a gated MLP in every layer,
no positional embedding, four scalar multipliers, tied embedding.

Per the published config and modeling code:
  x0 = embedding_multiplier * E[tok]
  every layer: x += residual_multiplier * mixer(rmsnorm(x));
               x += residual_multiplier * mlp(rmsnorm(x))
  mlp(u) = (silu(g) * v) W_out, [g, v] = u W_in
  attention: GQA, causal, no rotary, softmax scale attention_multiplier
  Mamba-2: [z, xBC, dt] = u W_in; xBC = silu(conv1d_causal(xBC) + b);
    x, B, C = split(xBC); dt = softplus(dt + dt_bias); A = -exp(A_log);
    per head h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,
    y_t = h_t C_t + D x_t; y = rmsnorm(y * silu(z)) over the whole
    inner width (one group); out = y W_out
  logits = rmsnorm(x) E^T / logits_scaling

The recurrence runs token by token (`lax.scan` over positions), not in
the chunked form the program uses. Departures from the published code,
none of which changes a value: `input_linear` is held as its two halves
(`w_gate`, `w_up`), `conv1d.weight` [C, 1, K] as [K, C]; the clamp of
dt to `time_step_limit` = (0, inf) is a no-op and left out. Weights are
upcast layer by layer inside the jitted layer functions, so the
reference fits beside the bf16 weights.

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins None). `tokens` is a 1-D int array; its tail may be padding
(every layer is causal, so padding is inert for earlier positions).
Imports nothing of the program.
"""

from functools import partial

import jax
import jax.numpy as jnp

from . import common

F32 = common.F32
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "rms_norm_eps", "attention_multiplier", "residual_multiplier",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
        "mamba_d_conv")


def _static(conf):
    return tuple((k, conf[k]) for k in KEYS)


def _mlp(x, layer, conf):
    h = common.rms_norm(x, layer["ln2"], conf["rms_norm_eps"])
    gate = jax.nn.silu(h @ layer["w_gate"].astype(F32))
    up = h @ layer["w_up"].astype(F32)
    out = (gate * up) @ layer["w_down"].astype(F32)
    return x + conf["residual_multiplier"] * out


def _attention(x, layer, conf):
    """Causal GQA without positions, scores scaled by
    attention_multiplier."""
    t = x.shape[0]
    n_h, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["hidden_size"] // n_h
    h = common.rms_norm(x, layer["ln1"], conf["rms_norm_eps"])
    q = (h @ layer["wq"].astype(F32)).reshape(t, n_h, hd)
    k = (h @ layer["wk"].astype(F32)).reshape(t, n_kv, hd)
    v = (h @ layer["wv"].astype(F32)).reshape(t, n_kv, hd)
    group = n_h // n_kv
    mask = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for g in range(n_kv):  # one KV head at a time keeps scores small
        qg = q[:, g * group:(g + 1) * group]
        s = jnp.einsum("tgh,sh->gts", qg, k[:, g]) \
            * conf["attention_multiplier"]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("gts,sh->tgh", p, v[:, g]))
    attn = jnp.concatenate(outs, axis=1).reshape(t, n_h * hd)
    return attn @ layer["wo"].astype(F32)


def _mamba(x, layer, conf):
    t = x.shape[0]
    H, P = conf["mamba_n_heads"], conf["mamba_d_head"]
    N, G = conf["mamba_d_state"], conf["mamba_n_groups"]
    K = conf["mamba_d_conv"]
    di = H * P
    u = common.rms_norm(x, layer["ln1"], conf["rms_norm_eps"])
    zxbcdt = u @ layer["in_proj"].astype(F32)
    z = zxbcdt[:, :di]
    xbc = zxbcdt[:, di:di + di + 2 * G * N]
    dt = zxbcdt[:, di + di + 2 * G * N:]
    # Causal depthwise convolution: position t sees inputs t-K+1 .. t.
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    w = layer["conv_w"].astype(F32)                       # [K, C]
    conv = sum(padded[j:j + t] * w[j] for j in range(K))
    xbc = jax.nn.silu(conv + layer["conv_b"].astype(F32))
    xs = xbc[:, :di].reshape(t, H, P)
    B = xbc[:, di:di + G * N]
    C = xbc[:, di + G * N:]
    dt = jax.nn.softplus(dt + layer["dt_bias"].astype(F32))  # [t, H]
    A = -jnp.exp(layer["A_log"].astype(F32))                 # [H]

    def one(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = h * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return h, h @ c_t

    _, y = jax.lax.scan(one, jnp.zeros((H, P, N), F32), (xs, B, C, dt))
    y = y + layer["D"].astype(F32)[:, None] * xs
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = common.rms_norm(y, layer["ssm_norm"], conf["rms_norm_eps"])
    return y @ layer["out_proj"].astype(F32)


@partial(jax.jit, static_argnames=("static", "kind"))
def _layer(x, layer, static, kind):
    conf = dict(static)
    mixer = _mamba if kind == "mamba" else _attention
    x = x + conf["residual_multiplier"] * mixer(x, layer, conf)
    return _mlp(x, layer, conf)


def forward(params, conf, tokens, positions):
    static = _static(conf)
    with jax.default_matmul_precision("highest"):
        x = common.embed(params, jnp.asarray(tokens, jnp.int32)) \
            * conf["embedding_multiplier"]
        for layer, kind in zip(params["layers"], conf["layer_types"]):
            x = _layer(x, layer, static, kind)
        xs = common.rms_norm(x[jnp.asarray(positions, jnp.int32)],
                             params["final_ln"], conf["rms_norm_eps"])
        logits = xs @ params["embed"].astype(F32).T / conf["logits_scaling"]
    return logits, None
