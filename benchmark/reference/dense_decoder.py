"""Plain float32 reference of the dense decoder family (Mistral,
Qwen2-style without biases): pre-norm GQA attention with RoPE and a
SwiGLU MLP, as published. Weights are upcast layer by layer inside the
jitted layer function, so the reference fits beside the bf16 weights.

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins None). `tokens` is a 1-D int array; its tail may be padding
(causal attention makes padding inert for earlier positions).
"""

from functools import partial

import jax
import jax.numpy as jnp

from . import common

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "rms_norm_eps", "rope_theta", "head_dim")


def _static(conf):
    return tuple((k, conf[k]) for k in KEYS if conf.get(k) is not None)


@partial(jax.jit, static_argnames=("static",))
def _layer(x, layer, static):
    conf = dict(static)
    x = common.attention_block(x, layer, conf)
    h = common.rms_norm(x, layer["ln2"], conf["rms_norm_eps"])
    gate = jax.nn.silu(h @ layer["w_gate"].astype(common.F32))
    up = h @ layer["w_up"].astype(common.F32)
    return x + (gate * up) @ layer["w_down"].astype(common.F32)


def forward(params, conf, tokens, positions):
    static = _static(conf)
    with jax.default_matmul_precision("highest"):
        x = common.embed(params, jnp.asarray(tokens, jnp.int32))
        for layer in params["layers"]:
            x = _layer(x, layer, static)
        logits = common.logits_at(
            params, x, jnp.asarray(positions, jnp.int32), dict(static))
    return logits, None
