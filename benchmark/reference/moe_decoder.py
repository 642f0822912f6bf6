"""Plain float32 reference of the sparse-expert decoder (Mixtral): the
dense decoder's attention, and an FFN of `num_local_experts` SwiGLU
experts of which each token uses the top `num_experts_per_tok` by router
softmax, their gates renormalised to sum to one. No capacity: every
token is computed by every expert it chose. Experts are upcast one by
one.

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins [P, layers] float32): margins[p, l] is the gap between the
k-th and (k+1)-th largest router LOGIT of position p in layer l (the
log of the ratio of the 2nd and 3rd router probability for top-2). A
small gap means the expert choice there is a near-tie that rounding can
flip; correct.py sets such positions aside.
"""

from functools import partial

import jax
import jax.numpy as jnp

from . import common
from .dense_decoder import KEYS

MOE_KEYS = KEYS + ("num_local_experts", "num_experts_per_tok")


def _static(conf):
    return tuple((k, conf[k]) for k in MOE_KEYS if conf.get(k) is not None)


@partial(jax.jit, static_argnames=("static",))
def _attn(x, layer, static):
    return common.attention_block(x, layer, dict(static))


@partial(jax.jit, static_argnames=("static",))
def _route(x, ln2, router, static):
    conf = dict(static)
    k = conf["num_experts_per_tok"]
    h = common.rms_norm(x, ln2, conf["rms_norm_eps"])
    z = h @ router.astype(common.F32)                      # [T, E]
    p = jax.nn.softmax(z, axis=-1)
    top_p, top_i = jax.lax.top_k(p, k)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    dense = jnp.sum(
        jax.nn.one_hot(top_i, z.shape[-1], dtype=common.F32)
        * gates[..., None], axis=1)                        # [T, E]
    zs = jnp.sort(z, axis=-1)
    margin = zs[:, -k] - zs[:, -k - 1]
    return h, dense, margin


@jax.jit
def _expert(h, w_gate, w_up, w_down, gate_col):
    a = jax.nn.silu(h @ w_gate.astype(common.F32))
    a = a * (h @ w_up.astype(common.F32))
    return (a @ w_down.astype(common.F32)) * gate_col[:, None]


def forward(params, conf, tokens, positions):
    static = _static(conf)
    positions = jnp.asarray(positions, jnp.int32)
    margins = []
    with jax.default_matmul_precision("highest"):
        x = common.embed(params, jnp.asarray(tokens, jnp.int32))
        for layer in params["layers"]:
            x = _attn(x, layer, static)
            h, gates, margin = _route(x, layer["ln2"], layer["router"],
                                      static)
            margins.append(margin[positions])
            for e in range(conf["num_local_experts"]):
                x = x + _expert(h, layer["e_gate"][e], layer["e_up"][e],
                                layer["e_down"][e], gates[:, e])
        logits = common.logits_at(params, x, positions, dict(static))
    return logits, jnp.stack(margins, axis=1)
