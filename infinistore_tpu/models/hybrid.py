"""Hybrid decoder family: Mamba-2 state layers with an attention layer
among every few, a gated MLP in every layer (granite-4.0-h, HF
`granitemoehybrid` without experts). The third family on the one
decoder stack (models/decoder.py): its config names each layer's mixer
(`layer_types`), its init makes both kinds of layer, and the
feed-forward block is models/llama.py's gated MLP.

Two kinds of cache, side by side. Attention layers keep K and V in
pages, as every family does; the page pools hold those layers alone
(`cfg.n_kv_layers`). State layers keep, per sequence, a recurrent state
`h` [H, P, N] and the last K-1 inputs of the depthwise convolution,
both in `state_dtype` (float32): they do not grow with the sequence and
cannot be rewound, so the serving engine keeps beside each slot's state
a copy taken at its last page edge (serving.py), which is what a prefix
hit restores together with the attention layers' pages.

The surface the engine uses, beyond what every family has: `prefill`
and `prefill_with_prefix` take `s_real` (padded prompt positions must
not advance a recurrence) and return the per-layer states as a third
element; `decode_step` takes and returns the batch's state pools;
`state_pools` makes them.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder
from .llama import LlamaConfig, _mlp


@dataclass(frozen=True)
class HybridConfig(LlamaConfig):
    layer_types: tuple = ()   # per layer "mamba" | "attention"
    ssm_heads: int = 8        # H
    ssm_head_dim: int = 16    # P; H * P is the mixer's inner width
    ssm_state: int = 16       # N
    ssm_groups: int = 1       # groups of B and C (1 implemented)
    ssm_conv: int = 4         # K, the depthwise convolution's width
    ssm_chunk: int = 256      # positions a chunk of the prefill scan
    state_dtype: str = "float32"

    @property
    def layer_kinds(self):
        return self.layer_types

    @property
    def state_jdtype(self):
        return jnp.dtype(self.state_dtype)

    @property
    def n_state_layers(self):
        return sum(k == "mamba" for k in self.layer_types)

    @property
    def ssm_conv_dim(self):
        return (self.ssm_heads * self.ssm_head_dim
                + 2 * self.ssm_groups * self.ssm_state)

    def state_shapes(self):
        """One state layer's arrays for ONE sequence, {kind: shape}, in
        the order a snapshot row holds them: the state pools' keys."""
        return {"h": (self.ssm_heads, self.ssm_head_dim, self.ssm_state),
                "conv": (self.ssm_conv - 1, self.ssm_conv_dim)}


def init_params(rng, cfg: HybridConfig):
    """Plain-dict pytree, seeded; no `lm_head` leaf: the embedding
    is tied and decoder.lm_head contracts over its rows.
    A_log, dt_bias and D are drawn as the published initialisation
    draws them (A in [1, 16], dt in [1e-3, 1e-1])."""
    dt = cfg.jdtype
    f32 = jnp.float32
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    scale = cfg.d_model ** -0.5
    di = cfg.ssm_heads * cfg.ssm_head_dim
    c = cfg.ssm_conv_dim

    def dense(k, shape, s=scale):
        return (jax.random.normal(k, shape) * s).astype(dt)

    layers = []
    for li, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[1 + li], 10)
        layer = {
            "ln1": jnp.ones(cfg.d_model, dtype=dt),
            "ln2": jnp.ones(cfg.d_model, dtype=dt),
            "w_gate": dense(k[0], (cfg.d_model, cfg.d_ff)),
            "w_up": dense(k[1], (cfg.d_model, cfg.d_ff)),
            "w_down": dense(k[2], (cfg.d_ff, cfg.d_model), cfg.d_ff ** -0.5),
        }
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(
                k[6], (cfg.ssm_heads,), f32, jnp.log(1e-3), jnp.log(1e-1)))
            layer.update({
                "in_proj": dense(k[3], (cfg.d_model, 2 * di + 2
                                        * cfg.ssm_groups * cfg.ssm_state
                                        + cfg.ssm_heads)),
                "conv_w": dense(k[4], (cfg.ssm_conv, c),
                                cfg.ssm_conv ** -0.5),
                "conv_b": jnp.zeros(c, dtype=dt),
                "A_log": jnp.log(jax.random.uniform(
                    k[5], (cfg.ssm_heads,), f32, 1.0, 16.0)),
                # softplus(dt_bias) = step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "D": jnp.ones(cfg.ssm_heads, f32),
                "ssm_norm": jnp.ones(di, dtype=dt),
                "out_proj": dense(k[7], (di, cfg.d_model), di ** -0.5),
            })
        else:
            qd = cfg.n_heads * cfg.head_dim
            kd = cfg.n_kv_heads * cfg.head_dim
            layer.update({
                "wq": dense(k[3], (cfg.d_model, qd)),
                "wk": dense(k[4], (cfg.d_model, kd)),
                "wv": dense(k[5], (cfg.d_model, kd)),
                "wo": dense(k[6], (qd, cfg.d_model), qd ** -0.5),
            })
        layers.append(layer)
    return {
        "embed": dense(keys[0], (cfg.vocab_size, cfg.d_model)),
        "layers": layers,
        "final_ln": jnp.ones(cfg.d_model, dtype=dt),
    }


_forward_stack, _decode_step, verify_step = decoder.bind(_mlp)


def state_pools(cfg: HybridConfig, slots, device=None):
    """The batch's state: {"h": [...], "conv": [...]}, per state layer
    one array with a row a slot. One array a layer, so that a program
    that is donated them updates each where it lies."""
    return {
        kind: [jnp.zeros((slots, *shape), cfg.state_jdtype, device=device)
               for _ in range(cfg.n_state_layers)]
        for kind, shape in cfg.state_shapes().items()
    }


def _last(tokens, s_real, last_only):
    """decoder.forward_stack's `keep` of a caller that keeps the last
    real position's logits alone: the ONE `s_real` the state layers
    stop at says which that is."""
    if not last_only:
        return None
    return (tokens.shape[1] if s_real is None else s_real) - 1


def prefill(params, cfg: HybridConfig, tokens, s_real=None,
            last_only=False):
    """(logits, per attention layer (k, v), per state layer the states
    decoder.ssm_mixer_seq returns: after `s_real` tokens and at the
    last page edge). `last_only`: logits [batch, 1, vocab] of position
    `s_real - 1` (decoder.forward_stack's `keep`)."""
    logits, kvs, _, states = _forward_stack(
        params, cfg, tokens, s_real=s_real,
        keep=_last(tokens, s_real, last_only))
    return logits, kvs, states


def forward_dense(params, cfg: HybridConfig, tokens):
    logits, kvs, _ = prefill(params, cfg, tokens)
    return logits, kvs


def prefill_with_prefix(params, cfg: HybridConfig, tokens, prefix_kvs,
                        pos0=0, state=None, s_real=None, last_only=False):
    """Suffix prefill over a cached prefix: the attention layers attend
    over `prefix_kvs` + the suffix, the state layers continue from
    `state` (per state layer (h, conv tail) at the prefix's end: both
    or neither; a prefix without its state is no prefix). `last_only`:
    as in `prefill`."""
    logits, kvs, _, states = _forward_stack(
        params, cfg, tokens, prefix_kvs, pos0=pos0, state=state,
        s_real=s_real, keep=_last(tokens, s_real, last_only))
    return logits, kvs, states


def decode_step(params, cfg: HybridConfig, token, seq_lens, k_pages,
                v_pages, page_table, state):
    """decoder.decode_step with the state pools: returns (logits,
    k_pages, v_pages, state)."""
    return _decode_step(params, cfg, token, seq_lens, k_pages, v_pages,
                        page_table, state=state)
