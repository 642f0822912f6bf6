"""Command-A-plus-style sparse decoder (`model_type: cohere2_moe`): a
parallel block over window and full attention layers, many query heads
a kv head, and routed experts of which this chip may hold a SHARE. The
sixth family on the one decoder stack (models/decoder.py).

One layer, with x its input:

    h  = LayerNorm(x; ln1)         mean-centred, a weight and no bias
    a  = Attention(h)              a window layer rotates q and k in
                                   ADJACENT pairs and sees the last
                                   `band` positions; a full layer sees
                                   everything and has no positions
    m  = sum over the chosen experts HELD HERE of gate_e expert_e(h)
         + the MEAN of the shared experts' outputs
    x' = x + a + m                 ONE norm a layer: attention and the
                                   experts read the same h

What is its own:

- the parallel block, through the block contract that is there:
  `_block` reads `h_attn`, the attention block's normalised input, and
  owns no `ln2`; the loops add its output to the stream that already
  holds the attention's, which is x + a + m.
- the norm (`norm_center`, decoder.layer_norm) and the rotary form
  (`rope_adjacent`), LlamaConfig's; the per-layer spec of band and
  rotary is models/smallthinker.py's, so the engine keeps two pairs of
  page pools (`cfg.two_kinds`; serving.py).
- the router: sigmoid scores over `n_routed` experts, no bias, the
  `top_k` largest chosen, a gate the chosen score over the chosen
  scores' sum (models/moe.py:route_sigmoid). The layer holds the
  `n_experts` experts with ids from `first_expert` on and computes
  their part (MoEConfig's share; moe.sorted_moe_mlp): what the absent
  experts would add is left out, and nothing stands in for the chips
  that hold them or for the exchange between them.
- `n_shared` shared experts every token goes through, averaged
  (`shared_mean`), held whole on every chip.
- the head is the embedding's rows (tied: no `lm_head` leaf), which
  may be a slice of the published vocabulary.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder, moe


@dataclass(frozen=True)
class CohereConfig(moe.MoEConfig):
    """MoEConfig (`n_routed`, `first_expert`: the share; `layer_bands`,
    `layer_rope`: the per-layer spec) with this family's forms as the
    defaults. `q_init_gain` is read by `init_params` alone (random
    weights; a checkpoint brings its own): the query projection's
    width over the other matrices'. 1 draws every matrix alike; a
    configuration that wants its random model's attention sharper
    than uniform says so in its own file (hf.cohere_moe_config_from_hf:
    `random_init`)."""

    router: str = "sigmoid"
    n_shared: int = 4
    shared_mean: bool = True
    norm_center: bool = True
    rope_adjacent: bool = True
    q_init_gain: float = 1.0


def init_params(rng, cfg: CohereConfig):
    """Plain-dict pytree: models/moe.py's leaves without `ln2` and
    `lm_head`, the router `n_routed` wide and float32, the experts held
    here on a leading axis of `n_experts`, the shared experts side by
    side in one gated block `n_shared * d_ff` wide.
    Every matrix is drawn at d_model ** -0.5, the query projection at
    `cfg.q_init_gain` times that."""
    dt = cfg.jdtype
    d = cfg.d_model
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    scale = d ** -0.5

    def dense(k, shape, dtype=dt):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    ff_s = cfg.d_ff * cfg.n_shared
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[1 + li], 11)
        layers.append({
            "ln1": jnp.ones(d, dtype=dt),
            "wq": dense(k[0], (d, cfg.n_heads * cfg.head_dim))
            * jnp.asarray(cfg.q_init_gain, dt),
            "wk": dense(k[1], (d, cfg.n_kv_heads * cfg.head_dim)),
            "wv": dense(k[2], (d, cfg.n_kv_heads * cfg.head_dim)),
            "wo": dense(k[3], (cfg.n_heads * cfg.head_dim, d)),
            "router": dense(k[4], (d, cfg.n_routed or cfg.n_experts),
                            jnp.float32),
            "e_gate": dense(k[5], (cfg.n_experts, d, cfg.d_ff)),
            "e_up": dense(k[6], (cfg.n_experts, d, cfg.d_ff)),
            "e_down": dense(k[7], (cfg.n_experts, cfg.d_ff, d)),
            "s_gate": dense(k[8], (d, ff_s)),
            "s_up": dense(k[9], (d, ff_s)),
            "s_down": dense(k[10], (ff_s, d)),
        })
    return {
        "embed": dense(keys[0], (cfg.vocab_size, d)),
        "layers": layers,
        "final_ln": jnp.ones(d, dtype=dt),
    }


def _block(layer, x, cfg, valid, h_attn=None):
    """The experts' half of the parallel block (decoder.py's `block`
    contract): over `h_attn`, not over `x`."""
    return moe.sorted_moe_mlp(layer, x, cfg, valid, h_attn, own_norm=False)


_forward_stack, decode_step, verify_step = decoder.bind(_block)


def prefill(params, cfg: CohereConfig, tokens, keep=None):
    """(logits, per layer (k, v)) and, where the layers hold a share of
    their experts, the blocks' counts summed over the layers. `keep`:
    decoder.forward_stack."""
    logits, kvs, _, *counts = _forward_stack(params, cfg, tokens,
                                             keep=keep)
    return (logits, kvs, *counts)


forward_dense = prefill


def prefill_with_prefix(params, cfg: CohereConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """Suffix prefill over a cached prefix; each layer's prefix is what
    that layer may attend (decoder.forward_stack)."""
    logits, kvs, _, *counts = _forward_stack(
        params, cfg, tokens, prefix_kvs, pos0=pos0, keep=keep)
    return (logits, kvs, *counts)
