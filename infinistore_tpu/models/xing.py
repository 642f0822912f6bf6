"""Xing4.0-style sparse decoder: a latent cache, several residual
streams, a sigmoid router with a shared expert. The fifth family on the
one decoder stack (models/decoder.py).

What is its own:

- the attention layers are "latent" (multi-head latent attention as in
  DeepSeek-V2/V3; decoder.py has the mixer): a token's cache a layer is
  ONE row, the normalised compressed key-value c [kv_lora_rank] and the
  rotated shared key k_pe [qk_rope], zero-padded to `latent_width`
  lanes. The page contract's one page a layer is [page, latent_width]
  and there is no V page (`page_kinds` "c"; the serving engine's second
  pool is None). Prefill expands the rows into K and V per head and
  runs the flash kernel; decode attends the rows as they lie, absorbed
  (ops/pallas_latent_attention.py).
- the residual path: `hc_mult` streams mixed by manifold-constrained
  hyper-connections around each sublayer (decoder.hc_coef), the
  embedding copied to every stream and the streams summed before the
  final norm.
- YaRN rotary on the `qk_rope` lanes, and its mscale ** 2 on the
  softmax scale (decoder.latent_scale).
- the feed-forward block: the first `n_dense_lead` layers a dense
  SwiGLU `ffn_dense` wide (models/llama.py's), the others `top_k` of
  `n_experts` SwiGLU experts through models/moe.py's sorted dispatch
  with the sigmoid router (score + bias chooses, normalised scores
  times `route_scale` weigh) and `n_shared` shared experts beside them.

No multi-token-prediction module is held (the family's own inference
code drops it). What is not built over a latent cache, the serving
engine refuses at construction (`ServingEngine._check_latent_family`).
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder, llama, moe


@dataclass(frozen=True)
class XingConfig(moe.MoEConfig):
    """MoEConfig (`d_ff` the routed experts' width, `router`,
    `route_scale`, `n_shared`) plus the latent attention's ranks and
    head widths, the leading dense layers, the residual path's fields
    and YaRN's (factor, original_max, beta_fast, beta_slow, mscale,
    mscale_all_dim; () = plain rotary)."""

    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope: int = 16
    qk_rope: int = 8
    v_dim: int = 16
    n_dense_lead: int = 1
    ffn_dense: int = 256
    router: str = "sigmoid"
    route_scale: float = 2.0
    n_shared: int = 1
    hc_mult: int = 4
    hc_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    yarn: tuple = ()

    @property
    def layer_kinds(self):
        return ("latent",) * self.n_layers

    @property
    def n_kv_layers(self):
        """Layers that keep pages: all of them, one page each."""
        return self.n_layers

    @property
    def page_kinds(self):
        return "c"

    @property
    def latent_width(self):
        """A cache row's lanes: kv_lora_rank + qk_rope rounded up to a
        lane tile (576 -> 640 at the published widths): the row the
        decode kernel's matmuls and copies want, in the pool and on the
        wire alike, so that no call pads or slices the pool."""
        return -(-(self.kv_lora_rank + self.qk_rope) // 128) * 128

    def kv_page_shape(self):
        """One layer's ONE page: [page_size, latent_width]."""
        return (self.page_size, self.latent_width)


def init_params(rng, cfg: XingConfig):
    """Plain-dict pytree. The router, its bias and every mHC
    coefficient are float32. Assumed (the published checkpoint's
    initialisation is not part of its config): `b_res` near the
    identity's logit (2 on the diagonal, -2 off it), a_pre = a_post =
    a_res = 0.01, everything else normal at d_model ** -0.5, norms 1."""
    dt = cfg.jdtype
    f32 = jnp.float32
    d, n = cfg.d_model, cfg.hc_mult
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    scale = d ** -0.5

    def dense(k, shape, dtype=dt):
        return (jax.random.normal(k, shape) * scale).astype(dtype)

    def hc(k):
        k = jax.random.split(k, 2)
        bias = dense(k[1], (2 * n + n * n,), f32)
        b_res = 4.0 * jnp.eye(n, dtype=f32).reshape(-1) - 2.0
        return {"norm": jnp.ones(n * d, dtype=dt),
                "proj": dense(k[0], (n * d, 2 * n + n * n), f32),
                "bias": bias.at[2 * n:].set(b_res),
                "a": jnp.full((3,), 0.01, f32)}

    hq = cfg.qk_nope + cfg.qk_rope
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 16)
        layer = {
            "ln1": jnp.ones(d, dtype=dt),
            "wqa": dense(k[0], (d, cfg.q_lora_rank)),
            "q_ln": jnp.ones(cfg.q_lora_rank, dtype=dt),
            "wqb": dense(k[1], (cfg.q_lora_rank, cfg.n_heads * hq)),
            "wkva": dense(k[2], (d, cfg.kv_lora_rank + cfg.qk_rope)),
            "kv_ln": jnp.ones(cfg.kv_lora_rank, dtype=dt),
            "wkvb": dense(k[3], (cfg.kv_lora_rank,
                                 cfg.n_heads * (cfg.qk_nope + cfg.v_dim))),
            "wo": dense(k[4], (cfg.n_heads * cfg.v_dim, d)),
            "ln2": jnp.ones(d, dtype=dt),
            "hc_attn": hc(k[5]),
            "hc_ffn": hc(k[6]),
        }
        if li < cfg.n_dense_lead:
            layer.update({
                "w_gate": dense(k[7], (d, cfg.ffn_dense)),
                "w_up": dense(k[8], (d, cfg.ffn_dense)),
                "w_down": dense(k[9], (cfg.ffn_dense, d)),
            })
        else:
            ff_s = cfg.d_ff * cfg.n_shared
            layer.update({
                "router": dense(k[7], (d, cfg.n_experts), f32),
                "router_bias": dense(k[8], (cfg.n_experts,), f32),
                "e_gate": dense(k[9], (cfg.n_experts, d, cfg.d_ff)),
                "e_up": dense(k[10], (cfg.n_experts, d, cfg.d_ff)),
                "e_down": dense(k[11], (cfg.n_experts, cfg.d_ff, d)),
                "s_gate": dense(k[12], (d, ff_s)),
                "s_up": dense(k[13], (d, ff_s)),
                "s_down": dense(k[14], (ff_s, d)),
            })
        layers.append(layer)
    return {
        "embed": dense(keys[0], (cfg.vocab_size, d)),
        "layers": layers,
        "final_ln": jnp.ones(d, dtype=dt),
        "lm_head": dense(keys[1], (d, cfg.vocab_size)),
    }


def _block(layer, x, cfg, valid, h_attn=None):
    """The feed-forward sublayer (decoder.py's `block` contract): a
    leading layer's dense SwiGLU, or the routed experts and the shared
    one. Which, the layer's own weights say."""
    if "w_gate" in layer:
        return llama._mlp(layer, x, cfg, valid)
    return moe.sorted_moe_mlp(layer, x, cfg, valid)


_forward_stack, decode_step, verify_step = decoder.bind(_block)


def prefill(params, cfg: XingConfig, tokens, keep=None):
    """(logits, per layer (rows [b, s, latent_width], None)): the
    latent rows to page out. `keep`: decoder.forward_stack."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, keep=keep)
    return logits, kvs


forward_dense = prefill


def prefill_with_prefix(params, cfg: XingConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """Suffix prefill over cached rows: `prefix_kvs` per layer (rows
    [b, P, latent_width], None), as restored or as they lie in the
    pool."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0, keep=keep)
    return logits, kvs
