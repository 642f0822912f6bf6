"""Phi-4-mini-flash's decoder-hybrid-decoder (SambaY, arXiv:2507.06607;
HF `phi4flash`): a self-decoder of Mamba-1 layers alternating with
differential attention over a short band, one layer of differential
attention over the whole context, then a cross-decoder of Gated Memory
Units alternating with cross-attention layers that own no cache. The
seventh family on the one decoder stack (models/decoder.py): its config
names each layer's mixer (`layer_types`) and band, its init makes the
four kinds of layer, and the feed-forward block is models/llama.py's
gated MLP behind a LayerNorm with a bias.

THREE kinds of cache in one sequence, each with a life of its own:

- the banded attention layers' K and V pages, needed for the band
  alone (the engine's second pair of pools, under a short table);
- ONE layer's K and V pages of every position (the full pools, one
  layer): the model's only whole-context cache, attended by that layer
  and by every "cross" layer above it, which project queries alone;
- per "mamba1" layer a recurrent state `h` [N, C] and the
  convolution's last K-1 inputs, float32, with the boundary copy and
  the snapshot a hit every family with state has (models/hybrid.py).

Differential attention pairs heads: a cache row holds the pair of kv
heads one pair of softmax maps reads (`kv_pack` 2: 128 lanes), and the
parameters hold the projections' columns IN THAT ORDER: query heads 4g,
4g + 1 are map 1 of pairs 2g, 2g + 1, heads 4g + 2, 4g + 3 map 2 of the
same pairs; kv heads 2g, 2g + 1 are k1 / v1 and k2 / v2 of both
(decoder.diff_combine). The published pairing is by halves (q[j] with
q[n / 2 + j]): a permutation of columns, which a bridge that loads
published weights applies once.

The surface is models/hybrid.py's (`s_real`, `last_only`, the state
pools) with the banded layers' pools of a model with two kinds of
attention layer (`win`, as models/smallthinker.py's decode_step).
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder
from .hybrid import _last
from .llama import LlamaConfig, _mlp


@dataclass(frozen=True)
class PhiFlashConfig(LlamaConfig):
    # per layer "mamba1" | "attention" | "gmu" | "cross"; an attention
    # layer's band is LlamaConfig.layer_bands' (0 on every other kind)
    layer_types: tuple = ()
    ssm_inner: int = 256      # C, the mixer's inner width (d_inner)
    ssm_state: int = 16       # N
    ssm_conv: int = 4         # K, the depthwise convolution's width
    dt_rank: int = 8
    state_dtype: str = "float32"
    # init_params alone: what a "cross" layer's output projection is
    # drawn at, times fan_in ** -0.5 (a random model's fixture: the
    # benchmark's configuration says why it has one)
    cross_out_gain: float = 1.0
    diff_attn: bool = True    # decoder.diff_combine
    norm_center: bool = True  # LayerNorm (with a bias: `ln*_b`)
    use_rope: bool = False
    kv_pack: int = 2

    @property
    def layer_kinds(self):
        return self.layer_types

    @property
    def state_jdtype(self):
        return jnp.dtype(self.state_dtype)

    @property
    def n_state_layers(self):
        return sum(k == "mamba1" for k in self.layer_types)

    @property
    def pair_rows(self):
        """A cache row is the pair of kv heads one differential pair
        reads: what lets packed rows lie in two pairs of pools (both
        take the one `kv_page_shape`; serving.py)."""
        return self.diff_attn and self.kv_pack == 2

    @property
    def page_rows(self):
        """Cache rows a token (10 at the published widths). No multiple
        of the 8 rows a tile holds, so a page [page, rows, 128] would
        lie padded to 16 rows a token on the chip and could not be
        viewed as the decode kernel's rows without moving every tile:
        this family's pools hold a page as FLAT ROWS, `kv_page_shape`
        (ops/pallas_paged_attention.py `paged_flash_decode`)."""
        return self.n_kv_heads // self.kv_pack

    def kv_page_shape(self):
        """One K (or V) page of one layer as flat rows: [page_size *
        page_rows, 128], row = token * page_rows + cache row. The same
        bytes in the same order as [page_size, page_rows, 128]."""
        return (self.page_size * self.page_rows,
                self.head_dim * self.kv_pack)

    def state_shapes(self):
        """One state layer's arrays for ONE sequence, {kind: shape}, in
        the order a snapshot row holds them: the state pools' keys. `h`
        lies with the channels along the lanes."""
        return {"h": (self.ssm_state, self.ssm_inner),
                "conv": (self.ssm_conv - 1, self.ssm_inner)}


def init_params(rng, cfg: PhiFlashConfig):
    """Plain-dict pytree, seeded; no `lm_head` leaf: the embedding is
    tied. A (1..N a channel), dt in [1e-3, 1e-1] and D = 1 as the
    published Mamba-1 initialisation draws them; the lambda vectors
    normal(0, 0.1); norms 1, biases 0; a "cross" layer's `wo` at
    `cfg.cross_out_gain` times the others' deviation."""
    dt = cfg.jdtype
    f32 = jnp.float32
    keys = jax.random.split(rng, 1 + cfg.n_layers)
    d, c, n, r = cfg.d_model, cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
    qd = cfg.n_heads * cfg.head_dim
    kd = cfg.n_kv_heads * cfg.head_dim

    def dense(k, shape, s=None):
        s = shape[0] ** -0.5 if s is None else s
        return (jax.random.normal(k, shape) * s).astype(dt)

    def zeros(*shape):
        return jnp.zeros(shape, dtype=dt)

    layers = []
    for li, kind in enumerate(cfg.layer_types):
        k = jax.random.split(keys[1 + li], 12)
        layer = {
            "ln1": jnp.ones(d, dtype=dt), "ln1_b": zeros(d),
            "ln2": jnp.ones(d, dtype=dt), "ln2_b": zeros(d),
            "w_gate": dense(k[0], (d, cfg.d_ff)),
            "w_up": dense(k[1], (d, cfg.d_ff)),
            "w_down": dense(k[2], (cfg.d_ff, d)),
        }
        if kind == "mamba1":
            step = jnp.exp(jax.random.uniform(
                k[6], (c,), f32, jnp.log(1e-3), jnp.log(1e-1)))
            layer.update({
                "in_proj": dense(k[3], (d, 2 * c)),
                "conv_w": dense(k[4], (cfg.ssm_conv, c)),
                "conv_b": zeros(c),
                "x_proj": dense(k[5], (c, r + 2 * n)),
                "dt_proj": dense(k[7], (r, c)),
                # softplus(dt_bias) = step
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, n + 1, dtype=f32)[:, None], (n, c))),
                "D": jnp.ones(c, f32),
                "out_proj": dense(k[8], (c, d)),
            })
        elif kind == "gmu":
            layer.update({"gmu_in": dense(k[3], (d, c)),
                          "gmu_out": dense(k[4], (c, d))})
        else:
            layer.update({
                "wq": dense(k[3], (d, qd)), "bq": zeros(qd),
                "wo": dense(k[6], (qd, d), qd ** -0.5 * (
                    cfg.cross_out_gain if kind == "cross" else 1.0)),
                "bo": zeros(d),
                "sub_ln": jnp.ones(2 * cfg.head_dim, dtype=dt),
                **{name: jax.random.normal(kk, (cfg.head_dim,), f32) * 0.1
                   for name, kk in zip(("lam_q1", "lam_k1", "lam_q2",
                                        "lam_k2"), k[8:12])},
            })
            if kind == "attention":
                layer.update({
                    "wk": dense(k[4], (d, kd)), "bk": zeros(kd),
                    "wv": dense(k[5], (d, kd)), "bv": zeros(kd),
                })
        layers.append(layer)
    return {
        "embed": dense(keys[0], (cfg.vocab_size, d)),
        "layers": layers,
        "final_ln": jnp.ones(d, dtype=dt), "final_ln_b": zeros(d),
    }


_forward_stack, _decode_step, verify_step = decoder.bind(_mlp)


def state_pools(cfg: PhiFlashConfig, slots, device=None):
    """The batch's state: {"h": [...], "conv": [...]}, per state layer
    one array with a row a slot (models/hybrid.py's form)."""
    return {
        kind: [jnp.zeros((slots, *shape), cfg.state_jdtype, device=device)
               for _ in range(cfg.n_state_layers)]
        for kind, shape in cfg.state_shapes().items()
    }


def prefill(params, cfg: PhiFlashConfig, tokens, s_real=None,
            last_only=False):
    """(logits, per attention layer (k, v) in the cache's row form
    [batch, seq, rows, 128], per state layer the states
    decoder.mamba1_mixer_seq returns). `last_only`: logits [batch, 1,
    vocab] of position `s_real - 1`, and the cross-decoder (the last
    full layer's query and every layer above it) run on that row
    alone (decoder.forward_stack's `keep`)."""
    logits, kvs, _, states = _forward_stack(
        params, cfg, tokens, s_real=s_real,
        keep=_last(tokens, s_real, last_only))
    return logits, kvs, states


def forward_dense(params, cfg: PhiFlashConfig, tokens):
    """Every row through every layer."""
    logits, kvs, _ = prefill(params, cfg, tokens)
    return logits, kvs


def prefill_with_prefix(params, cfg: PhiFlashConfig, tokens, prefix_kvs,
                        pos0=0, state=None, s_real=None, last_only=False):
    """Suffix prefill over a cached prefix: each attention layer
    attends what that layer may of `prefix_kvs` (rows in the cache's
    form; a banded layer's may be the tail its band needs) + the
    suffix, the state layers continue from `state`."""
    logits, kvs, _, states = _forward_stack(
        params, cfg, tokens, prefix_kvs, pos0=pos0, state=state,
        s_real=s_real, keep=_last(tokens, s_real, last_only))
    return logits, kvs, states


def decode_step(params, cfg: PhiFlashConfig, token, seq_lens, k_pages,
                v_pages, page_table, state, win=None, fetched=False):
    """decoder.decode_step with the state pools and the banded layers'
    pools: returns (logits, k_pages, v_pages, state, wk, wv)."""
    return _decode_step(params, cfg, token, seq_lens, k_pages, v_pages,
                        page_table, state=state, win=win, fetched=fetched)
