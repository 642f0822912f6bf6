"""GLM-5.2-style sparse decoder (`model_type: glm_moe_dsa`): latent
attention under a LEARNED SELECTION of cache rows, whose index keys are
a second kind of page that only some layers keep, over routed experts
of which this chip may hold a SHARE. The seventh family on the one
decoder stack (models/decoder.py).

One layer, x its input, h = RMSNorm(x; ln1):

    c_q = RMSNorm(h Wqa);  q = c_q Wqb -> H x (nope | rope)
    [c_kv | k_pe] = h Wkva;  c = RMSNorm(c_kv)
    rotary (ADJACENT pairs) on q_pe and the one shared k_pe
    the cache row of a token is [c | k_pe | 0]          (`latent_width`)
    K_h = [c Wkb,h | k_pe],  V_h = c Wvb,h,  scale (nope + rope) ** -0.5

    a "full" layer's indexer (`indexer_kinds`):
    qI = c_q WqI -> Hi x Di;  kI = LayerNorm(h WkI) [Di], ONE a token
    rotary (adjacent pairs) on the first `qk_rope` lanes of qI_j and kI
    w  = (h Ww) Hi ** -0.5 Di ** -0.5
    I(t, s) = sum_j w(t, j) relu(qI_j(t) . kI(s)),  s <= t
    S(t) = the min(t + 1, index_topk) positions of largest I(t, .)
    kI is cached: the layer's second page, `index_dim` wide
    a "shared" layer has no indexer: S(t) of the nearest "full" layer
    below it, as made in this same program

    attention: softmax over S(t) and no other row;  x += concat_h(.) Wo
    u = RMSNorm(x; ln2);  a dense layer: SwiGLU `ffn_dense` wide;
    a sparse one: sigmoid router with a selection bias over `n_routed`
    experts, `top_k` chosen, gates the chosen SCORES over their sum
    times `route_scale`, plus `n_shared` ungated shared experts
    (models/moe.py's sorted dispatch; the experts HELD here are
    `n_experts` from `first_expert` on, MoEConfig's share)

What is its own: the selection (decoder.index_project, `indexed`,
latent_selected_prefill / _decode over ops/sparse_select.py) and the
page contract's second kind: `page_kinds` "ci", a "c" page [page,
latent_width] on every layer and an "i" page [page, index_dim] on the
layers that own an indexer (`page_layers`); the serving engine's second
pool holds them (serving.py). Everything else is there: the latent
mixer and its pool (models/xing.py's), the sigmoid router, the shared
expert and the leading dense layer (xing's), rotary in adjacent pairs
and the share of the experts (models/cohere.py's).

Not held: the multi-token-prediction module (a further layer behind
the last stage that drafts the next token; speculation over a latent
pool is not built), and the published deployment's float8 index cache
(index keys are the model's dtype here). The published inference code
rotates qI and kI by one Hadamard matrix before it quantises them; an
orthogonal map on both sides leaves every product as it is, and
without the quantisation it is left out.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder, llama, moe


@dataclass(frozen=True)
class GlmConfig(moe.MoEConfig):
    """MoEConfig (`d_ff` the experts' width, the share: `n_routed`,
    `first_expert`) plus the latent attention's ranks and head widths
    (models/xing.py's names), the indexer's, and the two per-layer
    specs: `indexer_kinds` ("full" | "shared" a layer) and
    `dense_layers` (True: a dense SwiGLU `ffn_dense` wide).
    `q_init_gain`, `o_init_gain` and `down_init_gain` are read by
    `init_params` alone (CohereConfig's `q_init_gain`; the widths Wqb,
    Wo and the feed-forward's down projections are drawn at)."""

    q_lora_rank: int = 48
    kv_lora_rank: int = 32
    qk_nope: int = 16
    qk_rope: int = 8
    v_dim: int = 16
    ffn_dense: int = 256
    dense_layers: tuple = ()
    router: str = "sigmoid"
    route_scale: float = 2.5
    n_shared: int = 1
    rope_adjacent: bool = True
    yarn: tuple = ()
    index_heads: int = 4
    index_dim: int = 16
    index_topk: int = 32
    indexer_kinds: tuple = ()
    index_rope_adjacent: bool = True
    index_norm_eps: float = 1e-6
    q_init_gain: float = 1.0
    o_init_gain: float = 1.0
    down_init_gain: float = 1.0

    @property
    def layer_kinds(self):
        return ("latent",) * self.n_layers

    @property
    def n_kv_layers(self):
        """Layers that keep pages: all of them (a row page each)."""
        return self.n_layers

    @property
    def page_kinds(self):
        """"c": a layer's latent rows; "i": its index keys, where it
        owns an indexer (`page_layers`)."""
        return "ci"

    @property
    def latent_width(self):
        """A cache row's lanes (models/xing.py: 576 -> 640)."""
        return -(-(self.kv_lora_rank + self.qk_rope) // 128) * 128

    @property
    def index_rope(self):
        """The leading lanes of qI_j and kI that rotate: the shared
        key's."""
        return self.qk_rope

    @property
    def index_layers(self):
        """The layers that own an indexer, in order."""
        return tuple(i for i, k in enumerate(self.indexer_kinds)
                     if k == "full")

    def kv_page_shape(self):
        """The first kind's: one layer's rows, [page, latent_width]."""
        return (self.page_size, self.latent_width)

    def page_shape(self, kind):
        return {"c": self.kv_page_shape(),
                "i": (self.page_size, self.index_dim)}[kind]

    def page_layers(self, kind):
        return {"c": tuple(range(self.n_layers)),
                "i": self.index_layers}[kind]


def init_params(rng, cfg: GlmConfig):
    """Plain-dict pytree, models/xing.py's leaves without the residual
    path's, plus an owner's indexer: wqi [q_lora_rank, Hi Di], wki
    [d, Di], its LayerNorm's weight and bias (ki_ln, ki_ln_b), wiw
    [d, Hi]. The router is `n_routed` wide and float32 with its bias;
    the experts held here lie on a leading axis of `n_experts`. Every
    matrix normal at d_model ** -0.5 (Wqb at `q_init_gain` times that,
    Wo at `o_init_gain`, every down projection of a feed-forward at
    `down_init_gain`: how much of the residual stream a sublayer
    writes), norms 1, biases 0 but the router's."""
    dt = cfg.jdtype
    f32 = jnp.float32
    d = cfg.d_model
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    scale = d ** -0.5

    def dense(k, shape, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape) * (scale * gain)).astype(dtype)

    down = cfg.down_init_gain

    hq = cfg.qk_nope + cfg.qk_rope
    routed = cfg.n_routed or cfg.n_experts
    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 18)
        layer = {
            "ln1": jnp.ones(d, dtype=dt),
            "wqa": dense(k[0], (d, cfg.q_lora_rank)),
            "q_ln": jnp.ones(cfg.q_lora_rank, dtype=dt),
            "wqb": dense(k[1], (cfg.q_lora_rank, cfg.n_heads * hq))
            * jnp.asarray(cfg.q_init_gain, dt),
            "wkva": dense(k[2], (d, cfg.kv_lora_rank + cfg.qk_rope)),
            "kv_ln": jnp.ones(cfg.kv_lora_rank, dtype=dt),
            "wkvb": dense(k[3], (cfg.kv_lora_rank,
                                 cfg.n_heads * (cfg.qk_nope + cfg.v_dim))),
            "wo": dense(k[4], (cfg.n_heads * cfg.v_dim, d),
                        gain=cfg.o_init_gain),
            "ln2": jnp.ones(d, dtype=dt),
        }
        if cfg.indexer_kinds[li] == "full":
            layer.update({
                "wqi": dense(k[5], (cfg.q_lora_rank,
                                    cfg.index_heads * cfg.index_dim)),
                "wki": dense(k[6], (d, cfg.index_dim)),
                "ki_ln": jnp.ones(cfg.index_dim, dtype=dt),
                "ki_ln_b": jnp.zeros(cfg.index_dim, dtype=dt),
                "wiw": dense(k[7], (d, cfg.index_heads)),
            })
        if cfg.dense_layers[li]:
            layer.update({
                "w_gate": dense(k[8], (d, cfg.ffn_dense)),
                "w_up": dense(k[9], (d, cfg.ffn_dense)),
                "w_down": dense(k[10], (cfg.ffn_dense, d), gain=down),
            })
        else:
            ff_s = cfg.d_ff * cfg.n_shared
            layer.update({
                "router": dense(k[8], (d, routed), f32),
                "router_bias": dense(k[9], (routed,), f32),
                "e_gate": dense(k[10], (cfg.n_experts, d, cfg.d_ff)),
                "e_up": dense(k[11], (cfg.n_experts, d, cfg.d_ff)),
                "e_down": dense(k[12], (cfg.n_experts, cfg.d_ff, d),
                                gain=down),
                "s_gate": dense(k[13], (d, ff_s)),
                "s_up": dense(k[14], (d, ff_s)),
                "s_down": dense(k[15], (ff_s, d), gain=down),
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
    dense layer's SwiGLU, or the routed experts held here and the
    shared one. Which, the layer's own weights say."""
    if "w_gate" in layer:
        return llama._mlp(layer, x, cfg, valid)
    return moe.sorted_moe_mlp(layer, x, cfg, valid)


_forward_stack, decode_step, verify_step = decoder.bind(_block)


def prefill(params, cfg: GlmConfig, tokens, keep=None):
    """(logits, per layer (rows [b, s, latent_width], index keys
    [b, s, index_dim] or None): what to page out) and, where the
    layers hold a share of their experts, the blocks' counts. `keep`:
    decoder.forward_stack."""
    logits, kvs, _, *counts = _forward_stack(params, cfg, tokens,
                                             keep=keep)
    return (logits, kvs, *counts)


forward_dense = prefill


def prefill_with_prefix(params, cfg: GlmConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """Suffix prefill over cached rows and index keys: `prefix_kvs`
    per layer (rows [b, P, latent_width], index keys [b, P, index_dim]
    or None), as restored or as they lie in the pools."""
    logits, kvs, _, *counts = _forward_stack(
        params, cfg, tokens, prefix_kvs, pos0=pos0, keep=keep)
    return (logits, kvs, *counts)


def prefill_selections(params, cfg: GlmConfig, tokens):
    """Per owner layer, in order, the selection every query of a cold
    prefill of `tokens` [b, s] makes: (positions [b, s, k], taken
    [b, s, k]); s must exceed `index_topk`. Traceable: jit it."""
    with decoder.selection_tap([]) as taps:
        _forward_stack(params, cfg, tokens)
    return taps


def decode_selections(params, cfg: GlmConfig, token, seq_lens, k_pages,
                      v_pages, page_table):
    """Per owner layer, in order, the selection one decode step over
    the pools makes for each row: (positions [b, k], taken [b, k]).
    The pools are read, and written in a copy that is dropped.
    Traceable: jit it."""
    with decoder.selection_tap([]) as taps:
        decode_step.__wrapped__(params, cfg, token, seq_lens, k_pages,
                                v_pages, page_table)
    return taps
