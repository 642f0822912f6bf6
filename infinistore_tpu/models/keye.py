"""Keye-VL-2.0-style sparse decoder, the language model alone
(`model_type: KeyeVL2`): grouped-query attention with a per-head
RMSNorm on q and k, under a LEARNED SELECTION of K and V rows whose
index keys are a THIRD kind of page on every layer, over softmax-routed
experts that are all held. The eighth family on the one decoder stack
(models/decoder.py).

One layer, x its input, h = RMSNorm(x; ln1):

    q, k, v = h Wq, h Wk, h Wv -> H, G, G heads of `head_dim`
    q_h = RMSNorm(q_h; q_norm),  k_g = RMSNorm(k_g; k_norm)   per head
    rotary on all lanes of q_h and k_g (the halves i, i + hd / 2)
    the cache of a token is a K row and a V row [G, hd] a layer

    every layer's indexer (`sa_config`):
    qI = h WqI -> Hi x Di;  kI = LayerNorm(h WkI) [Di], ONE a token
    rotary (the halves) on all lanes of qI_j and kI
    w  = (h Ww) Hi ** -0.5 Di ** -0.5
    I(t, s) = sum_j w(t, j) relu(qI_j(t) . kI(s)),  s <= t
    S(t) = the min(t + 1, index_topk) positions of largest I(t, .)
    kI is cached: the layer's third page, `index_width` lanes wide
    (Di and zeros up to whole tiles of 128)

    o(t, h) = sum_{s in S(t)} softmax_s(q_h(t) . k_g(h)(s) hd ** -0.5)
              v_g(h)(s),  g(h) = h // (H / G);   x += concat_h(o) Wo
    u = RMSNorm(x; ln2);  softmax router over `n_experts`, `top_k`
    chosen, gates the softmax's shares renormalised over the chosen
    (= the softmax over the chosen logits), SwiGLU experts `d_ff`
    wide, no shared expert (models/moe.py's sorted dispatch)

What is its own: the selection on the "attention" mixer
(decoder.kv_selected_prefill / _decode over ops/sparse_select.py), the
q and k norms (the leaves `q_norm` / `k_norm`, which `decoder._qkv`
applies where a layer has them) and the page contract's three kinds:
`page_kinds` "kvi", a K and a V page [page, G, hd] and an "i" page
[page, index_width] on every layer; the serving engine holds a pool a
kind under one page id (serving.py). Everything else is there: the
indexer's arithmetic (decoder.index_project, models/glm.py's), the
router and the experts (models/moe.py's, smallthinker's forms).

Not held: the vision tower in front of the embedding (inputs are
token ids; with text alone the three position rows of `mrope_section`
are equal and rotary is the plain one).
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder, moe


@dataclass(frozen=True)
class KeyeConfig(moe.MoEConfig):
    """MoEConfig (`d_ff` the experts' width) plus the indexer's fields
    (models/glm.py's names). `q_init_gain`, `o_init_gain` and
    `down_init_gain` are read by `init_params` alone (GlmConfig's: the
    q norm's weight, and the widths Wo and the experts' down
    projections are drawn at)."""

    index_heads: int = 4
    index_dim: int = 16
    index_topk: int = 32
    index_rope_adjacent: bool = False
    index_norm_eps: float = 1e-6
    q_init_gain: float = 1.0
    o_init_gain: float = 1.0
    down_init_gain: float = 1.0

    @property
    def indexer_kinds(self):
        """Every layer owns its indexer."""
        return ("full",) * self.n_layers

    @property
    def index_rope(self):
        """Every lane of qI_j and kI rotates."""
        return self.index_dim

    @property
    def index_width(self):
        """An index row's lanes as cached: whole tiles of 128."""
        return -(-self.index_dim // 128) * 128

    @property
    def page_kinds(self):
        """"k", "v": a layer's K and V rows; "i": its index keys."""
        return "kvi"

    def page_shape(self, kind):
        return (self.page_size, self.index_width) if kind == "i" \
            else self.kv_page_shape()


def init_params(rng, cfg: KeyeConfig):
    """Plain-dict pytree, models/moe.py's leaves plus q_norm / k_norm
    [head_dim] and the indexer's: wqi [d, Hi Di], wki [d, Di], its
    LayerNorm's weight and bias (ki_ln, ki_ln_b), wiw [d, Hi]. Every
    matrix normal at d_model ** -0.5 (Wo at `o_init_gain` times that,
    the experts' down projections at `down_init_gain`), norms 1 (the q
    norm's weight `q_init_gain`: the norm takes a width of Wq out
    again, so the softmax's sharpness is its weight), the bias 0, the
    router float32."""
    dt = cfg.jdtype
    d, hd = cfg.d_model, cfg.head_dim
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    scale = d ** -0.5

    def dense(k, shape, dtype=dt, gain=1.0):
        return (jax.random.normal(k, shape) * (scale * gain)).astype(dtype)

    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 11)
        layers.append({
            "ln1": jnp.ones(d, dtype=dt),
            "wq": dense(k[0], (d, cfg.n_heads * hd)),
            "wk": dense(k[1], (d, cfg.n_kv_heads * hd)),
            "wv": dense(k[2], (d, cfg.n_kv_heads * hd)),
            "q_norm": jnp.full(hd, cfg.q_init_gain, dtype=dt),
            "k_norm": jnp.ones(hd, dtype=dt),
            "wo": dense(k[3], (cfg.n_heads * hd, d), gain=cfg.o_init_gain),
            "wqi": dense(k[4], (d, cfg.index_heads * cfg.index_dim)),
            "wki": dense(k[5], (d, cfg.index_dim)),
            "ki_ln": jnp.ones(cfg.index_dim, dtype=dt),
            "ki_ln_b": jnp.zeros(cfg.index_dim, dtype=dt),
            "wiw": dense(k[6], (d, cfg.index_heads)),
            "ln2": jnp.ones(d, dtype=dt),
            "router": dense(k[7], (d, cfg.n_experts), jnp.float32),
            "e_gate": dense(k[8], (cfg.n_experts, d, cfg.d_ff)),
            "e_up": dense(k[9], (cfg.n_experts, d, cfg.d_ff)),
            "e_down": dense(k[10], (cfg.n_experts, cfg.d_ff, d),
                            gain=cfg.down_init_gain),
        })
    return {
        "embed": dense(keys[0], (cfg.vocab_size, d)),
        "layers": layers,
        "final_ln": jnp.ones(d, dtype=dt),
        "lm_head": dense(keys[1], (d, cfg.vocab_size)),
    }


def _block(layer, x, cfg, valid, h_attn=None):
    return moe.sorted_moe_mlp(layer, x, cfg, valid)


_forward_stack, decode_step, verify_step = decoder.bind(_block)


def prefill(params, cfg: KeyeConfig, tokens, keep=None):
    """(logits, per layer (k, v [b, s, G, hd], index keys [b, s,
    index_width]): what to page out). `keep`: decoder.forward_stack."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, keep=keep)
    return logits, kvs


forward_dense = prefill


def prefill_with_prefix(params, cfg: KeyeConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """Suffix prefill over cached K, V and index keys: `prefix_kvs`
    per layer (k, v [b, P, G, hd], index keys [b, P, index_width]), as
    restored or as they lie in the pools."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0, keep=keep)
    return logits, kvs


def prefill_selections(params, cfg: KeyeConfig, tokens):
    """Per layer, in order, the selection every query of a cold
    prefill of `tokens` [b, s] makes: (positions [b, s, k], taken
    [b, s, k]); s must exceed `index_topk`. Traceable: jit it."""
    with decoder.selection_tap([]) as taps:
        _forward_stack(params, cfg, tokens)
    return taps


def decode_selections(params, cfg: KeyeConfig, token, seq_lens, k_pages,
                      v_pages, page_table):
    """Per layer, in order, the selection one decode step over the
    pools makes for each row: (positions [b, k], taken [b, k]).
    `v_pages`: the pair (V pool, index pool). The pools are read, and
    written in a copy that is dropped. Traceable: jit it."""
    with decoder.selection_tap([]) as taps:
        decode_step.__wrapped__(params, cfg, token, seq_lens, k_pages,
                                v_pages, page_table)
    return taps
