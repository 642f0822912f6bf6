"""Llama-style decoder with a paged KV cache — the flagship consumer of
the store.

The reference ships no model; its purpose is serving vLLM's paged KV
blocks (reference docs/source/design.rst:54-63: the engine calls
get_match_last_index / allocate / write / read layer by layer). This
module provides the TPU-side engine stand-in used by benchmarks, tests
and the graft entry: a GQA + RoPE + SwiGLU decoder (Llama-3-ish at
miniature scale) whose KV cache lives in fixed-size pages — the exact
unit the store transports — plus a jit-able training step for the
multi-chip dry run.

The family's own: its config (the base of every family's), its
parameter init and quantization, and the dense gated MLP. The layer
loops, the attention side and the page helpers are models/decoder.py's,
re-exported here under the names callers know.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder
from .decoder import (  # noqa: F401 — the page contract, by its old names
    kv_to_pages,
    page_keys,
    pages_to_kv,
    restore_prefix_kvs,
    restore_prefix_pages,
)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 512
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 256
    max_seq: int = 256
    page_size: int = 16  # tokens per KV page (the store's transfer unit)
    rope_theta: float = 10000.0
    # Llama-3.1-style frequency-dependent RoPE scaling, as a hashable
    # tuple (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings); () = unscaled. Matches HF's
    # rope_scaling={"rope_type": "llama3", ...} (the long-context
    # Llama-3.1/3.2 checkpoints), numerically pinned by
    # tests/test_hf_bridge.py against transformers itself.
    rope_scaling: tuple = ()
    # Sliding-window attention width (Mistral / Qwen2 long-context):
    # each query sees at most the last `window` positions (including
    # itself). 0 = full causal attention. Applied identically in dense
    # prefill, prefix-cached prefill, paged decode and multi-token
    # verify (parity vs transformers pinned in tests/test_hf_bridge).
    window: int = 0
    # The per-layer spec of the attention layers (models/decoder.py),
    # where the layers differ: layer i's band (0 = full causal
    # attention, w = the last w positions) and whether it rotates.
    # () = `window` / `use_rope` for every layer, the one-band case of
    # the same spec. A model with full AND banded layers keeps two
    # kinds of page (`two_kinds`; serving.py has the cache manager).
    layer_bands: tuple = ()
    layer_rope: tuple = ()
    norm_eps: float = 1e-5
    # The norm of a layer's input and of the stack's output is
    # mean-centred (Cohere's LayerNorm, a weight and no bias:
    # decoder.layer_norm), not RMSNorm.
    norm_center: bool = False
    # Rotary positions turn ADJACENT lanes (2 i, 2 i + 1), GPT-J's and
    # Cohere's form, not the halves (i, i + head_dim / 2).
    rope_adjacent: bool = False
    # Family knobs beyond the Llama defaults (the Gemma-1 geometry:
    # GeGLU activation, zero-centered RMSNorm weights applied as
    # (1 + w), sqrt(d_model)-scaled embeddings, and a head_dim that
    # does not equal d_model // n_heads — also used by Mistral-NeMo):
    act: str = "silu"           # "silu" (SwiGLU) | "gelu" (tanh-approx
    #                             GeGLU) | "gelu_exact" (erf GELU)
    norm_plus_one: bool = False  # rms_norm multiplies by (1 + w)
    embed_scale: float = 1.0     # embedding output multiplier
    head_dim_override: int = 0   # 0 = d_model // n_heads
    # ... and the Granite knobs: a softmax scale that is not
    # head_dim ** -0.5 (0 = that default), no positional embedding,
    # scaled residual branches and divided logits.
    attn_scale: float = 0.0
    use_rope: bool = True
    residual_mult: float = 1.0
    logits_div: float = 1.0
    # KV heads packed side by side into one cache row: a head_dim
    # under the 128 lanes of a TPU tile would make every paged-decode
    # call slice, pad and re-lay-out its layer of the pool (30 ms of a
    # 48 ms step at head_dim 64, PERF.md, PR 31); `kv_pack` heads of
    # head_dim lanes each fill the row instead, the same bytes in the
    # same order (decoder.pack_heads has the attention side). 1 = off.
    kv_pack: int = 1
    # Residual streams (models/decoder.py's residual path): 1 = the
    # one stream `x + out` every family but models/xing.py has.
    hc_mult: int = 1
    dtype: str = "bfloat16"

    @property
    def head_dim(self):
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def layer_kinds(self):
        """Each layer's mixer (decoder.py's per-layer spec): all
        attention here; models/hybrid.py's config overrides it."""
        return ("attention",) * self.n_layers

    @property
    def layer_windows(self):
        """Each layer's band (decoder.py's per-layer spec; 0 = full
        causal attention)."""
        return self.layer_bands or (self.window,) * self.n_layers

    @property
    def layer_ropes(self):
        """Whether each layer rotates."""
        return self.layer_rope or (self.use_rope,) * self.n_layers

    @property
    def two_kinds(self):
        """Full AND banded attention layers in one model: two kinds of
        page with lives of their own, so two page pools
        (decoder.attn_layers; serving.py). One band, the only other
        case built, is `window_band`."""
        bands = {w for w, k in zip(self.layer_windows, self.layer_kinds)
                 if k == "attention"}
        return 0 in bands and len(bands) > 1

    @property
    def window_band(self):
        """The band of the banded attention layers (0: none)."""
        return max(self.layer_windows, default=0)

    @property
    def n_kv_layers(self):
        """Layers that keep K and V pages: the page pools' layer
        axis."""
        return sum(k == "attention" for k in self.layer_kinds)

    @property
    def page_kinds(self):
        """The kinds of page a layer keeps, one letter each: the kind
        of a page's store key, in the order of a page-major row. K and
        V here; a latent family (models/xing.py) keeps one."""
        return "kv"

    def kv_page_shape(self):
        """Shape of one K (or V) page for ONE layer — what goes into the
        store as one block: [page_size, n_kv_heads, head_dim], with
        `kv_pack` heads side by side in a row where that is set."""
        return (self.page_size, self.n_kv_heads // self.kv_pack,
                self.head_dim * self.kv_pack)

    def kv_page_bytes(self):
        import numpy as np

        return int(np.prod(self.kv_page_shape())) * self.jdtype.itemsize

    def page_shape(self, kind):
        """Shape of ONE layer's page of `kind` (a letter of
        `page_kinds`): `kv_page_shape()`, the first kind's, for every
        kind here. A family whose kinds differ in width
        (models/glm.py: latent rows "c", index keys "i") overrides
        it."""
        return self.kv_page_shape()

    def page_layers(self, kind):
        """Which of the layers that keep pages (by their rank among
        them: the L of a page's store key) keep a page of `kind`: all
        of them here; models/glm.py's index keys live on some."""
        return tuple(range(self.n_kv_layers))


def init_params(rng, cfg: LlamaConfig):
    """Plain-dict pytree; leaf names match parallel.mesh sharding rules."""
    dt = cfg.jdtype
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    scale = cfg.d_model ** -0.5

    def dense(k, shape):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 7)
        layers.append(
            {
                "ln1": jnp.ones(cfg.d_model, dtype=dt),
                "wq": dense(k[0], (cfg.d_model, cfg.n_heads * cfg.head_dim)),
                "wk": dense(k[1], (cfg.d_model, cfg.n_kv_heads * cfg.head_dim)),
                "wv": dense(k[2], (cfg.d_model, cfg.n_kv_heads * cfg.head_dim)),
                "wo": dense(k[3], (cfg.n_heads * cfg.head_dim, cfg.d_model)),
                "ln2": jnp.ones(cfg.d_model, dtype=dt),
                "w_gate": dense(k[4], (cfg.d_model, cfg.d_ff)),
                "w_up": dense(k[5], (cfg.d_model, cfg.d_ff)),
                "w_down": dense(k[6], (cfg.d_ff, cfg.d_model)),
            }
        )
    return {
        "embed": dense(keys[0], (cfg.vocab_size, cfg.d_model)),
        "layers": layers,
        "final_ln": jnp.ones(cfg.d_model, dtype=dt),
        "lm_head": dense(keys[1], (cfg.d_model, cfg.vocab_size)),
    }


# 2-D matmul weights eligible for int8 weight-only quantization; norms
# and biases (1-D, negligible bytes) stay in the compute dtype.
_QUANT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _quantize_leaf(w, dtype, axis=0):
    """Symmetric absmax int8: {"int8": int8 [in, out], "scale": dtype}.

    axis=0 (default): per-OUTPUT-column scales [out] — the matmul form,
    where (x @ int8) * scale is exact w.r.t. the quantized weights.
    axis=1: per-ROW scales [in] — the gather form used for the
    embedding table, where each token's row is its own quantization
    unit (a per-column scale over a 128k vocab would collapse
    small-norm token rows to a few int8 levels). All-zero groups get
    scale 0 (values are 0 anyway)."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=axis)
    scale = absmax / 127.0
    denom = jnp.where(scale > 0, scale, 1.0)
    denom = denom[None, :] if axis == 0 else denom[:, None]
    q = jnp.round(wf / denom)
    return {
        "int8": jnp.clip(q, -127, 127).astype(jnp.int8),
        "scale": scale.astype(dtype),
    }


def quantize_params(params, cfg: LlamaConfig):
    """Weight-only int8 quantization of a bf16/f32 parameter tree: every
    2-D matmul weight (attention, MLP, embed, lm_head) becomes an
    {"int8", "scale"} leaf that _matmul/_embed dequantize at the tile
    level — HBM streams ~half the bytes, so bandwidth-bound decode gets
    ~2x lighter and an 8 B-param geometry fits a 16 GB v5e (BASELINE
    configs 3-4 arithmetic: 8.03 B x 2 B bf16 = 16.06 GB cannot fit;
    8.03 B x 1 B int8 + scales ~= 8.1 GB does). Accuracy: per-column
    symmetric int8 on normal-ish weights is ~0.4% relative error per
    matmul (same recipe as ops/kv_quant for KV pages)."""
    dt = cfg.jdtype

    def one_layer(layer):
        out = {}
        for name, w in layer.items():
            out[name] = (
                _quantize_leaf(w, dt) if name in _QUANT_LEAVES else w
            )
        return out

    return {
        # Embed is consumed by GATHER, not matmul: per-row scales.
        "embed": _quantize_leaf(params["embed"], dt, axis=1),
        "layers": [one_layer(la) for la in params["layers"]],
        "final_ln": params["final_ln"],
        "lm_head": _quantize_leaf(params["lm_head"], dt),
    }


def init_params_quantized(rng, cfg: LlamaConfig):
    """Random int8-quantized parameters WITHOUT ever materializing the
    bf16 tree — init_params at 8 B would allocate 16 GB before
    quantize_params could halve it, defeating the point on a 16 GB
    chip. Weights draw uniform int8 in [-127, 127] (std 127/sqrt(3)),
    so matching init_params' normal(0, d_model**-0.5) std needs
    scale = sqrt(3) * d_model**-0.5 / 127."""
    dt = cfg.jdtype
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    col_scale = (3.0 ** 0.5) * cfg.d_model ** -0.5 / 127.0

    def qdense(k, shape, scale_axis=1):
        q = jax.random.randint(k, shape, -127, 128, dtype=jnp.int8)
        return {
            "int8": q,
            "scale": jnp.full((shape[scale_axis],), col_scale, dtype=dt),
        }

    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 7)
        layers.append(
            {
                "ln1": jnp.ones(cfg.d_model, dtype=dt),
                "wq": qdense(k[0], (cfg.d_model, cfg.n_heads * cfg.head_dim)),
                "wk": qdense(
                    k[1], (cfg.d_model, cfg.n_kv_heads * cfg.head_dim)
                ),
                "wv": qdense(
                    k[2], (cfg.d_model, cfg.n_kv_heads * cfg.head_dim)
                ),
                "wo": qdense(k[3], (cfg.n_heads * cfg.head_dim, cfg.d_model)),
                "ln2": jnp.ones(cfg.d_model, dtype=dt),
                "w_gate": qdense(k[4], (cfg.d_model, cfg.d_ff)),
                "w_up": qdense(k[5], (cfg.d_model, cfg.d_ff)),
                "w_down": qdense(k[6], (cfg.d_ff, cfg.d_model)),
            }
        )
    return {
        # Per-row scales for the gather-consumed embed (see _embed).
        "embed": qdense(keys[0], (cfg.vocab_size, cfg.d_model),
                        scale_axis=0),
        "layers": layers,
        "final_ln": jnp.ones(cfg.d_model, dtype=dt),
        "lm_head": qdense(keys[1], (cfg.d_model, cfg.vocab_size)),
    }


def param_bytes(params):
    """Total bytes of every array leaf (int8 trees count int8)."""
    import numpy as np

    return sum(
        int(np.prod(p.shape)) * p.dtype.itemsize
        for p in jax.tree_util.tree_leaves(params)
    )


def _mlp(layer, x, cfg, valid, h_attn=None):
    """The family's feed-forward block (decoder.py's `block` contract):
    a gated MLP, every token on its own, so `valid` is not needed, nor
    the attention block's input; no auxiliary loss."""
    with jax.named_scope("mlp"):
        h = decoder.norm(cfg, x, layer["ln2"], layer.get("ln2_b"))
        gated = decoder.act(cfg)(decoder.matmul(h, layer["w_gate"])) * (
            decoder.matmul(h, layer["w_up"])
        )
        return decoder.matmul(gated, layer["w_down"]), None


_forward_stack, decode_step, verify_step = decoder.bind(_mlp)


def forward_dense(params, cfg: LlamaConfig, tokens):
    """Dense causal forward (training / prefill compute). tokens:
    [batch, seq] int32 → logits [batch, seq, vocab] (fp32)."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens)
    return logits, kvs


def prefill(params, cfg: LlamaConfig, tokens, keep=None):
    """Prefill: returns (logits, per-layer (k, v) arrays
    [batch, seq, n_kv, hd]) — the KV to page out to the store. `keep`:
    the one position whose logits the caller keeps (logits
    [batch, 1, vocab]: decoder.forward_stack); None: every position's."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, keep=keep)
    return logits, kvs


def prefill_with_prefix(params, cfg: LlamaConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """Suffix prefill over a cached prefix — the store's cache-HIT path.

    This is what a prefix-cache hit buys (reference design.rst:54-63:
    vLLM calls get_match_last_index, restores the matched pages, and
    prefills only the un-cached tail): compute runs over the suffix
    tokens only, attending over restored-prefix + suffix KV with the
    causal diagonal shifted by the prefix length — O(s_new * (P + s_new))
    attention FLOPs instead of O((P + s_new)^2) for a full re-prefill,
    and none of the prefix's QKV/MLP matmuls.

    tokens:     [batch, s_new] int32 — the NOT-cached suffix tokens.
    prefix_kvs: per-layer list of (k, v), each [batch, P, n_kv, hd],
                post-RoPE as produced by `prefill` / restored via
                `pages_to_kv` — positions are absolute, so restored K
                needs no re-rotation.

    Returns (logits [batch, s_new, vocab] fp32, or [batch, 1, vocab]
    of position `keep` of the suffix where the caller keeps one,
    per-layer suffix (k, v) [batch, s_new, n_kv, hd] — the new pages to
    put to the store).
    `pos0`: absolute position of the prefix's first token (see
    decoder.forward_stack — used by the windowed engine's trimmed-prefix
    admission).
    """
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0, keep=keep)
    return logits, kvs


def token_nll(logits, targets):
    """Mean next-token NLL (fp32 log-softmax) — shared by every model
    family's loss."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def loss_fn(params, cfg: LlamaConfig, tokens):
    """Next-token cross-entropy (fp32 accumulation)."""
    logits, _ = forward_dense(params, cfg, tokens[:, :-1])
    return token_nll(logits, tokens[:, 1:])


def train_step(params, opt_state, cfg, tokens, optimizer, loss=None):
    """One optimizer step (used by the multi-chip dry run; grads average
    over the dp axis automatically under jit + NamedShardings). The ONE
    optimizer-step implementation for all model families — pass `loss`
    to train a different family (moe.train_step does)."""
    loss_f = loss_fn if loss is None else loss
    loss_val, grads = jax.value_and_grad(loss_f)(params, cfg, tokens)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(
        lambda p, u: (p + u).astype(p.dtype), params, updates
    )
    return params, opt_state, loss_val
