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

TPU-first choices: bf16 params with fp32 softmax/loss accumulation (MXU
native), static shapes everywhere (page budgets are compile-time),
functional pytree params (plain dicts — pjit/NamedSharding attach by leaf
name, see parallel/mesh.py), no Python control flow inside jit.
"""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.pallas_flash_attention import flash_prefill
from ..ops.paged_attention import (
    prefill_attention,  # noqa: F401 — kept as the XLA reference path
    scatter_kv_multi,
    scatter_kv_to_pages,
)
from ..ops.pallas_paged_attention import (
    decode_attention as paged_decode_attention,
    verify_attention as paged_verify_attention,
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
    norm_eps: float = 1e-5
    # Family knobs beyond the Llama defaults (the Gemma-1 geometry:
    # GeGLU activation, zero-centered RMSNorm weights applied as
    # (1 + w), sqrt(d_model)-scaled embeddings, and a head_dim that
    # does not equal d_model // n_heads — also used by Mistral-NeMo):
    act: str = "silu"           # "silu" (SwiGLU) | "gelu" (tanh-approx
    #                             GeGLU) | "gelu_exact" (erf GELU)
    norm_plus_one: bool = False  # rms_norm multiplies by (1 + w)
    embed_scale: float = 1.0     # embedding output multiplier
    head_dim_override: int = 0   # 0 = d_model // n_heads
    dtype: str = "bfloat16"

    @property
    def head_dim(self):
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def kv_page_shape(self):
        """Shape of one K (or V) page for ONE layer — what goes into the
        store as one block: [page_size, n_kv_heads, head_dim]."""
        return (self.page_size, self.n_kv_heads, self.head_dim)

    def kv_page_bytes(self):
        import numpy as np

        return int(np.prod(self.kv_page_shape())) * self.jdtype.itemsize


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


def rms_norm(x, w, eps=1e-5, plus_one=False):
    """plus_one: Gemma convention — stored weights are zero-centered
    and applied as (1 + w)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    xn = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return xn * (1.0 + w) if plus_one else xn * w


def _llama3_scale_freqs(freqs, scaling):
    """Frequency-dependent RoPE rescale (Llama-3.1 "llama3" rope_type):
    long-wavelength (low-frequency) components are slowed by `factor`,
    short wavelengths kept, and the band between low/high_freq_factor
    interpolated — the published recipe that lets 8k-trained weights
    address 128k positions. Mirrors HF `_compute_llama3_parameters`."""
    factor, low_f, high_f, orig_max = scaling
    wavelen = 2.0 * jnp.pi / freqs
    low_wl = orig_max / low_f
    high_wl = orig_max / high_f
    smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
    mid = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(
        wavelen > low_wl, freqs / factor,
        jnp.where(wavelen < high_wl, freqs, mid),
    )


def rope(x, positions, theta, scaling=()):
    """x: [..., seq, heads, hd]; positions broadcastable to [..., seq]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if scaling:
        freqs = _llama3_scale_freqs(freqs, scaling)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., s, half]
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _matmul(h, w):
    """x @ W where W is either a dense array or an int8 weight-only
    quantized leaf {"int8": [in, out] int8, "scale": [out] f32}
    (produced by quantize_params / init_params_quantized).

    The quantized form computes (x @ int8.astype(x.dtype)) * scale —
    mathematically identical to x @ (int8 * scale) because the scale is
    per OUTPUT column, but HBM only ever streams the int8 bytes: XLA
    fuses the convert into the dot's operand fetch (tile-level dequant
    in VMEM), which is what makes bandwidth-bound decode ~2x lighter
    and lets an 8 B-param geometry fit a 16 GB chip."""
    if isinstance(w, dict):
        return (h @ w["int8"].astype(h.dtype)) * w["scale"].astype(h.dtype)
    return h @ w


def _proj(h, layer, w, b_, shape=None):
    """_matmul with an optional bias leaf (absent in native checkpoints;
    the HF bridge adds bq/bk/bv/bo for attention_bias=True families
    like Qwen2 — pytree structure is static under jit either way)."""
    out = _matmul(h, layer[w])
    bias = layer.get(b_)
    if bias is not None:
        out = out + bias
    return out if shape is None else out.reshape(shape)


# Stage names (jax.named_scope): every device operation's metadata
# carries the stage it came from — one name a stage and none a layer
# index, the same in every model family — so a trace reduction finds a
# stage's operations whatever the compiler calls its fusions:
#   embed, attn.qkv, attn.rope, attn.kernel, attn.out, mlp (moe.route,
#   moe.dispatch, moe.experts, moe.combine in models/moe.py),
#   pool.update, lm_head.


def _qkv(layer, x, cfg, positions):
    b = x.shape[0]
    s = x.shape[1]
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, layer["ln1"], cfg.norm_eps, cfg.norm_plus_one)
        q = _proj(h, layer, "wq", "bq", (b, s, cfg.n_heads, cfg.head_dim))
        k = _proj(h, layer, "wk", "bk",
                  (b, s, cfg.n_kv_heads, cfg.head_dim))
        v = _proj(h, layer, "wv", "bv",
                  (b, s, cfg.n_kv_heads, cfg.head_dim))
    with jax.named_scope("attn.rope"):
        q = rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def _attn_out(layer, attn_flat):
    """attn @ Wo (+ optional bo) — the attention output projection."""
    with jax.named_scope("attn.out"):
        return _proj(attn_flat, layer, "wo", "bo")


def _act(cfg):
    # HF "gelu_pytorch_tanh"/"gelu_new" are jax.nn.gelu's tanh
    # approximation; plain "gelu" is the exact erf form — they differ
    # by up to ~1e-3 per activation, so the bridge maps them apart.
    if cfg.act == "silu":
        return jax.nn.silu
    if cfg.act == "gelu_exact":
        return lambda x: jax.nn.gelu(x, approximate=False)
    return lambda x: jax.nn.gelu(x, approximate=True)


def _mlp(layer, x, cfg):
    with jax.named_scope("mlp"):
        h = rms_norm(x, layer["ln2"], cfg.norm_eps, cfg.norm_plus_one)
        gated = _act(cfg)(_matmul(h, layer["w_gate"])) * _matmul(
            h, layer["w_up"]
        )
        return _matmul(gated, layer["w_down"])


def _embed(params, tokens, cfg=None):
    """Token embedding gather; int8-quantized embeds gather int8 rows
    and their PER-ROW scales (shape [vocab] — each token's row is its
    own quantization unit) — HBM reads stay int8. The scale leaf
    carries the model's compute dtype (quantize_params stores it as
    cfg.jdtype), so the result matches the dense path."""
    e = params["embed"]
    with jax.named_scope("embed"):
        if isinstance(e, dict):
            rows = jnp.take(e["int8"], tokens, axis=0)
            row_scale = jnp.take(e["scale"], tokens, axis=0)
            out = rows.astype(row_scale.dtype) * row_scale[..., None]
        else:
            out = jnp.take(e, tokens, axis=0)
        if cfg is not None and cfg.embed_scale != 1.0:
            out = out * jnp.asarray(cfg.embed_scale, out.dtype)
    return out


def _logits(params, x):
    """Final projection to vocab, fp32 output."""
    with jax.named_scope("lm_head"):
        return _matmul(x, params["lm_head"]).astype(jnp.float32)


def _forward_stack(params, cfg: LlamaConfig, tokens, prefix_kvs=None,
                   pos0=0):
    """The ONE decoder-stack loop shared by dense forward and
    prefix-cached prefill (the cache-hit identity depends on these two
    paths never diverging). With `prefix_kvs` (per-layer (k, v) of shape
    [batch, P, n_kv, hd], post-RoPE), positions shift by P and each
    layer attends over prefix + suffix KV through the rectangular flash
    kernel; with None this reduces exactly to the dense causal forward.

    `pos0` shifts every ABSOLUTE rope position (prefix starts at pos0,
    suffix at pos0 + P): a sliding-window engine trims the restored
    prefix to the in-window tail pages, whose KV was roped at absolute
    positions — the band mask itself needs no shift because it depends
    only on RELATIVE (query - key) distance, which local indices
    preserve."""
    b, s = tokens.shape
    prefix_len = 0 if prefix_kvs is None else prefix_kvs[0][0].shape[1]
    x = _embed(params, tokens, cfg)
    positions = jnp.broadcast_to(
        pos0 + prefix_len + jnp.arange(s)[None], (b, s)
    )
    kvs = []
    for li, layer in enumerate(params["layers"]):
        q, k, v = _qkv(layer, x, cfg, positions)
        if prefix_kvs is None:
            k_full, v_full = k, v
        else:
            pk, pv = prefix_kvs[li]
            k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        # Pallas flash kernel on TPU (O(S) memory; speed against the
        # XLA path not measured), XLA path elsewhere. kv may be
        # longer than q — the causal diagonal shifts by the prefix.
        with jax.named_scope("attn.kernel"):
            attn = flash_prefill(q, k_full, v_full, causal=True,
                                 window=cfg.window)
        x = x + _attn_out(layer, attn.reshape(b, s, -1))
        x = x + _mlp(layer, x, cfg)
        kvs.append((k, v))
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.norm_plus_one)
    logits = _logits(params, x)
    return logits, kvs


def forward_dense(params, cfg: LlamaConfig, tokens):
    """Dense causal forward (training / prefill compute). tokens:
    [batch, seq] int32 → logits [batch, seq, vocab] (fp32)."""
    return _forward_stack(params, cfg, tokens)


def prefill(params, cfg: LlamaConfig, tokens):
    """Prefill: returns (logits, per-layer (k, v) arrays
    [batch, seq, n_kv, hd]) — the KV to page out to the store."""
    return forward_dense(params, cfg, tokens)


def prefill_with_prefix(params, cfg: LlamaConfig, tokens, prefix_kvs,
                        pos0=0):
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

    Returns (logits [batch, s_new, vocab] fp32, per-layer suffix (k, v)
    [batch, s_new, n_kv, hd] — the new pages to put to the store).
    `pos0`: absolute position of the prefix's first token (see
    _forward_stack — used by the windowed engine's trimmed-prefix
    admission).
    """
    return _forward_stack(params, cfg, tokens, prefix_kvs, pos0=pos0)


@partial(jax.jit, static_argnames=("cfg",))
def decode_step(params, cfg: LlamaConfig, token, seq_lens, k_pages, v_pages,
                page_table):
    """One decode step over paged KV.

    token:      [batch] int32 — current input token
    seq_lens:   [batch] int32 — tokens already in cache (excl. current)
    k_pages/v_pages: [n_layers, n_pages, page, n_kv, hd]
    page_table: [batch, max_pages] int32

    Returns (logits [batch, vocab] fp32, k_pages, v_pages): the pools
    it was given with, per layer, the new token's K and V rows written
    at (layer, page of position seq_lens, seq_lens % page_size). The
    pools stay the 5-D arrays they arrive as — every layer's scatter and
    every layer's attention address `li` inside them, and nothing the
    size of a layer is sliced out or stacked back — so a caller that
    donates them (the engine's fused programs) updates them in place.
    """
    b = token.shape[0]
    x = _embed(params, token[:, None], cfg)  # [b, 1, d]
    positions = seq_lens[:, None]  # current position
    page_idx_in_seq = seq_lens // cfg.page_size
    target_page = jnp.take_along_axis(
        page_table, page_idx_in_seq[:, None], axis=1
    )[:, 0]
    slot = seq_lens % cfg.page_size

    for li, layer in enumerate(params["layers"]):
        q, k, v = _qkv(layer, x, cfg, positions)
        with jax.named_scope("pool.update"):
            k_pages = scatter_kv_to_pages(k_pages, k, target_page, slot,
                                          layer=li)
            v_pages = scatter_kv_to_pages(v_pages, v, target_page, slot,
                                          layer=li)
        with jax.named_scope("attn.kernel"):
            attn = paged_decode_attention(
                q[:, 0], k_pages, v_pages, page_table, seq_lens + 1,
                window=cfg.window, layer=li
            )
        x = x + _attn_out(layer, attn.reshape(b, 1, -1))
        x = x + _mlp(layer, x, cfg)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.norm_plus_one)
    return _logits(params, x[:, 0]), k_pages, v_pages


@partial(jax.jit, static_argnames=("cfg",))
def verify_step(params, cfg: LlamaConfig, tokens, seq_lens, k_pages,
                v_pages, page_table, valid_len=None):
    """m-token decode over paged KV — speculative decoding's verify
    step (and the chunked-prefill inner step). Consumes m tokens per
    sequence in ONE pass and returns next-token logits at every one of
    the m positions, exactly as if `decode_step` had run m times.

    tokens:     [batch, m] int32 — token j lands at position
                seq_lens[b] + j (its KV is scattered into the pages).
    seq_lens:   [batch] int32 — tokens already in cache.
    k_pages/v_pages: [n_layers, n_pages, page, n_kv, hd]
    page_table: [batch, max_pages] int32 (pages covering positions up
                to seq_lens + valid_len - 1 must be allocated).
    valid_len:  [batch] int32 or None — tokens per row that are REAL;
                padded columns (j >= valid_len[b]) scatter their KV
                into page 0 (the engine's scratch page) at slot
                j % page_size, so ragged counts can't clamp into — and
                corrupt — a sequence's live pages. m may exceed
                page_size: wrapped scratch slots collide, which is
                harmless (scratch values are never attended — page 0
                appears in no sequence's page table). None means all m
                are valid.

    Returns (logits [batch, m, vocab] fp32, k_pages, v_pages): the
    pools it was given with the m tokens' rows written per layer, 5-D
    throughout as in `decode_step`.
    A rejected speculative tail needs no rollback: its KV sits at
    positions >= the accepted seq_len, which later steps overwrite
    before attending (attention is masked by per-token length).
    """
    b, m = tokens.shape
    x = _embed(params, tokens, cfg)  # [b, m, d]
    positions = seq_lens[:, None] + jnp.arange(m)[None, :]
    page_idx_in_seq = positions // cfg.page_size  # [b, m]
    target_page = jnp.take_along_axis(page_table, page_idx_in_seq, axis=1)
    slot = positions % cfg.page_size
    if valid_len is not None:
        ok = jnp.arange(m)[None, :] < valid_len[:, None]  # [b, m]
        target_page = jnp.where(ok, target_page, 0)
        slot = jnp.where(ok, slot, jnp.arange(m)[None, :] % cfg.page_size)

    for li, layer in enumerate(params["layers"]):
        q, k, v = _qkv(layer, x, cfg, positions)
        with jax.named_scope("pool.update"):
            k_pages = scatter_kv_multi(k_pages, k, target_page, slot,
                                       layer=li)
            v_pages = scatter_kv_multi(v_pages, v, target_page, slot,
                                       layer=li)
        # Pallas streaming kernel on TPU (pages HBM->VMEM, nothing
        # gathered), XLA gather path elsewhere.
        with jax.named_scope("attn.kernel"):
            attn = paged_verify_attention(
                q, k_pages, v_pages, page_table, seq_lens,
                window=cfg.window, layer=li
            )
        x = x + _attn_out(layer, attn.reshape(b, m, -1))
        x = x + _mlp(layer, x, cfg)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.norm_plus_one)
    return _logits(params, x), k_pages, v_pages


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


# ---------------------------------------------------------------------------
# KV paging helpers: model pages ↔ store pages
# ---------------------------------------------------------------------------

def kv_to_pages(cfg: LlamaConfig, k, v):
    """Split prefill KV [batch, seq, n_kv, hd] into store pages.

    Returns (k_pages, v_pages) of shape [batch, n_pages, page, n_kv, hd]
    with zero padding in the tail page — page-aligned exactly like the
    store's fixed-size blocks."""
    b, s, n_kv, hd = k.shape
    n_pages = -(-s // cfg.page_size)
    pad = n_pages * cfg.page_size - s
    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    shape = (b, n_pages, cfg.page_size, n_kv, hd)
    return k.reshape(shape), v.reshape(shape)


def pages_to_kv(cfg: LlamaConfig, k_pages, v_pages, length):
    """Inverse of `kv_to_pages`: reassemble contiguous KV from store
    pages. k_pages/v_pages: [batch, n_pages, page, n_kv, hd] →
    (k, v) [batch, length, n_kv, hd], dropping tail-page padding."""
    b, n_pages, page, n_kv, hd = k_pages.shape
    k = k_pages.reshape(b, n_pages * page, n_kv, hd)[:, :length]
    v = v_pages.reshape(b, n_pages * page, n_kv, hd)[:, :length]
    return k, v


def page_keys(prefix, layer, kind, n_pages):
    """Content-addressed store keys for a sequence's pages, one namespace
    per (layer, k/v) — mirrors vLLM's per-layer block keys
    (design.rst:54-63)."""
    return [f"{prefix}/L{layer}/{kind}/p{i}" for i in range(n_pages)]


def restore_prefix_pages(store, cfg: LlamaConfig, key_fn, n_pages,
                         getter=None):
    """Restore a matched prefix from the store in PAGE form: the one
    get_kv_pages recipe every cache-hit consumer shares. `key_fn(layer,
    kind)` returns that (layer, kind)'s n_pages keys (index-addressed
    `page_keys` or the serving engine's content-addressed keys);
    `getter` overrides the fetch method (e.g.
    store.get_kv_pages_quantized for int8 pages).

    ONE batched store call covers every (layer, kind): 2L small
    fetches would pay 2L pin/transfer round trips where the batch pays
    one, and one large DMA beats 2L small ones. The keys go page-major
    (page, layer, k then v), the order the serving engine's offload
    allocates them in (serving.content_page_keys_by_page): pages that
    one offload wrote then lie in the store's pool in the order asked
    for, and the SHM read is one zero-copy view of the pool and not a
    view a block plus a stacking copy. The split back into per-layer
    stacks is one device transpose, then slicing.
    Returns (k_pages, v_pages) [n_layers, n_pages, page, n_kv, hd]."""
    get = getter if getter is not None else store.get_kv_pages
    per = [key_fn(li, kind) for li in range(cfg.n_layers) for kind in "kv"]
    keys = [ks[p] for p in range(n_pages) for ks in per]
    flat = get(keys, cfg.kv_page_shape(), cfg.jdtype)
    both = jnp.moveaxis(
        flat.reshape(n_pages, cfg.n_layers, 2, *cfg.kv_page_shape()), 0, 2
    )
    return both[:, 0], both[:, 1]


def restore_prefix_kvs(store, cfg: LlamaConfig, seq_id, n_pages):
    """Restore a matched prefix from the store into the per-layer
    contiguous (k, v) list `prefill_with_prefix` consumes — the
    documented cache-HIT recipe after `store.cached_prefix_len` reports
    `n_pages` hits for `seq_id`. `store` is a TpuKVStore (duck-typed:
    needs get_kv_pages). Batch dim is 1 (one sequence per key prefix,
    as vLLM's block tables are per-sequence)."""
    kp, vp = restore_prefix_pages(
        store, cfg, lambda li, kind: page_keys(seq_id, li, kind, n_pages),
        n_pages,
    )
    return [
        pages_to_kv(cfg, kp[li][None], vp[li][None],
                    n_pages * cfg.page_size)
        for li in range(cfg.n_layers)
    ]
