"""The decoder stack every model family shares, and the KV page contract.

One definition each of the three loops over a model's layers: the
dense / prefix-cached forward (`forward_stack`), the one-token paged
step (`decode_step`) and the m-token paged step (`verify_step`). A
family (models/llama.py, models/moe.py, models/hybrid.py) supplies its
config fields, its parameter init and its feed-forward `block`, and
gets the loops with that block bound in from `bind`; nothing here knows
which families exist. What a layer's MIXER is the config says per layer
(`cfg.layer_kinds`): attention over K and V pages, the Mamba-2 or the
Mamba-1 mixer over a recurrent state (ops/ssm.py), or a layer that
keeps nothing and borrows another layer's pages or scan output.

`block(layer, x, cfg, valid, h_attn)` maps the residual stream [b, s, d]
to the block's output [b, s, d] and the family's auxiliary loss for that
layer (None where it has none); a block over routed experts returns a
third: the experts this call fetched, an int32 scalar, where it fetched
fewer than it holds, else None (`decode_step` sums them for the
engine's counter); a block whose layer holds a SHARE of the experts its
router scores returns a fourth, its counts: "pairs_held" [b, s] int32,
per row the pairs (row, chosen expert) that fell on an expert held
here, zero for a row that is not valid, and "rows", the rows its
matmuls over a prompt ran (the loops sum both over the layers).
`valid` ([b, s] bool or None) marks the
rows that hold a real token: a block whose tokens compete for something
(MoE expert capacity) keeps the others out, a block that treats tokens
independently ignores it. `h_attn` is the normalised input the layer's
attention block read (None after a state layer): a family whose router
sits before attention routes on it, the others ignore it.

The attention side is GQA + RoPE over a paged KV cache: bf16 params
with fp32 softmax accumulation, static shapes everywhere (page budgets
are compile-time), functional pytree params (plain dicts), no Python
control flow inside jit. `cfg` is a models.llama.LlamaConfig or a
subclass of it. The paging helpers at the bottom turn a model's KV into
the store's fixed-size pages and back.
"""

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import ssm
from ..ops.pallas_latent_attention import latent_decode_attention
from ..ops import sparse_select
from ..ops.pallas_flash_attention import flash_prefill
from ..ops.paged_attention import scatter_kv_multi, scatter_kv_to_pages
from ..ops.pallas_paged_attention import (
    decode_attention as paged_decode_attention,
    verify_attention as paged_verify_attention,
)


def rms_norm(x, w, eps=1e-5, plus_one=False):
    """plus_one: Gemma convention — stored weights are zero-centered
    and applied as (1 + w)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    xn = x * jax.lax.rsqrt(var + eps).astype(x.dtype)
    return xn * (1.0 + w) if plus_one else xn * w


def layer_norm(x, w, eps=1e-5, b=None):
    """The mean-centred norm: (x - mean) over the standard deviation,
    a weight and, where the family has one (`b`; Cohere's has none,
    models/phi_flash.py's has), a bias; statistics and product in
    float32."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    out = xc * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out.astype(x.dtype)


def norm(cfg, x, w, b=None):
    """The family's norm of a layer's input and of the stack's output:
    `rms_norm`, or `layer_norm` where `cfg.norm_center` (with the bias
    `b` where the parameters hold one)."""
    if cfg.norm_center:
        return layer_norm(x, w, cfg.norm_eps, b)
    out = rms_norm(x, w, cfg.norm_eps, cfg.norm_plus_one)
    # over a float32 stream (`stream_open`) the sublayers still compute
    # in the model's type
    return out.astype(cfg.jdtype) if getattr(cfg, "fp32_stream", False) \
        else out


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


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature term: 0.1 mscale ln(factor) + 1."""
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def _yarn_scale_freqs(freqs, theta, yarn):
    """YaRN ("NTK-by-parts", arXiv:2309.00071, as DeepSeek-V2/V3 compute
    it): dimensions that turn more than `beta_fast` times within the
    original context keep their frequency, those that turn fewer than
    `beta_slow` times are slowed by `factor`, a linear ramp between.
    `yarn` = (factor, original_max, beta_fast, beta_slow, mscale,
    mscale_all_dim). Returns (frequencies, the multiplier of cos and
    sin: mscale over mscale_all_dim's term, 1 where they are equal)."""
    factor, orig_max, beta_fast, beta_slow, mscale, mscale_all = yarn
    half = freqs.shape[0]

    def turns_dim(turns):
        return (2 * half * np.log(orig_max / (turns * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(int(np.floor(turns_dim(beta_fast))), 0)
    high = min(int(np.ceil(turns_dim(beta_slow))), 2 * half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    freqs = freqs / factor * ramp + freqs * (1.0 - ramp)
    return freqs, yarn_mscale(factor, mscale) / yarn_mscale(factor,
                                                            mscale_all)


def rope(x, positions, theta, scaling=(), yarn=(), adjacent=False):
    """x: [..., seq, heads, hd]; positions broadcastable to [..., seq].
    `scaling`: the llama3 rescale; `yarn`: the YaRN one. A frequency
    turns the pair of lanes (i, i + hd / 2), or with `adjacent`
    (GPT-J's form, Cohere's) the pair (2 i, 2 i + 1)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    if scaling:
        freqs = _llama3_scale_freqs(freqs, scaling)
    mult = 1.0
    if yarn:
        freqs, mult = _yarn_scale_freqs(freqs, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., s, half]

    def trig(f):
        t = f(angles) * mult if mult != 1.0 else f(angles)
        return t[..., None, :].astype(x.dtype)

    cos, sin = trig(jnp.cos), trig(jnp.sin)
    if adjacent:
        # x cos + (the pair's other lane, signed) sin, every lane where
        # it lies: lane 2 i takes -x[2 i + 1], lane 2 i + 1 takes
        # x[2 i]. Two rolls along the lanes and a select; slicing the
        # even and the odd lanes apart compiles to gathers that put the
        # head's lanes on a major dimension.
        cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
        even = jnp.arange(hd) % 2 == 0
        other = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                          jnp.roll(x, 1, axis=-1))
        return x * cos + other * sin
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def matmul(h, w):
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


def weight_where_it_lies(product):
    """A decode step's product of one token a row with a projection's
    weight, as it leaves the dot and before bias, scale or reshape:
    nothing the compiler does behind this point reaches the dot. The
    Pallas decode kernels take q (and the pools' scatters k and v) in
    a layout of their own, and the TPU's layout assignment otherwise
    pulls it back through the reshape into the dot and TRANSPOSES THE
    WEIGHT to fit, a copy of every q, k and v weight every step (134 MB
    a layer at 128 query heads: 1.8 ms of an 8.2 ms step, PERF.md
    section 6, PR 43); held here, the dot reads the parameter as it
    lies and what is re-laid is the handful of rows. No value changes.
    The barrier placed after the reshape keeps every copy. The
    admission programs and `verify_step` do not ask for it: their
    weights are re-laid once for hundreds of rows (under 1 % of a cold
    program, a few percent of a hit's: ROADMAP S12), and a barrier
    there stands between the dot and what fuses into it."""
    return jax.lax.optimization_barrier(product)


def proj(h, layer, w, b_, shape=None, decode=False):
    """matmul with an optional bias leaf (absent in native checkpoints;
    the HF bridge adds bq/bk/bv/bo for attention_bias=True families
    like Qwen2 — pytree structure is static under jit either way).
    `decode`: the product is `weight_where_it_lies`'s."""
    out = matmul(h, layer[w])
    if decode:
        out = weight_where_it_lies(out)
    bias = layer.get(b_)
    if bias is not None:
        out = out + bias
    return out if shape is None else out.reshape(shape)


# Stage names (jax.named_scope): every device operation's metadata
# carries the stage it came from — one name a stage and none a layer
# index, the same in every model family — so a trace reduction finds a
# stage's operations whatever the compiler calls its fusions:
#   embed, attn.qkv, attn.rope, attn.kernel, attn.out, the family's
#   block (mlp in models/llama.py; moe.route, moe.dispatch, moe.experts,
#   moe.combine in models/moe.py), pool.update, lm_head.


def qkv(layer, x, cfg, positions, rotate=None):
    """q, k, v of one attention layer; `rotate` (None: cfg.use_rope)
    says whether this layer applies rotary positions."""
    return _qkv(layer, x, cfg, positions, rotate)[:3]


def _qkv(layer, x, cfg, positions, rotate=None, decode=False, keep=None):
    """... and `h`, the normalised input they were projected from,
    which a family's feed-forward block may read too (a router placed
    before attention). `decode`: a decode step asks (`proj`). A layer
    without `wk` (a "cross" layer: it attends another layer's K and V)
    gets k = v = None. `keep`: the one position whose QUERY is
    projected (`forward_stack` below the rows it cuts); K and V are
    every position's."""
    b = x.shape[0]
    s = x.shape[1]
    with jax.named_scope("attn.qkv"):
        h = norm(cfg, x, layer["ln1"], layer.get("ln1_b"))
        hq = h
        if keep is not None:
            hq = jax.lax.dynamic_slice_in_dim(h, keep, 1, axis=1)
            qpos = jax.lax.dynamic_slice_in_dim(positions, keep, 1, axis=1)
        q = proj(hq, layer, "wq", "bq",
                 (b, hq.shape[1], cfg.n_heads, cfg.head_dim), decode)
        k = v = None
        if "wk" in layer:
            k = proj(h, layer, "wk", "bk",
                     (b, s, cfg.n_kv_heads, cfg.head_dim), decode)
            v = proj(h, layer, "wv", "bv",
                     (b, s, cfg.n_kv_heads, cfg.head_dim), decode)
        if "q_norm" in layer:
            # a per-head RMSNorm on q and k before rotary (Qwen3's;
            # models/keye.py), a weight of head_dim each
            q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
            k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
        if cfg.attn_scale:
            # The kernels scale scores by head_dim ** -0.5; a family
            # with a softmax scale of its own folds the ratio into q.
            q = q * jnp.asarray(cfg.attn_scale * cfg.head_dim ** 0.5,
                                q.dtype)
    if cfg.use_rope if rotate is None else rotate:
        with jax.named_scope("attn.rope"):
            q = rope(q, positions if keep is None else qpos, cfg.rope_theta,
                     cfg.rope_scaling, adjacent=cfg.rope_adjacent)
            k = rope(k, positions, cfg.rope_theta, cfg.rope_scaling,
                     adjacent=cfg.rope_adjacent)
    return q, k, v, h


def pack_heads(cfg, q, k, v):
    """q, k, v of one token a row, [b, 1, heads, hd], in the form of a
    cache whose rows hold `cfg.kv_pack` kv heads side by side: k and v
    reshaped (heads p*j .. p*j + p - 1 become lanes of row j), and
    each query head widened to a row's lanes, its values in the lanes
    of its own kv head and zeros in the others, so that its scores
    against a row are its scores against that head. The attention
    kernels scale by the row width ** -0.5; sqrt(p) puts head_dim **
    -0.5 back. `unpack_heads` picks the same lanes of the output."""
    p = cfg.kv_pack
    packed = (*k.shape[:2], cfg.n_kv_heads // p, p * k.shape[-1])
    return pack_queries(cfg, q), k.reshape(packed), v.reshape(packed)


def pack_queries(cfg, q):
    """`pack_heads`' query side alone (a layer that attends another
    layer's packed rows has queries and no K or V of its own)."""
    p = cfg.kv_pack
    b, s, n_heads, hd = q.shape
    lanes = _own_lanes(cfg, q.dtype)                       # [heads, p]
    q = (q[..., None, :] * lanes[:, :, None]).reshape(b, s, n_heads, p * hd)
    return q * jnp.asarray(p ** 0.5, q.dtype)


def _own_lanes(cfg, dtype):
    """[n_heads, kv_pack] one-hot: which of a packed row's heads is
    query head i's own kv head."""
    group = cfg.n_heads // cfg.n_kv_heads
    return jax.nn.one_hot(
        (jnp.arange(cfg.n_heads) // group) % cfg.kv_pack, cfg.kv_pack,
        dtype=dtype)


def unpack_heads(cfg, attn):
    """[b, n_heads, kv_pack * hd] from attention over packed rows ->
    [b, n_heads, hd]: each query head's own kv head's lanes."""
    b, n_heads, width = attn.shape
    rows = attn.reshape(b, n_heads, cfg.kv_pack, width // cfg.kv_pack)
    return jnp.sum(rows * _own_lanes(cfg, attn.dtype)[None, :, :, None],
                   axis=2)


def attn_out(layer, attn_flat):
    """attn @ Wo (+ optional bo) — the attention output projection."""
    with jax.named_scope("attn.out"):
        return proj(attn_flat, layer, "wo", "bo")


def act(cfg):
    # HF "gelu_pytorch_tanh"/"gelu_new" are jax.nn.gelu's tanh
    # approximation; plain "gelu" is the exact erf form — they differ
    # by up to ~1e-3 per activation, so the bridge maps them apart.
    if cfg.act == "silu":
        return jax.nn.silu
    if cfg.act == "gelu_exact":
        return lambda x: jax.nn.gelu(x, approximate=False)
    return lambda x: jax.nn.gelu(x, approximate=True)


def embed(params, tokens, cfg=None):
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


def lm_head(params, x, cfg=None):
    """Final projection to vocab, fp32 output (divided by the
    family's `logits_div` where it has one)."""
    with jax.named_scope("lm_head"):
        if "lm_head" in params:
            out = matmul(x, params["lm_head"])
        else:  # tied: the embedding's rows, contracted where they lie
            out = jnp.einsum("...d,vd->...v", x, params["embed"])
        out = out.astype(jnp.float32)
        if cfg is not None and cfg.logits_div != 1.0:
            out = out / cfg.logits_div
        return out


# The residual path, a function of the family: what carries a layer's
# input around each of its two sublayers. One stream (`cfg.hc_mult` 1,
# every family but models/xing.py): `x + out`, and `stream_in` hands the
# sublayer the stream itself. n streams [b, s, n, d] mixed by
# manifold-constrained hyper-connections (mHC, arXiv:2512.24880): per
# token, from the normalised streams, a read vector Hpre (sigmoid), a
# write vector Hpost (2 sigmoid) and a doubly stochastic stream mixer
# Hres (exp, then `hc_iters` Sinkhorn iterations), all float32; the
# sublayer reads sum_i Hpre[i] X[i] and the streams become
# Hres X + Hpost y. Each sublayer has coefficients of its own (the
# layer's "hc_attn" / "hc_ffn": norm [n d], proj [n d, n n + 2 n],
# bias [n n + 2 n], a [3] = a_pre, a_post, a_res).


def hc_coef(p, x, cfg):
    """(Hpre [b, s, n], Hpost [b, s, n], Hres [b, s, n, n]) float32 of
    the streams x [b, s, n, d]."""
    f32 = jnp.float32
    b, s, n, d = x.shape
    with jax.named_scope("hc.coef"):
        xt = rms_norm(x.reshape(b, s, n * d), p["norm"], cfg.norm_eps)
        z = xt.astype(f32) @ p["proj"]                     # [b, s, 2n + nn]
        a, bias = p["a"], p["bias"]
        pre = jax.nn.sigmoid(a[0] * z[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + bias[n:2 * n])
        res = (a[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
        m = jnp.exp(jnp.clip(res, -cfg.hc_clamp, cfg.hc_clamp))
        for _ in range(cfg.hc_iters):
            m = m / (jnp.sum(m, axis=-2, keepdims=True) + cfg.hc_eps)
            m = m / (jnp.sum(m, axis=-1, keepdims=True) + cfg.hc_eps)
    return pre, post, m


def stream_open(cfg, x):
    """The embedding as the stack's streams: itself, or n copies; in
    float32 where the family adds its residuals there
    (`cfg.fp32_stream`, models/evabyte.py: every `residual` then adds
    a sublayer's output to a float32 stream, and `norm` hands the
    sublayers the model's type)."""
    if getattr(cfg, "fp32_stream", False):
        x = x.astype(jnp.float32)
    if cfg.hc_mult == 1:
        return x
    return jnp.broadcast_to(x[:, :, None], (*x.shape[:2], cfg.hc_mult,
                                            x.shape[-1]))


def stream_in(cfg, layer, x, which):
    """(a sublayer's input [b, s, d], what `residual` needs to write
    its output back): the stream itself and None, or the streams read
    through Hpre and this sublayer's (Hpost, Hres). `which`: "attn" or
    "ffn"."""
    if cfg.hc_mult == 1:
        return x, None
    pre, post, res = hc_coef(layer["hc_" + which], x, cfg)
    with jax.named_scope("hc.mix"):
        x_in = sum(pre[..., i, None] * x[:, :, i]
                   for i in range(cfg.hc_mult))
    return x_in.astype(x.dtype), (post, res)


def residual(cfg, x, out, mix=None):
    """x + out, the branch scaled by the family's `residual_mult`; with
    `mix` (n streams) Hres x + Hpost out."""
    if mix is not None:
        post, res = mix
        n = cfg.hc_mult
        with jax.named_scope("hc.mix"):
            rows = [sum(res[..., i, j, None] * x[:, :, j] for j in range(n))
                    + post[..., i, None] * out for i in range(n)]
            return jnp.stack(rows, axis=2).astype(x.dtype)
    if cfg.residual_mult != 1.0:
        out = out * jnp.asarray(cfg.residual_mult, out.dtype)
    return x + out


def stream_close(cfg, x):
    """The streams as the final norm's input: their sum."""
    if cfg.hc_mult == 1:
        return x
    with jax.named_scope("hc.mix"):
        return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


# Per-layer spec: `cfg.layer_kinds` names each layer's mixer, and with
# it the cache that layer keeps. "attention": GQA over K and V pages,
# pool layer = the layer's rank among the attention layers (the page
# pools hold those alone). "mamba": the Mamba-2 mixer below over a
# recurrent state (`h` [b, H, P, N] float32 and the convolution's tail
# [b, K-1, C]), state index = its rank among the state layers;
# "mamba1": Mamba-1's selective scan over a state of another shape, the
# same rank. Two kinds keep NOTHING and read what a layer below them
# left in the same program: "cross" attends the K and V of the last
# attention layer below it that owns a full pool (so `attn_layers`
# gives it no pool layer), "gmu" gates the scan output of the last
# "mamba1" layer below it. Every layer ends in the family's
# feed-forward `block`.
#
# An attention layer also has a band (`cfg.layer_windows`: 0 = full
# causal attention, w = the last w positions) and may or may not rotate
# (`cfg.layer_ropes`); `LlamaConfig.window` and `use_rope` are the case
# of one value for every layer. A model whose attention layers are of
# BOTH kinds (full and banded: `cfg.two_kinds`) keeps two page pools,
# because the two kinds of page live differently long: the full layers'
# under the page table, the banded layers' under a short table a
# sequence that holds the band alone (`attn_layers`; serving.py has the
# cache manager's side).


def attn_layers(cfg):
    """Per attention layer, in model order: (band, rotates, pool,
    layer of that pool). pool is "full" (the page pools every family
    has) or "window" (the second pair of pools of a model with both
    kinds, whose banded layers are held there)."""
    out = []
    n = {"full": 0, "window": 0}
    for kind, band, rotates in zip(cfg.layer_kinds, cfg.layer_windows,
                                   cfg.layer_ropes):
        if kind not in ("attention", "latent"):
            continue
        pool = "window" if cfg.two_kinds and band else "full"
        out.append((band, rotates, pool, n[pool]))
        n[pool] += 1
    return out


def cache_rows(cfg, pos):
    """The cache row position `pos` of a sequence is written at, which
    is also how many rows the sequence holds below it: `pos` itself
    for every family whose cache rows are positions. A family whose
    finished windows FOLD (`cfg.fold_window`, models/evabyte.py: a
    window of `fold_window` positions leaves one row a chunk of
    `fold_chunk`) holds, at a position in window w, w windows' summary
    rows and the window's own exact rows. int or int32 array."""
    fold = getattr(cfg, "fold_window", 0)
    if not fold:
        return pos
    return pos - (fold - fold // cfg.fold_chunk) * (pos // fold)


def _kernel_scope(cfg, pool):
    """attn.kernel, split by kind of layer only where a model has
    both."""
    return f"attn.kernel.{pool}" if cfg.two_kinds else "attn.kernel"


def _ssm_project(layer, x, cfg):
    """rmsnorm and in_proj of a Mamba-2 mixer: (z, xBC, dt raw)."""
    di = cfg.ssm_heads * cfg.ssm_head_dim
    c = di + 2 * cfg.ssm_groups * cfg.ssm_state
    with jax.named_scope("ssm.in"):
        u = rms_norm(x, layer["ln1"], cfg.norm_eps, cfg.norm_plus_one)
        zxbcdt = matmul(u, layer["in_proj"])
    return zxbcdt[..., :di], zxbcdt[..., di:di + c], zxbcdt[..., di + c:]


def _ssm_split(xbc, cfg):
    """The convolved xBC as (x [..., H, P], B [..., N], C [..., N])."""
    di = cfg.ssm_heads * cfg.ssm_head_dim
    n = cfg.ssm_groups * cfg.ssm_state
    x = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.ssm_heads,
                              cfg.ssm_head_dim)
    return x, xbc[..., di:di + n], xbc[..., di + n:]


def _ssm_dt(layer, dt):
    f32 = jnp.float32
    return (jax.nn.softplus(dt.astype(f32) + layer["dt_bias"].astype(f32)),
            -jnp.exp(layer["A_log"].astype(f32)))


def _ssm_out(layer, y, xs, z, cfg):
    """y + D x, gated by silu(z), rmsnorm over the whole inner width
    (one group), out_proj. y float32 [..., H, P]."""
    f32 = jnp.float32
    with jax.named_scope("ssm.out"):
        y = y + layer["D"].astype(f32)[:, None] * xs.astype(f32)
        y = y.reshape(*z.shape) * jax.nn.silu(z.astype(f32))
        var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
        y = y * jax.lax.rsqrt(var + cfg.norm_eps) \
            * layer["ssm_norm"].astype(f32)
        return matmul(y.astype(z.dtype), layer["out_proj"])


def ssm_zero_state(cfg, b):
    """(h, conv tail) of `b` sequences at position 0."""
    c = (cfg.ssm_heads * cfg.ssm_head_dim
         + 2 * cfg.ssm_groups * cfg.ssm_state)
    return (jnp.zeros((b, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                      cfg.state_jdtype),
            jnp.zeros((b, cfg.ssm_conv - 1, c), cfg.state_jdtype))


def _states_at_edge(cfg, h1, h2, full, s_real, first):
    """What a state mixer's scan over a sequence leaves, from the state
    `h1` at the start of the last page (`first`), `h2` after `s_real`
    tokens and the convolution's inputs `full`: {"h", "conv": after
    s_real tokens; "h_b", "conv_b": at the last page edge at or before
    s_real}."""
    at_edge = s_real == first + cfg.page_size
    k = cfg.ssm_conv
    return {
        "h": h2, "conv": ssm.conv_tail(full, s_real, k),
        "h_b": jnp.where(at_edge, h2, h1),
        "conv_b": ssm.conv_tail(full, jnp.where(at_edge, s_real, first), k),
    }


def ssm_mixer_seq(layer, x, cfg, state, s_real):
    """The Mamba-2 mixer over a sequence [b, s, d], from `state` = (h,
    conv tail) of what came before. Only the first `s_real` positions
    (traced scalar) are real: the others get dt = 0 and so leave the
    state alone, and all of them lie in the last page of the sequence.

    Returns (out [b, s, d], {"h", "conv": the state after s_real
    tokens; "h_b", "conv_b": the state at the last page edge at or
    before s_real, which is where stored pages can end}). The scan is
    cut at the start of the last page so that both exist."""
    b, s, _ = x.shape
    h0, conv0 = state
    z, xbc, dt = _ssm_project(layer, x, cfg)
    xbc, full = ssm.conv_seq(conv0, xbc, layer["conv_w"], layer["conv_b"])
    xs, B, C = _ssm_split(xbc, cfg)
    dt, A = _ssm_dt(layer, dt)
    dt = jnp.where((jnp.arange(s) < s_real)[None, :, None], dt, 0.0)
    first = (s - 1) // cfg.page_size * cfg.page_size
    h1, ys = h0, []
    if first:
        y, h1 = ssm.scan(h0, xs[:, :first], dt[:, :first], A, B[:, :first],
                         C[:, :first], cfg.ssm_chunk)
        ys.append(y)
    y, h2 = ssm.scan(h1, xs[:, first:], dt[:, first:], A, B[:, first:],
                     C[:, first:], cfg.ssm_chunk)
    y = jnp.concatenate(ys + [y], axis=1)
    st = _states_at_edge(cfg, h1, h2, full, s_real, first)
    return _ssm_out(layer, y, xs, z, cfg), st


def ssm_mixer_step(layer, x, cfg, state, rows):
    """The same mixer for one token a row: x [b, 1, d], `state` = (h
    [b, H, P, N], conv tail [b, K-1, C]). `rows` (ssm.decoding's
    triple): the rows that decode; the state of the others stays as it
    lies and their output may be anything. Returns (out [b, 1, d], new
    (h, conv tail))."""
    h, conv = state
    z, xbc, dt = _ssm_project(layer, x[:, 0], cfg)
    xbc, conv = ssm.conv_step(conv, xbc, layer["conv_w"], layer["conv_b"],
                              rows)
    xs, B, C = _ssm_split(xbc, cfg)
    dt, A = _ssm_dt(layer, dt)
    y, h = ssm.step(h, xs, dt, A, B, C, rows)
    return _ssm_out(layer, y, xs, z, cfg)[:, None], (h, conv)


# A "mamba1" layer (Mamba-1's selective scan, models/phi_flash.py): the
# same place in the stack as "mamba" and the same two arrays a sequence
# (`h`, here [N, C]: one decay a channel AND a state element, the
# channels along the lanes; the convolution's tail [K-1, C]), another
# recurrence (ops/ssm.py `selective_scan` / `selective_step`) and no
# norm inside the mixer. Its scan output y (with the D skip, BEFORE the
# gate) is what the "gmu" layers above read, position by position.


def _mamba1_project(layer, x, cfg):
    """LayerNorm and in_proj: (x' [..., C], z [..., C])."""
    c = cfg.ssm_inner
    with jax.named_scope("ssm.in"):
        u = norm(cfg, x, layer["ln1"], layer.get("ln1_b"))
        xz = matmul(u, layer["in_proj"])
    return xz[..., :c], xz[..., c:]


def _mamba1_select(layer, xs, cfg):
    """The input-dependent terms, from the convolved x': (dt [..., C]
    float32 after softplus, A [N, C], B [..., N], C [..., N])."""
    f32 = jnp.float32
    r, n = cfg.dt_rank, cfg.ssm_state
    with jax.named_scope("ssm.in"):
        sel = matmul(xs, layer["x_proj"])
        dt = jax.nn.softplus(
            matmul(sel[..., :r], layer["dt_proj"]).astype(f32)
            + layer["dt_bias"].astype(f32))
        return (dt, -jnp.exp(layer["A_log"].astype(f32)),
                sel[..., r:r + n], sel[..., r + n:])


def _mamba1_out(layer, y, xs, z):
    """(out_proj((y + D x') silu(z)), the memory y + D x' in z's
    dtype). y float32 [..., C]."""
    f32 = jnp.float32
    with jax.named_scope("ssm.out"):
        y = y + layer["D"].astype(f32) * xs.astype(f32)
        out = matmul((y * jax.nn.silu(z.astype(f32))).astype(z.dtype),
                     layer["out_proj"])
        return out, y.astype(z.dtype)


def mamba1_zero_state(cfg, b):
    """(h, conv tail) of `b` sequences at position 0."""
    return (jnp.zeros((b, cfg.ssm_state, cfg.ssm_inner), cfg.state_jdtype),
            jnp.zeros((b, cfg.ssm_conv - 1, cfg.ssm_inner),
                      cfg.state_jdtype))


def mamba1_mixer_seq(layer, x, cfg, state, s_real):
    """`ssm_mixer_seq` for a "mamba1" layer: the same contract (the
    padded positions get dt = 0; the scan is cut at the start of the
    last page so that the state at the last page edge exists), and a
    third result, the memory [b, s, C]."""
    b, s, _ = x.shape
    h0, conv0 = state
    xs, z = _mamba1_project(layer, x, cfg)
    xs, full = ssm.conv_seq(conv0, xs, layer["conv_w"], layer["conv_b"])
    dt, A, B, C = _mamba1_select(layer, xs, cfg)
    dt = jnp.where((jnp.arange(s) < s_real)[None, :, None], dt, 0.0)
    first = (s - 1) // cfg.page_size * cfg.page_size
    h1, ys = h0, []
    if first:
        y, h1 = ssm.selective_scan(h0, xs[:, :first], dt[:, :first], A,
                                   B[:, :first], C[:, :first])
        ys.append(y)
    y, h2 = ssm.selective_scan(h1, xs[:, first:], dt[:, first:], A,
                               B[:, first:], C[:, first:])
    y = jnp.concatenate(ys + [y], axis=1)
    st = _states_at_edge(cfg, h1, h2, full, s_real, first)
    return (*_mamba1_out(layer, y, xs, z), st)


def mamba1_mixer_step(layer, x, cfg, state, rows):
    """`ssm_mixer_step` for a "mamba1" layer: (out [b, 1, d], the
    memory [b, 1, C], new (h, conv tail))."""
    h, conv = state
    xs, z = _mamba1_project(layer, x[:, 0], cfg)
    xs, conv = ssm.conv_step(conv, xs, layer["conv_w"], layer["conv_b"],
                             rows)
    dt, A, B, C = _mamba1_select(layer, xs, cfg)
    y, h = ssm.selective_step(h, xs, dt, A, B, C, rows)
    out, mem = _mamba1_out(layer, y, xs, z)
    return out[:, None], mem[:, None], (h, conv)


def gmu(layer, x, cfg, memory):
    """A "gmu" layer (Gated Memory Unit, arXiv:2507.06607): the memory
    of the last "mamba1" layer below, at the SAME position, gated by
    this layer's own projection of its input and projected back. It
    keeps no cache and no state. x: [b, s, d]; memory: [b, s, C]."""
    with jax.named_scope("ssm.gmu"):
        u = norm(cfg, x, layer["ln1"], layer.get("ln1_b"))
        gate = jax.nn.silu(matmul(u, layer["gmu_in"]))
        return matmul(gate * memory, layer["gmu_out"])


# Differential attention (arXiv:2410.05258; `cfg.diff_attn`): query
# head pair j attends with TWO softmax maps, subtracted, over a value
# of 2 x head_dim lanes. With `kv_pack` 2 the pair of a cache row IS
# the pair of kv heads the two maps read: row g = [k[2g] | k[2g + 1]],
# [v[2g] | v[2g + 1]], and of the 4 query heads of its group the first
# two score against the row's first half (map 1 of pairs 2g, 2g + 1),
# the last two against its second (map 2 of the same pairs), each
# widened to the row's lanes with zeros in the other half
# (`pack_queries`). ONE pass over K and V then gives, per widened
# query, softmax(.) [v1 | v2] in all 128 lanes: nothing is unpacked,
# and pair j's output is out[map 1] - lam out[map 2].


def diff_lambda(layer, depth):
    """(lam, lam0) of the attention layer at index `depth` of the
    stack: lam0 = 0.8 - 0.6 exp(-0.3 depth), lam = exp(lq1 . lk1) -
    exp(lq2 . lk2) + lam0 (float32 scalars)."""
    f32 = jnp.float32
    lam0 = 0.8 - 0.6 * float(np.exp(-0.3 * depth))

    def dot(a, b):
        return jnp.exp(jnp.sum(layer[a].astype(f32) * layer[b].astype(f32)))

    return dot("lam_q1", "lam_k1") - dot("lam_q2", "lam_k2") + lam0, lam0


def diff_combine(layer, cfg, attn, depth):
    """[..., n_heads, 2 hd] of the widened queries over packed rows ->
    [..., n_heads * hd]: per pair map 1 less lam times map 2, RMSNorm
    over the 2 hd lanes (one weight a layer), times 1 - lam0."""
    f32 = jnp.float32
    with jax.named_scope("attn.diff"):
        lam, lam0 = diff_lambda(layer, depth)
        *lead, n_heads, width = attn.shape
        maps = attn.reshape(*lead, cfg.n_kv_heads // 2, 2,
                            n_heads // cfg.n_kv_heads, width).astype(f32)
        o = maps[..., 0, :, :] - lam * maps[..., 1, :, :]
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + cfg.norm_eps) \
            * layer["sub_ln"].astype(f32) * (1.0 - lam0)
        return o.reshape(*lead, -1).astype(attn.dtype)


def _attend_row(q, k, v, last):
    """Causal attention of ONE query a sequence (q [b, 1, H, D]) over
    the keys [0, `last`] (traced) of k, v [b, S, G, D], in plain XLA:
    the form an admission takes above the layer that cuts its rows
    (`forward_stack`), where the flash kernel's diagonal (the queries
    are the LAST rows) does not say which row this is."""
    f32 = jnp.float32
    b, _, n_heads, d = q.shape
    s, g = k.shape[1:3]
    qg = q[:, 0].reshape(b, g, n_heads // g, d)
    sc = jnp.einsum("bgqd,bsgd->bgqs", qg, k,
                    preferred_element_type=f32) * d ** -0.5
    sc = jnp.where(jnp.arange(s) <= last, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgqs,bsgd->bgqd", p, v, preferred_element_type=f32)
    return out.reshape(b, 1, n_heads, d).astype(q.dtype)


def _cut_rows(x, memory, keep, before):
    """The streams from the cut layer up: (x, the scan output the "gmu"
    layers read if any, each [b, 1, ...] at position `keep`, and that
    position among the cut layer's keys, `before` of which lie ahead of
    the sequence: a prefix's)."""
    last = before + keep
    x = jax.lax.dynamic_slice_in_dim(x, keep, 1, axis=1)
    if memory is not None:
        memory = jax.lax.dynamic_slice_in_dim(memory, keep, 1, axis=1)
    return x, memory, last


def rows_cut(cfg):
    """The layer of the stack at which an admission that keeps ONE
    position's logits may cut its streams to that position: the last
    layer that keeps a cache (pages or a state). Every layer above it
    ("gmu", "cross") reads what the layers up to it left and keeps
    nothing, so it runs on that row alone. The last layer of every
    family whose layers all keep a cache, and wherever that layer is
    no attention layer (the cut is built inside one, `forward_stack`):
    there the cut is the one before the final norm."""
    cut = max(i for i, kind in enumerate(cfg.layer_kinds)
              if kind not in ("gmu", "cross"))
    return cut if cfg.layer_kinds[cut] == "attention" else cfg.n_layers - 1


def stack_rows(cfg, s):
    """(token-layer rows an admission of `s` positions runs, of s x
    layers): below `rows_cut` every row a layer, the cut layer (whose
    K and V alone are every row's) and the layers above it one; every
    row where the cut is the last layer."""
    n = cfg.n_layers
    cut = rows_cut(cfg)
    if cut == n - 1:
        return s * n, s * n
    return s * cut + (n - cut), s * n


# A "latent" layer (multi-head latent attention, DeepSeek-V2/V3): the
# cache of a token is ONE row, the normalised compressed key-value c
# [kv_lora_rank] and the rotated shared key k_pe [qk_rope], zero-padded
# to `cfg.latent_width` lanes; K and V of every head are c Wkvb. Two
# paths on purpose (tests/test_latent.py pins that they agree): a
# prefill EXPANDS the rows of prefix and suffix into per-head K (nope |
# pe) and V and runs the flash kernel; a decode step ABSORBS Wkvb's key
# half into the query and its value half into the output, and attends
# the rows as they lie in the pool (ops/pallas_latent_attention.py).


def latent_scale(cfg):
    """The softmax scale: (qk_nope + qk_rope) ** -0.5 times YaRN's
    mscale(factor, mscale_all_dim) squared."""
    m = yarn_mscale(cfg.yarn[0], cfg.yarn[5]) if cfg.yarn else 1.0
    return (cfg.qk_nope + cfg.qk_rope) ** -0.5 * m * m


def latent_project(layer, x, cfg, positions, decode=False, with_cq=False):
    """(q_nope [b, s, H, nope], q_pe [b, s, H, rope] rotated, the cache
    rows [b, s, latent_width], h the normalised input). `decode`: a
    decode step asks, as of `proj`: Wqb's is the product that goes
    reshaped into the kernel (Wqa's and Wkva's go through a norm, and
    their weights were never re-laid). `with_cq`: a fifth, the
    normalised compressed query c_q [b, s, q_lora_rank], which a
    layer's indexer projects its own queries from."""
    b, s, _ = x.shape
    r = cfg.kv_lora_rank
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, layer["ln1"], cfg.norm_eps)
        cq = rms_norm(matmul(h, layer["wqa"]), layer["q_ln"], cfg.norm_eps)
        q = matmul(cq, layer["wqb"])
        if decode:
            q = weight_where_it_lies(q)
        q = q.reshape(b, s, cfg.n_heads, cfg.qk_nope + cfg.qk_rope)
        ckv = matmul(h, layer["wkva"])
        c = rms_norm(ckv[..., :r], layer["kv_ln"], cfg.norm_eps)
    with jax.named_scope("attn.rope"):
        q_pe = rope(q[..., cfg.qk_nope:], positions, cfg.rope_theta,
                    yarn=cfg.yarn, adjacent=cfg.rope_adjacent)
        k_pe = rope(ckv[..., None, r:], positions, cfg.rope_theta,
                    yarn=cfg.yarn, adjacent=cfg.rope_adjacent)[..., 0, :]
        pad = cfg.latent_width - r - cfg.qk_rope
        rows = jnp.concatenate(
            [c, k_pe, jnp.zeros((b, s, pad), c.dtype)], axis=-1)
    out = (q[..., :cfg.qk_nope], q_pe, rows, h)
    return out + (cq,) if with_cq else out


def _wkvb(layer, cfg):
    """Wkvb as [kv_lora_rank, H, nope + v]."""
    return layer["wkvb"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                                 cfg.qk_nope + cfg.v_dim)


def latent_prefill_attention(layer, cfg, q_nope, q_pe, rows):
    """Causal attention of s queries over the rows [b, S, width] of
    prefix + suffix (S >= s; the queries are the last s), unabsorbed:
    [b, s, H * v_dim]."""
    b, s = q_nope.shape[:2]
    r, hq = cfg.kv_lora_rank, cfg.qk_nope + cfg.qk_rope
    with jax.named_scope("attn.expand"):
        kv = jnp.einsum("bsr,rhd->bshd", rows[..., :r], _wkvb(layer, cfg))
        k_pe = jnp.broadcast_to(
            rows[:, :, None, r:r + cfg.qk_rope],
            (*kv.shape[:3], cfg.qk_rope))
        k = jnp.concatenate([kv[..., :cfg.qk_nope], k_pe], axis=-1)
        v = kv[..., cfg.qk_nope:]
        # flash scales by the query width ** -0.5; YaRN's mscale ** 2
        # rides on q
        q = jnp.concatenate([q_nope, q_pe], axis=-1) * jnp.asarray(
            latent_scale(cfg) * hq ** 0.5, q_nope.dtype)
    with jax.named_scope("attn.kernel"):
        return flash_prefill(q, k, v, causal=True).reshape(b, s, -1)


def latent_decode(layer, cfg, q_nope, q_pe, pool, table, lens, pl):
    """One new token a row over the latent pool, absorbed: q_nope
    [b, H, nope], q_pe [b, H, rope] -> [b, H * v_dim]. K and V are
    never built."""
    b = q_nope.shape[0]
    w = _wkvb(layer, cfg)
    with jax.named_scope("attn.absorb"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w[..., :cfg.qk_nope])
        pad = cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope
        q = jnp.concatenate(
            [q_lat, q_pe, jnp.zeros((*q_pe.shape[:2], pad), q_pe.dtype)],
            axis=-1) * jnp.asarray(latent_scale(cfg), q_pe.dtype)
    with jax.named_scope("attn.kernel"):
        o_lat = latent_decode_attention(q, pool, table, lens,
                                        rank=cfg.kv_lora_rank, layer=pl)
    with jax.named_scope("attn.absorb"):
        out = jnp.einsum("bhr,rhd->bhd", o_lat, w[..., cfg.qk_nope:])
    return out.reshape(b, -1)


# A latent layer under a LEARNED SELECTION (`cfg.index_topk` > 0;
# DeepSeek-V3.2's lightning indexer, models/glm.py): a query attends
# the `index_topk` cache rows its layer's indexer scores highest and no
# other (ops/sparse_select.py). `cfg.indexer_kinds` names, per latent
# layer, who owns an indexer ("full": its own queries, ONE index key a
# token, which is cached as a second kind of page) and who borrows
# ("shared": the selection of the nearest "full" layer below, made in
# this same program; it computes none and caches none). Where the keys
# a program can see are `index_topk` or fewer (a shape: an admission's
# prefix + suffix, a decode step's table width) every row is selected
# and the layer runs the dense latent path above; the index keys are
# written all the same, for the longer context that follows.


_SELECTION_TAP = None


@contextlib.contextmanager
def selection_tap(into):
    """While it is open, every selection an owner layer makes in a loop
    TRACED OR RUN on this thread is appended to the list `into`, in
    layer order: (positions, taken) as ops/sparse_select.py returns
    them. For tests and benchmark/tools/selection_agreement.py, which
    trace a loop inside a jit of their own and return what was
    tapped; no program of the engine opens it."""
    global _SELECTION_TAP
    _SELECTION_TAP = into
    try:
        yield into
    finally:
        _SELECTION_TAP = None


def _tapped(sel):
    if _SELECTION_TAP is not None:
        _SELECTION_TAP.append(sel)
    return sel


def owns_indexer(cfg, li):
    """Whether latent layer `li` owns an indexer (and caches index
    keys); False for a family without a selection."""
    return getattr(cfg, "indexer_kinds", ())[li:li + 1] == ("full",)


def indexed(cfg, n_keys):
    """Whether a program whose queries see up to `n_keys` keys runs the
    selection."""
    return 0 < getattr(cfg, "index_topk", 0) < n_keys


def index_project(layer, cfg, cq, h, positions, keys_only=False):
    """A "full" layer's indexer: (qI [b, s, Hi, Di], kI [b, s, Di], w
    [b, s, Hi] float32), qI and kI rotated on their first
    `cfg.index_rope` lanes (and both zero-padded to `cfg.index_width`
    lanes where the family caches a key wider than Di). kI =
    LayerNorm(h Wki) is what the layer caches. `cq`: what the queries
    are projected from (a latent layer's compressed query; an
    attention layer's normalised input, `h` itself)."""
    b, s, _ = h.shape
    hi, di, rl = cfg.index_heads, cfg.index_dim, cfg.index_rope

    def rotate(x):  # [b, s, heads, di]
        return jnp.concatenate(
            [rope(x[..., :rl], positions, cfg.rope_theta,
                  adjacent=cfg.index_rope_adjacent), x[..., rl:]], axis=-1)

    with jax.named_scope("attn.index"):
        ki = matmul(h, layer["wki"]).astype(jnp.float32)
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
        ki = ((ki - mean) * jax.lax.rsqrt(var + cfg.index_norm_eps)
              * layer["ki_ln"].astype(jnp.float32)
              + layer["ki_ln_b"].astype(jnp.float32)).astype(h.dtype)
        ki = rotate(ki[:, :, None])[:, :, 0]
        # a key is cached in whole tiles of lanes (models/keye.py: 64
        # of 128); the zero lanes add nothing to a product
        extra = getattr(cfg, "index_width", di) - di
        if extra:
            ki = jnp.pad(ki, ((0, 0), (0, 0), (0, extra)))
        if keys_only:
            return None, ki, None
        qi = rotate(matmul(cq, layer["wqi"]).reshape(b, s, hi, di))
        if extra:
            qi = jnp.pad(qi, ((0, 0), (0, 0), (0, 0), (0, extra)))
        w = jnp.einsum("bsd,dh->bsh", h, layer["wiw"],
                       preferred_element_type=jnp.float32) \
            * (hi ** -0.5 * di ** -0.5)
    return qi, ki, w


# An ATTENTION layer under the same selection (models/keye.py): the
# rows a query attends are K and V rows by head group, and the index
# keys are a third kind of page. Every such layer owns its indexer (a
# borrowed selection over K and V pages is not built; serving.py
# refuses it), whose queries come from the layer's normalised input.


def kv_selected_prefill(cfg, q, k_all, v_all, qi, wi, keys, positions):
    """Causal attention of s queries over the K and V rows [b, S, G,
    hd] of prefix + suffix under the selection their index queries
    make over `keys` [b, S, Di]: a block of queries at a time, its
    selection as a mask over the contiguous rows
    (sparse_select.select_attend_seq). positions: [b, s], counted in
    the rows. Returns [b, s, H * hd]."""
    b, s = q.shape[:2]
    sel, out = jax.vmap(partial(
        sparse_select.select_attend_seq, k=cfg.index_topk,
        scale=cfg.head_dim ** -0.5,
        with_positions=_SELECTION_TAP is not None)
    )(qi, wi, keys, positions, q, k_all, v_all)
    _tapped(sel)
    return out.reshape(b, s, -1)


def kv_selected_decode(cfg, q, kp, vp, table, sel, pl, active):
    """One new token a row over the K and V pools under a selection:
    the rows `sel` names are gathered through the page table from both
    pools and attended by head group, for the decoding slots alone
    (`active`: sparse_select.active_first's); no other row of either
    pool is read. q: [b, H, hd] -> [b, H * hd]."""
    scale = cfg.head_dim ** -0.5  # `_qkv` folds another into q

    def attend(q, table, idx, taken):
        k_rows = sparse_select.gather_paged(kp, pl, table, idx)
        v_rows = sparse_select.gather_paged(vp, pl, table, idx)
        with jax.named_scope("attn.kernel"):
            return sparse_select.attend_grouped(q, k_rows, v_rows, taken,
                                                scale)

    out = sparse_select.over_active(attend, active, q, table, *sel)
    return out.reshape(q.shape[0], -1)


def _absorbed_query(layer, cfg, q_nope, q_pe):
    """[..., H, latent_width]: q_nope through Wkvb's key half, q_pe,
    zero lanes; scaled."""
    w = _wkvb(layer, cfg)
    q_lat = jnp.einsum("...hd,rhd->...hr", q_nope, w[..., :cfg.qk_nope])
    pad = cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope
    return jnp.concatenate(
        [q_lat, q_pe, jnp.zeros((*q_pe.shape[:-1], pad), q_pe.dtype)],
        axis=-1) * jnp.asarray(latent_scale(cfg), q_pe.dtype)


def latent_selected_prefill(layer, cfg, q_nope, q_pe, rows, sel):
    """`latent_prefill_attention` under a selection: each of the s
    queries attends the rows `sel` = (positions [b, s, k], taken
    [b, s, k]) names, absorbed as a decode step attends (K and V per
    head are never built; what is read follows the selection):
    [b, s, H * v_dim]."""
    b, s = q_nope.shape[:2]
    w = _wkvb(layer, cfg)

    def absorb(qn, qp):
        with jax.named_scope("attn.absorb"):
            return _absorbed_query(layer, cfg, qn, qp)

    o_lat = jax.vmap(
        lambda qn, qp, r, idx, taken: sparse_select.attend_seq(
            absorb, (qn, qp), r, idx, taken, cfg.kv_lora_rank)
    )(q_nope, q_pe, rows, *sel)
    with jax.named_scope("attn.absorb"):
        out = jnp.einsum("bshr,rhd->bshd", o_lat, w[..., cfg.qk_nope:])
    return out.reshape(b, s, -1)


def latent_selected_decode(layer, cfg, q_nope, q_pe, pool, table, sel, pl,
                           active):
    """`latent_decode` under a selection: the rows `sel` names are
    gathered through the page table and attended, for the decoding
    slots alone (`active`: sparse_select.active_first's); no other row
    of the pool is read."""
    b = q_nope.shape[0]
    with jax.named_scope("attn.absorb"):
        q = _absorbed_query(layer, cfg, q_nope, q_pe)

    def attend(q, table, idx, taken):
        picked = sparse_select.gather_paged(pool, pl, table, idx)
        with jax.named_scope("attn.kernel"):
            return sparse_select.attend(q, picked, taken, cfg.kv_lora_rank)

    o_lat = sparse_select.over_active(attend, active, q, table, *sel)
    with jax.named_scope("attn.absorb"):
        out = jnp.einsum("bhr,rhd->bhd", o_lat,
                         _wkvb(layer, cfg)[..., cfg.qk_nope:])
    return out.reshape(b, -1)


def forward_stack(block, params, cfg, tokens, prefix_kvs=None, pos0=0,
                  state=None, s_real=None, keep=None):
    """The ONE decoder-stack loop shared by dense forward and
    prefix-cached prefill (the cache-hit identity depends on these two
    paths never diverging). With `prefix_kvs` (per attention layer (k,
    v) of shape [batch, P, n_kv, hd], post-RoPE), positions shift by P
    and each attention layer attends over prefix + suffix KV through
    the rectangular flash kernel; with None this reduces exactly to the
    dense causal forward.

    tokens: [batch, seq] int32. Returns (logits [batch, seq, vocab]
    fp32, or [batch, 1, vocab] with `keep`; per attention layer (k, v)
    [batch, seq, n_kv, hd] — the KV to page out to the store — and the
    per-layer list of what `block` returned as its auxiliary loss). A
    family with state layers gets a fourth element: per state layer
    what `ssm_mixer_seq` returned; one whose layers hold a share of
    their experts a last: the blocks' counts (the `block` contract's
    fourth), summed over the layers.

    `pos0` shifts every ABSOLUTE rope position (prefix starts at pos0,
    suffix at pos0 + P): a sliding-window engine trims the restored
    prefix to the in-window tail pages, whose KV was roped at absolute
    positions — the band mask itself needs no shift because it depends
    only on RELATIVE (query - key) distance, which local indices
    preserve. The layers' prefixes may differ in length (a banded
    layer's is the tail its band needs): P is the longest, each
    shorter one is its layer's last positions before the suffix.

    `state`: per state layer (h, conv tail) of the prefix the suffix
    continues (None: position 0); `s_real`: how many of the seq
    positions are real tokens (None: all; the others must lie in the
    last page and may not advance a recurrence).

    `keep`: the one position (int32 scalar, may be traced) whose logits
    the caller keeps, as an admission program does of its last real
    one (None: every position's). Every layer that keeps a cache still
    runs every position (their K/V, rows, index keys, state and counts
    are what they were); the streams are cut to that position at
    `rows_cut`: after the last layer, so the final norm and the head
    run on one row a batch and the head reads its weights once for it;
    where layers above the last cache keep none ("gmu", "cross":
    models/phi_flash.py), INSIDE that layer: its K and V are every
    position's, its query, attention, output projection and block and
    every layer above it run on the one row (`stack_rows`)."""
    b, s = tokens.shape
    prefix_len = 0 if prefix_kvs is None else max(
        k.shape[1] for k, *_ in prefix_kvs)
    spec = attn_layers(cfg)
    x = stream_open(cfg, embed(params, tokens, cfg))
    positions = jnp.broadcast_to(
        pos0 + prefix_len + jnp.arange(s)[None], (b, s)
    )
    kvs = []
    auxes = []
    states = []
    held = []
    cut = rows_cut(cfg)
    if keep is None or cut == cfg.n_layers - 1:
        cut = None  # no layer runs on the kept row alone
    diff = getattr(cfg, "diff_attn", False)
    memory = shared = last = None
    for depth, (layer, kind) in enumerate(zip(params["layers"],
                                              cfg.layer_kinds)):
        h_attn = None
        x_in, mix = stream_in(cfg, layer, x, "attn")
        if kind == "mamba":
            st = state[len(states)] if state is not None \
                else ssm_zero_state(cfg, b)
            out, st = ssm_mixer_seq(layer, x_in, cfg, st,
                                    s if s_real is None else s_real)
            x = residual(cfg, x, out, mix)
            states.append(st)
        elif kind == "mamba1":
            st = state[len(states)] if state is not None \
                else mamba1_zero_state(cfg, b)
            out, memory, st = mamba1_mixer_seq(
                layer, x_in, cfg, st, s if s_real is None else s_real)
            x = residual(cfg, x, out, mix)
            states.append(st)
        elif kind == "gmu":
            x = residual(cfg, x, gmu(layer, x_in, cfg, memory), mix)
        elif kind == "cross":
            # the K and V of the last layer that owns a full pool, all
            # its positions; this layer's own queries, lambda and output
            q, _, _, h_attn = _qkv(layer, x_in, cfg, positions, False)
            q = pack_queries(cfg, q)
            with jax.named_scope("attn.kernel.cross"):
                if last is None:
                    attn = flash_prefill(q, *shared, causal=True)
                else:
                    attn = _attend_row(q, *shared, last)
            attn = diff_combine(layer, cfg, attn, depth)
            x = residual(cfg, x, attn_out(layer, attn), mix)
        elif kind == "latent":
            owner = owns_indexer(cfg, len(kvs))
            q_nope, q_pe, rows, h_attn, cq = latent_project(
                layer, x_in, cfg, positions, with_cq=True)
            rows_all = rows if prefix_kvs is None else jnp.concatenate(
                [prefix_kvs[len(kvs)][0].astype(rows.dtype), rows], axis=1)
            selects = indexed(cfg, rows_all.shape[1])
            ki = None
            if owner:  # the layer's index keys, cached beside its rows
                qi, ki, wi = index_project(layer, cfg, cq, h_attn,
                                           positions,
                                           keys_only=not selects)
            if owner and selects:
                keys = ki if prefix_kvs is None else jnp.concatenate(
                    [prefix_kvs[len(kvs)][1].astype(ki.dtype), ki], axis=1)
                sel = _tapped(jax.vmap(partial(
                    sparse_select.select_seq, k=cfg.index_topk)
                )(qi, wi, keys, positions - pos0))
            if selects:
                attn = latent_selected_prefill(layer, cfg, q_nope, q_pe,
                                               rows_all, sel)
            else:
                attn = latent_prefill_attention(layer, cfg, q_nope, q_pe,
                                                rows_all)
            x = residual(cfg, x, attn_out(layer, attn), mix)
            kvs.append((rows, ki))
        else:
            band, rotates, pool, _ = spec[len(kvs)]
            here = depth == cut
            q, k, v, h_attn = _qkv(layer, x_in, cfg, positions, rotates,
                                   keep=keep if here else None)
            if diff:
                # differential attention: K and V are made, cached and
                # attended in the cache's row form, nothing is unpacked
                q, k, v = pack_heads(cfg, q, k, v)
            owner = owns_indexer(cfg, len(kvs))
            if prefix_kvs is None:
                k_full, v_full = k, v
            else:
                pk, pv, *pi = prefix_kvs[len(kvs)]
                k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
                v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            if here:
                x, memory, last = _cut_rows(x, memory, keep,
                                            k_full.shape[1] - s)
            selects = owner and indexed(cfg, k_full.shape[1])
            if owner:  # the layer's index keys, a third page of its own
                qi, ki, wi = index_project(layer, cfg, h_attn, h_attn,
                                           positions,
                                           keys_only=not selects)
            if selects:
                keys = ki if prefix_kvs is None else jnp.concatenate(
                    [pi[0].astype(ki.dtype), ki], axis=1)
                attn = kv_selected_prefill(cfg, q, k_full, v_full, qi, wi,
                                           keys, positions - pos0)
            else:
                # Pallas flash kernel on TPU (O(S) memory; speed against
                # the XLA path not measured), XLA path elsewhere. kv may
                # be longer than q — the causal diagonal shifts by the
                # prefix.
                with jax.named_scope(_kernel_scope(cfg, pool)):
                    if here:
                        attn = _attend_row(q, k_full, v_full, last)
                    else:
                        attn = flash_prefill(q, k_full, v_full, causal=True,
                                             window=band)
            if diff:
                if pool == "full":
                    shared = (k_full, v_full)
                attn = diff_combine(layer, cfg, attn, depth)
            else:
                attn = attn.reshape(*attn.shape[:2], -1)
            x = residual(cfg, x, attn_out(layer, attn), mix)
            kvs.append((k, v, ki) if owner else (k, v))
        x_in, mix = stream_in(cfg, layer, x, "ffn")
        out, aux, *more = block(layer, x_in, cfg, None, h_attn)
        held += more[1:]
        x = residual(cfg, x, out, mix)
        auxes.append(aux)
    if keep is not None and cut is None:
        x = jax.lax.dynamic_slice_in_dim(x, keep, 1, axis=1)
    x = norm(cfg, stream_close(cfg, x), params["final_ln"],
             params.get("final_ln_b"))
    out = (lm_head(params, x, cfg), kvs, auxes)
    if states:
        out += (states,)
    if held:
        out += ({k: sum(c[k] for c in held) for k in held[0]},)
    return out


def decode_step(block, params, cfg, token, seq_lens, k_pages, v_pages,
                page_table, state=None, win=None, fetched=False):
    """One decode step over paged KV.

    token:      [batch] int32 — current input token
    seq_lens:   [batch] int32 — tokens already in cache (excl. current):
                the new token's POSITION. Its cache row is
                `cache_rows(cfg, seq_lens)`: the same number, but for a
                family whose finished windows fold
    k_pages/v_pages: [n_kv_layers, n_pages, page, n_kv, hd] (a family
                with `cfg.page_rows`: [n_kv_layers, n_pages, page *
                rows, hd], a page as flat rows); where the
                attention layers own an indexer (models/keye.py),
                `v_pages` is the pair (V pool, index pool [index
                layers, n_pages, page, index_dim]) and comes back so
    page_table: [batch, max_pages] int32
    state:      for a family with state layers, {"h": [...], "conv":
                [...]}: per state layer the batch's recurrent state
                and convolution tail, row = slot. The rows of the
                slots that decode (seq_lens > 0) are advanced where
                they lie; no other row is read or written
    win:        for a model with full AND banded attention layers
                (`cfg.two_kinds`), the banded layers' cache: (k pool,
                v pool [n_window_layers, n_pages, page, n_kv, hd],
                short table [batch, entries] int32, base [batch] int32:
                the absolute position of each row's first entry, a
                page multiple). k_pages/v_pages then hold the full
                layers alone.
    fetched:    also return, last, the experts this step's blocks
                fetched, summed over the layers (int32; 0 where no
                block reports any); where the layers hold a share of
                their experts, int32 [2]: that, and the valid rows'
                pairs that fell on experts held here. Where layers
                attend under a learned selection, between the two: the
                cache rows the valid rows' attention took, summed over
                those layers, and the slots the selection's stages ran
                over (int32 [4]).

    Returns (logits [batch, vocab] fp32, k_pages, v_pages): the pools
    it was given with, per attention layer, the new token's K and V
    rows written at (layer, page of position seq_lens, seq_lens %
    page_size). The pools stay the 5-D arrays they arrive as — every
    layer's scatter and every layer's attention address the layer
    inside them, and nothing the size of a layer is sliced out or
    stacked back — so a caller that donates them (the engine's fused
    programs) updates them in place. With `state`, a fourth element:
    the state after this token, in the form it came. With `win`, the
    banded layers' two pools follow, updated the same way: a banded
    layer's kernel call walks the short table with lengths counted
    from its base (keys were rotated at their absolute positions
    before they were cached, and the band is relative).
    """
    b = token.shape[0]
    x = stream_open(cfg, embed(params, token[:, None], cfg))  # [b, 1, d]
    positions = seq_lens[:, None]  # current position

    def place(table, lens):
        page = jnp.take_along_axis(
            table, (lens // cfg.page_size)[:, None], axis=1)[:, 0]
        return table, lens, page, lens % cfg.page_size

    # (a new row is ROTATED at its position, `positions` above, and
    # WRITTEN at its cache row, which is where the kernel's length ends)
    pools = {"full": [k_pages, v_pages,
                      *place(page_table, cache_rows(cfg, seq_lens))]}
    if win is not None:
        wk, wv, wtable, wbase = win
        # inactive rows (seq_lens 0) stay at 0: entry 0 of an empty
        # short table is the banded pools' scratch page
        pools["window"] = [wk, wv, *place(
            wtable, jnp.maximum(seq_lens - wbase, 0))]
    # Slots with an empty cache are the engine's inactive rows; `block`
    # may keep their garbage tokens out of whatever its tokens compete
    # for (best-effort: a previously-active slot's stale row still
    # counts as valid).
    valid = (seq_lens > 0)[:, None]  # [b, 1]
    # ... and under a learned selection the slots its stages run over
    active = sparse_select.active_first(valid[:, 0]) if indexed(
        cfg, page_table.shape[1] * cfg.page_size) else None
    # ... and over a recurrent state the rows its layers advance
    rows = None if state is None else ssm.decoding(valid[:, 0])

    spec = attn_layers(cfg)
    li = mi = ii = 0  # rank among the attention / state / index layers
    hs, convs, experts, pairs, taken = [], [], [], [], []
    diff = getattr(cfg, "diff_attn", False)
    # kv rows a token, where the pools hold a page as flat rows
    # (`cfg.page_rows`; 0: the 5-D pool every other family has)
    flat = getattr(cfg, "page_rows", 0)
    memory = shared = None  # what "gmu" and "cross" layers read
    for depth, (layer, kind) in enumerate(zip(params["layers"],
                                              cfg.layer_kinds)):
        h_attn = None
        x_in, mix = stream_in(cfg, layer, x, "attn")
        if kind == "mamba":
            out, (h, conv) = ssm_mixer_step(
                layer, x_in, cfg, (state["h"][mi], state["conv"][mi]), rows)
            x = residual(cfg, x, out, mix)
            hs.append(h)
            convs.append(conv)
            mi += 1
        elif kind == "mamba1":
            out, memory, (h, conv) = mamba1_mixer_step(
                layer, x_in, cfg, (state["h"][mi], state["conv"][mi]), rows)
            x = residual(cfg, x, out, mix)
            hs.append(h)
            convs.append(conv)
            mi += 1
        elif kind == "gmu":
            x = residual(cfg, x, gmu(layer, x_in, cfg, memory), mix)
        elif kind == "cross":
            # the full pools' layer `shared`, this step's row in it,
            # through the page table: read here once more, never copied
            q, _, _, h_attn = _qkv(layer, x_in, cfg, positions, False,
                                   decode=True)
            kp, vp, table, lens, _, _ = pools["full"]
            with jax.named_scope("attn.kernel.cross"):
                attn = paged_decode_attention(
                    pack_queries(cfg, q)[:, 0], kp, vp, table, lens + 1,
                    layer=shared, rows=flat)
            attn = diff_combine(layer, cfg, attn, depth)
            x = residual(cfg, x, attn_out(layer, attn[:, None]), mix)
        elif kind == "latent":
            # ONE pool of rows (k_pages); v_pages is None, or where
            # some layers own an indexer the pool of their index keys
            owner = owns_indexer(cfg, li)
            q_nope, q_pe, rows, h_attn, cq = latent_project(
                layer, x_in, cfg, positions, decode=True, with_cq=True)
            held = pools["full"]
            _, _, table, lens, target_page, slot = held
            selects = indexed(cfg, table.shape[1] * cfg.page_size)
            with jax.named_scope("pool.update"):
                held[0] = held[0].at[li, target_page, slot].set(
                    rows[:, 0], mode="drop")
            if owner:
                # ... and its index key into the second pool (held[1]:
                # [index layers, pages, page, index_dim]), layer = the
                # layer's rank among the owners
                qi, ki, wi = index_project(layer, cfg, cq, h_attn,
                                           positions, keys_only=not selects)
                with jax.named_scope("pool.update"):
                    held[1] = held[1].at[ii, target_page, slot].set(
                        ki[:, 0], mode="drop")
                if selects:
                    sel = _tapped(sparse_select.over_active(
                        partial(sparse_select.select_paged, ipool=held[1],
                                layer=ii, k=cfg.index_topk),
                        active, qi[:, 0], wi[:, 0], table, lens + 1))
                ii += 1
            if selects:
                attn = latent_selected_decode(
                    layer, cfg, q_nope[:, 0], q_pe[:, 0], held[0], table,
                    sel, li, active)
                taken.append(jnp.sum(sel[1] & valid))
            else:
                attn = latent_decode(layer, cfg, q_nope[:, 0], q_pe[:, 0],
                                     held[0], table, lens + 1, li)
            x = residual(cfg, x, attn_out(layer, attn[:, None]), mix)
            li += 1
        else:
            band, rotates, pool, pl = spec[li]
            q, k, v, h_attn = _qkv(layer, x_in, cfg, positions, rotates,
                                   decode=True)
            if cfg.kv_pack > 1:
                q, k, v = pack_heads(cfg, q, k, v)
            held = pools[pool]
            kp, vp, table, lens, target_page, slot = held
            owner = owns_indexer(cfg, li)
            if owner:  # the second of the pair is (V pool, index pool)
                vp, ip = vp
            with jax.named_scope("pool.update"):
                kp = scatter_kv_to_pages(kp, k, target_page, slot, layer=pl)
                vp = scatter_kv_to_pages(vp, v, target_page, slot, layer=pl)
            selects = owner and indexed(cfg, table.shape[1] * cfg.page_size)
            if owner:
                # ... and its index key into the third pool ([index
                # layers, pages, page, index_dim])
                qi, ki, wi = index_project(layer, cfg, h_attn, h_attn,
                                           positions, keys_only=not selects)
                with jax.named_scope("pool.update"):
                    ip = ip.at[ii, target_page, slot].set(ki[:, 0],
                                                          mode="drop")
            held[0], held[1] = kp, (vp, ip) if owner else vp
            if selects:
                sel = _tapped(sparse_select.over_active(
                    partial(sparse_select.select_paged, ipool=ip, layer=ii,
                            k=cfg.index_topk),
                    active, qi[:, 0], wi[:, 0], table, lens + 1))
                attn = kv_selected_decode(cfg, q[:, 0], kp, vp, table, sel,
                                          pl, active)
                taken.append(jnp.sum(sel[1] & valid))
            else:
                with jax.named_scope(_kernel_scope(cfg, pool)):
                    attn = paged_decode_attention(
                        q[:, 0], kp, vp, table, lens + 1, window=band,
                        layer=pl, rows=flat)
                    if cfg.kv_pack > 1 and not diff:
                        attn = unpack_heads(cfg, attn)
                if diff:
                    attn = diff_combine(layer, cfg, attn, depth)
                    if pool == "full":
                        shared = pl
            ii += owner
            x = residual(cfg, x, attn_out(layer, attn.reshape(b, 1, -1)),
                         mix)
            li += 1
        x_in, mix = stream_in(cfg, layer, x, "ffn")
        out, _aux, *n = block(layer, x_in, cfg, valid, h_attn)
        experts += [c for c in n[:1] if c is not None]
        pairs += [jnp.sum(c["pairs_held"]) for c in n[1:]]
        x = residual(cfg, x, out, mix)
    x = norm(cfg, stream_close(cfg, x), params["final_ln"],
             params.get("final_ln_b"))
    logits = lm_head(params, x[:, 0], cfg)
    out = (logits, *pools["full"][:2])
    if state is not None:
        out += ({"h": hs, "conv": convs},)
    if win is not None:
        out += tuple(pools["window"][:2])
    if fetched:
        counts = [sum(experts, jnp.int32(0))]
        if taken:
            counts += [sum(taken), sparse_select.slots_run(active)]
        counts += [sum(pairs)] if pairs else []
        out += (jnp.stack(counts) if len(counts) > 1 else counts[0],)
    return out


def verify_step(block, params, cfg, tokens, seq_lens, k_pages, v_pages,
                page_table, valid_len=None):
    """m-token decode over paged KV — speculative decoding's verify
    step. Consumes m tokens per
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
                appears in no sequence's page table). They are also
                the rows `block` is told are not valid. None means all
                m are valid.

    Returns (logits [batch, m, vocab] fp32, k_pages, v_pages): the
    pools it was given with the m tokens' rows written per layer, 5-D
    throughout as in `decode_step`.
    A rejected speculative tail needs no rollback: its KV sits at
    positions >= the accepted seq_len, which later steps overwrite
    before attending (attention is masked by per-token length). A
    recurrent state has no such rollback, so a family with state
    layers is refused here (speculation over state is not built).
    """
    if {"mamba", "mamba1"} & set(cfg.layer_kinds):
        raise NotImplementedError(
            "verify_step over state layers: a rejected draft cannot be "
            "rolled back out of a recurrent state")
    if "latent" in cfg.layer_kinds or cfg.hc_mult > 1:
        raise NotImplementedError(
            "verify_step over a latent cache or several residual streams "
            "is not built")
    if cfg.kv_pack > 1:
        raise NotImplementedError("verify_step over packed kv heads")
    if cfg.two_kinds:
        raise NotImplementedError(
            "verify_step over full and banded layers: speculation over "
            "two kinds of page is not built")
    b, m = tokens.shape
    x = embed(params, tokens, cfg)  # [b, m, d]
    positions = seq_lens[:, None] + jnp.arange(m)[None, :]
    page_idx_in_seq = positions // cfg.page_size  # [b, m]
    target_page = jnp.take_along_axis(page_table, page_idx_in_seq, axis=1)
    slot = positions % cfg.page_size
    ok = None
    if valid_len is not None:
        ok = jnp.arange(m)[None, :] < valid_len[:, None]  # [b, m]
        target_page = jnp.where(ok, target_page, 0)
        slot = jnp.where(ok, slot, jnp.arange(m)[None, :] % cfg.page_size)

    spec = attn_layers(cfg)
    for li, layer in enumerate(params["layers"]):
        band, rotates, _, _ = spec[li]
        q, k, v, h_attn = _qkv(layer, x, cfg, positions, rotates)
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
                window=band, layer=li
            )
        x = residual(cfg, x, attn_out(layer, attn.reshape(b, m, -1)))
        out, _aux, *_ = block(layer, x, cfg, ok, h_attn)
        x = residual(cfg, x, out)
    x = norm(cfg, x, params["final_ln"])
    return lm_head(params, x, cfg), k_pages, v_pages


def bind(block):
    """The three loops with one family's feed-forward `block` bound in:
    (forward_stack, decode_step, verify_step), the two paged steps
    jitted on a static `cfg`. Each keeps its loop's name (a partial has
    none, and jit would name the program and its call inside the
    engine's fused programs `<unknown>`) and its loop as `.func`
    (`.__wrapped__.func` under the jit)."""
    def bound(loop):
        f = partial(loop, block)
        f.__name__ = loop.__name__
        return f

    return (
        bound(forward_stack),
        jax.jit(bound(decode_step), static_argnames=("cfg", "fetched")),
        jax.jit(bound(verify_step), static_argnames=("cfg",)),
    )


# ---------------------------------------------------------------------------
# KV paging helpers: model pages ↔ store pages
# ---------------------------------------------------------------------------

def kv_to_pages(cfg, k, v):
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


def pages_to_kv(cfg, k_pages, v_pages, length):
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


def restored_to_pages(cfg, flat):
    """What one page-major store call returned (`flat`: [n * L * 2,
    page, n_kv, hd], rows ordered page, layer, k then v) as per-layer
    stacks in pool form: (k_pages, v_pages) [n_kv_layers, n, page,
    n_kv, hd]. One transpose; traceable, so the serving engine's hit program
    (serving._admit_fused_px) does it on the device inside the program
    that also scatters the stacks into the pool. For a family whose
    kinds of page share ONE shape and ONE set of layers (K and V; a
    latent family's rows alone): one stack a kind of `cfg.page_kinds`,
    of that kind's shape (`cfg.page_shape`)."""
    kinds = cfg.page_kinds
    n = flat.shape[0] // (len(kinds) * cfg.n_kv_layers)
    both = jnp.moveaxis(
        flat.reshape(n, cfg.n_kv_layers, len(kinds),
                     *cfg.page_shape(kinds[0])), 0, 2
    )
    return tuple(both[:, i] for i in range(len(kinds)))


def restore_prefix_pages(store, cfg, key_fn, n_pages,
                         getter=None):
    """Restore a matched prefix from the store in PAGE form: the one
    get_kv_pages recipe every cache-hit consumer shares. `key_fn(layer,
    kind)` returns that (layer, kind)'s n_pages keys (index-addressed
    `page_keys` or the serving engine's content-addressed keys);
    `getter` overrides the fetch method (e.g.
    store.get_kv_pages_quantized for int8 pages).

    ONE batched store call covers every (layer, kind) of one shape: 2L
    small fetches would pay 2L pin/transfer round trips where the batch
    pays one, and one large DMA beats 2L small ones. The keys go
    page-major (page, layer, k then v), the order the serving engine's
    offload allocates them in (serving.content_page_keys_by_page):
    pages that one offload wrote then lie in the store's pool in the
    order asked for, and the SHM read is one zero-copy view of the pool
    and not a view a block plus a stacking copy. The split back into
    per-layer stacks is one device transpose, then slicing
    (`restored_to_pages`). Returns one stack a kind of
    `cfg.page_kinds`: (k_pages, v_pages) [n_layers, n_pages, page,
    n_kv, hd]. A family whose kinds differ in shape or in the layers
    that keep them (models/glm.py: rows on every layer, index keys on
    some) makes a call a kind, since a call carries pages of one
    shape, each page-major over ITS layers (`cfg.page_layers`), and
    returns each kind's stack [its layers, n_pages, *its shape]."""
    get = getter if getter is not None else store.get_kv_pages
    kinds = cfg.page_kinds
    first = (cfg.page_shape(kinds[0]), cfg.page_layers(kinds[0]))
    if all((cfg.page_shape(k), cfg.page_layers(k)) == first for k in kinds):
        per = [key_fn(li, kind) for li in first[1] for kind in kinds]
        keys = [ks[p] for p in range(n_pages) for ks in per]
        return restored_to_pages(cfg, get(keys, first[0], cfg.jdtype))
    out = []
    for kind in kinds:
        shape, layers = cfg.page_shape(kind), cfg.page_layers(kind)
        per = [key_fn(li, kind) for li in layers]
        keys = [ks[p] for p in range(n_pages) for ks in per]
        flat = get(keys, shape, cfg.jdtype)
        out.append(jnp.moveaxis(
            flat.reshape(n_pages, len(layers), *shape), 0, 1))
    return tuple(out)


def restore_prefix_kvs(store, cfg, seq_id, n_pages):
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
        for li in range(cfg.n_kv_layers)
    ]
