"""Mixtral-style sparse-MoE decoder — second model family, and the
expert-parallel (ep) consumer of the store.

The reference ships no models (its scope is the KV pool; SURVEY.md §2);
this family exists so the TPU engine side of the stack exercises expert
parallelism end-to-end: MoE KV pages are identical store blocks (the
attention stack is the same GQA+RoPE design as models/llama.py and pages
out through the same kv_to_pages/page_keys helpers), while the FFN is a
top-k routed expert layer whose experts shard over a mesh "ep" axis.

TPU-first routing (GShard dense-dispatch formulation): routing is
expressed entirely as static-shape einsums — a [tokens, experts,
capacity] one-hot dispatch tensor scatters tokens to per-expert slots,
experts run as ONE batched [E, C, d] x [E, d, ff] matmul on the MXU, and
a combine einsum gathers weighted outputs back. No gather/scatter with
dynamic shapes, no per-expert Python loops; with the expert dimension
sharded P("ep"), XLA partitions the expert matmuls across chips and
inserts the dispatch/combine collectives itself (the scaling-book
recipe: annotate shardings, let the compiler place all-to-alls).
Over-capacity tokens are dropped (standard switch/GShard semantics) and
a load-balance auxiliary loss keeps the router spread.
"""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import llama as _llama
from .llama import rms_norm


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 512
    d_model: int = 128
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 256          # per-expert hidden size
    n_experts: int = 4
    top_k: int = 2
    capacity_factor: float = 1.5
    max_seq: int = 256
    page_size: int = 16
    rope_theta: float = 10000.0
    rope_scaling: tuple = ()  # see LlamaConfig.rope_scaling
    window: int = 0           # see LlamaConfig.window
    norm_plus_one: bool = False  # mirror of LlamaConfig's family knobs
    embed_scale: float = 1.0     # (the expert FFN itself stays SwiGLU)
    head_dim_override: int = 0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    aux_loss_weight: float = 0.01

    @property
    def head_dim(self):
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def kv_page_shape(self):
        return (self.page_size, self.n_kv_heads, self.head_dim)

    def capacity(self, n_tokens):
        """Per-expert token slots: ceil(top_k * T / E * factor), rounded
        up to 8 (sublane tile) so the expert batch stays MXU-friendly."""
        c = int(np.ceil(self.top_k * n_tokens / self.n_experts
                        * self.capacity_factor))
        return max(8, -(-c // 8) * 8)


def init_params(rng, cfg: MoEConfig):
    """Plain-dict pytree. Attention leaves reuse the llama naming (the
    tp sharding rules in parallel/mesh.py apply unchanged); expert
    weights are stacked on a leading E axis for the ep sharding."""
    dt = cfg.jdtype
    keys = jax.random.split(rng, 2 + cfg.n_layers)
    scale = cfg.d_model ** -0.5

    def dense(k, shape):
        return (jax.random.normal(k, shape) * scale).astype(dt)

    layers = []
    for li in range(cfg.n_layers):
        k = jax.random.split(keys[2 + li], 9)
        layers.append(
            {
                "ln1": jnp.ones(cfg.d_model, dtype=dt),
                "wq": dense(k[0], (cfg.d_model, cfg.n_heads * cfg.head_dim)),
                "wk": dense(k[1], (cfg.d_model, cfg.n_kv_heads * cfg.head_dim)),
                "wv": dense(k[2], (cfg.d_model, cfg.n_kv_heads * cfg.head_dim)),
                "wo": dense(k[3], (cfg.n_heads * cfg.head_dim, cfg.d_model)),
                "ln2": jnp.ones(cfg.d_model, dtype=dt),
                # Router in fp32: tiny, and routing decisions should not
                # quantize with the bf16 params.
                "router": (jax.random.normal(
                    k[4], (cfg.d_model, cfg.n_experts)) * scale
                ).astype(jnp.float32),
                "e_gate": dense(k[5], (cfg.n_experts, cfg.d_model, cfg.d_ff)),
                "e_up": dense(k[6], (cfg.n_experts, cfg.d_model, cfg.d_ff)),
                "e_down": dense(k[7], (cfg.n_experts, cfg.d_ff, cfg.d_model)),
            }
        )
    return {
        "embed": dense(keys[0], (cfg.vocab_size, cfg.d_model)),
        "layers": layers,
        "final_ln": jnp.ones(cfg.d_model, dtype=dt),
        "lm_head": dense(keys[1], (cfg.d_model, cfg.vocab_size)),
    }


def _route(layer, h, cfg: MoEConfig, valid=None):
    """Top-k routing → static dispatch/combine tensors + aux loss.

    h: [T, d]. `valid` ([T] bool or None): tokens marked invalid
    (decode-batch slots with nothing in cache, ragged verify padding)
    are excluded from routing BEFORE the capacity cumsum — otherwise
    garbage tokens would consume expert capacity slots and could evict
    REAL tokens' FFN computation, breaking the inherited contract that
    padding is inert. Returns (dispatch [T, E, C] bool-ish, combine
    [T, E, C] fp32, aux_loss scalar).
    """
    T = h.shape[0]
    E = cfg.n_experts
    C = cfg.capacity(T)
    logits = h.astype(jnp.float32) @ layer["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)  # [T, k]
    # Renormalize the selected gates (Mixtral convention).
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # mask[t, e] = gate weight if e selected for t else 0.
    sel = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # [T, k, E]
    gates = jnp.einsum("tk,tke->te", top_w, sel)
    chosen = jnp.sum(sel, axis=1)  # [T, E] in {0, 1}
    if valid is not None:
        keep_t = valid.astype(jnp.float32)[:, None]  # [T, 1]
        chosen = chosen * keep_t
        gates = gates * keep_t

    # Position of each token within its expert's slot list — cumsum over
    # tokens (static shape; earlier tokens win slots, later ones drop).
    pos = jnp.cumsum(chosen, axis=0) - chosen  # [T, E], pos of t in e
    keep = chosen * (pos < C)
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos.astype(jnp.int32), C, dtype=jnp.float32
    )
    combine = dispatch * gates[..., None]  # [T, E, C]

    # Switch-style load-balance loss: E * Σ_e (frac tokens to e) * (mean
    # router prob of e) — minimized when both are uniform.
    frac = jnp.mean(chosen, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def _moe_mlp(layer, x, cfg: MoEConfig, valid=None):
    """[B, S, d] → [B, S, d] through the routed expert FFN; also returns
    the layer's aux loss. `valid` ([B, S] bool or None) masks tokens
    out of routing (see _route)."""
    b, s, d = x.shape
    # Stage names as in models/llama.py (one a stage, no layer index).
    with jax.named_scope("moe.route"):
        h = rms_norm(x, layer["ln2"], cfg.norm_eps,
                     cfg.norm_plus_one).reshape(b * s, d)
        vflat = None if valid is None else valid.reshape(b * s)
        dispatch, combine, aux = _route(layer, h, cfg, vflat)
    # Scatter to per-expert slots: ONE einsum, [E, C, d] activations.
    with jax.named_scope("moe.dispatch"):
        xe = jnp.einsum("tec,td->ecd", dispatch.astype(h.dtype), h)
    # Batched expert SwiGLU on the MXU (E stacked matmuls; sharded over
    # the ep axis when the params carry P("ep", ...) shardings).
    with jax.named_scope("moe.experts"):
        a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, layer["e_gate"]))
        a = a * jnp.einsum("ecd,edf->ecf", xe, layer["e_up"])
        oe = jnp.einsum("ecf,efd->ecd", a, layer["e_down"])
    with jax.named_scope("moe.combine"):
        out = jnp.einsum("tec,ecd->td", combine.astype(oe.dtype), oe)
    return out.reshape(b, s, d), aux


def _forward_stack(params, cfg: MoEConfig, tokens, prefix_kvs=None,
                   pos0=0):
    """The decoder-stack loop shared by dense forward and prefix-cached
    prefill (mirrors llama._forward_stack — same attention, routed
    FFN): with `prefix_kvs` the positions shift by the prefix length
    and each layer attends over prefix + suffix KV through the
    rectangular flash kernel."""
    b, s = tokens.shape
    prefix_len = 0 if prefix_kvs is None else prefix_kvs[0][0].shape[1]
    x = _llama._embed(params, tokens, cfg)
    positions = jnp.broadcast_to(
        pos0 + prefix_len + jnp.arange(s)[None], (b, s)
    )
    kvs = []
    aux_total = jnp.float32(0)
    for li, layer in enumerate(params["layers"]):
        q, k, v = _llama._qkv(layer, x, cfg, positions)
        if prefix_kvs is None:
            k_full, v_full = k, v
        else:
            pk, pv = prefix_kvs[li]
            k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        with jax.named_scope("attn.kernel"):
            attn = _llama.flash_prefill(q, k_full, v_full, causal=True,
                                        window=cfg.window)
        x = x + _llama._attn_out(layer, attn.reshape(b, s, -1))
        moe_out, aux = _moe_mlp(layer, x, cfg)
        x = x + moe_out
        kvs.append((k, v))
        aux_total = aux_total + aux
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.norm_plus_one)
    logits = _llama._logits(params, x)
    return logits, kvs, aux_total


def forward_dense(params, cfg: MoEConfig, tokens):
    """Dense causal forward. tokens: [B, S] int32 → (logits [B, S, V]
    fp32, per-layer (k, v), total aux loss)."""
    return _forward_stack(params, cfg, tokens)


def prefill(params, cfg: MoEConfig, tokens):
    logits, kvs, _ = forward_dense(params, cfg, tokens)
    return logits, kvs


def prefill_with_prefix(params, cfg: MoEConfig, tokens, prefix_kvs,
                        pos0=0):
    """Suffix prefill over a cached prefix — the cache-HIT path, same
    contract as llama.prefill_with_prefix (the serving engine calls it
    through its model parameter)."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0)
    return logits, kvs


@partial(jax.jit, static_argnames=("cfg",))
def decode_step(params, cfg: MoEConfig, token, seq_lens, k_pages, v_pages,
                page_table):
    """One paged decode step — llama.decode_step with the routed expert
    FFN in place of the dense MLP (same KV page contract, so the store,
    the pallas decode kernels and the serving engine work unchanged).

    MIRROR CONTRACT: the paging/scatter/attention plumbing here and in
    verify_step is a deliberate mirror of models/llama.py (the FFN call
    is the only divergence) — any fix to llama's paging, scratch-page
    or rollback logic MUST be applied here too; the MoE serving parity
    suite (tests/test_moe.py) is the drift alarm."""
    b = token.shape[0]
    x = _llama._embed(params, token[:, None], cfg)  # [b, 1, d]
    positions = seq_lens[:, None]
    page_idx_in_seq = seq_lens // cfg.page_size
    target_page = jnp.take_along_axis(
        page_table, page_idx_in_seq[:, None], axis=1
    )[:, 0]
    slot = seq_lens % cfg.page_size
    # Slots with an empty cache are the engine's inactive rows: keep
    # their garbage tokens out of expert routing/capacity (best-effort
    # — a previously-active slot's stale row may still route, but
    # capacity() is sized for the full batch so it cannot evict real
    # tokens unless the router is badly imbalanced).
    valid = (seq_lens > 0)[:, None]  # [b, 1]

    for li, layer in enumerate(params["layers"]):
        q, k, v = _llama._qkv(layer, x, cfg, positions)
        with jax.named_scope("pool.update"):
            k_pages = _llama.scatter_kv_to_pages(k_pages, k, target_page,
                                                 slot, layer=li)
            v_pages = _llama.scatter_kv_to_pages(v_pages, v, target_page,
                                                 slot, layer=li)
        with jax.named_scope("attn.kernel"):
            attn = _llama.paged_decode_attention(
                q[:, 0], k_pages, v_pages, page_table, seq_lens + 1,
                window=cfg.window, layer=li
            )
        x = x + _llama._attn_out(layer, attn.reshape(b, 1, -1))
        moe_out, _aux = _moe_mlp(layer, x, cfg, valid)
        x = x + moe_out
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.norm_plus_one)
    return _llama._logits(params, x[:, 0]), k_pages, v_pages


@partial(jax.jit, static_argnames=("cfg",))
def verify_step(params, cfg: MoEConfig, tokens, seq_lens, k_pages,
                v_pages, page_table, valid_len=None):
    """m-token paged step (speculative verify / chunked prefill) —
    llama.verify_step with the routed FFN; see that docstring for the
    scratch-page and rollback contracts."""
    b, m = tokens.shape
    x = _llama._embed(params, tokens, cfg)  # [b, m, d]
    positions = seq_lens[:, None] + jnp.arange(m)[None, :]
    page_idx_in_seq = positions // cfg.page_size
    target_page = jnp.take_along_axis(page_table, page_idx_in_seq, axis=1)
    slot = positions % cfg.page_size
    ok = None
    if valid_len is not None:
        ok = jnp.arange(m)[None, :] < valid_len[:, None]
        target_page = jnp.where(ok, target_page, 0)
        slot = jnp.where(ok, slot, jnp.arange(m)[None, :] % cfg.page_size)

    for li, layer in enumerate(params["layers"]):
        q, k, v = _llama._qkv(layer, x, cfg, positions)
        with jax.named_scope("pool.update"):
            k_pages = _llama.scatter_kv_multi(k_pages, k, target_page, slot,
                                              layer=li)
            v_pages = _llama.scatter_kv_multi(v_pages, v, target_page, slot,
                                              layer=li)
        with jax.named_scope("attn.kernel"):
            attn = _llama.paged_verify_attention(
                q, k_pages, v_pages, page_table, seq_lens,
                window=cfg.window, layer=li
            )
        x = x + _llama._attn_out(layer, attn.reshape(b, m, -1))
        # Ragged padding + inactive rows stay out of expert capacity.
        moe_out, _aux = _moe_mlp(layer, x, cfg, ok)
        x = x + moe_out
    x = rms_norm(x, params["final_ln"], cfg.norm_eps, cfg.norm_plus_one)
    return _llama._logits(params, x), k_pages, v_pages


def loss_fn(params, cfg: MoEConfig, tokens):
    logits, _, aux = forward_dense(params, cfg, tokens[:, :-1])
    return (_llama.token_nll(logits, tokens[:, 1:])
            + cfg.aux_loss_weight * aux)


def train_step(params, opt_state, cfg: MoEConfig, tokens, optimizer):
    # The shared optimizer step with this family's loss plugged in.
    return _llama.train_step(
        params, opt_state, cfg, tokens, optimizer, loss=loss_fn
    )


# ---------------------------------------------------------------------------
# Expert-parallel sharding
# ---------------------------------------------------------------------------

def make_ep_mesh(dp, ep, devices=None):
    """(dp, ep) mesh: data parallel outer (DCN-friendly), experts inner
    (the dispatch/combine all-to-alls ride ICI)."""
    if devices is None:
        devices = jax.devices()[: dp * ep]
    arr = np.asarray(devices).reshape(dp, ep)
    return Mesh(arr, axis_names=("dp", "ep"))


_EP_RULES = {
    # Expert-stacked leaves shard over ep on the E axis; the router must
    # be replicated (every token routes everywhere).
    "e_gate": P("ep", None, None),
    "e_up": P("ep", None, None),
    "e_down": P("ep", None, None),
}


def param_shardings(mesh: Mesh, params):
    """NamedShardings: experts over ep, everything else replicated
    (attention tp can be layered on a third axis in larger meshes)."""

    def spec(path, leaf):
        name = None
        for p in reversed(path):
            key = getattr(p, "key", None) or getattr(p, "name", None)
            if key is not None:
                name = str(key)
                break
        return NamedSharding(mesh, _EP_RULES.get(name, P()))

    return jax.tree_util.tree_map_with_path(spec, params)


__all__ = [
    "MoEConfig", "init_params", "forward_dense", "prefill",
    "prefill_with_prefix", "decode_step", "verify_step", "loss_fn",
    "train_step", "make_ep_mesh", "param_shardings",
]
