"""Mixtral-style sparse-MoE decoder — second model family, and the
expert-parallel (ep) consumer of the store.

The reference ships no models (its scope is the KV pool; SURVEY.md §2);
this family exists so the TPU engine side of the stack exercises expert
parallelism end-to-end: MoE KV pages are identical store blocks (the
attention stack and the layer loops are models/decoder.py's, shared with
models/llama.py, and pages go out through its kv_to_pages/page_keys
helpers), while the FFN is a top-k routed expert layer whose experts
shard over a mesh "ep" axis.

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

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..ops import pallas_moe_decode
from . import decoder, llama


@dataclass(frozen=True)
class MoEConfig(llama.LlamaConfig):
    """LlamaConfig plus the routed feed-forward's own fields. `d_ff` is
    the per-expert hidden size; the dense dispatch does not read `act`
    (its expert FFN is SwiGLU, and the HF bridge refuses anything
    else), the sorted dispatch does ("relu": ReGLU)."""

    n_experts: int = 4
    top_k: int = 2
    capacity_factor: float = 1.5
    aux_loss_weight: float = 0.01
    # The sorted dispatch's router form and what runs beside the routed
    # experts: "softmax" (`route_top_k`) or "sigmoid" (`route_sigmoid`,
    # gates summing to `route_scale`), and `n_shared` experts every
    # token goes through ungated (weights s_gate / s_up / s_down).
    router: str = "softmax"
    route_scale: float = 1.0
    n_shared: int = 0
    # One chip's SHARE of a layer's experts (the sorted dispatch only):
    # the router scores `n_routed` experts (0: `n_experts`, all of them
    # held here) and chooses `top_k` of them; this chip holds the
    # `n_experts` with ids from `first_expert` on (the weights' leading
    # axis) and computes their part of a token's sum. A chosen pair
    # whose expert is absent contributes nothing: the other chips'
    # parts, and the exchange that would add them, are not here.
    n_routed: int = 0
    first_expert: int = 0
    # the shared experts' outputs are averaged, not summed
    shared_mean: bool = False

    @property
    def holds_share(self):
        """The router scores more experts than are held here."""
        return self.n_routed > self.n_experts

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


def _top_k_gates(layer, h, cfg: MoEConfig):
    """(router probabilities [T, E] float32, chosen experts [T, k],
    their gates [T, k] renormalised over the chosen: the Mixtral
    convention)."""
    logits = h.astype(jnp.float32) @ layer["router"]  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)  # [T, k]
    return probs, top_idx, top_w / jnp.sum(top_w, axis=-1, keepdims=True)


def _balance_loss(chosen, probs):
    """Switch-style load-balance loss: E * Σ_e (frac tokens to e) *
    (mean router prob of e) — minimized when both are uniform.
    chosen: [T, E] in {0, 1}."""
    return chosen.shape[1] * jnp.sum(jnp.mean(chosen, axis=0)
                                     * jnp.mean(probs, axis=0))


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
    probs, top_idx, top_w = _top_k_gates(layer, h, cfg)

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

    return dispatch, combine, _balance_loss(chosen, probs)


def _moe_mlp(layer, x, cfg: MoEConfig, valid, h_attn=None):
    """The family's feed-forward block (decoder.py's `block` contract):
    [B, S, d] → [B, S, d] through the routed expert FFN, the layer's
    aux loss, and the experts the layer fetched where that is fewer
    than all (else None). `valid` ([B, S] bool or None) masks tokens
    out of routing (see _route); the router reads the block's own
    normalised input, so `h_attn` goes unused.

    A handful of rows of which none can be dropped (the capacity holds
    them all, so the capacity dispatch and the plain sum over a row's
    chosen experts are the same mathematics) go through
    `experts_gathered`: the row count and the capacity are shapes."""
    b, s, d = x.shape
    T = b * s
    vflat = None if valid is None else valid.reshape(T)
    # Stage names as in models/decoder.py (one a stage, no layer index).
    with jax.named_scope("moe.route"):
        h = decoder.rms_norm(x, layer["ln2"], cfg.norm_eps,
                             cfg.norm_plus_one).reshape(T, d)
    if T <= GATHERED_EXPERTS_MAX_ROWS and cfg.capacity(T) >= T:
        with jax.named_scope("moe.route"):
            probs, top_idx, top_w = _top_k_gates(layer, h, cfg)
            chosen = jnp.sum(jax.nn.one_hot(top_idx, cfg.n_experts,
                                            dtype=jnp.float32), axis=1)
            if vflat is not None:
                chosen = chosen * vflat.astype(jnp.float32)[:, None]
            aux = _balance_loss(chosen, probs)
        out, fetched = experts_gathered(layer, h, top_idx, top_w,
                                        jax.nn.silu, vflat)
        return out.reshape(b, s, d), aux, fetched
    with jax.named_scope("moe.route"):
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
    return out.reshape(b, s, d), aux, None

_forward_stack, decode_step, verify_step = decoder.bind(_moe_mlp)


# ---------------------------------------------------------------------------
# The sorted dispatch: no capacity, no dropped token
# ---------------------------------------------------------------------------
# The dense dispatch above builds [T, E, C] tensors; with many small
# experts (64 of them, 6 a token) and C = T they cannot be built at all
# for a long prompt. Here the T x k chosen (token, expert) pairs are
# sorted by expert and the experts run as ONE grouped matmul over the
# sorted rows; every chosen pair is computed, whatever the router's
# skew. Models/smallthinker.py binds it; this family keeps the dense
# dispatch above a decode batch until its cell has been measured on the
# other (ROADMAP S4, the prefill half).

# Which form runs a block's experts, by the rows it holds. Measured on
# a v5e (tools/time_moe_decode.py; PERF.md, PR 41; us a layer, 16 rows,
# dense / sorted / gathered by the rows that hold a token): 64 experts
# of 2,560 x 768, 6 a token: 1,003 / 2,175 / 110 at 1 row (6 experts
# fetched), 203 at 2 (12), 342 at 4 (21), 805 at 16 (51 of 64); 64 of
# 3,584 x 1,024, 4 a token, 8 rows: 1,919 / 1,952 / 133 at 1 (4), 824
# at 8 (28); 8 of 4,096 x 14,336, 2 a token: 3,751 / 8,899 / 962 at 1
# (2), 2,808 at 4 (6), 3,731 where all 8 are touched (the capacity
# dispatch: 3,753). Every form is bound by the weights it reads, at
# 79-92 % of the HBM's rate; the gathered kernel reads the experts some
# valid row chose and is no slower than the dense form where that is
# all of them, so up to a decode step's batch it always runs.
GATHERED_EXPERTS_MAX_ROWS = 16
# Above that, with token x expert rows at or under this, every token
# runs through every expert and the gates zero the ones it did not
# choose (PERF.md, PR 35; ms a layer at the first shape, dense /
# sorted: 128 tokens 1.16 / 2.22, 256 1.24 / 3.05, 1024 4.21 / 3.73):
# a hit's short suffix touches every expert anyway, and the sort, two
# gathers and a grouped matmul over groups of a few rows cost more than
# the rows they save until the rows are many.
# The rows are counted over the experts the ROUTER scores: a chip that
# holds a share of them (`MoEConfig.n_routed`) holds fewer and the
# dense form's work grows with tokens x held, while what it saves, the
# sort and the gathers, does not. Measured for a share (PERF.md, PR 42;
# 16 held of 128 scored, 4,096 x 4,096, 8 a token; ms a layer, dense /
# `experts_sorted_held`): 128 tokens 2.23 / 5.21, 256 2.40 / 5.23, 512
# 4.48 / 5.54. A hit's suffix of 128-256 tokens leaves a held pair a
# token, 8-16 rows an expert: both forms read all 16 experts' weights
# (1.61 GB, 1.97 ms at the HBM's rate), the dense form at 82-88 % of
# that rate, the grouped matmul over groups that small at 38 %. The
# crossover lies above 512 tokens, where no program of a cell runs
# (suffixes end at 256, cold prompts start at 1,136).
DENSE_EXPERTS_MAX_ROWS = 512 * 64


def route_top_k(router, h, top_k):
    """(router logits [T, E] float32, chosen experts [T, k], their
    gates [T, k] float32): the k largest logits, softmax over those k
    (= softmax over all E, renormalised on the chosen)."""
    logits = h.astype(jnp.float32) @ router
    top_z, top_idx = jax.lax.top_k(logits, top_k)
    return logits, top_idx, jax.nn.softmax(top_z, axis=-1)


def route_sigmoid(router, bias, h, top_k, scale):
    """(scores + bias [T, E] float32, chosen experts [T, k], their
    gates [T, k] float32) of a sigmoid router with a selection bias
    (DeepSeek-V3's `noaux_tc` without groups): the score of an expert
    is sigmoid(h . w_e); the k largest of score + bias are chosen (the
    bias chooses, it does not weigh; None: a router without one); a gate is the chosen expert's
    SCORE over the sum of the chosen scores, times `scale`."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ router)
    biased = scores if bias is None else scores + bias
    _, top_idx = jax.lax.top_k(biased, top_k)
    top_s = jnp.take_along_axis(scores, top_idx, axis=-1)
    gates = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * scale
    return biased, top_idx, gates


def shared_expert(layer, u, act, mean_of=1):
    """The shared experts, side by side in one gated block: every
    token, ungated; their sum, or with `mean_of` their number the
    mean. u: [T, d]."""
    with jax.named_scope("moe.shared"):
        a = act(decoder.matmul(u, layer["s_gate"])) \
            * decoder.matmul(u, layer["s_up"])
        out = decoder.matmul(a, layer["s_down"])
        return out if mean_of == 1 else out * jnp.asarray(1.0 / mean_of,
                                                          out.dtype)


# The grouped matmul's rows are padded to a multiple of this. Measured
# on a v5e (PERF.md, PR 35; one layer, 64 experts of 2560 x 768, 6 a
# token): 12,544 tokens (75,264 rows = 147 x 512) 19.6 ms, 12,528 tokens
# (75,168 rows) 67.3 ms, 6,256 tokens 34.4: `ragged_dot` over a row count
# that is no multiple of its tile runs at a third of its speed.
SORTED_ROW_TILE = 512


def experts_sorted(layer, u, top_idx, gates, act):
    """sum over a token's chosen experts of gate * W_down(act(W_gate u)
    * (W_up u)), by sorting the pairs by expert. u: [T, d]; top_idx,
    gates: [T, k]. Returns [T, d]."""
    T, d = u.shape
    k = top_idx.shape[1]
    E = layer["e_gate"].shape[0]
    pad = -(T * k) % SORTED_ROW_TILE
    with jax.named_scope("moe.dispatch"):
        flat = top_idx.reshape(-1)
        order = jnp.argsort(flat)          # pairs by expert (stable)
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
        # the padding rows ride in the last expert's group (copies of
        # token 0, computed and cut off below): the sizes still sum to
        # the row count
        sizes = sizes.at[-1].add(pad)
        rows = jnp.take(u, jnp.pad(order // k, (0, pad)), axis=0)
    with jax.named_scope("moe.experts"):
        a = act(jax.lax.ragged_dot(rows, layer["e_gate"], sizes))
        a = a * jax.lax.ragged_dot(rows, layer["e_up"], sizes)
        out = jax.lax.ragged_dot(a, layer["e_down"], sizes)  # [T k, d]
    with jax.named_scope("moe.combine"):
        back = jnp.take(out, jnp.argsort(order), axis=0).reshape(T, k, d)
        return jnp.einsum("tkd,tk->td", back, gates.astype(back.dtype))


# A layer that holds a SHARE of the experts its router scores runs the
# grouped matmul over the pairs that fell on held experts, in passes of
# a fixed number of rows (a shape): what falls here in expectation, T k
# n_experts / n_routed, and a quarter more, in whole tiles. One pass
# holds every held pair unless the routing leans this chip's way by
# more than that quarter; then a second pass runs (a loop whose trip
# count the held pairs set), so no pair is dropped and no capacity
# exists, and the rows computed follow the held pairs, not T k.
HELD_ROWS_SLACK = 1.25


def held_rows(n_tokens, cfg):
    """Rows one pass of `experts_sorted_held` runs for `n_tokens`."""
    def tiles(n):
        return -(-n // SORTED_ROW_TILE) * SORTED_ROW_TILE

    pairs = n_tokens * cfg.top_k
    want = int(np.ceil(pairs * cfg.n_experts / cfg.n_routed
                       * HELD_ROWS_SLACK))
    return min(tiles(want), tiles(pairs))


def experts_sorted_held(layer, u, local, gates, act, rows):
    """`experts_sorted` over the pairs whose expert is held here.
    local: [T, k] chosen ids counted from the first held expert, so
    outside [0, E) where the expert is absent; such a pair adds
    nothing. The held pairs, sorted by expert, run `rows` at a time.
    Returns ([T, d], the passes run, int32)."""
    T, d = u.shape
    k = local.shape[1]
    E = layer["e_gate"].shape[0]
    with jax.named_scope("moe.dispatch"):
        here = (local >= 0) & (local < E)
        flat = jnp.where(here, local, E).reshape(-1)  # absent pairs last
        order = jnp.argsort(flat)
        place = jnp.argsort(order).reshape(T, k)  # a pair's sorted row
        ends = jnp.cumsum(
            jnp.bincount(flat, length=E + 1)[:E]).astype(jnp.int32)
        starts = ends - jnp.diff(ends, prepend=0)
        token = jnp.pad(order // k, (0, rows))
        w = jnp.where(here, gates, 0.0)

    def one_pass(i, acc):
        lo = i * rows
        with jax.named_scope("moe.dispatch"):
            sizes = (jnp.clip(ends, lo, lo + rows)
                     - jnp.clip(starts, lo, lo + rows))
            # rows past the last held pair ride in the last expert's
            # group: computed, and combined into no token
            sizes = sizes.at[-1].add(rows - jnp.sum(sizes))
            x = jnp.take(u, jax.lax.dynamic_slice(token, (lo,), (rows,)),
                         axis=0)
        with jax.named_scope("moe.experts"):
            a = act(jax.lax.ragged_dot(x, layer["e_gate"], sizes))
            a = a * jax.lax.ragged_dot(x, layer["e_up"], sizes)
            y = jax.lax.ragged_dot(a, layer["e_down"], sizes)  # [rows, d]
        with jax.named_scope("moe.combine"):
            at = place - lo
            mine = here & (at >= 0) & (at < rows)
            for j in range(k):  # a token's j-th pair, where it ran here
                part = jnp.take(y, jnp.clip(at[:, j], 0, rows - 1), axis=0)
                acc = acc + jnp.where(
                    mine[:, j, None],
                    part.astype(acc.dtype) * w[:, j, None], 0.0)
        return acc

    passes = -(-ends[-1] // rows)
    acc = jax.lax.fori_loop(0, passes, one_pass,
                            jnp.zeros((T, d), jnp.float32))
    return acc.astype(u.dtype), passes


def experts_dense(layer, u, top_idx, gates, act):
    """The same sum with every token through every expert and the
    gates of the experts it did not choose at zero: for a handful of
    tokens (DENSE_EXPERTS_MAX_ROWS)."""
    E = layer["e_gate"].shape[0]
    with jax.named_scope("moe.dispatch"):
        dense = jnp.einsum("tk,tke->te", gates,
                           jax.nn.one_hot(top_idx, E, dtype=gates.dtype))
    with jax.named_scope("moe.experts"):
        a = act(jnp.einsum("td,edf->tef", u, layer["e_gate"]))
        a = a * jnp.einsum("td,edf->tef", u, layer["e_up"])
        a = a * dense[..., None].astype(a.dtype)
    with jax.named_scope("moe.combine"):
        return jnp.einsum("tef,efd->td", a, layer["e_down"])


def experts_gathered(layer, u, top_idx, gates, act, valid=None):
    """The same sum by fetching only the experts some valid row chose
    (ops/pallas_moe_decode.py), and how many those were: for the rows
    of a decode step (GATHERED_EXPERTS_MAX_ROWS). valid: [T] bool or
    None; a row that is not valid comes back zero."""
    with jax.named_scope("moe.dispatch"):
        dense, ids, n = pallas_moe_decode.live_experts(
            top_idx, gates, valid, layer["e_gate"].shape[0])
    with jax.named_scope("moe.experts"):
        out = pallas_moe_decode.gathered_call(
            u, layer["e_gate"], layer["e_up"], layer["e_down"], dense, ids,
            n, act=act, interpret=jax.default_backend() != "tpu")
    return out, n


def sorted_moe_mlp(layer, x, cfg: MoEConfig, valid, h_attn=None,
                   early_router=False, own_norm=True):
    """A feed-forward block (decoder.py's `block` contract) without
    capacity: a row that holds no real token takes nothing from one
    that does, so `valid` ([b, s] bool or None) only keeps such a
    row's experts from being fetched (`experts_gathered`). With
    `early_router` the router reads `h_attn`, the attention block's
    normalised input, and not the block's own; without `own_norm` the
    block has no norm of its own and its input IS `h_attn` (a parallel
    block: attention and experts read one normalised input, and `x`
    goes unread). Which of the three
    forms runs is decided by the number of tokens, which is a shape.
    The gate's activation is the config's (`cfg.act`), and so are the
    router's form (`cfg.router`) and the shared expert (`cfg.n_shared`).
    No auxiliary loss (serving only); third, the experts the layer
    fetched where that is fewer than all (else None).

    A layer that holds a share of the experts its router scores
    (`cfg.holds_share`) routes over all of them and computes its own
    experts' part: the chosen ids are counted from `cfg.first_expert`,
    and in every form a pair whose expert is absent adds nothing (a
    one-hot of an id outside the held ones is all zero; the sorted
    form sorts such pairs last and runs the others). It returns a
    fourth, the block contract's counts: `pairs_held` [b, s] int32
    (zero for a row that is not valid) and `rows`, the rows its
    matmuls over a prompt's tokens ran (0 for a decode step's)."""
    b, s, d = x.shape
    T = b * s
    with jax.named_scope("moe.route"):
        if own_norm:
            u = decoder.rms_norm(x, layer["ln2"], cfg.norm_eps,
                                 cfg.norm_plus_one).reshape(T, d)
        else:
            u = h_attn.reshape(T, d)
        seen = h_attn.reshape(T, d) if early_router else u
        if cfg.router == "sigmoid":
            _, top_idx, gates = route_sigmoid(
                layer["router"], layer.get("router_bias"), seen, cfg.top_k,
                cfg.route_scale)
        else:
            _, top_idx, gates = route_top_k(layer["router"], seen,
                                            cfg.top_k)
        if cfg.holds_share:
            top_idx = top_idx - cfg.first_expert
            here = (top_idx >= 0) & (top_idx < cfg.n_experts)
            if valid is not None:
                here = here & valid.reshape(T, 1)
            pairs_held = jnp.sum(here, axis=-1, dtype=jnp.int32)
    fetched = None
    rows = 0  # of the matmuls over a prompt's tokens (a share's count)
    if T <= GATHERED_EXPERTS_MAX_ROWS:
        out, fetched = experts_gathered(
            layer, u, top_idx, gates, _gate_act(cfg),
            None if valid is None else valid.reshape(T))
    elif T * (cfg.n_routed or cfg.n_experts) <= DENSE_EXPERTS_MAX_ROWS:
        out = experts_dense(layer, u, top_idx, gates, _gate_act(cfg))
        rows = T * cfg.n_experts
    elif cfg.holds_share:
        per_pass = held_rows(T, cfg)
        out, passes = experts_sorted_held(layer, u, top_idx, gates,
                                          _gate_act(cfg), per_pass)
        rows = passes * per_pass
    else:
        out = experts_sorted(layer, u, top_idx, gates, _gate_act(cfg))
    if cfg.n_shared:
        out = out + shared_expert(
            layer, u, _gate_act(cfg),
            cfg.n_shared if cfg.shared_mean else 1)
    if cfg.holds_share:
        return out.reshape(b, s, d), None, fetched, {
            "pairs_held": pairs_held.reshape(b, s),
            "rows": jnp.asarray(rows, jnp.int32)}
    return out.reshape(b, s, d), None, fetched


def _gate_act(cfg):
    return jax.nn.relu if cfg.act == "relu" else decoder.act(cfg)


def forward_dense(params, cfg: MoEConfig, tokens):
    """Dense causal forward. tokens: [B, S] int32 → (logits [B, S, V]
    fp32, per-layer (k, v), total aux loss)."""
    logits, kvs, auxes = _forward_stack(params, cfg, tokens)
    return logits, kvs, sum(auxes, jnp.float32(0))


def prefill(params, cfg: MoEConfig, tokens, keep=None):
    logits, kvs, _ = _forward_stack(params, cfg, tokens, keep=keep)
    return logits, kvs


def prefill_with_prefix(params, cfg: MoEConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """Suffix prefill over a cached prefix — the cache-HIT path, same
    contract as llama.prefill_with_prefix (the serving engine calls it
    through its model parameter)."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0, keep=keep)
    return logits, kvs


def loss_fn(params, cfg: MoEConfig, tokens):
    logits, _, aux = forward_dense(params, cfg, tokens[:, :-1])
    return (llama.token_nll(logits, tokens[:, 1:])
            + cfg.aux_loss_weight * aux)


def train_step(params, opt_state, cfg: MoEConfig, tokens, optimizer):
    # The shared optimizer step with this family's loss plugged in.
    return llama.train_step(
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
