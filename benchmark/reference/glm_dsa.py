"""Plain float32 reference of the GLM-5.2 sparse decoder (`model_type:
glm_moe_dsa`) as ONE CHIP'S SHARE of it: latent attention under a
learned selection of cache rows, index keys on the layers that own an
indexer, a sigmoid router with a selection bias over all the published
experts of which this chip holds some, one shared expert, an untied
head over the rows of the vocabulary held here. jax.numpy only: no
kernels, no cache, no pages, no pieces, no batching; nothing of the
program is imported. Hyper-parameters are read from the configuration
FILE (the published keys and its `expert_share` group).

One layer, x its input [T, C], h = RMSNorm(x; ln1):

    c_q = RMSNorm(h Wqa);  q = c_q Wqb -> H x (nope | rope)
    [c_kv | k_pe] = h Wkva;  c = RMSNorm(c_kv)
    q_pe, k_pe rotated in ADJACENT pairs (rope_interleave) at
    rope_parameters.rope_theta, rope_type default
    K_h = [c Wkb,h | k_pe],  V_h = c Wvb,h,  scale qk_head_dim ** -0.5

    indexer_types[i] == "full":
    qI = c_q WqI -> index_n_heads x index_head_dim
    kI = LayerNorm(h WkI)                     a weight and a bias, ONE a token
    the first qk_rope_head_dim lanes of qI_j and of kI rotated (adjacent)
    w  = (h Ww) index_n_heads ** -0.5 index_head_dim ** -0.5
    I(t, s) = sum_j w(t, j) relu(qI_j(t) . kI(s)),  s <= t
    S(t) = the min(t + 1, index_topk) positions of largest I(t, .)
    indexer_types[i] == "shared": S(t) of the nearest "full" layer below

    A_ts ~ exp(scale q_t . K_s) over s in S(t) and NO other s
    x' = x + concat_h(A V_h) Wo
    u = RMSNorm(x'; ln2)
    mlp_layer_types[i] == "dense": SwiGLU intermediate_size wide
    "sparse": sc = sigmoid(u Wr) over ALL `router_width` experts,
    Sx = the num_experts_per_tok largest of sc + bias,
    g_e = routed_scaling_factor sc_e / sum_Sx sc,
    out = sum_{e in Sx, e HELD HERE} g_e expert_e(u) + shared(u)
    logits = RMSNorm(x_L; final_ln) Whead

The share: `params` hold the experts first_expert .. first_expert +
n_routed_experts - 1 of the `router_width` the router scores; a chosen
expert that is absent adds nothing and nothing stands in for it.

Departures from the published description and assumptions, each also
under `assumed` in the configuration's file: (1) the published
inference code rotates qI and kI by one Hadamard matrix before it
quantises them to float8 with a scale a row; an orthogonal map on both
sides leaves every product as it is, and this configuration holds
index keys in bfloat16, so both are left out; (2) the indexer's
LayerNorm has eps 1e-6 (DeepSeek-V3.2's; no key of `config` says); (3)
the top-level `head_dim` 192 equals `qk_nope_head_dim` and plays no
part; (4) the prediction module (`num_nextn_predict_layers`) is not
part of the main model's forward pass and is left out; (5) ties of the
index score go to the lower position (`jax.lax.top_k`).

The reference scores EVERY causal pair of a "full" layer, takes its
OWN top-k and attends that set and no other: each query gathers the
rows [c | k_pe] its selection names and attends them in the absorbed
form (q_h Wkb,h against c, the weights' sum over c through Wvb,h: the
same sums as over K_h and V_h, which tests/test_glm.py holds it to
with every head's own K and V under a mask). What it costs follows the
selection, so a pass over 35k tokens is seconds, not minutes. To fit
35k tokens beside a serving engine the stream is held as blocks of
TOKEN_BLOCK tokens, QUERY_BLOCK queries are scored and ranked at a
time (a group of index heads at a time), GATHER_BLOCK queries gather
and attend at a time, and a held expert runs over the tokens that chose
it (indices found on the host, padded to a multiple of ROW_PAD with
gate 0): no capacity, nothing dropped. Every block has ONE shape (the
stream is padded with token 0 up to whole blocks, the keys are always
the whole padded length under the causal mask), so each routine
compiles once whatever the length. Only what `positions` need is
computed: blocks that begin after the last of them are never run, and
the LAST layer runs its queries, its selection and its feed-forward at
`positions` alone (its keys come from every token below them).

forward(params, conf, tokens, positions) -> (logits [P, vocab held]
float32, margins [P, layers] float32): margins[p, l] is the gap between
the k-th and (k+1)-th largest of sc + bias of position p in layer l
over the WHOLE router (a dense layer: a large constant).
`forward_with_selection` adds, per "full" layer, the selected
positions, their scores and the gap to the first row left out at
`positions` (the agreement tool's side of the comparison).
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = common.F32
TOKEN_BLOCK = 4096
QUERY_BLOCK = 256    # queries scored and ranked at a time
GATHER_BLOCK = 64    # queries that gather their rows and attend at a time
INDEX_HEAD_GROUP = 8
ROW_PAD = 1024
NO_ROUTER = 1e9
INDEX_NORM_EPS = 1e-6
# (exponent bits, mantissa bits) the index keys are rounded to before
# they are scored, or None: benchmark/tools/precision_reading_index.py
# reads what a float8 index cache would do to this configuration's
# logits ((4, 3): float8_e4m3fn). Never set in a run that decides
# `correct`.
INDEX_KEY_BITS = None
# A planted wrong selection, or None: the same tool reads what `correct`
# would see of a fault the selection can have. "recent_rows": every
# owner takes the newest index_topk positions, not the best scored;
# "other_layer_keys": every owner but the first scores the FIRST
# owner's index keys; "stale_keys": the index keys of the first half
# of the positions are the ones a page (16 tokens) earlier, as a
# restore that placed pages one off would leave them. Never set in a
# run that decides `correct`.
FAULT = None
KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "num_experts_per_tok", "routed_scaling_factor", "index_n_heads",
        "index_head_dim", "index_topk")


def _static(conf):
    return tuple((k, conf[k]) for k in KEYS) + (
        ("rope_theta", conf["rope_parameters"]["rope_theta"]),)


def _rope(x, pos, theta):
    """x: [T, heads, dim] at positions pos [T]; ADJACENT pairs (2 i,
    2 i + 1) turn by pos theta ** (-2 i / dim)."""
    dim = x.shape[-1]
    inv = jnp.asarray(theta ** (-np.arange(0, dim, 2, dtype=np.float64)
                                / dim), F32)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("static",))
def _latents(h, wkva, kv_ln, pos0, static):
    """(c [T, R], k_pe [T, rope] rotated) of one block."""
    conf = dict(static)
    r = conf["kv_lora_rank"]
    ckv = h @ wkva.astype(F32)
    pos = pos0 + jnp.arange(h.shape[0])
    k_pe = _rope(ckv[:, None, r:], pos, conf["rope_theta"])[:, 0]
    return common.rms_norm(ckv[:, :r], kv_ln, conf["rms_norm_eps"]), k_pe


@partial(jax.jit, static_argnames=("static", "bits"))
def _index_keys(h, wki, ln_w, ln_b, pos0, static, bits=None):
    """kI [T, Di] of one block: LayerNorm, then its rope lanes (then
    rounded to `bits`, INDEX_KEY_BITS's reading)."""
    conf = dict(static)
    rl = conf["qk_rope_head_dim"]
    k = h @ wki.astype(F32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                          + INDEX_NORM_EPS)
    k = k * ln_w.astype(F32) + ln_b.astype(F32)
    pos = pos0 + jnp.arange(h.shape[0])
    k = jnp.concatenate(
        [_rope(k[:, None, :rl], pos, conf["rope_theta"])[:, 0], k[:, rl:]],
        axis=-1)
    if bits is not None:
        k = jax.lax.reduce_precision(k, exponent_bits=bits[0],
                                     mantissa_bits=bits[1])
    return k


def _mapped(fn, block, *arrays):
    """`fn` over blocks of `block` of the leading entries of `arrays`
    (padded up with copies of entry 0), the results' leading axes
    joined and cut back."""
    n = arrays[0].shape[0]
    block = min(block, n)
    pad = -n % block

    def cut(a):
        a = jnp.concatenate([a, jnp.broadcast_to(a[:1], (pad, *a.shape[1:]))])
        return a.reshape(-1, block, *a.shape[1:])

    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(a) for a in arrays))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(-1, *a.shape[2:])[:n], out)


@partial(jax.jit, static_argnames=("static", "block", "group"))
def _select(h, ki, layer, qpos, static, block, group):
    """The queries' own ranking (h [n, C] at positions qpos [n]) of
    the index keys ki [S, Di] of positions 0 .. S - 1, twice as deep as
    the selection: (positions [n, k2], live [n, k2], scores [n, k2]),
    k2 = min(2 index_topk, S), best first. The selection is the first
    min(index_topk, S) of it; what lies behind says how far a row
    another arithmetic chose lay from the edge."""
    conf = dict(static)
    n, s = h.shape[0], ki.shape[0]
    hi, di = conf["index_n_heads"], conf["index_head_dim"]
    rl, k2 = conf["qk_rope_head_dim"], min(2 * conf["index_topk"], s)
    cq = common.rms_norm(h @ layer["wqa"].astype(F32), layer["q_ln"],
                         conf["rms_norm_eps"])
    qi = (cq @ layer["wqi"].astype(F32)).reshape(n, hi, di)
    qi = jnp.concatenate(
        [_rope(qi[..., :rl], qpos, conf["rope_theta"]), qi[..., rl:]],
        axis=-1)
    w = (h @ layer["wiw"].astype(F32)) * (hi ** -0.5 * di ** -0.5)
    kpos = jnp.arange(s)

    def one(q, wq, pos):
        score = jnp.zeros((q.shape[0], s), F32)
        for g in range(0, hi, group):
            dots = jnp.einsum("qhd,sd->qhs", q[:, g:g + group], ki)
            score = score + jnp.sum(
                jax.nn.relu(dots) * wq[:, g:g + group, None], axis=1)
        score = jnp.where(kpos[None, :] <= pos[:, None], score, -jnp.inf)
        top, idx = jax.lax.top_k(score, k2)
        return idx, top > -jnp.inf, top

    return _mapped(one, block, qi, w, qpos)


@partial(jax.jit, static_argnames=("static", "block"))
def _attend(h, rows, idx, taken, layer, qpos, static, block):
    """Wo . attention of the queries h [n, C] at positions qpos [n],
    each over the rows `idx` [n, k'] of `rows` [S, R + rope] = [c |
    k_pe] where `taken`, and no other."""
    conf = dict(static)
    n_h = conf["num_attention_heads"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    vd, r = conf["v_head_dim"], conf["kv_lora_rank"]
    wqa, wqb = layer["wqa"].astype(F32), layer["wqb"].astype(F32)
    wkvb = layer["wkvb"].astype(F32).reshape(r, n_h, nope + vd)

    def one(hb, pos, ix, tk):
        cq = common.rms_norm(hb @ wqa, layer["q_ln"], conf["rms_norm_eps"])
        q = (cq @ wqb).reshape(-1, n_h, nope + rope)
        q_pe = _rope(q[..., nope:], pos, conf["rope_theta"])
        # q_h . K_h(s) = (q_nope,h Wkb,h) . c(s) + q_pe,h . k_pe(s)
        q_lat = jnp.einsum("bhd,rhd->bhr", q[..., :nope], wkvb[..., :nope])
        q_row = jnp.concatenate([q_lat, q_pe], axis=-1) \
            * (nope + rope) ** -0.5
        picked = rows[ix]                              # [b, k', R + rope]
        sc = jnp.einsum("bhw,bkw->bhk", q_row, picked)
        p = jax.nn.softmax(jnp.where(tk[:, None], sc, -jnp.inf), axis=-1)
        o_lat = jnp.einsum("bhk,bkr->bhr", p, picked[..., :r])
        # sum_s p(s) V_h(s) = (sum_s p(s) c(s)) Wvb,h
        return jnp.einsum("bhr,rhd->bhd", o_lat,
                          wkvb[..., nope:]).reshape(-1, n_h * vd)

    attn = _mapped(one, block, h, qpos, idx, taken)
    return attn @ layer["wo"].astype(F32)


@jax.jit
def _swiglu(u, w_gate, w_up, w_down):
    a = jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
    return a @ w_down.astype(F32)


@partial(jax.jit, static_argnames=("static",))
def _route(u, router, bias, static):
    """(gates [T, E] with zeros off the chosen, margin [T]) over the
    whole router."""
    conf = dict(static)
    k = conf["num_experts_per_tok"]
    sc = jax.nn.sigmoid(u @ router.astype(F32))
    biased = sc + bias.astype(F32)
    _, top_i = jax.lax.top_k(biased, k)
    chosen = jnp.sum(jax.nn.one_hot(top_i, sc.shape[-1], dtype=F32), axis=1)
    picked = sc * chosen
    gates = conf["routed_scaling_factor"] * picked \
        / jnp.sum(picked, axis=-1, keepdims=True)
    zs = jnp.sort(biased, axis=-1)
    return gates, zs[:, -k] - zs[:, -k - 1]


@partial(jax.jit, donate_argnums=(0,))
def _expert_into(out, u, idx, gate, w_gate, w_up, w_down):
    """out[idx] += gate * expert(u[idx])."""
    y = _swiglu(u[idx], w_gate, w_up, w_down) * gate[:, None]
    return out.at[idx].add(y)


def _experts(u, layer, first, static):
    """(shared(u) + the HELD routed experts' sum [T, C], margin [T])."""
    gates, margin = _route(u, layer["router"], layer["router_bias"], static)
    out = _swiglu(u, layer["s_gate"], layer["s_up"], layer["s_down"])
    held = layer["e_gate"].shape[0]
    g_host = np.asarray(gates[:, first:first + held])
    for e in range(held):
        idx = np.nonzero(g_host[:, e])[0]
        if not len(idx):
            continue
        pad = -len(idx) % ROW_PAD
        gate = np.pad(g_host[idx, e], (0, pad))      # gate 0: adds nothing
        idx = np.pad(idx, (0, pad)).astype(np.int32)
        out = _expert_into(out, u, jnp.asarray(idx), jnp.asarray(gate),
                           layer["e_gate"][e], layer["e_up"][e],
                           layer["e_down"][e])
    return out, margin


@partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, eps):
    return common.rms_norm(x, w, eps)


def _run(params, conf, tokens, positions, want_selection=False):
    static = _static(conf)
    eps = conf["rms_norm_eps"]
    first = int((conf.get("expert_share") or {}).get("first_expert", 0))
    positions = np.asarray(positions, np.int64)
    t = len(tokens)
    block = min(TOKEN_BLOCK, -(-t // 128) * 128)
    total = -(-t // block) * block          # the keys' one length
    starts = list(range(0, int(positions.max()) + 1, block))
    tokens = jnp.asarray(np.pad(np.asarray(tokens, np.int32),
                                (0, total - t)))
    margins, chosen = [], []
    last = len(params["layers"]) - 1
    with jax.default_matmul_precision("highest"):
        x = [common.embed(params, tokens[a:a + block]) for a in starts]
        # the queries, as groups of rows of the stream with their
        # positions: the blocks, and in the last layer `positions` alone
        qpos = [a + np.arange(block) for a in starts]
        sel = None  # per group (positions, taken) of the nearest owner
        for i, layer in enumerate(params["layers"]):
            owner = conf["indexer_types"][i] == "full"
            cs, pes, kis = [], [], []
            for a, xb in zip(starts, x):
                h = _normed(xb, layer["ln1"], eps)
                c, k_pe = _latents(h, layer["wkva"], layer["kv_ln"], a,
                                   static)
                cs.append(c), pes.append(k_pe)
                if owner:
                    kis.append(_index_keys(h, layer["wki"], layer["ki_ln"],
                                           layer["ki_ln_b"], a, static,
                                           bits=INDEX_KEY_BITS))
            rest = total - len(starts) * block  # never computed, never seen
            rows_all = jnp.pad(jnp.concatenate(
                [jnp.concatenate(cs), jnp.concatenate(pes)], axis=-1),
                ((0, rest), (0, 0)))
            ki_all = jnp.pad(jnp.concatenate(kis), ((0, rest), (0, 0))) \
                if owner else None
            if owner and FAULT == "stale_keys":
                ki_all = ki_all.at[16:t // 2].set(ki_all[:t // 2 - 16])
            if owner and FAULT == "other_layer_keys":
                ki_all = first_keys = ki_all if sel is None else first_keys
            del cs, pes, kis, c, k_pe, h
            if i == last:
                x = [jnp.stack([x[p // block][p % block] for p in positions])]
                qpos = [positions]
                if not owner:
                    sel = [tuple(jnp.stack([sel[p // block][j][p % block]
                                            for p in positions])
                                 for j in range(2))]
            attn = {k: layer[k] for k in ("wqa", "q_ln", "wqb", "wkvb",
                                          "wo")}
            if owner:
                sel = []
                index = {k: layer[k] for k in ("wqa", "q_ln", "wqi", "wiw")}
            for b, at in enumerate(qpos):
                h = _normed(x[b], layer["ln1"], eps)
                at_dev = jnp.asarray(at, jnp.int32)
                if owner:
                    idx, live, top = _select(h, ki_all, index, at_dev, static,
                                             QUERY_BLOCK, INDEX_HEAD_GROUP)
                    k = min(conf["index_topk"], idx.shape[1])
                    if FAULT == "recent_rows":
                        back = at_dev[:, None] \
                            - jnp.arange(idx.shape[1])[None, :]
                        idx, live = jnp.maximum(back, 0), back >= 0
                    sel.append((idx[:, :k], live[:, :k]))
                    if want_selection:
                        where = {int(p): j for j, p in enumerate(at)}
                        got = [where[int(p)] for p in positions
                               if int(p) in where]
                        chosen.append((i, k) + tuple(
                            np.asarray(v)[got] for v in (idx, live, top)))
                    del idx, live, top
                x[b] = x[b] + _attend(h, rows_all, *sel[b], attn, at_dev,
                                      static, GATHER_BLOCK)
            del rows_all, ki_all, h
            margin = []
            for b in range(len(x)):
                u = _normed(x[b], layer["ln2"], eps)
                if conf["mlp_layer_types"][i] == "dense":
                    y = _swiglu(u, layer["w_gate"], layer["w_up"],
                                layer["w_down"])
                else:
                    y, m = _experts(u, layer, first, static)
                    margin.append(np.asarray(m))
                x[b] = x[b] + y
                del u, y
            if not margin:
                margin = [np.full(len(positions), NO_ROUTER, np.float32)]
            elif i != last:
                margin = [np.concatenate(margin)[positions]]
            margins.append(margin[0])
        xs = common.rms_norm(x[0], params["final_ln"], eps)
        logits = xs @ params["lm_head"].astype(F32)
    return logits, jnp.asarray(np.stack(margins, axis=1)), chosen


def forward(params, conf, tokens, positions):
    logits, margins, _ = _run(params, conf, tokens, positions)
    return logits, margins


def forward_with_selection(params, conf, tokens, positions):
    """`forward`'s pair and, third, {owner layer: (positions [P, k'],
    taken [P, k'], scores [P, k'], gap [P], ranked)}: the reference's
    own selection at `positions` (ascending). gap: the last score taken
    minus the first left out (inf where every live key is taken); a gap
    near float32's grain is a near-tie that rounding can flip, as a
    router's margin. ranked: (positions [P, k2], scores [P, k2]) of the
    ranking twice as deep as the selection."""
    logits, margins, chosen = _run(params, conf, tokens, positions,
                                   want_selection=True)
    out = {}
    for layer, k, idx, live, top in chosen:
        if not len(idx):
            continue
        gap = np.full(len(idx), np.inf, np.float32)
        if top.shape[1] > k:
            both = np.isfinite(top[:, k])  # a row was left out
            gap[both] = top[both, k - 1] - top[both, k]
        out.setdefault(layer, []).append(
            (idx[:, :k], live[:, :k], top[:, :k], gap, idx, top))
    return logits, margins, {
        layer: tuple(np.concatenate([p[j] for p in parts])
                     for j in range(4))
        + ((np.concatenate([p[4] for p in parts]),
            np.concatenate([p[5] for p in parts])),)
        for layer, parts in out.items()}


def selection(params, conf, tokens, positions):
    return forward_with_selection(params, conf, tokens, positions)[2]
