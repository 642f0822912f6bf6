"""Plain float32 reference of the language model of Keye-VL-2.0-30B-A3B
(`model_type: KeyeVL2`): grouped-query attention with a per-head
RMSNorm on q and k, under a learned selection of K and V rows
(`sa_config`), an indexer on every layer, a softmax router over all the
published experts, every one of them held, an untied head. jax.numpy
only: no kernels, no cache, no pages, no pieces, no batching; nothing
of the program is imported. Hyper-parameters are read from the
configuration FILE (the published keys).

One layer, x its input [T, C], h = RMSNorm(x; ln1):

    q, k, v = h Wq, h Wk, h Wv -> H, G, G heads of head_dim
    q_h = RMSNorm(q_h; q_norm), k_g = RMSNorm(k_g; k_norm), eps rms_norm_eps
    q_h, k_g rotated on all lanes, the halves (i, i + head_dim / 2),
    at rope_theta

    qI = h WqI -> indexer_num_heads x indexer_head_dim
    kI = LayerNorm(h WkI)                     a weight and a bias, ONE a token
    qI_j, kI rotated on all lanes, the halves, at rope_theta
    w  = (h Ww) indexer_num_heads ** -0.5 indexer_head_dim ** -0.5
    I(t, s) = sum_j w(t, j) relu(qI_j(t) . kI(s)),  s <= t
    S(t) = the min(t + 1, topk) positions of largest I(t, .)

    A_ts ~ exp(q_h(t) . k_g(h)(s) head_dim ** -0.5) over s in S(t) and
    NO other s, g(h) = h // (H / G);  x' = x + concat_h(A v_g(h)) Wo
    u = RMSNorm(x'; ln2);  z = u Wr over num_experts
    Sx = the num_experts_per_tok largest of z;  g = softmax(z_Sx)
    (= softmax over all, renormalised on the chosen: norm_topk_prob)
    out = x' + sum_{e in Sx} g_e Wdown_e(silu(Wgate_e u) * (Wup_e u))
    logits = RMSNorm(x_L; final_ln) Whead

Departures from the published description and assumptions, each also
under `assumed` in the configuration's file: (1) TEXT ALONE: the vision
tower is absent (the catalog row holds no `vision_config`), inputs are
token ids, and with text alone the three position rows of
`mrope_section` are equal, so rotary is the plain one at `rope_theta`;
(2) the per-head RMSNorm on q and k is Qwen3-MoE's, whose key set the
config carries and which has no key for it; (3) the indexer is
DeepSeek-V3.2's lightning indexer with its query from the layer's
normalised input, its LayerNorm at eps 1e-6, rotary over all its lanes
in the halves layout; (4) ties of the index score go to the lower
position (`jax.lax.top_k`); (5) `q_chunk_size` / `kv_chunk_size` are a
kernel's tile sizes and go unread, as do `max_window_layers`,
`sliding_window` null and `use_sliding_window` false.

The reference scores EVERY causal pair of every layer, takes its OWN
top-k and attends that set and no other: each query gathers the K and
V rows its selection names. To fit 35k tokens beside a serving engine
the stream is held as blocks of TOKEN_BLOCK tokens, QUERY_BLOCK queries
are scored and ranked at a time, GATHER_BLOCK queries gather and attend
at a time, and an expert runs over the tokens that chose it (indices
found on the host, every expert's padded with gate 0 to the busiest
one's count, a multiple of ROW_PAD; one scan over the experts a block):
no capacity, nothing dropped. Every block has ONE shape (the stream is
padded with token 0 up to whole blocks, the keys are always the whole
padded length under the causal mask). Only what `positions` need is
computed: blocks that begin after the last of them are never run, and
the LAST layer runs its queries, its selection and its experts at
`positions` alone (its keys come from every token below them).

forward(params, conf, tokens, positions) -> (logits [P, vocab] float32,
margins [P, layers] float32): margins[p, l] is the gap between the k-th
and (k+1)-th largest router LOGIT of position p in layer l.
`forward_with_selection` adds, per layer, the selected positions, their
scores and the gap to the first row left out at `positions`.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import common

F32 = common.F32
TOKEN_BLOCK = 4096
QUERY_BLOCK = 128    # queries scored and ranked at a time
GATHER_BLOCK = 32    # queries that gather their rows and attend at a time
ROW_PAD = 256
INDEX_NORM_EPS = 1e-6
# (exponent bits, mantissa bits) the index keys are rounded to before
# they are scored, or None: benchmark/tools/precision_reading_kvi.py
# reads what a float8 index cache would do to this configuration's
# logits ((4, 3): float8_e4m3fn). Never set in a run that decides
# `correct`.
INDEX_KEY_BITS = None
# A planted fault, or None: the same tool reads what `correct` would
# see of it. "recent_rows": every layer takes the newest topk
# positions, not the best scored; "other_layer_keys": every layer but
# the first scores the FIRST layer's index keys; "stale_keys": the
# index keys of the first half of the positions are the ones a page
# (16 tokens) earlier; "half_rows": a selection that DROPS rows, the
# lower-scored half of every selection left out (topk / 2 taken, as a
# wrong k would); "twice_rows": one that ADDS rows, the 2 topk best
# scored taken; "no_qk_norm": q and k go unnormalised. (What 64 rows
# more or fewer do cannot be told from bf16's own swaps at the
# selection's edge: tolerances_keye.json.) Never set in a run that
# decides `correct`.
FAULT = None
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok")


def _static(conf):
    sa = conf["sa_config"]
    return tuple((k, conf[k]) for k in KEYS) + (
        ("index_heads", sa["indexer_num_heads"]),
        ("index_dim", sa["indexer_head_dim"]), ("topk", sa["topk"]))


def _rope(x, pos, theta):
    """x: [T, heads, dim] at positions pos [T]; the halves (i, i +
    dim / 2) turn by pos theta ** (-2 i / dim)."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half),
                      F32)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _head_norm(x, w, eps, off):
    return x if off else common.rms_norm(x, w, eps)


@partial(jax.jit, static_argnames=("static", "fault"))
def _kv(h, layer, pos0, static, fault=None):
    """[k | v] [T, G, 2 hd] of one block: k normed and rotated, v as
    projected, side by side, so that a query gathers a position's K
    and V in one row."""
    conf = dict(static)
    g, hd = conf["num_key_value_heads"], conf["head_dim"]
    pos = pos0 + jnp.arange(h.shape[0])
    k = (h @ layer["wk"].astype(F32)).reshape(-1, g, hd)
    k = _head_norm(k, layer["k_norm"], conf["rms_norm_eps"],
                   fault == "no_qk_norm")
    v = (h @ layer["wv"].astype(F32)).reshape(-1, g, hd)
    return jnp.concatenate([_rope(k, pos, conf["rope_theta"]), v], axis=-1)


@partial(jax.jit, static_argnames=("static", "bits"))
def _index_keys(h, wki, ln_w, ln_b, pos0, static, bits=None):
    """kI [T, Di] of one block: LayerNorm, then rotary (then rounded
    to `bits`, INDEX_KEY_BITS's reading)."""
    conf = dict(static)
    k = h @ wki.astype(F32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * jax.lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                          + INDEX_NORM_EPS)
    k = k * ln_w.astype(F32) + ln_b.astype(F32)
    pos = pos0 + jnp.arange(h.shape[0])
    k = _rope(k[:, None], pos, conf["rope_theta"])[:, 0]
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


@partial(jax.jit, static_argnames=("static", "block", "deep"))
def _select(h, ki, layer, qpos, static, block, deep=False):
    """The queries' own ranking (h [n, C] at positions qpos [n]) of
    the index keys ki [S, Di] of positions 0 .. S - 1: (positions
    [n, k2], live [n, k2], scores [n, k2]), best first, k2 =
    min(topk, S), the selection; with `deep` twice as deep, k2 =
    min(2 topk, S), of which the selection is the first min(topk, S)
    (what lies behind says how far a row another arithmetic chose lay
    from the edge)."""
    conf = dict(static)
    n, s = h.shape[0], ki.shape[0]
    hi, di = conf["index_heads"], conf["index_dim"]
    k2 = min((2 if deep else 1) * conf["topk"], s)
    qi = (h @ layer["wqi"].astype(F32)).reshape(n, hi, di)
    qi = _rope(qi, qpos, conf["rope_theta"])
    w = (h @ layer["wiw"].astype(F32)) * (hi ** -0.5 * di ** -0.5)
    kpos = jnp.arange(s)

    def one(q, wq, pos):
        dots = jnp.einsum("qhd,sd->qhs", q, ki)
        score = jnp.sum(jax.nn.relu(dots) * wq[:, :, None], axis=1)
        score = jnp.where(kpos[None, :] <= pos[:, None], score, -jnp.inf)
        top, idx = jax.lax.top_k(score, k2)
        return idx, top > -jnp.inf, top

    return _mapped(one, block, qi, w, qpos)


@partial(jax.jit, static_argnames=("static", "block", "fault"))
def _attend(h, kv_all, idx, taken, layer, qpos, static, block, fault=None):
    """Wo . attention of the queries h [n, C] at positions qpos [n],
    each over the rows `idx` [n, k'] of kv_all [S, G, 2 hd] = [k | v]
    where `taken`, and no other."""
    conf = dict(static)
    n_h, g = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["head_dim"]
    wq = layer["wq"].astype(F32)

    def one(hb, pos, ix, tk):
        q = (hb @ wq).reshape(-1, n_h, hd)
        q = _head_norm(q, layer["q_norm"], conf["rms_norm_eps"],
                       fault == "no_qk_norm")
        q = _rope(q, pos, conf["rope_theta"]).reshape(-1, g, n_h // g, hd)
        picked = kv_all[ix]                        # [b, k', G, 2 hd]
        sc = jnp.einsum("bgjd,bkgd->bgjk", q, picked[..., :hd]) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(tk[:, None, None], sc, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bgjk,bkgd->bgjd", p,
                          picked[..., hd:]).reshape(-1, n_h * hd)

    attn = _mapped(one, block, h, qpos, idx, taken)
    return attn @ layer["wo"].astype(F32)


@partial(jax.jit, static_argnames=("static",))
def _route(u, router, static):
    """(gates [T, E] with zeros off the chosen, margin [T])."""
    k = dict(static)["num_experts_per_tok"]
    z = u @ router.astype(F32)
    top_z, top_i = jax.lax.top_k(z, k)
    gates = jnp.sum(jax.nn.one_hot(top_i, z.shape[-1], dtype=F32)
                    * jax.nn.softmax(top_z, axis=-1)[..., None], axis=1)
    zs = jnp.sort(z, axis=-1)
    return gates, zs[:, -k] - zs[:, -k - 1]


@jax.jit
def _experts_over(u, idx, gate, w_gate, w_up, w_down):
    """sum_e scatter(gate_e * expert_e(u[idx_e])): idx, gate [E, rows]
    name each expert's tokens (padded with token 0 at gate 0)."""
    def one(out, xs):
        ix, g, wg, wu, wd = xs
        ub = u[ix]
        a = jax.nn.silu(ub @ wg.astype(F32)) * (ub @ wu.astype(F32))
        return out.at[ix].add((a @ wd.astype(F32)) * g[:, None]), None

    return jax.lax.scan(one, jnp.zeros_like(u),
                        (idx, gate, w_gate, w_up, w_down))[0]


def _experts(u, layer, static):
    """(the chosen experts' gated sum [T, C], margin [T])."""
    gates, margin = _route(u, layer["router"], static)
    g_host = np.asarray(gates)
    chosen = [np.nonzero(g_host[:, e])[0] for e in range(g_host.shape[1])]
    rows = max(max(len(ix) for ix in chosen), 1)
    rows = -(-rows // ROW_PAD) * ROW_PAD    # the busiest expert's, padded
    idx = np.zeros((len(chosen), rows), np.int32)
    gate = np.zeros((len(chosen), rows), np.float32)   # 0: adds nothing
    for e, ix in enumerate(chosen):
        idx[e, :len(ix)] = ix
        gate[e, :len(ix)] = g_host[ix, e]
    out = _experts_over(u, jnp.asarray(idx), jnp.asarray(gate),
                        layer["e_gate"], layer["e_up"], layer["e_down"])
    return out, margin


@partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, eps):
    return common.rms_norm(x, w, eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_ln, lm_head, eps):
    """Final norm and head of the rows x; jitted, so that the head's
    float32 form (1.2 GB at 151,936 rows) is the dot's operand and not
    an array of its own."""
    return common.rms_norm(x, final_ln, eps) @ lm_head.astype(F32)


def _run(params, conf, tokens, positions, want_selection=False):
    static = _static(conf)
    eps, topk = conf["rms_norm_eps"], conf["sa_config"]["topk"]
    positions = np.asarray(positions, np.int64)
    t = len(tokens)
    block = min(TOKEN_BLOCK, -(-t // 128) * 128)
    total = -(-t // block) * block          # the keys' one length
    starts = list(range(0, int(positions.max()) + 1, block))
    tokens = jnp.asarray(np.pad(np.asarray(tokens, np.int32),
                                (0, total - t)))
    margins, chosen = [], []
    last = len(params["layers"]) - 1
    first_keys = None
    with jax.default_matmul_precision("highest"):
        x = [common.embed(params, tokens[a:a + block]) for a in starts]
        # the queries, as groups of rows of the stream with their
        # positions: the blocks, and in the last layer `positions` alone
        qpos = [a + np.arange(block) for a in starts]
        for i, layer in enumerate(params["layers"]):
            kvs, kis = [], []
            kv_w = {k: layer[k] for k in ("wk", "wv", "k_norm")}
            for a, xb in zip(starts, x):
                h = _normed(xb, layer["ln1"], eps)
                kvs.append(_kv(h, kv_w, a, static, fault=FAULT))
                kis.append(_index_keys(h, layer["wki"], layer["ki_ln"],
                                       layer["ki_ln_b"], a, static,
                                       bits=INDEX_KEY_BITS))
            rest = total - len(starts) * block  # never computed, never seen
            kv_all = jnp.pad(jnp.concatenate(kvs),
                             ((0, rest), (0, 0), (0, 0)))
            ki_all = jnp.pad(jnp.concatenate(kis), ((0, rest), (0, 0)))
            if FAULT == "stale_keys":
                ki_all = ki_all.at[16:t // 2].set(ki_all[:t // 2 - 16])
            if FAULT == "other_layer_keys":
                ki_all = first_keys = ki_all if first_keys is None \
                    else first_keys
            del kvs, kis, h
            if i == last:
                x = [jnp.stack([x[p // block][p % block] for p in positions])]
                qpos = [positions]
            attn = {k: layer[k] for k in ("wq", "q_norm", "wo")}
            index = {k: layer[k] for k in ("wqi", "wiw")}
            for b, at in enumerate(qpos):
                h = _normed(x[b], layer["ln1"], eps)
                at_dev = jnp.asarray(at, jnp.int32)
                idx, live, top = _select(
                    h, ki_all, index, at_dev, static, QUERY_BLOCK,
                    deep=want_selection or FAULT == "twice_rows")
                k = min(topk, idx.shape[1])
                if FAULT == "recent_rows":
                    back = at_dev[:, None] - jnp.arange(idx.shape[1])[None, :]
                    idx, live = jnp.maximum(back, 0), back >= 0
                if FAULT == "half_rows":
                    k = max(k // 2, 1)
                if FAULT == "twice_rows":
                    k = idx.shape[1]
                if want_selection:
                    where = {int(p): j for j, p in enumerate(at)}
                    got = [where[int(p)] for p in positions
                           if int(p) in where]
                    chosen.append((i, k) + tuple(
                        np.asarray(v)[got] for v in (idx, live, top)))
                x[b] = x[b] + _attend(h, kv_all, idx[:, :k], live[:, :k],
                                      attn, at_dev, static, GATHER_BLOCK,
                                      fault=FAULT)
                del idx, live, top
            del kv_all, ki_all, h
            margin = []
            for b in range(len(x)):
                u = _normed(x[b], layer["ln2"], eps)
                y, m = _experts(u, layer, static)
                margin.append(np.asarray(m))
                x[b] = x[b] + y
                del u, y
            margin = np.concatenate(margin)
            margins.append(margin if i == last else margin[positions])
        logits = _head(x[0], params["final_ln"], params["lm_head"], eps)
    return logits, jnp.asarray(np.stack(margins, axis=1)), chosen


def forward(params, conf, tokens, positions):
    logits, margins, _ = _run(params, conf, tokens, positions)
    return logits, margins


def forward_with_selection(params, conf, tokens, positions):
    """`forward`'s pair and, third, {layer: (positions [P, k'], taken
    [P, k'], scores [P, k'], gap [P], ranked)}: the reference's own
    selection at `positions` (ascending). gap: the last score taken
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
