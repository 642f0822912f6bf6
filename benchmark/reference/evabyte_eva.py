"""Plain float32 reference of the EvaByte byte-level decoder: EVA
attention ("Efficient attention via control variates",
arXiv:2302.04542, as the EvaByte release fixes it) over the WHOLE
sequence. jax.numpy only: no kernels, no cache, no pages, no fold, no
pieces; nothing of the program is imported. Hyper-parameters are read
from the configuration FILE (the published keys).

Layer l, x the residual stream (float32), N(.) an RMSNorm with weight
(1 + g); t a byte's position, w(t) = t // window_size its window,
c = t // chunk_size its chunk; n = window_size // chunk_size chunks a
window:

    h = N1(x);  q, k, v = Wq h, Wk h, Wv h   (heads of hd, no bias)
    q, k rotated at t (rope_theta, by halves, no scaling)
    summary of chunk c (positions j = 16 c .. 16 c + 15, rotated keys),
    head by head, phi, mu in R^hd a head a layer:
        a_j = softmax_j((k_j . phi) / sqrt(hd))
        v~_c = sum_j a_j v_j;   k~_c = (1 / 16) sum_j k_j + mu
    query t attends, in ONE softmax, the exact set
        E(t) = {j : w(j) = w(t), j <= t}
    and the summary set
        S(t) = {c : c < n w(t)}     (every chunk of every EARLIER window)
        s_j = q_t . k_j / sqrt(hd),  s_c = q_t . k~_c / sqrt(hd)
        o_t = sum_E p_j v_j + sum_S p_c v~_c;   x += Wo concat_heads(o_t)
    x += Wdown(silu(Wgate N2(x)) * Wup N2(x))
    logits = Whead N(x): num_pred_heads x vocab_size wide; the next
    byte's are the first vocab_size.

Every chunk's summary is computed from the sequence's own K and V, the
two sets are masks: a block of QUERY_BLOCK queries (which lies inside
one window) scores its window's positions and every chunk's summary
and masks what it may not see. To fit 30k bytes beside a serving
engine, x, K and V are held whole (0.5 GB each at 30k) and everything
else runs a block of tokens at a time.

Assumed (the configuration file's `assumed` has it): phi, mu and the
forms of a_j and k~_c above, summaries of ROTATED keys, the head as
one [hidden, num_pred_heads x vocab] matrix with the next byte first.

forward(params, conf, tokens, positions, fault=None) -> (logits
[P, vocab_size] float32: the next byte's head, margins None).
`all_heads=True` keeps every head's logits [P, heads x vocab].
`fault`: one of FAULTS planted in the mathematics, for the readings a
tolerance is set between (benchmark/tools/precision_reading_eva.py):
what a program with that fault would compute, in exact arithmetic.
"""

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512

KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "rms_norm_eps", "rope_theta", "window_size", "chunk_size",
        "vocab_size")

FAULTS = (
    "no_summaries",      # S(t) empty: summaries never visible
    "summaries_early",   # every finished chunk visible, its own window's too
    "fold_120",          # the last 8 chunks of every window never folded
    "no_phi",            # a_j uniform: a plain mean of v
    "no_mu",             # k~ without mu
    "rotated_at_row",    # the last window's q and k rotated at their cache
    #                      ROW (t less the rows its earlier windows lost)
)


def _static(conf, fault):
    return tuple((k, conf[k]) for k in KEYS) + (("fault", fault),)


def norm(x, g, eps):
    """RMSNorm with the unit offset: weight (1 + g)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(F32))


def rope(x, pos, theta):
    """x [T, heads, hd] rotated at `pos` [T], by halves."""
    half = x.shape[-1] // 2
    inv = jnp.exp(-jnp.log(F32(theta)) * jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _rot_pos(pos, conf, t_all):
    """Where position `pos` is rotated: itself."""
    if conf["fault"] != "rotated_at_row":
        return pos
    w, n = conf["window_size"], conf["window_size"] // conf["chunk_size"]
    last = (t_all - 1) // w
    return jnp.where(pos // w == last, pos - (w - n) * (pos // w), pos)


def _project(x, layer, t0, conf, t_all, names):
    """The projections `names` ("wq", "wk", "wv") [B, heads, hd] of a
    block of tokens at positions t0.., q and k rotated."""
    b = x.shape[0]
    hd = conf["hidden_size"] // conf["num_attention_heads"]
    h = norm(x, layer["ln1"], conf["rms_norm_eps"])
    pos = _rot_pos(t0 + jnp.arange(b), conf, t_all)
    out = []
    for name in names:
        y = (h @ layer[name].astype(F32)).reshape(b, -1, hd)
        out.append(y if name == "wv" else rope(y, pos, conf["rope_theta"]))
    return out


@partial(jax.jit, static_argnames=("static", "t_all"))
def _kv(x, layer, t0, static, t_all):
    return _project(x, layer, t0, dict(static), t_all, ("wk", "wv"))


@partial(jax.jit, static_argnames=("static",))
def summaries(k, v, phi, mu, static):
    """(k~, v~) [T / chunk, heads, hd] of every chunk of k, v
    [T, heads, hd]."""
    conf = dict(static)
    c = conf["chunk_size"]
    t, n_kv, hd = k.shape
    kc = k.reshape(t // c, c, n_kv, hd)
    vc = v.reshape(t // c, c, n_kv, hd)
    phi, mu = phi.astype(F32), mu.astype(F32)
    if conf["fault"] == "no_phi":
        phi = jnp.zeros_like(phi)
    if conf["fault"] == "no_mu":
        mu = jnp.zeros_like(mu)
    a = jax.nn.softmax(jnp.sum(kc * phi, axis=-1) * hd ** -0.5, axis=-2)
    return jnp.mean(kc, axis=-3) + mu, jnp.sum(a[..., None] * vc, axis=-3)


@partial(jax.jit, static_argnames=("static", "t_all"))
def _block(x, k_all, v_all, k_sum, v_sum, layer, t0, static, t_all):
    """One block of queries at positions t0.. (inside one window):
    attention over its window's positions and the summaries, the
    output projection, the MLP. Returns the block's new stream."""
    conf = dict(static)
    q, = _project(x, layer, t0, conf, t_all, ("wq",))
    b, n_h, hd = q.shape
    w, c = conf["window_size"], conf["chunk_size"]
    n = w // c
    group = n_h // conf["num_key_value_heads"]
    t = t0 + jnp.arange(b)
    win = t0 // w
    k_win = jax.lax.dynamic_slice_in_dim(k_all, win * w, w, axis=0)
    v_win = jax.lax.dynamic_slice_in_dim(v_all, win * w, w, axis=0)
    j = win * w + jnp.arange(w)
    exact = j[None, :] <= t[:, None]                       # [B, W]
    chunk = jnp.arange(k_sum.shape[0])
    seen = chunk[None, :] < n * (t // w)[:, None]          # [B, chunks]
    if conf["fault"] == "no_summaries":
        seen = jnp.zeros_like(seen)
    elif conf["fault"] == "summaries_early":
        seen = (chunk[None, :] * c + c - 1) <= t[:, None]
    elif conf["fault"] == "fold_120":
        seen = seen & (chunk[None, :] % n < n - 8)
    rep = lambda a: jnp.repeat(a, group, axis=1)  # noqa: E731
    s_e = jnp.einsum("bhd,jhd->hbj", q, rep(k_win)) * hd ** -0.5
    s_s = jnp.einsum("bhd,chd->hbc", q, rep(k_sum)) * hd ** -0.5
    s = jnp.concatenate([jnp.where(exact[None], s_e, -jnp.inf),
                         jnp.where(seen[None], s_s, -jnp.inf)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hbj,jhd->bhd", p[..., :w], rep(v_win)) \
        + jnp.einsum("hbc,chd->bhd", p[..., w:], rep(v_sum))
    x = x + o.reshape(b, n_h * hd) @ layer["wo"].astype(F32)
    h = norm(x, layer["ln2"], conf["rms_norm_eps"])
    gate = jax.nn.silu(h @ layer["w_gate"].astype(F32))
    up = h @ layer["w_up"].astype(F32)
    return x + (gate * up) @ layer["w_down"].astype(F32)


def forward(params, conf, tokens, positions, fault=None, all_heads=False):
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    static = _static(conf, fault)
    w, c = conf["window_size"], conf["chunk_size"]
    qb = min(QUERY_BLOCK, w)
    assert w % qb == 0 and w % c == 0, (w, qb, c)
    tokens = jnp.asarray(tokens, jnp.int32)
    t_real = tokens.shape[0]
    # whole windows: a block's window is a slice of K and V
    t_all = -(-t_real // w) * w
    tokens = jnp.pad(tokens, (0, t_all - t_real))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        for layer in params["layers"]:
            kvs = [_kv(x[a:a + qb], layer, jnp.int32(a), static, t_real)
                   for a in range(0, t_all, qb)]
            k_all = jnp.concatenate([k for k, _ in kvs])
            v_all = jnp.concatenate([v for _, v in kvs])
            del kvs
            k_sum, v_sum = summaries(k_all, v_all, layer["fold_phi"],
                                     layer["fold_mu"], static)
            x = jnp.concatenate([
                _block(x[a:a + qb], k_all, v_all, k_sum, v_sum, layer,
                       jnp.int32(a), static, t_real)
                for a in range(0, t_all, qb)])
            del k_all, v_all
        xs = norm(x[jnp.asarray(positions, jnp.int32)], params["final_ln"],
                  conf["rms_norm_eps"])
        logits = xs @ params["lm_head"].astype(F32)
    if not all_heads:
        logits = logits[:, :conf["vocab_size"]]
    return logits, None
