"""EvaByte-style byte-level decoder: attention whose cache rows are not
positions. The eleventh family on the one decoder stack
(models/decoder.py), a dense one: llama's block, 32 / 32 heads, a
vocabulary of 320 bytes and an output of `n_pred_heads` heads of 320
logits each, the next byte's first.

What is its own is EVA ("Efficient attention via control variates",
arXiv:2302.04542, as the EvaByte release fixes it): a query at
position t attends, in ONE softmax,

- every position j <= t of ITS OWN window of `fold_window` positions
  exactly (the window t lies in, `t // fold_window`: a block-aligned
  band, not the last w positions), and
- every chunk of `fold_chunk` positions of every EARLIER window as ONE
  row: the chunk's pooled key and a value weighted inside the chunk
  (`fold`, below). A window's own chunks are never visible to it.

So a window that has ended is never read position by position again,
and the cache keeps its summary in its place: `fold` makes, from the
window's K and V rows, one k~ row and one v~ row a chunk, which lie in
the same pools under the same page shape (a page of `page_size` rows =
`page_size` chunks). A sequence's cache rows are then, in order, the
summary rows of its finished windows and the exact rows of the window
it is in, and `cache_rows` says how many that is at a position: a pure
function of the position, which is how one `seq_lens` still serves the
decode step (decoder.decode_step rotates a new row at its POSITION and
writes it at its ROW) and `pos0` the prefix program (a piece lies
inside one window, so every prefix row is visible to every suffix row
and the suffix's first position is `pos0` + the prefix's rows). When a
window folds is the engine's (serving.py `_fold_due`): behind the
program that wrote the window's last row.

Assumed, not in the published config (the configuration file's
`assumed` says so too): a head's two learned vectors `fold_phi` and
`fold_mu`, where they enter (`fold`), that summaries are of ROTATED
keys, and the head as one [d_model, n_pred_heads x vocab] matrix with
the next byte first. A bridge that ever loads published weights must
check them against the published modeling code.

Not built: multi-byte decoding (the further heads as a drafter); the
serving programs keep the next byte's 320 logits and emit from them.
"""

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from . import decoder
from .llama import LlamaConfig, _mlp
from .llama import init_params as _llama_init


@dataclass(frozen=True)
class EvaByteConfig(LlamaConfig):
    """LlamaConfig plus the fold: `fold_window` positions attended
    exactly, each earlier window as `fold_window // fold_chunk` summary
    rows. `vocab_size` is the byte vocabulary (what ids are drawn
    under and what the serving programs' logits span); the head is
    `n_pred_heads` times as wide. `phi_gain` / `mu_gain`: how a
    configuration WITHOUT a checkpoint draws the summariser's vectors
    (`init_params`)."""

    fold_window: int = 2048
    fold_chunk: int = 16
    n_pred_heads: int = 8
    norm_plus_one: bool = True
    # the residual stream in float32 (`fp32_skip_add`): the embedding
    # is widened once and every sublayer's output is added in float32
    fp32_stream: bool = True
    phi_gain: float = 1.0
    mu_gain: float = 1.0

    def __post_init__(self):
        if self.fold_chunk != self.page_size:
            raise ValueError(
                f"fold_chunk {self.fold_chunk} is not the page "
                f"({self.page_size}): a summary page must stand for whole "
                "pages of positions")
        if self.fold_window % (self.fold_chunk * self.page_size):
            raise ValueError(
                f"fold_window {self.fold_window} is no multiple of "
                f"{self.fold_chunk * self.page_size} (whole summary pages)")

    @property
    def head_width(self):
        return self.n_pred_heads * self.vocab_size


def fold_rows(cfg):
    """Rows a finished window leaves: one a chunk."""
    return cfg.fold_window // cfg.fold_chunk


cache_rows = decoder.cache_rows  # rows held below a position = its row


def init_params(rng, cfg: EvaByteConfig):
    """llama's dense parameters with the head `n_pred_heads` wide and,
    a layer, the summariser's two vectors a head: normal, clipped to
    [-1, 1], times head_dim ** -0.5 and the configuration's gain. Norm
    weights are stored zero-centred (applied as 1 + g) and drawn at
    0.1, so the unit offset is a parameter a test can see."""
    k_base, k_head, k_fold, k_norm = jax.random.split(rng, 4)
    params = _llama_init(k_base, cfg)
    dt = cfg.jdtype
    params["lm_head"] = (
        jax.random.normal(k_head, (cfg.d_model, cfg.head_width))
        * cfg.d_model ** -0.5).astype(dt)
    shape = (cfg.n_kv_heads, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    kn = jax.random.split(k_norm, 2 * cfg.n_layers + 1)

    def g(k):
        return (0.1 * jax.random.normal(k, (cfg.d_model,))).astype(dt)

    for li, layer in enumerate(params["layers"]):
        kp, km = jax.random.split(jax.random.fold_in(k_fold, li))
        layer["fold_phi"] = (jnp.clip(jax.random.normal(kp, shape), -1, 1)
                             * scale * cfg.phi_gain).astype(dt)
        layer["fold_mu"] = (jnp.clip(jax.random.normal(km, shape), -1, 1)
                            * scale * cfg.mu_gain).astype(dt)
        layer["ln1"], layer["ln2"] = g(kn[2 * li]), g(kn[2 * li + 1])
    params["final_ln"] = g(kn[-1])
    return params


def fold(cfg, layer, k, v):
    """The summary rows of whole chunks: k, v [..., chunks * c, n_kv,
    hd] (rotated keys) -> (k~, v~) [..., chunks, n_kv, hd]. Head by
    head over a chunk's c positions, in float32: a_j = softmax_j((k_j .
    phi) / sqrt(hd)), v~ = sum_j a_j v_j, k~ = mean_j k_j + mu."""
    c = cfg.fold_chunk
    *lead, n, h, hd = k.shape
    kf = k.astype(jnp.float32).reshape(*lead, n // c, c, h, hd)
    vf = v.astype(jnp.float32).reshape(*lead, n // c, c, h, hd)
    phi = layer["fold_phi"].astype(jnp.float32)
    mu = layer["fold_mu"].astype(jnp.float32)
    a = jax.nn.softmax(jnp.sum(kf * phi, axis=-1) * hd ** -0.5, axis=-2)
    v_sum = jnp.sum(a[..., None] * vf, axis=-3)
    k_sum = jnp.mean(kf, axis=-3) + mu
    return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def fold_pages(params, cfg, k_pages, v_pages, ids):
    """One finished window of one sequence, folded where it lies:
    `ids` [fold_window // page] are the window's pool pages in
    sequence order; every layer's summary rows are written into the
    first `fold_rows // page` of them (the caller frees the others).
    Traced inside the engine's fold program; pools
    [layers, pages, page, n_kv, hd]."""
    n_out = fold_rows(cfg) // cfg.page_size
    with jax.named_scope("attn.fold"):
        for li, layer in enumerate(params["layers"]):
            k = k_pages[li, ids].reshape(-1, *k_pages.shape[3:])
            v = v_pages[li, ids].reshape(-1, *v_pages.shape[3:])
            ks, vs = fold(cfg, layer, k, v)
            shape = (n_out, cfg.page_size, *k_pages.shape[3:])
            k_pages = k_pages.at[li, ids[:n_out]].set(ks.reshape(shape))
            v_pages = v_pages.at[li, ids[:n_out]].set(vs.reshape(shape))
    return k_pages, v_pages


_forward_stack, _decode_step, verify_step = decoder.bind(_mlp)


def _next_byte(cfg, logits):
    """The first head's logits of a row of all `n_pred_heads`."""
    return logits[..., :cfg.vocab_size]


def forward_dense(params, cfg: EvaByteConfig, tokens):
    """Every position's logits of ALL heads [batch, seq, n_pred_heads *
    vocab] and the K and V. Inside one window EVA is plain causal
    attention, one pass of the stack; a longer sequence goes window by
    window (`forward_folded`, which returns no K and V)."""
    if tokens.shape[1] > cfg.fold_window:
        return forward_folded(params, cfg, tokens)
    logits, kvs, _ = _forward_stack(params, cfg, tokens)
    return logits, kvs


def forward_folded(params, cfg: EvaByteConfig, tokens):
    """The stack over a sequence of any length, window by window as an
    admission in pieces runs it: each window's tokens over the summary
    rows of the windows before it (`fold` of that window's K and V).
    Returns (all heads' logits [batch, seq, head_width], None)."""
    w = cfg.fold_window
    prefix, out = None, []
    for a in range(0, tokens.shape[1], w):
        piece = tokens[:, a:a + w]
        pos0 = 0 if prefix is None else a - prefix[0][0].shape[1]
        logits, kvs, _ = _forward_stack(params, cfg, piece, prefix,
                                        pos0=pos0)
        out.append(logits)
        if piece.shape[1] < w:
            break
        sums = [fold(cfg, layer, k, v)
                for layer, (k, v) in zip(params["layers"], kvs)]
        prefix = sums if prefix is None else [
            (jnp.concatenate([pk, k], axis=1),
             jnp.concatenate([pv, v], axis=1))
            for (pk, pv), (k, v) in zip(prefix, sums)]
    return jnp.concatenate(out, axis=1), None


def prefill(params, cfg: EvaByteConfig, tokens, keep=None):
    """A prompt that lies inside window 0 (the engine cuts pieces at
    window edges): the next byte's logits and the K and V to page
    out."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, keep=keep)
    return _next_byte(cfg, logits), kvs


def prefill_with_prefix(params, cfg: EvaByteConfig, tokens, prefix_kvs,
                        pos0=0, keep=None):
    """A piece inside ONE window over the sequence's cache rows so far
    (summary rows of the finished windows, then the window's exact
    rows): every prefix row is visible to every suffix row, which is
    the prefix program's attention as it is. `pos0`: the suffix's
    first position less the prefix's rows."""
    logits, kvs, _ = _forward_stack(params, cfg, tokens, prefix_kvs,
                                    pos0=pos0, keep=keep)
    return _next_byte(cfg, logits), kvs


def decode_step(params, cfg: EvaByteConfig, token, seq_lens, k_pages,
                v_pages, page_table, fetched=False):
    """decoder.decode_step over a table of ROWS: `seq_lens` are
    positions, and the stack places and attends by `cache_rows` of
    them. Returns the next byte's logits [batch, vocab]."""
    logits, *rest = _decode_step(params, cfg, token, seq_lens, k_pages,
                                 v_pages, page_table, fetched=fetched)
    return (_next_byte(cfg, logits), *rest)
