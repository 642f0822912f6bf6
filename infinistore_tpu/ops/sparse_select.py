"""A learned selection of cache rows (DeepSeek-V3.2's lightning indexer,
GLM-5.2's `index_*` keys, Keye-VL-2.0's `sa_config`): index scores
over a sequence's index keys, the EXACT top-k of them, and attention
over the rows the selection names and no other. Plain jax.numpy on
every backend: the gathers are XLA's.

    I(t, s) = sum_j w(t, j) relu(qI_j(t) . kI(s))        s <= t
    S(t)    = the min(t + 1, k) positions of largest I(t, .)
    a latent cache (one row a token):
    o(t, h) = sum_{s in S(t)} softmax_s(q_h(t) . row_s) row_s[:rank]
    a K and V cache (grouped queries, g(h) the head's kv head):
    o(t, h) = sum_{s in S(t)} softmax_s(q_h(t) . k_g(h)(s)) v_g(h)(s)

The scores' products take their inputs as they are handed over (the
cached keys in the pool's type) and accumulate in float32; relu, the
weights and the sum over the index heads are float32. `select` is
`jax.lax.top_k` (ties go to the lower position) and nothing that may
return another set. The latent attention is the absorbed form of
ops/pallas_latent_attention.py (`latent_decode_xla`'s arithmetic) over
gathered rows: what it reads follows the selection, not the context.

Two callers (models/decoder.py): a decode step, one query a DECODING
sequence over the paged pools through the page table (`*_paged` under
`over_active`: the slots that hold no sequence are not scored, sorted
or gathered for), and an admission, a block of queries at a time over
the contiguous rows of prefix + suffix (`*_seq`). An admission over K
and V rows does not gather: a block's selection becomes a MASK over
the contiguous rows (`taken_mask`) and the block attends all of them
under it (`select_attend_seq`): two matmuls over every row are cheaper
there than two gathers of the selected ones (PERF.md, PR 49).
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = -1e30
# Queries an admission scores, selects and attends at a time: the
# float32 scores of a block are [block, index heads, keys] (64 x 32 x
# 35,072 x 4 B = 287 MB) and its gathered rows [block, k, width]
# (64 x 2,048 x 640 x 2 B = 168 MB).
QUERY_BLOCK = 64


def _precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def _scores(q, w, keys, eq):
    """sum_j w_j relu(q_j . k), float32. `eq`: the products' einsum."""
    dots = jnp.einsum(eq, q, keys.astype(q.dtype),
                      preferred_element_type=F32,
                      precision=_precision(q.dtype))
    return jnp.sum(jax.nn.relu(dots) * w.astype(F32)[..., None], axis=-2)


def select(scores, n_live, k, with_scores=False):
    """The exact top-min(n_live, k) of each row of `scores` [n, S]
    over its first `n_live` [n] positions: (positions [n, k'] int32,
    taken [n, k'] bool), k' = min(k, S); positions not taken are
    arbitrary (in range). `with_scores`: a third, the scores at those
    positions [n, k'] (-inf where not taken)."""
    s = scores.shape[-1]
    k = min(k, s)
    live = jnp.arange(s)[None] < n_live[:, None]
    top, idx = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), k)
    taken = jnp.arange(k)[None] < jnp.minimum(n_live, k)[:, None]
    out = (idx.astype(jnp.int32), taken)
    return out + (top,) if with_scores else out


def taken_mask(scores, n_live, sel):
    """`select`'s set as a mask [n, S] over the positions, from its
    triple `sel` (positions, taken, their scores) of `scores` [n, S]
    over their first `n_live` [n] positions, and no scatter: the
    positions scored above the last one taken, and of those that tie
    with it the ones up to the highest position
    taken among them (ties go to the lower position, so what `select`
    took of a tie is its lowest positions). The set is `select`'s own,
    ties included (tests/test_keye.py plants them)."""
    idx, taken, top = sel
    edge = jnp.min(jnp.where(taken, top, jnp.inf), axis=-1, keepdims=True)
    last = jnp.max(jnp.where(taken & (top == edge), idx, -1), axis=-1,
                   keepdims=True)
    pos = jnp.arange(scores.shape[-1])[None]
    return (pos < n_live[:, None]) & (
        (scores > edge) | ((scores == edge) & (pos <= last)))


def attend(q, rows, taken, rank):
    """Absorbed latent attention of each query over ITS gathered rows.
    q: [n, H, width] (scaled); rows: [n, k, width]; taken: [n, k].
    Returns o_lat [n, H, rank] in q's type."""
    precision = _precision(q.dtype)
    logits = jnp.einsum("nhw,nkw->nhk", q, rows,
                        preferred_element_type=F32, precision=precision)
    logits = jnp.where(taken[:, None], logits, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("nhk,nkr->nhr", p.astype(q.dtype), rows[..., :rank],
                     preferred_element_type=F32, precision=precision)
    return out.astype(q.dtype)


def _attend_by_group(q, k_rows, v_rows, keep, scale, rows):
    """Grouped-query attention, query head h over kv head h // (H //
    G): q [n, H, hd]; k_rows, v_rows with axes `rows` ("nkgd": each
    query's own gathered rows; "sgd": one sequence's rows, shared);
    keep [n, rows] which of them a query attends (at least one);
    `scale` multiplies the float32 logits. [n, H, hd] in q's type."""
    n, h, hd = q.shape
    g = k_rows.shape[-2]
    precision = _precision(q.dtype)
    logits = jnp.einsum(f"nghd,{rows}->ngh{rows[-3]}",
                        q.reshape(n, g, h // g, hd), k_rows.astype(q.dtype),
                        preferred_element_type=F32, precision=precision)
    logits = jnp.where(keep[:, None, None], logits * scale, NEG)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(f"ngh{rows[-3]},{rows}->nghd", p.astype(q.dtype),
                     v_rows.astype(q.dtype),
                     preferred_element_type=F32, precision=precision)
    return out.reshape(n, h, hd).astype(q.dtype)


def attend_grouped(q, k_rows, v_rows, taken, scale):
    """Each query over ITS gathered K and V rows [n, k, G, hd] where
    `taken` [n, k] (a decode step's form)."""
    return _attend_by_group(q, k_rows, v_rows, taken, scale, "nkgd")


def attend_masked(q, k_rows, v_rows, mask, scale):
    """A block of queries over ALL the contiguous rows [S, G, hd] of
    one sequence under `mask` [n, S] (an admission's form)."""
    return _attend_by_group(q, k_rows, v_rows, mask, scale, "sgd")


# ---- a decode step: one query a sequence, over the paged pools ---------


def ladder(b):
    """The batch sizes the selection of a decode step of `b` slots runs
    at: 1, 2, 4, ... below b, and b."""
    return [1 << i for i in range((b - 1).bit_length())] + [b]


def active_first(valid):
    """For `over_active`, from a decode step's `valid` [b] (the slots
    that hold a sequence): (the slots in a stable order with the valid
    ones first [b] int32, the rung of `ladder(b)` that is the least
    size to hold them all: a traced index)."""
    order = jnp.argsort(~valid, stable=True).astype(jnp.int32)
    count = jnp.sum(valid)
    return order, sum((count > n).astype(jnp.int32)
                      for n in ladder(valid.shape[0])[:-1])


def slots_run(active):
    """The slots `over_active` runs over under `active`: the size of
    its rung (traced int32)."""
    order, rung = active
    return jnp.asarray(ladder(order.shape[0]), jnp.int32)[rung]


def over_active(fn, active, *arrays):
    """`fn(*arrays)` of a decode step, run over the decoding slots
    alone. `arrays` and `fn`'s results are arrays with a leading slot
    axis [b]; `active` is `active_first`'s pair. ONE program: a branch a
    rung n of `ladder(b)`, each `fn` as it is over the first n slots of
    the order (the full batch: over `arrays` as they come), its results
    put back in slot order. A slot that was not run reads zeros (a
    selection: nothing taken), and what follows ignores it as it
    ignored what an empty slot computed. Called OUTSIDE any named scope:
    the conditional itself belongs to no stage, `fn`'s operations keep
    theirs."""
    order, rung = active
    b = order.shape[0]

    def at(n):
        def run(order, *xs):
            with jax.named_scope("attn.active"):
                rows = order[:n]
                xs = [x[rows] for x in xs]
            out = fn(*xs)
            with jax.named_scope("attn.active"):
                return jax.tree_util.tree_map(
                    lambda a: jnp.zeros((b, *a.shape[1:]), a.dtype)
                    .at[rows].set(a), out)

        return run if n < b else lambda order, *xs: fn(*xs)

    return jax.lax.switch(rung, [at(n) for n in ladder(b)], order, *arrays)


def select_paged(q, w, page_table, n_live, ipool, layer, k):
    """One query a sequence over the index keys its page table names,
    every entry of the table scored and what is not live masked.
    q: [b, Hi, Di]; w: [b, Hi]; ipool: [index layers, pages, page, Di];
    n_live: [b] keys to score (the new token's included). Returns
    `select`'s pair, positions counted in the sequence."""
    with jax.named_scope("attn.index"):
        keys = ipool.at[(layer, page_table)].get(mode="clip")
        b, n, page, di = keys.shape
        scores = _scores(q, w, keys.reshape(b, n * page, di),
                         "bhd,bsd->bhs")
    with jax.named_scope("attn.topk"):
        return select(scores, n_live, k)


def gather_paged(pool, layer, page_table, idx):
    """Rows `idx` [b, k] (positions in the sequence) of layer `layer`
    of the paged pool [layers, pages, page, *row]: [b, k, *row] (a
    latent row [width]; a K or V row [kv heads, hd]). The pool is
    addressed where it lies: no layer is sliced out."""
    page = pool.shape[2]
    with jax.named_scope("attn.gather"):
        pages = jnp.take_along_axis(page_table, idx // page, axis=1)
        return pool.at[(jnp.full_like(pages, layer), pages,
                        idx % page)].get(mode="clip")


# ---- an admission: blocks of queries over contiguous rows --------------


def _blocked(fn, n, *arrays):
    """`fn` over blocks of QUERY_BLOCK of the leading `n` entries of
    `arrays` (padded up with copies of entry 0), its results' leading
    axes joined and cut back to n."""
    block = min(QUERY_BLOCK, n)
    pad = -n % block

    def cut(a):
        a = jnp.concatenate([a, jnp.broadcast_to(a[:1], (pad, *a.shape[1:]))])
        return a.reshape(-1, block, *a.shape[1:])

    out = jax.lax.map(lambda xs: fn(*xs), tuple(cut(a) for a in arrays))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(-1, *a.shape[2:])[:n], out)


def select_seq(q, w, keys, positions, k):
    """The queries of one sequence at `positions` [s] (position t sees
    keys 0 .. t) over its index keys [S, Di]. q: [s, Hi, Di]; w:
    [s, Hi]. Returns `select`'s pair [s, k']."""
    def one(qb, wb, pos):
        with jax.named_scope("attn.index"):
            scores = _scores(qb, wb, keys, "qhd,sd->qhs")
        with jax.named_scope("attn.topk"):
            return select(scores, pos + 1, k)

    return _blocked(one, q.shape[0], q, w, positions)


def attend_seq(absorb, q_parts, rows, idx, taken, rank):
    """Each query over its selected rows of `rows` [S, width]. `absorb`
    turns a block of `q_parts` (arrays with a leading query axis) into
    the absorbed, scaled queries [block, H, width]. Returns o_lat
    [s, H, rank]."""
    def one(idx_b, taken_b, *parts):
        q = absorb(*parts)
        with jax.named_scope("attn.gather"):
            picked = jnp.take(rows, idx_b, axis=0)
        with jax.named_scope("attn.kernel"):
            return attend(q, picked, taken_b, rank)

    return _blocked(one, idx.shape[0], idx, taken, *q_parts)


def select_attend_seq(q, w, keys, positions, qa, k_rows, v_rows, k, scale):
    """`select_seq` and, in the same block of queries, grouped-query
    attention over the contiguous K and V rows [S, G, hd] of one
    sequence under the selection's mask: the scores a block ranks are
    the scores its mask is read from. qa: [s, H, hd] the attention's
    queries, `scale` their logits' (`attend_masked`). Returns
    (`select`'s pair [s, k'], out [s, H, hd])."""
    def one(qb, wb, pos, qab):
        with jax.named_scope("attn.index"):
            scores = _scores(qb, wb, keys, "qhd,sd->qhs")
        with jax.named_scope("attn.topk"):
            sel = select(scores, pos + 1, k, with_scores=True)
        with jax.named_scope("attn.mask"):
            mask = taken_mask(scores, pos + 1, sel)
        with jax.named_scope("attn.kernel"):
            return sel[:2], attend_masked(qab, k_rows, v_rows, mask, scale)

    return _blocked(one, q.shape[0], q, w, positions, qa)
