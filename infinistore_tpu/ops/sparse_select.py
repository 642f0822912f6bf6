"""A learned selection of cache rows (DeepSeek-V3.2's lightning indexer,
GLM-5.2's `index_*` keys, Keye-VL-2.0's `sa_config`): index scores
over a sequence's index keys, the EXACT top-k of them, and attention
over the rows the selection names and no other. Plain jax.numpy on
every backend (the gathers are XLA's) but for the bisection and an
admission's attention under a mask, each of which on a TPU is one
Pallas call.

    I(t, s) = sum_j w(t, j) relu(qI_j(t) . kI(s))        s <= t
    S(t)    = the min(t + 1, k) positions of largest I(t, .)
    a latent cache (one row a token):
    o(t, h) = sum_{s in S(t)} softmax_s(q_h(t) . row_s) row_s[:rank]
    a K and V cache (grouped queries, g(h) the head's kv head):
    o(t, h) = sum_{s in S(t)} softmax_s(q_h(t) . k_g(h)(s)) v_g(h)(s)

The scores' products take their inputs as they are handed over (the
cached keys in the pool's type) and accumulate in float32; relu, the
weights and the sum over the index heads are float32. S(t) is the set
`jax.lax.top_k` would take (ties go to the lower position) and nothing
that may return another, but no sort finds it: nobody reads an order.
`threshold` maps a row's scores to integer keys and builds the key of
its k-th largest from the top bit down, each pass one compare-and-count
over the row (on a TPU all 32 in one kernel, the row held in VMEM);
`taken_from` is the mask above that key, with the first
of its equals by position (`taken_mask`: both); `positions_of` turns a
mask into positions in ASCENDING order where a gather reads them
(`select`: all three), by running counts and a one-hot product, no
scatter. The latent attention is the absorbed form of
ops/pallas_latent_attention.py (`latent_decode_xla`'s arithmetic) over
gathered rows: what it reads follows the selection, not the context.

Two callers (models/decoder.py): a decode step, one query a DECODING
sequence over the paged pools through the page table (`*_paged` under
`over_active`: the slots that hold no sequence are not scored, selected
or gathered for), and an admission, a block of queries at a time over
the contiguous rows of prefix + suffix (`*_seq`). An admission over K
and V rows does not gather and makes no positions: a block's mask over
the contiguous rows is what it attends under (`select_attend_seq`). On
a TPU that attention is ONE Pallas call a block, the flash kernel with
the mask an operand (ops/pallas_masked_attention.py, through
`block_attention`): a block's logits never leave the chip and the key
tiles past the block's last position are not run, so a block by two
products over every live row (17 x the selection's FLOPs at 35k rows,
at half the peak) costs a quarter of a block by two gathers of the
selected ones (PERF.md, PRs 49 and 54). Elsewhere `attend_masked`, the
same sums in XLA, which is also what the kernel is tested against.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_masked_attention import by_head, masked_flash_attention

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


# Bits of the threshold one pass of the XLA bisection settles (a divisor
# of 32): a pass counts the keys at or above 2 ** _PASS_BITS - 1
# candidates in ONE read of the row. The chip runs the kernel, whose
# passes are one bit; the loop's width by tools/time_select_decode.py
# and tools/time_admit_select.py `--bits` (PERF.md, PR 50).
_PASS_BITS = 1
# Positions a chunk: a running count is two levels, within a chunk (a
# product with a triangle of ones: counts of at most _CHUNK are exact
# in bfloat16) and over the chunks before it.
_CHUNK = 128
_LEAST = jnp.iinfo(jnp.int32).min


def _keys(scores, live):
    """Each float32 score as an int32 key in the scores' order: its
    magnitude's bits, negated under the sign bit, so both zeros are ONE
    key (equal keys are equal scores) and a denormal keeps its place;
    what is not live, the least key, which no score has."""
    bits = jax.lax.bitcast_convert_type(scores.astype(F32), jnp.int32)
    return jnp.where(live, jnp.where(bits < 0, _LEAST - bits, bits), _LEAST)


def _kth_key_loop(keys, k_row):
    """`_kth_key` in XLA: one loop of 32 / _PASS_BITS compare-and-counts
    over the row, a pass 2 ** _PASS_BITS - 1 candidates."""
    steps = jnp.arange(1, 1 << _PASS_BITS, dtype=jnp.int32)

    def one(i, t):
        # int32 arithmetic wraps: the least key stands for 0
        low = 32 - _PASS_BITS * (i + 1)
        cand = t[:, None] + (steps << low)[None]
        reached = jnp.sum(keys[:, None, :] >= cand[:, :, None], axis=-1,
                          dtype=jnp.int32) >= k_row[:, None]
        return t + (jnp.sum(reached, axis=-1, dtype=jnp.int32) << low)

    return jax.lax.fori_loop(0, 32 // _PASS_BITS, one,
                             jnp.full(keys.shape[:1], _LEAST))


def _kth_key_body(keys_ref, k_ref, edge_ref):
    """A few rows' keys [r, C, 128] held in VMEM through all 32 one-bit
    passes; k and the result a row of lanes [r, 1, 128] each."""
    keys = keys_ref[...]
    k = k_ref[...].astype(F32)

    def one(i, t):
        cand = t + (1 << (31 - i))          # wraps: the least key is 0
        at_or_above = jnp.where(keys >= cand, 1.0, 0.0)
        count = jnp.sum(jnp.sum(at_or_above, axis=1, keepdims=True), axis=2,
                        keepdims=True)
        return jnp.where(count >= k, cand, t)

    edge_ref[...] = jax.lax.fori_loop(0, 32, one,
                                      jnp.full(k.shape, _LEAST))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kth_key_kernel(keys, k_row, interpret=False):
    """`_kth_key` as ONE Pallas call: a row's keys (140 KB at 35,072)
    stay in VMEM through the passes."""
    n, s = keys.shape
    c = -(-s // (8 * _CHUNK)) * 8
    rows = max(r for r in range(1, 9) if n % r == 0)

    def spec(width):
        return pl.BlockSpec((rows, width, _CHUNK), lambda i: (i, 0, 0))

    edge = pl.pallas_call(
        _kth_key_body, grid=(n // rows,),
        in_specs=[spec(c), spec(1)], out_specs=spec(1),
        out_shape=jax.ShapeDtypeStruct((n, 1, _CHUNK), jnp.int32),
        interpret=interpret,
    )(jnp.pad(keys, ((0, 0), (0, c * _CHUNK - s)),
              constant_values=_LEAST).reshape(n, c, _CHUNK),
      jnp.broadcast_to(k_row[:, None, None], (n, 1, _CHUNK)))
    return edge[:, 0, 0]


def _kth_key(keys, k_row):
    """The `k_row` [n]-th largest key of each row of `keys` [n, S] (1 <=
    k_row <= S), by bisection from the top bit down: the largest t with
    at least k_row keys >= t; no sort. The kernel on TPU backends (ONE
    call and no loop in the program: a loop under a decode step's
    `attn.topk` would be counted beside its children,
    benchmark/metrics/_scoped_ops.py), the XLA loop elsewhere."""
    if jax.default_backend() == "tpu":
        return _kth_key_kernel(keys, k_row)
    return _kth_key_loop(keys, k_row)


def _running_count(mask):
    """Of `mask` [n, S], in chunks of _CHUNK positions (S padded up):
    (within [n, C, _CHUNK]: how many are set in a position's chunk up
    to and including it; before [n, C]: how many in the chunks before).
    float32 holding integers; two products and no scan."""
    n, s = mask.shape
    c = -(-s // _CHUNK)
    bf = jnp.bfloat16
    chunks = jnp.pad(mask, ((0, 0), (0, c * _CHUNK - s))).reshape(
        n, c, _CHUNK)
    at = jnp.arange(_CHUNK)
    within = jnp.einsum("ncl,lm->ncm", chunks.astype(bf),
                        (at[:, None] <= at[None]).astype(bf),
                        preferred_element_type=F32)
    at = jnp.arange(c)
    before = jnp.einsum("nc,cd->nd", within[..., -1].astype(bf),
                        (at[:, None] < at[None]).astype(bf),
                        preferred_element_type=F32)
    return within, before


def threshold(scores, n_live, k):
    """What `taken_from` reads of each row of `scores` [n, S] over its
    first `n_live` [n] positions (1 <= n_live): (keys [n, S] int32,
    edge [n] the key of the row's min(n_live, k)-th largest live score,
    k_row [n] that count)."""
    s = scores.shape[-1]
    keys = _keys(scores, jnp.arange(s)[None] < n_live[:, None])
    k_row = jnp.minimum(n_live, min(k, s)).astype(jnp.int32)
    return keys, _kth_key(keys, k_row), k_row


def taken_from(keys, edge, k_row):
    """The mask [n, S] of `threshold`'s triple: the positions whose key
    is above the edge (what is not live lies under every edge), and of
    those equal to it the first by position that fill the row's count
    (one running count over the equals, no scatter)."""
    n, s = keys.shape
    edge = edge[:, None]
    above = keys > edge
    room = (k_row - jnp.sum(above, axis=-1, dtype=jnp.int32)).astype(F32)
    equal = keys == edge
    within, before = _running_count(equal)
    first = (before[..., None] + within <= room[:, None, None]).reshape(
        n, -1)[:, :s]
    return above | (equal & first)


def taken_mask(scores, n_live, k):
    """The exact top-min(n_live, k) of each row of `scores` [n, S] over
    its first `n_live` [n] positions as a mask [n, S]: `jax.lax.top_k`'s
    set, ties to the lower position, found as a THRESHOLD and not by a
    sort (tests/test_glm.py and tests/test_keye.py plant the ties)."""
    return taken_from(*threshold(scores, n_live, k))


def positions_of(mask, k):
    """The positions a `mask` [n, S] of at most k' = min(k, S) a row
    sets, in ASCENDING position: (positions [n, k'] int32, taken [n,
    k'] bool: the slots that hold one, the first of each row; the rest
    arbitrary, in range). No sort and no scatter: slot j finds its
    chunk by the chunks' running counts, that chunk's running count by
    a one-hot product (exact: counts of at most _CHUNK) and its place
    in the chunk by one more compare-and-count."""
    s = mask.shape[-1]
    k = min(k, s)
    within, before = _running_count(mask)
    slot = jnp.arange(k, dtype=F32)
    upto = before + within[..., -1]                           # [n, C]
    passed = upto[:, None, :] <= slot[None, :, None]          # [n, k', C]
    chunk = jnp.sum(passed, axis=-1, dtype=jnp.int32)
    ahead = jnp.sum(jnp.where(passed, within[:, None, :, -1], 0), axis=-1)
    bf = jnp.bfloat16
    mine = jnp.einsum(
        "nkc,ncl->nkl",
        (chunk[..., None] == jnp.arange(upto.shape[-1])).astype(bf),
        within.astype(bf), preferred_element_type=F32)        # [n, k', L]
    place = jnp.sum(mine <= (slot[None] - ahead)[..., None], axis=-1,
                    dtype=jnp.int32)
    return (jnp.minimum(chunk * _CHUNK + place, s - 1),
            slot[None] < upto[:, -1:])


def select(scores, n_live, k):
    """The exact top-min(n_live, k) of each row of `scores` [n, S]
    over its first `n_live` [n] positions: (positions [n, k'] int32,
    taken [n, k'] bool), k' = min(k, S); `taken` is the first
    min(n_live, k) slots and positions not taken are arbitrary (in
    range). `taken_mask`'s set, in ascending position."""
    return positions_of(taken_mask(scores, n_live, k), k)


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


def block_attention(k_rows, v_rows, scale):
    """`attend_masked` over the rows [S, G, hd] of one sequence for
    block after block of its queries: fn(q, mask, live_rows), `mask`
    keeping no row at or past `live_rows` (a traced count: the block's
    last position + 1). On TPU backends ONE Pallas call a block, the
    flash form with the mask an operand (ops/pallas_masked_attention.py:
    no [n, H, S] logits in HBM, no key tile that starts at or past
    `live_rows`), over rows relaid by kv head ONCE here; `attend_masked`
    in XLA elsewhere."""
    if jax.default_backend() == "tpu":
        k_heads, v_heads = by_head(k_rows), by_head(v_rows)
        return lambda q, mask, live_rows: masked_flash_attention(
            q, k_heads, v_heads, mask, live_rows, scale=scale)
    return lambda q, mask, live_rows: attend_masked(q, k_rows, v_rows, mask,
                                                    scale)


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


def select_attend_seq(q, w, keys, positions, qa, k_rows, v_rows, k, scale,
                      with_positions=False):
    """`select_seq`'s selection and, in the same block of queries,
    grouped-query attention over the contiguous K and V rows [S, G, hd]
    of one sequence under the selection's mask: scores, threshold,
    mask, attention, and neither a sort nor positions. qa: [s, H, hd]
    the attention's queries, `scale` their logits' (`attend_masked`).
    Returns (None, out [s, H, hd]); `with_positions` (a selection
    somebody reads: decoder.selection_tap): `select`'s pair [s, k']
    first, of the same mask."""
    with jax.named_scope("attn.kernel"):
        attend = block_attention(k_rows, v_rows, scale)

    def one(qb, wb, pos, qab):
        with jax.named_scope("attn.index"):
            scores = _scores(qb, wb, keys, "qhd,sd->qhs")
        with jax.named_scope("attn.topk"):
            found = threshold(scores, pos + 1, k)
        with jax.named_scope("attn.mask"):
            mask = taken_from(*found)
        with jax.named_scope("attn.topk"):
            sel = positions_of(mask, k) if with_positions else None
        with jax.named_scope("attn.kernel"):
            return sel, attend(qab, mask, jnp.max(pos) + 1)

    return _blocked(one, q.shape[0], q, w, positions, qa)
