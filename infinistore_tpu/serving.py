"""Continuous-batching serving engine over the paged-KV store.

The reference stops at the store API and leaves the engine to vLLM
(reference docs/source/design.rst:54-63 describes the engine-side loop it
expects: get_match_last_index → restore → prefill the tail → decode →
offload). This module IS that loop, TPU-native — the consumer that turns
the store's primitives into end-to-end serving:

- **Slot-based continuous batching**: a fixed batch of `max_slots`
  sequences decodes in lockstep through ONE jitted `decode_step` (static
  shapes — one compile, any request mix); requests are admitted into free
  slots as others finish, vLLM-style.
- **Paged HBM pool**: KV lives in fixed-size pages [n_kv_layers,
  total_pages, page, n_kv, hd] with a host-side free list and per-slot
  page tables; pages are allocated on demand as sequences grow.
- **A second kind of cache, per slot**: a family with recurrent layers
  (models/hybrid.py) keeps pages for its attention layers alone and,
  for the others, a state a slot that does not grow, plus a copy of it
  taken when the sequence last crossed a page edge (a recurrence
  cannot be rewound to where the stored pages end). A finish or a
  preemption offloads the new full pages AND that copy as a snapshot
  keyed by the same digest chain; a prefix hit is the pages and the
  snapshot at their end, both or nothing.
- **Prefix-cache HIT admission**: page keys are content-addressed (a
  hash chain over token ids, vLLM-style — see `content_page_keys`), so
  any request whose prompt extends a cached token prefix automatically
  restores those pages straight into the pool and prefills ONLY the
  un-cached tail via the rectangular flash kernel (the model family's
  prefill_with_prefix) — no prefix recompute, no caller-side
  sequence-id coordination.
- **Offload on finish**: completed sequences' full pages go back to the
  store (first-writer-wins dedup makes repeats free), so the next request
  sharing the prompt — e.g. the next turn of the same conversation —
  hits. One gather program, one device-to-host transfer and one store
  batch per chunk of OFFLOAD_CHUNK_BYTES, closed by one sync: the
  engine thread dispatches the gathers, frees the pages and steps on;
  the engine's upload thread makes the transfers' waits, the store
  batches and the sync, and the request's `done` follows its
  acknowledgement (`_offload_full_pages`, `_finish`).
- **Quantized wire (opt-in)**: `ServingConfig(quantized_store=True)`
  moves pages to/from the store int8-packed (per-token-per-head scales,
  ops/kv_quant.py) — half the restore/offload bytes and store capacity
  at ~0.4% KV error; quantized and raw pages live in disjoint key
  namespaces so they can share one store safely.
- **Preemption THROUGH the store**: when the HBM page pool runs out
  mid-decode, a sequence is swapped out vLLM-style — but the swap device
  is the disaggregated store, not local CPU RAM: its full pages are
  offloaded, its pool pages freed, and it requeues at the front;
  re-admission rides the ordinary prefix-HIT path (restore pages,
  recompute only the partial tail page) and generation resumes exactly
  where it stopped (with `quantized_store` the restored prefix carries
  the ~0.4% dequantization error, so a near-tie greedy step may diverge
  from an uncontended run). Store-less engines preempt too — they just
  recompute the prefix on resume.

TPU-first choices: decode is one fixed-shape jit over all slots (inactive
slots scatter into a sacrificial scratch page and their logits are
ignored on host); prefill lengths are bucketed to page multiples so the
jit cache stays small; pool writes are a fixed-arity donated jit with
out-of-range page ids dropped — no recompilation as counts vary.
"""

import collections
import hashlib
import logging
import operator
import queue
import threading
import time
import weakref
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .lib import InfiniStoreKeyNotFound
from .models import decoder, llama
from .tpu import to_host
from .utils import profiling

# Digests of the pages an engine itself offloaded, kept to tell a hit
# on its own pages from one on another engine's (stats
# "foreign_hit_pages"), oldest first out. Four replicas near their knee
# write 130 pages a second between them (PERF.md, PR 26), so the 40 s
# of writes a store pool is sized for are some 1,300 digests an
# engine; 64 Ki outlast any pool one host holds, at 8 MB at most.
OWN_DIGESTS = 65536


def content_page_digests(tokens, page_size, n_pages, namespace=""):
    """Per-page content digests, vLLM-style: digest i is the hash CHAIN
    over `namespace` plus all tokens up to the end of page i, so two
    requests share exactly the pages whose full token prefix (and model
    namespace) is identical — no caller-side sequence-id coordination,
    and a divergent prompt can never restore another sequence's KV
    (SURVEY §5: 'sequences become many fixed-size pages addressed by
    content keys'). `namespace` must identify everything that shapes the
    bytes: model/checkpoint id, page_size, dtype (see
    ServingEngine._namespace) — without it, two engines with different
    weights sharing one store would cross-hit each other's KV.

    The digest is layer/kind-independent: compute it ONCE per sequence
    and format the per-(layer, kind) keys with `content_page_keys`."""
    digests = []
    h = hashlib.sha256(namespace.encode())
    _extend_digest_chain(
        h, digests,
        lambda i: tokens[i * page_size:(i + 1) * page_size], n_pages,
    )
    return digests


def _extend_digest_chain(h, digests, get_chunk, n_pages):
    """Append pages [len(digests), n_pages) to a digest chain in place —
    the ONE definition of the per-page hash step (dtype, framing,
    truncation), shared by content_page_digests and the engine's
    per-slot incremental chain so the two can never drift (a drift
    would turn every prefix probe into a silent miss). `get_chunk(i)`
    returns page i's token slice."""
    for i in range(len(digests), n_pages):
        chunk = np.asarray(get_chunk(i), dtype=np.int32)
        h.update(chunk.tobytes())
        digests.append(h.hexdigest()[:32])


def content_page_keys(tokens, page_size, n_pages, layer, kind,
                      namespace="", digests=None):
    """Store keys for one (layer, kind) from content digests (computed
    here unless the caller passes precomputed `digests`)."""
    if digests is None:
        digests = content_page_digests(tokens, page_size, n_pages,
                                       namespace)
    return [f"cp/{d}/L{layer}/{kind}" for d in digests]


def content_page_keys_by_page(digests, layers, kinds="kv"):
    """The same keys for every layer and kind of each page, page-major
    (page, layer, k then v): the row order of `_gather_pages`, so one
    offload is one key list over one array. `layers`: how many (0 ..
    layers - 1), or which: a pool that holds some of the layers that
    keep pages (one kind of two) names them. `kinds`: the family's
    kinds of page (`cfg.page_kinds`; a latent family has one)."""
    if isinstance(layers, int):
        layers = range(layers)
    return [f"cp/{d}/L{layer}/{kind}" for d in digests
            for layer in layers for kind in kinds]


def snapshot_keys(digest, lo, hi):
    """Store keys of rows [lo, hi) of the state snapshot taken at the
    end of the page whose digest is `digest` (one row a state layer):
    the page's own chain, so a snapshot belongs to exactly the token
    prefix its pages belong to."""
    return [f"cp/{digest}/S{j}" for j in range(lo, hi)]


@dataclass(frozen=True)
class ServingConfig:
    max_slots: int = 4           # concurrent sequences (the static batch)
    total_pages: int = 64        # HBM pool capacity (page 0 is scratch)
    max_pages_per_seq: int = 16  # page-table width (compile-time budget)
    eos_id: int = -1             # -1: no EOS, run to max_new_tokens
    model_id: str = "default"    # distinct per checkpoint: part of the
    #                              store-key namespace; engines with
    #                              different weights sharing one store
    #                              MUST use different model_ids
    quantized_store: bool = False  # int8 pages on the store wire: halves
    #                                restore/offload bytes and store
    #                                capacity use at ~0.4% KV error
    #                                (ops/kv_quant.py); keys are
    #                                namespaced apart from bf16 pages
    spec_k: int = 0              # speculative decoding: propose up to k
    #                              tokens per step and verify them in ONE
    #                              multi-token pass (0 = off). Greedy
    #                              requests use argmax-prefix acceptance;
    #                              sampled requests use rejection-sampling
    #                              acceptance, which preserves their exact
    #                              output distribution (see _spec_decode
    #                              for the kernel-numerics caveat)
    host_steps: int = 1          # multi-step host scheduling (vLLM's
    #                              --num-scheduler-steps, TPU-native):
    #                              when every active slot is greedy and
    #                              mid-decode, fuse up to this many
    #                              decode steps into ONE device program
    #                              (_decode_scan) — one dispatch + one
    #                              tiny D2H per BURST instead of per
    #                              token. Bit-identical tokens; trades
    #                              per-token streaming latency for
    #                              dispatch amortization. Bursts are
    #                              power-of-2 bucketed so the jit cache
    #                              stays O(log host_steps)
    admit_piece: int = 0         # admission in pieces (0 = off): tokens,
    #                              a page multiple. A prompt whose
    #                              uncached part is longer is admitted a
    #                              piece an engine step, each piece ONE
    #                              call of the program a hit runs (the
    #                              prefix program) over the pages the
    #                              slot holds so far; the other slots
    #                              decode between pieces. Bounds an
    #                              admission program's temporaries by
    #                              the piece, not by the prompt


@dataclass
class Request:
    request_id: str
    prompt: list              # token ids
    max_new_tokens: int = 16
    cache: bool = True        # use the store for prefix reuse + offload
    temperature: float = 0.0  # 0 = greedy; > 0 samples softmax(z/T)
    top_k: int = 0            # 0 = full distribution; else top-k filter
    seed: int = 0             # per-request sampling stream (reproducible
    #                           across runs AND across preemptions — the
    #                           RNG travels with the request's _Work.
    #                           With spec_k>0, drafts consume extra
    #                           draws, so reproducibility under load is
    #                           DISTRIBUTION-level, not stream-level)
    on_token: object = None   # optional callable(request_id, token):
    #                           streaming delivery, fired once per
    #                           generated token as it is produced (incl.
    #                           across preemptions; a mid-draft EOS
    #                           truncation emits only the kept tokens)
    arrived_ns: int = field(default_factory=time.time_ns)
    #                         # unix ns at which the request reached the
    #                           system (the HTTP edge stamps it before it
    #                           reads the body): the origin of its
    #                           istpu.sched.queue_wait span


@dataclass
class _Work:
    """A request's schedulable state, surviving preemption: `prompt`
    grows by the tokens generated before each swap-out, `done`
    accumulates the request's full output across incarnations, and
    `rng` carries the sampling stream. On non-speculative engines that
    is one draw per generated token, so a preempted-and-resumed sampled
    run replays identically to an uncontended one; with spec_k>0,
    rejection-sampling acceptance consumes a variable number of draws,
    so replay under preemption is distribution-identical rather than
    stream-identical."""
    req: Request
    prompt: list
    done: list = field(default_factory=list)
    rng: object = None
    probe: tuple = None   # cached (hit, digests) from _probe_hit — a
    #                       queued request retries admission every step
    #                       under pool pressure, and re-hashing the
    #                       prompt + re-RPCing the store per retry
    #                       would throttle the running slots' decode
    #                       (invalidated whenever prompt changes:
    #                       preemption)
    queued_ns: int = 0    # unix ns it entered the queue (the request's
    #                       arrival; after a preemption, the swap-out)
    queue_len: int = 0    # requests that were ahead of it then
    gap_at: tuple = None  # where its last token was emitted: (ns on
    #                       the engine's clock, the five causes' ns
    #                       then), `ServingEngine._gap_mark`. None until
    #                       the first; kept across a swap-out, so the gap
    #                       a resumed sequence's next token closes
    #                       began before it
    staged: object = None  # its `_Stage`, from `submit` until an
    #                       admission has taken it (or let it go): like
    #                       `probe` it outlives a failed admission, so a
    #                       request that retries under pool pressure
    #                       pays ONE store call

    def __post_init__(self):
        if self.req.temperature > 0 and self.rng is None:
            self.rng = np.random.default_rng(self.req.seed)
        if not self.queued_ns:
            self.queued_ns = self.req.arrived_ns


class _AdmitPagesRefunded(Exception):
    """Internal: admission already returned its pages to the pool and
    the request should simply stay queued (not an error)."""


@dataclass
class _Slot:
    work: _Work
    page_ids: list            # pool pages owned, in sequence order
    seq_len: int              # tokens whose KV is in pages
    cached_pages: int = 0     # pages restored from the store at admission
    released: int = 0         # leading pages returned to the pool (their
    #                           positions fell wholly below the sliding-
    #                           window band floor; see _release_windowed)
    digests: list = field(default_factory=list)  # content-digest chain,
    digest_h: object = None   # + its hash state — extended incrementally
    #                           (one sha256 update per page per slot; see
    #                           _slot_digests)
    generated: list = field(default_factory=list)
    index: int = -1           # the slot it sits in: its row of the
    #                           state pools (families with state)
    todo: list = field(default_factory=list)  # prompt tokens whose pieces
    #                           are still to run (admission in pieces):
    #                           the slot decodes once this is empty
    pieces: int = 0           # pieces run so far
    # A model with full and banded layers (two kinds of page): the
    # banded layers' pool pages, in sequence order from page `wbase`
    # of the sequence on (what lies below left the band), and the page
    # below which the store holds every banded layer's page already.
    wpage_ids: list = field(default_factory=list)
    wbase: int = 0
    wstored: int = 0
    # A family whose finished windows fold (`cfg.fold_window`): then
    # `page_ids` is the table of cache ROWS the kernel walks, the
    # summary pages of the `folded` windows first and the exact pages
    # of the window the sequence is in behind them, and `seq_len`,
    # `cached_pages` and the digests still count POSITIONS. The first
    # `sum_stored` of the summary pages are in the store already.
    folded: int = 0
    sum_stored: int = 0

    def total_generated(self):
        return len(self.work.done) + len(self.generated)


@dataclass
class _Upload:
    """One offload on its way to the store: what the engine thread
    hands the engine's upload thread, and gets back as the
    acknowledgement. Plain data: the upload thread holds the last one
    while it waits for the next."""
    reason: str
    request: object           # request id of the offload's span, or None
    pages: int                # as the offload's span counts them
    nbytes: int               # ... and what the in-flight cap counts
    # Store batches in order: (flat device array, its gather dispatched
    # and its transfer started, or a device array the quantized wire
    # packs itself; shape of one row; the function that formats the
    # batch's keys; its arguments). Rows beyond the keys are a bucket's
    # padding and stay behind.
    chunks: list = field(default_factory=list)
    counts: dict = field(default_factory=dict)  # stats an ack adds
    digests: list = field(default_factory=list)  # -> _own_digests
    # (request id, tokens, perf_counter at its finish) of the request
    # whose `done` waits for this acknowledgement; set by _finish.
    done: tuple = None
    put_ns: int = 0           # perf_counter_ns at the put on the queue
    # The upload thread's answer: the exception that ended it, or that
    # it was not tried (an earlier upload had failed).
    error: object = None
    skipped: bool = False


@dataclass(eq=False)
class _Stage:
    """A queued request's probe and store read on their way into HBM:
    what `submit` hands the engine's restore thread and the request's
    admission takes from its `_Work`. The restore thread writes the
    results and sets the two events, `probed` behind the depth and
    `done` behind the pages; the engine thread reads a result only
    behind its event. `nbytes`, `dropped` and the arrays change hands
    under the engine's `_stage_cv`."""
    request: object           # request id of the spans
    prompt: list              # the prompt it was probed for
    put_ns: int               # perf_counter_ns at the put on the queue
    probed: object = field(default_factory=threading.Event)
    done: object = field(default_factory=threading.Event)
    hit: int = 0              # the probe's answer: pages, their digests,
    digests: list = ()        # ... and the stats it moves once it is
    counts: dict = field(default_factory=dict)  # taken (`_probe`)
    first_live: int = 0       # the pages read are [first_live, hit)
    restored: object = None   # what `_read_hit` returned: the pages in
    snap: object = None       # HBM, the snapshot, how the read lay in
    read: dict = None         # the store's pool. No pages and no error:
    #                           nothing was read (let go, or no hit)
    nbytes: int = 0           # what RESTORE_STAGED_BYTES counts of it
    error: tuple = None       # ("probe" | "restore", the exception)
    dur_ns: int = 0           # its istpu.cache.stage span's duration
    dropped: bool = False     # the engine thread let it go


@dataclass
class _Flight:
    """A plain decode step that is dispatched and not landed: what
    `_land` needs when its tokens are pulled. The engine holds at most
    one (`ServingEngine._flight`); the device arrays the step behind it
    takes as inputs are the steady cache's (`_steady`)."""
    active: list              # [(slot index, slot)] it decodes
    pull: object              # device array the host pulls: the next
    #                           tokens and the counts that ride in it
    counted: bool             # ... the experts it fetched among them
    logits: object            # device logits (a sampling slot's row)
    ahead: bool               # dispatched before the step before it landed


# What the engine thread did in a gap between two tokens of one
# request, by the span it did it under: the causes in the order the
# engine keeps their ns (`ServingEngine._cause_ns`) and the `gap_ns_*`
# counters name them. An admission is a miss or a hit by the
# `hit_pages` its span closes with.
GAP_CAUSES = ("step", "admit_miss", "admit_hit", "admit_piece", "offload")
_STEP, _ADMIT_MISS, _ADMIT_HIT, _ADMIT_PIECE, _OFFLOAD = range(5)
_CAUSE_OF_SPAN = {"istpu.model.decode": _STEP,
                  "istpu.sched.admit": _ADMIT_MISS,
                  "istpu.sched.admit_piece": _ADMIT_PIECE,
                  "istpu.cache.offload": _OFFLOAD}
_GAP_KEYS = tuple(f"gap_ns_{cause}" for cause in GAP_CAUSES)


def _cause_of(span):
    """Index into GAP_CAUSES of a span `_CAUSE_OF_SPAN` lists."""
    cause = _CAUSE_OF_SPAN[span.name]
    if cause == _ADMIT_MISS and span.fields["hit_pages"] > 0:
        return _ADMIT_HIT
    return cause


class _CauseSpan(profiling.span):
    """A span of the engine thread that `_CAUSE_OF_SPAN` lists. The
    OUTERMOST one open is the engine's `_cause_open`, and as it closes
    its duration goes to its cause: a prefill inside an admission, an
    offload inside either or a settle count once, under the span that
    holds them, so the five never overlap and never exceed the
    thread's wall clock."""

    __slots__ = ("owner",)

    def __enter__(self):
        fields = super().__enter__()
        if self.owner._cause_open is None:
            self.owner._cause_open = self
        return fields

    def __exit__(self, *exc):
        super().__exit__(*exc)
        eng = self.owner
        if eng._cause_open is self:
            eng._cause_open = None
            eng._cause_ns[_cause_of(self)] += self.dur_ns
        return False


def prompt_lookup_propose(context, k, ngram=2):
    """Draft-model-free proposer (prompt-lookup / n-gram speculation):
    find the most recent earlier occurrence of the context's last
    `ngram` tokens and propose the k tokens that followed it. Free to
    compute, surprisingly effective on repetitive text (code,
    multi-turn chat, retrieval-augmented prompts); returns [] when the
    pattern has no earlier occurrence."""
    n = len(context)
    if n < ngram + 1:
        return []
    tail = context[n - ngram:]
    # Scan right-to-left for the latest match strictly before the tail.
    for start in range(n - ngram - 1, -1, -1):
        if context[start:start + ngram] == tail:
            nxt = context[start + ngram:start + ngram + k]
            return list(nxt)
    return []


class _LazyHost:
    """Device array → host, transferred at most once and only if read
    (sampling slots need full logits rows; greedy slots never pay)."""

    def __init__(self, arr):
        self._arr = arr
        self._host = None

    def __call__(self):
        if self._host is None:
            self._host = np.asarray(self._arr)
        return self._host


@partial(jax.jit, static_argnames=("cfg", "model"))
def _prefill_px_jit(params, cfg, tokens, prefix_kvs, pos0=0, model=llama):
    """Module-level prefix-HIT prefill jit (static cfg + model family):
    every engine with the same config shares one compilation — a
    per-engine jax.jit(partial) would silently recompile identical HLO
    for each new engine instance. The suffix prefill alone, for callers
    that hold the prefix in contiguous form (decoder.restore_prefix_kvs)
    and want every position's logits and the suffix KV; the engine's
    own admissions use _admit_fused (cold) and _admit_fused_px (hit)."""
    return model.prefill_with_prefix(params, cfg, tokens, prefix_kvs,
                                     pos0=pos0)


@partial(jax.jit, static_argnames=("cfg", "n_steps", "model"),
         donate_argnums=(4, 5))
def _decode_scan(params, cfg, token, seq_lens, k_pages, v_pages, rows,
                 n_steps, model=llama):
    """`n_steps` greedy decode steps fused into one device program
    (lax.scan) — multi-step host scheduling (the vLLM
    --num-scheduler-steps idea, TPU-native): ONE dispatch and ONE tiny
    D2H deliver n_steps tokens per slot, amortizing the host's
    per-step dispatch and Python bookkeeping.
    Bit-identical to n_steps repeated single fused steps — the scan
    body IS the model family's decode_step."""
    def body(carry, _):
        token, lens, kp, vp = carry
        logits, kp, vp = model.decode_step(
            params, cfg, token, lens, kp, vp, rows
        )
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Advance only live rows: inactive slots (lens == 0) must stay
        # at 0 across steady-state cache reuse, or MoE decode_step's
        # validity mask (models/moe.py `valid = seq_lens > 0`) stops
        # excluding them and garbage rows can evict real tokens from
        # expert capacity (round-4 advisor finding).
        return (token, lens + (lens > 0), kp, vp), token

    (token, lens, kp, vp), toks = jax.lax.scan(
        body, (token, seq_lens, k_pages, v_pages), None, length=n_steps
    )
    return toks.T, lens, kp, vp  # [batch, n_steps]


def _index_kind(cfg):
    """The letter of the LAST kind of page of a family whose layers
    select the rows they attend (the index keys of the layers that own
    an indexer: "i" of models/glm.py's "ci" and of models/keye.py's
    "kvi"), or None: a kind with a shape and a set of layers of its
    own (`cfg.page_shape`, `cfg.page_layers`). Such a family's engine
    holds a pool a KIND under the same page ids (`_kind_pools`), and
    its offload, its restore and a piece's prefix read make a call a
    kind."""
    kinds = cfg.page_kinds
    return kinds[-1] if getattr(cfg, "indexer_kinds", ()) \
        and len(kinds) > 1 else None


def _kind_pools(k_pages, v_pages):
    """The pools of a family with `_index_kind`, one a kind in the
    order of `cfg.page_kinds`, from the pair every program carries:
    (latent rows, index keys), or (K, (V, index keys)): three pools
    ride in the pair's second place as a pytree, so the fused programs
    take, donate and return them as they do an array."""
    return (k_pages, *v_pages) if isinstance(v_pages, tuple) \
        else (k_pages, v_pages)


def _as_pair(pools):
    """`_kind_pools`' inverse."""
    first, *rest = pools
    return first, rest[0] if len(rest) == 1 else tuple(rest)


def _page_out(cfg, kvs, k_pages, v_pages, ids):
    """A prefill's per-layer (k, v) [1, s_pad, kv, hd], cut into pages
    and scattered into the pools at the first s_pad // page of `ids`
    (`_pad_ids` form; ids at total_pages are dropped). Traced inside
    the admission programs."""
    with jax.named_scope("pool.update"):  # stage names: models/decoder.py
        page = cfg.page_size
        m = kvs[0][0].shape[1] // page
        shape = (cfg.n_kv_layers, m, *cfg.kv_page_shape())
        k_sfx = jnp.stack([k[0] for k, *_ in kvs]).reshape(shape)
        if v_pages is None:  # a latent family's one pool
            return k_pages.at[:, ids[:m]].set(k_sfx, mode="drop"), None
        if _index_kind(cfg):
            # ... and a pool a further kind: a layer's entry holds an
            # array a kind (None where the layer keeps none), each kind
            # of a shape of its own on the layers that keep it
            sfx = [k_sfx]
            for j, kind in enumerate(cfg.page_kinds[1:], 1):
                a = jnp.stack([kv[j][0] for kv in kvs if kv[j] is not None])
                sfx.append(a.reshape(a.shape[0], m, *cfg.page_shape(kind)))
            return _as_pair([
                pool.at[:, ids[:m]].set(a, mode="drop")
                for pool, a in zip(_kind_pools(k_pages, v_pages), sfx)])
        v_sfx = jnp.stack([v[0] for _, v in kvs]).reshape(shape)
        k_pages = k_pages.at[:, ids[:m]].set(k_sfx, mode="drop")
        v_pages = v_pages.at[:, ids[:m]].set(v_sfx, mode="drop")
    return k_pages, v_pages


def _last_row(logits, s_real, counts):
    """An admission program's logits row, the last real position's:
    `logits` [1, 1, vocab] is that position's alone, because the
    program asked its model's prefill to keep `s_real - 1` and the
    head ran on that row (decoder.forward_stack's `keep`).
    Where the model's layers hold a share of the experts their routers
    score (`counts`: what its prefill returned third, else empty), two
    more values ride behind it in the host's one pull: the real rows'
    pairs that fell on experts held here and the rows the experts'
    matmuls ran, summed over the layers (whole numbers, exact in
    float32 below 2 ** 24)."""
    row = logits[0, 0]
    if not counts:
        return row
    c, = counts
    real = jnp.arange(c["pairs_held"].shape[1]) < s_real
    tail = jnp.stack([jnp.sum(jnp.where(real, c["pairs_held"][0], 0)),
                      c["rows"]])
    return jnp.concatenate([row, tail.astype(row.dtype)])


@partial(jax.jit, static_argnames=("cfg", "model"), donate_argnums=(3, 4))
def _admit_fused(params, cfg, tokens, k_pages, v_pages, ids, s_real,
                 model=llama):
    """Cold-prefill admission as ONE device program: prefill + page the
    suffix KV + scatter it into the (donated) pool at `ids` + the last
    real position's logits row, the one row the final norm and the
    head run on (`keep`: every layer runs every position, the head
    reads its weights for one; over all `s_pad` it was a sixth of a
    piece of 8,192 tokens at a vocabulary of 131,072, PERF.md PR 48).
    The unfused path was ~10 dispatches
    (prefill, per-layer kv_to_pages, stacks, pads, pool write, logits
    indexing) and pulled a full [s,vocab] row source; this is one
    dispatch and one [vocab] row pull. Padded positions beyond s_real
    write their (garbage) KV into the tail page's unused slots — those
    slots are masked by seq_len, overwritten by decode before the page
    can ever fill, and partial pages are never offloaded, so the bytes
    are unreachable. `ids` is padded with total_pages (mode=drop);
    the first s_pad // page of them are read.
    tokens: [1, s_pad] (page multiple); ids: [max_pages_per_seq]."""
    logits, kvs, *counts = model.prefill(params, cfg, tokens,
                                         keep=s_real - 1)
    k_pages, v_pages = _page_out(cfg, kvs, k_pages, v_pages, ids)
    return _last_row(logits, s_real, counts), k_pages, v_pages


def _place_restored(cfg, restored, k_pages, v_pages, restored_ids):
    """A hit's restored pages into the pools at `restored_ids`, and as
    the per-layer contiguous prefix (k, v) the suffix attends over.
    Traced inside the hit programs (`_admit_fused_px` has the forms)."""
    page = cfg.page_size
    n = restored_ids.shape[0]
    L = cfg.n_kv_layers
    if v_pages is None:
        # A latent family: ONE page a layer, rows (page, layer); the
        # prefix is the rows themselves, layer-major.
        with jax.named_scope("pool.update"):
            rows = restored.reshape(n, L, *cfg.kv_page_shape())
            for li in range(L):
                k_pages = k_pages.at[li, restored_ids].set(rows[:, li],
                                                           mode="drop")
            pfx = jnp.moveaxis(rows, 0, 1).reshape(L, 1, n * page, -1)
        return k_pages, None, [(pfx[li], None) for li in range(L)]
    if _index_kind(cfg):
        # ... a pool a kind: `restored` holds each kind's store call,
        # page-major over ITS layers; a layer's prefix is an array a
        # kind (rows, index keys or None; K, V, index keys).
        kinds = cfg.page_kinds
        owners = [cfg.page_layers(kind) for kind in kinds]
        pools = list(_kind_pools(k_pages, v_pages))
        with jax.named_scope("pool.update"):
            stacks = [r.reshape(n, len(own), *cfg.page_shape(kind))
                      for r, own, kind in zip(restored, owners, kinds)]
            for j, (stack, own) in enumerate(zip(stacks, owners)):
                for li in range(len(own)):
                    pools[j] = pools[j].at[li, restored_ids].set(
                        stack[:, li], mode="drop")
            pfx = [jnp.moveaxis(stack, 0, 1).reshape(
                len(own), 1, n * page, *stack.shape[3:])
                for stack, own in zip(stacks, owners)]
        return *_as_pair(pools), [
            tuple(p[own.index(li)] if li in own else None
                  for p, own in zip(pfx, owners))
            for li in range(L)]
    with jax.named_scope("pool.update"):  # stage names: models/decoder.py
        # Each layer's pages go from the page-major rows straight into
        # that layer of the pool. Scattered as one `[:, ids]` update
        # from the transposed stacks below, the compiled program held 8
        # times the restored bytes in temporaries (a second layout of
        # the stacks, and weight copies pushed out of fast memory); so
        # it holds one (tests/test_model.py, lowered for a v5e).
        rows = restored.reshape(n, L, 2, *cfg.kv_page_shape())
        for li in range(L):
            k_pages = k_pages.at[li, restored_ids].set(rows[:, li, 0],
                                                       mode="drop")
            v_pages = v_pages.at[li, restored_ids].set(rows[:, li, 1],
                                                       mode="drop")
        # The contiguous form the suffix attends over: the same values,
        # layer-major, reshaped (decoder.pages_to_kv, every layer at
        # once).
        kp, vp = decoder.restored_to_pages(cfg, restored)
        flat = (L, 1, n * page, cfg.n_kv_heads, cfg.head_dim)
        k_pfx, v_pfx = kp.reshape(flat), vp.reshape(flat)
    return k_pages, v_pages, [(k_pfx[li], v_pfx[li]) for li in range(L)]


@partial(jax.jit, static_argnames=("cfg", "model"), donate_argnums=(4, 5))
def _admit_fused_px(params, cfg, tokens, restored, k_pages, v_pages,
                    restored_ids, suffix_ids, s_real, pos0, model=llama):
    """Prefix-HIT admission as ONE device program, as `_admit_fused` is
    for the cold one: the restored pages go into the (donated) pool,
    the suffix is prefilled over them, its KV is paged out into the
    pool, and the last real position's logits row comes back. Done
    eagerly (transpose, per-layer slices, pads to max_pages_per_seq,
    two pool writes, kv_to_pages per layer) the same data movement was
    some 300 dispatches for 16 layers, each holding the engine thread
    (PERF.md, PR 24 and PR 29).

    restored: the store call's result as `get_kv_pages` returns it,
      page-major [n * L * 2, page, n_kv, hd] (decoder.restored_to_pages
      has the order); n restored pages.
    restored_ids: [n] pool ids of those pages, exactly n: nothing is
      padded to the pool's arity.
    suffix_ids: the suffix pages' ids in `_pad_ids` form; the first
      s_pad // page are read. Padded positions beyond s_real land in
      the tail page's unused slots (`_admit_fused` says why those bytes
      are unreachable).
    pos0: absolute position of the restored prefix's first token (> 0
      for a windowed engine's trimmed prefix).
    Ids at total_pages are dropped, so with every id there the pool
    comes back untouched (`ServingEngine.first_token_logits`).
    tokens: [1, s_pad] (page multiple). One program per (s_pad, n), as
    `_prefill_px_jit` has per (s_pad, prefix length)."""
    k_pages, v_pages, prefix = _place_restored(cfg, restored, k_pages,
                                               v_pages, restored_ids)
    logits, kvs, *counts = model.prefill_with_prefix(
        params, cfg, tokens, prefix, pos0=pos0, keep=s_real - 1)
    k_pages, v_pages = _page_out(cfg, kvs, k_pages, v_pages, suffix_ids)
    return _last_row(logits, s_real, counts), k_pages, v_pages


def _with_fetched(nxt, fetched):
    """The array the host pulls after a step of a family with routed
    experts: the next tokens and, last, the experts the step fetched
    (one transfer for both)."""
    return jnp.append(nxt, fetched)


@partial(jax.jit, static_argnames=("cfg", "model", "fetched"),
         donate_argnums=(4, 5))
def _decode_fused(params, cfg, token, seq_lens, k_pages, v_pages, rows,
                  model=llama, fetched=False):
    """One fused device program per decode step: model forward + argmax
    + seq_lens advance, with the KV pools DONATED. Donation alone does
    not keep the pool in place: the step must also never slice a layer
    out of the pool or stack layers back (a Pallas operand is a buffer
    of its own, so a sliced layer is a copied layer). `decode_step`
    therefore scatters each layer's new rows into the 5-D pool itself
    and hands the kernel the whole pool plus the layer to read, and the
    compiled program's temporaries stay under one layer of the pool
    (tests/test_model.py holds that). Host pulls only `nxt`
    (4 bytes/slot) in the greedy steady state; `logits` stays
    device-resident unless a sampling slot needs it. Fusing also
    collapses ~6 host API calls per step into one dispatch + one tiny
    D2H. With `fetched` (a family with routed experts) a sixth output
    is what the host pulls in place of `nxt`: `_with_fetched`."""
    logits, k_pages, v_pages, *n = model.decode_step(
        params, cfg, token, seq_lens, k_pages, v_pages, rows,
        fetched=fetched
    )
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # Live-rows-only advance — see _decode_scan's body comment.
    out = (logits, nxt, seq_lens + (seq_lens > 0), k_pages, v_pages)
    return out + (_with_fetched(nxt, n[0]),) if fetched else out


@partial(jax.jit, static_argnames=("cfg", "model"), donate_argnums=(2, 3))
def _fold_window(params, cfg, k_pages, v_pages, ids, model=None):
    """ONE finished window of one sequence folded where it lies (a
    family with `cfg.fold_window`, models/evabyte.py `fold_pages`):
    every layer's pages `ids` (the window's, in sequence order) are
    gathered and summarised, and the summary rows written over the
    first of them, the pools donated. The engine dispatches it behind
    the program that wrote the window's last row."""
    return model.fold_pages(params, cfg, k_pages, v_pages, ids)


# ---- the same programs for a family with state layers ------------------
# Each carries, beside the page pools, the state pools its model
# declares (`model.state_pools`: {"h": [...], "conv": [...]}, one array
# a state layer, a row a slot) and, where it admits, the pools of
# boundary copies: the state as it was at the slot's last page edge.
# They have names of their own, so a trace tells the two kinds of
# family apart and the families without state run exactly the
# programs above.


def _state_rows(cfg, pools, slot):
    """Slot `slot` of the state pools `pools` as snapshot rows: one
    flat row a state layer, h then the convolution tail, zero-padded
    to `_snapshot_row_elems`. [n_state_layers, row]."""
    row = _snapshot_row_elems(cfg)
    out = []
    # a layer's arrays, kind by kind in the order of cfg.state_shapes()
    # (a dict that has been through jit comes back with sorted keys)
    for layer in zip(*(pools[kind] for kind in cfg.state_shapes())):
        flat = jnp.concatenate([
            jax.lax.dynamic_index_in_dim(a, slot, keepdims=False).reshape(-1)
            for a in layer])
        out.append(jnp.pad(flat, (0, row - flat.shape[0])))
    return jnp.stack(out)


def _snapshot_row_elems(cfg):
    """Elements of one snapshot row: a state layer's h and convolution
    tail for one sequence, rounded up to whole K pages' bytes (the
    store's allocation unit is the smallest object an offload writes,
    one K page; rows that are whole blocks lie back to back in its
    pool, so a snapshot reads back as one view)."""
    raw = sum(int(np.prod(shape)) for shape in cfg.state_shapes().values())
    block = max(1, cfg.kv_page_bytes() // cfg.state_jdtype.itemsize)
    return -(-raw // block) * block


def _rows_to_state(cfg, snap):
    """Inverse of `_state_rows` for one sequence: per state layer its
    arrays in the order of cfg.state_shapes(), each [1, *shape]."""
    out = []
    for j in range(snap.shape[0]):
        at, layer = 0, []
        for shape in cfg.state_shapes().values():
            n = int(np.prod(shape))
            layer.append(snap[j, at:at + n].reshape(1, *shape))
            at += n
        out.append(tuple(layer))
    return out


def _state_in(state, bstate, states, slot):
    """An admission's per-layer states (decoder.ssm_mixer_seq) into row
    `slot` of the state pools and of the boundary copies; a slot of
    max_slots is dropped (first_token_logits admits nothing)."""
    with jax.named_scope("state.update"):
        for j, st in enumerate(states):
            for kind in state:
                state[kind][j] = state[kind][j].at[slot].set(
                    st[kind][0], mode="drop")
                bstate[kind][j] = bstate[kind][j].at[slot].set(
                    st[kind + "_b"][0], mode="drop")
    return state, bstate


@partial(jax.jit, static_argnames=("cfg", "model"),
         donate_argnums=(3, 4, 5, 6))
def _admit_fused_st(params, cfg, tokens, k_pages, v_pages, state, bstate,
                    ids, s_real, slot, model):
    """`_admit_fused` for a family with state layers: the attention
    layers' KV is paged out as there; the state layers run from
    position 0 with the padded positions masked (dt = 0: they may not
    advance a recurrence), and the state after `s_real` tokens and the
    one at the last page edge go into row `slot` of the (donated) state
    pools and boundary copies."""
    logits, kvs, states = model.prefill(params, cfg, tokens, s_real=s_real,
                                        last_only=True)
    k_pages, v_pages = _page_out(cfg, kvs, k_pages, v_pages, ids)
    state, bstate = _state_in(state, bstate, states, slot)
    return logits[0, 0], k_pages, v_pages, state, bstate


@partial(jax.jit, static_argnames=("cfg", "model"),
         donate_argnums=(5, 6, 7, 8))
def _admit_fused_px_st(params, cfg, tokens, restored, snap, k_pages,
                       v_pages, state, bstate, restored_ids, suffix_ids,
                       s_real, slot, model):
    """`_admit_fused_px` for a family with state layers: the restored
    pages go into the pool, the restored snapshot `snap` ([n_state
    layers, row], the state at the end of the restored pages) is where
    the state layers continue from, the suffix is prefilled over both,
    and pages and states go where `_admit_fused_st` puts them. One
    program per (s_pad, n)."""
    k_pages, v_pages, prefix = _place_restored(cfg, restored, k_pages,
                                               v_pages, restored_ids)
    logits, kvs, states = model.prefill_with_prefix(
        params, cfg, tokens, prefix, state=_rows_to_state(cfg, snap),
        s_real=s_real, last_only=True)
    k_pages, v_pages = _page_out(cfg, kvs, k_pages, v_pages, suffix_ids)
    state, bstate = _state_in(state, bstate, states, slot)
    return logits[0, 0], k_pages, v_pages, state, bstate


@partial(jax.jit, static_argnames=("cfg", "model"),
         donate_argnums=(4, 5, 6))
def _decode_fused_st(params, cfg, token, seq_lens, k_pages, v_pages,
                     state, rows, model):
    """`_decode_fused` for a family with state layers: the state of
    the slots that decode (`seq_lens` > 0) is read and written where it
    lies (one array a layer, donated); no other slot's row is touched,
    and an admission overwrites its slot's whole row."""
    logits, k_pages, v_pages, state = model.decode_step(
        params, cfg, token, seq_lens, k_pages, v_pages, rows, state
    )
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return logits, nxt, seq_lens + (seq_lens > 0), k_pages, v_pages, state


@partial(jax.jit, donate_argnums=(1,))
def _copy_boundary(state, bstate, slot):
    """Slot `slot`'s state into its boundary copy: dispatched behind
    the decode step in which the slot's sequence reached a page edge,
    for that slot alone (76 MB at granite-4.0-h-micro's widths; doing
    it for every slot inside every step would move 16 times that)."""
    with jax.named_scope("state.snapshot"):
        return jax.tree_util.tree_map(
            lambda live, copy: jax.lax.dynamic_update_slice_in_dim(
                copy, jax.lax.dynamic_slice_in_dim(live, slot, 1), slot, 0),
            state, bstate)


@partial(jax.jit, static_argnames=("cfg", "rows_a_chunk"))
def _gather_snapshot(cfg, bstate, slot, rows_a_chunk):
    """Slot `slot`'s boundary copy as snapshot rows (`_state_rows`),
    in flat chunks of `rows_a_chunk` rows: the state's part of an
    offload, one program; each chunk is one device-to-host transfer
    and one store batch."""
    with jax.named_scope("state.gather"):
        rows = _state_rows(cfg, bstate, slot)
        return tuple(rows[a:a + rows_a_chunk].reshape(-1)
                     for a in range(0, rows.shape[0], rows_a_chunk))


# ---- the same programs for a model with two kinds of attention layer ----
# Full layers keep every page for the life of a sequence; banded layers
# need the last `band` positions alone (decoder.attn_layers). Each kind
# has its pools: the full layers' under the page table every family
# has, the banded layers' (`wk`, `wv`: [banded layers, slots x short
# table + 1, page, n_kv, hd]) under a short table a slot. The store's
# contract is the one every family has (every full page of every layer
# is written once), so what a banded layer computes below its band at
# admission leaves the admission program as `sub`, on its way to the
# store without a pool page of its own. Names of their own, so a trace
# tells them apart; a model of one kind runs exactly the programs
# above.


def _sub_chunk_pages(cfg):
    """Pages (over every banded layer, K and V) that one chunk of an
    admission's `sub` holds: at most OFFLOAD_CHUNK_BYTES, as every
    device-to-host transfer of an offload."""
    n_win = sum(pool == "window" for *_, pool, _ in decoder.attn_layers(cfg))
    return max(1, OFFLOAD_CHUNK_BYTES // (2 * n_win * cfg.kv_page_bytes()))


def _page_out_two(cfg, kvs, k_pages, v_pages, wk, wv, ids, wids, n_sub):
    """`_page_out` for two kinds. Suffix page i of a full layer goes to
    ids[i] of the full pools, of a banded layer to wids[i] of the
    banded pools (both in `_pad_ids` form; the sentinel drops). The
    banded layers' first `n_sub` suffix pages (static; the caller left
    their wids at the sentinel: they lie below the band) come back as
    `sub`: page-major rows (page, layer, k then v), flat, in chunks of
    `_sub_chunk_pages` pages."""
    page = cfg.page_size
    m = kvs[0][0].shape[1] // page
    spec = decoder.attn_layers(cfg)

    def stack(pool, which):
        rows = [kv[which][0] for kv, (*_, held, _) in zip(kvs, spec)
                if held == pool]
        return jnp.stack(rows).reshape(len(rows), m, *cfg.kv_page_shape())

    with jax.named_scope("pool.update"):  # stage names: models/decoder.py
        k_pages = k_pages.at[:, ids[:m]].set(stack("full", 0), mode="drop")
        v_pages = v_pages.at[:, ids[:m]].set(stack("full", 1), mode="drop")
        kw, vw = stack("window", 0), stack("window", 1)
        wk = wk.at[:, wids[:m]].set(kw, mode="drop")
        wv = wv.at[:, wids[:m]].set(vw, mode="drop")
    with jax.named_scope("pool.gather"):
        rows = jnp.swapaxes(
            jnp.stack([kw[:, :n_sub], vw[:, :n_sub]], axis=2), 0, 1)
        c = _sub_chunk_pages(cfg)
        sub = tuple(rows[a:a + c].reshape(-1) for a in range(0, n_sub, c))
    return k_pages, v_pages, wk, wv, sub


@partial(jax.jit, static_argnames=("cfg", "model", "n_sub"),
         donate_argnums=(3, 4, 5, 6))
def _admit_fused_wf(params, cfg, tokens, k_pages, v_pages, wk, wv, ids,
                    wids, s_real, model, n_sub):
    """`_admit_fused` for two kinds of attention layer: every layer's K
    and V are computed for the whole prompt; the full layers' pages go
    to their pools at `ids`, the banded layers' in-band tail to theirs
    at `wids`, and the banded layers' first `n_sub` pages come back as
    `sub` (`_page_out_two`). One program per (s_pad, n_sub); n_sub is a
    function of s_pad in an admission."""
    logits, kvs, *counts = model.prefill(params, cfg, tokens,
                                         keep=s_real - 1)
    k_pages, v_pages, wk, wv, sub = _page_out_two(
        cfg, kvs, k_pages, v_pages, wk, wv, ids, wids, n_sub)
    return (_last_row(logits, s_real, counts), k_pages, v_pages, wk, wv,
            sub)


def _place_restored_two(cfg, restored, k_pages, v_pages, wk, wv, r_ids,
                        wr_ids):
    """`_place_restored` for two kinds: a hit's restored pages
    (`_admit_fused_px_wf` has the form) into both pairs of pools, and
    as the per-layer contiguous prefix (k, v) each layer may attend,
    rows in the cache's form ([1, positions, *a page's row]: packed
    where the family packs, as its prefill makes and attends them)."""
    page = cfg.page_size
    spec = decoder.attn_layers(cfg)
    n_full = sum(pool == "full" for *_, pool, _ in spec)
    p, nw = r_ids.shape[0], wr_ids.shape[0]
    with jax.named_scope("pool.update"):  # stage names: models/decoder.py
        cut = p * n_full * 2
        rf = restored[:cut].reshape(p, n_full, 2, *cfg.kv_page_shape())
        rw = restored[cut:].reshape(nw, len(spec) - n_full, 2,
                                    *cfg.kv_page_shape())
        # per layer, straight from the page-major rows, as
        # `_place_restored` does and for its reason
        for li in range(n_full):
            k_pages = k_pages.at[li, r_ids].set(rf[:, li, 0], mode="drop")
            v_pages = v_pages.at[li, r_ids].set(rf[:, li, 1], mode="drop")
        for li in range(len(spec) - n_full):
            wk = wk.at[li, wr_ids].set(rw[:, li, 0], mode="drop")
            wv = wv.at[li, wr_ids].set(rw[:, li, 1], mode="drop")
        prefix = []
        row = (cfg.n_kv_heads // cfg.kv_pack, cfg.head_dim * cfg.kv_pack)
        for *_, pool, li in spec:
            rows = rf if pool == "full" else rw
            flat = (1, rows.shape[0] * page, *row)
            prefix.append((rows[:, li, 0].reshape(flat),
                           rows[:, li, 1].reshape(flat)))
    return k_pages, v_pages, wk, wv, prefix


@partial(jax.jit, static_argnames=("cfg", "model", "n_sub"),
         donate_argnums=(4, 5, 6, 7))
def _admit_fused_px_wf(params, cfg, tokens, restored, k_pages, v_pages, wk,
                       wv, r_ids, wr_ids, s_ids, ws_ids, s_real, model,
                       n_sub):
    """`_admit_fused_px` for two kinds of attention layer.

    restored: ONE store call's result: first the full layers' pages
      [0, P) page-major (page, full layer, k then v), then the banded
      layers' pages [first_live, P) page-major (page, banded layer, k
      then v); P = len(r_ids), P - first_live = len(wr_ids).
    r_ids / wr_ids: pool ids of those pages, exactly that many; a
      banded layer's restored page that the sequence will not attend
      after this admission (below the floor of the prompt's last
      position) has the sentinel: the suffix attends it here and it
      takes no pool page.
    s_ids / ws_ids, n_sub, sub: as in `_admit_fused_wf`, for the
      suffix.
    The prefix a layer attends is what that layer may attend: [0, P)
    for a full layer, [first_live, P) for a banded one; rotary
    positions are absolute (keys were rotated before they were cached)
    and the band is relative, so neither needs the other's length
    (decoder.forward_stack). One program per (s_pad, P)."""
    k_pages, v_pages, wk, wv, prefix = _place_restored_two(
        cfg, restored, k_pages, v_pages, wk, wv, r_ids, wr_ids)
    logits, kvs, *counts = model.prefill_with_prefix(
        params, cfg, tokens, prefix, keep=s_real - 1)
    k_pages, v_pages, wk, wv, sub = _page_out_two(
        cfg, kvs, k_pages, v_pages, wk, wv, s_ids, ws_ids, n_sub)
    return (_last_row(logits, s_real, counts), k_pages, v_pages, wk, wv,
            sub)


@partial(jax.jit, static_argnames=("cfg", "model", "fetched"),
         donate_argnums=(4, 5, 6, 7))
def _decode_fused_wf(params, cfg, token, seq_lens, k_pages, v_pages, wk, wv,
                     rows, model, fetched=False):
    """`_decode_fused` for two kinds of attention layer. `rows`: (page
    table, the banded layers' short table [slots, entries], its base
    [slots]: the absolute position of each row's first entry). A banded
    layer's kernel call walks the short table alone: `band / page + 1`
    live entries a sequence however long the sequence is."""
    table, wtable, wbase = rows
    logits, k_pages, v_pages, wk, wv, *n = model.decode_step(
        params, cfg, token, seq_lens, k_pages, v_pages, table,
        win=(wk, wv, wtable, wbase), fetched=fetched)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = (logits, nxt, seq_lens + (seq_lens > 0), k_pages, v_pages, wk,
           wv)
    return out + (_with_fetched(nxt, n[0]),) if fetched else out


# ---- ... and for a model with all three: full pages, banded pages and
# a recurrent state in one sequence (models/phi_flash.py). Built from
# the two trios' helpers; names of their own, for the trace's sake.


@partial(jax.jit, static_argnames=("cfg", "model", "n_sub"),
         donate_argnums=(3, 4, 5, 6, 7, 8))
def _admit_fused_wf_st(params, cfg, tokens, k_pages, v_pages, wk, wv, state,
                       bstate, ids, wids, s_real, slot, model, n_sub):
    """`_admit_fused_wf` with `_admit_fused_st`'s state: pages of both
    kinds go where the first puts them, the state after `s_real` tokens
    and the one at the last page edge into row `slot` of the state
    pools and boundary copies."""
    logits, kvs, states = model.prefill(params, cfg, tokens, s_real=s_real,
                                        last_only=True)
    k_pages, v_pages, wk, wv, sub = _page_out_two(
        cfg, kvs, k_pages, v_pages, wk, wv, ids, wids, n_sub)
    state, bstate = _state_in(state, bstate, states, slot)
    return logits[0, 0], k_pages, v_pages, wk, wv, sub, state, bstate


@partial(jax.jit, static_argnames=("cfg", "model", "n_sub"),
         donate_argnums=(5, 6, 7, 8, 9, 10))
def _admit_fused_px_wf_st(params, cfg, tokens, restored, snap, k_pages,
                          v_pages, wk, wv, state, bstate, r_ids, wr_ids,
                          s_ids, ws_ids, s_real, slot, model, n_sub):
    """`_admit_fused_px_wf` with `_admit_fused_px_st`'s snapshot: a hit
    of three kinds. `restored` and the ids as there; `snap` ([state
    layers, row]) is the state at the end of the restored pages, where
    the state layers continue from."""
    k_pages, v_pages, wk, wv, prefix = _place_restored_two(
        cfg, restored, k_pages, v_pages, wk, wv, r_ids, wr_ids)
    logits, kvs, states = model.prefill_with_prefix(
        params, cfg, tokens, prefix, state=_rows_to_state(cfg, snap),
        s_real=s_real, last_only=True)
    k_pages, v_pages, wk, wv, sub = _page_out_two(
        cfg, kvs, k_pages, v_pages, wk, wv, s_ids, ws_ids, n_sub)
    state, bstate = _state_in(state, bstate, states, slot)
    return logits[0, 0], k_pages, v_pages, wk, wv, sub, state, bstate


@partial(jax.jit, static_argnames=("cfg", "model"),
         donate_argnums=(4, 5, 6, 7, 8))
def _decode_fused_wf_st(params, cfg, token, seq_lens, k_pages, v_pages, wk,
                        wv, state, rows, model):
    """`_decode_fused_wf` with `_decode_fused_st`'s state: `rows` as
    there; the state of the slots that decode is advanced where it
    lies."""
    table, wtable, wbase = rows
    logits, k_pages, v_pages, state, wk, wv = model.decode_step(
        params, cfg, token, seq_lens, k_pages, v_pages, table, state,
        win=(wk, wv, wtable, wbase))
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (logits, nxt, seq_lens + (seq_lens > 0), k_pages, v_pages, wk,
            wv, state)


# Trivial programs dispatched behind a one-shot admission's program
# (ServingEngine._settle): 4 ended the slow mode in two runs of two, 1
# and 2 did not; 8 behind the admissions out of idle alone ended it some
# nine times in ten, which left one run in three with a slow spell of
# 3-7 s (PERF.md, PR 29). They cost 0.2 ms each on the v5e host.
SETTLE_PROGRAMS = 16
# ... behind every admission for this long after one that found the
# engine idle: a slow spell starts only there and the longest seen
# lasted 13 s. An engine that is never idle pays nothing.
SETTLE_S = 20.0
# An engine with nothing to step sends the device one trivial program
# this often (ServingEngine.idle): the spell that precedes the slow
# mode was never under 1.0 s (PERF.md, PR 29).
IDLE_TICK_S = 0.1


@jax.jit
def _tick(x):
    return x + 1


# The most bytes one device-to-host transfer of an offload brings over
# (and one store batch holds). Measured on a v5e host (PERF.md, PR 27):
# a transfer lands in a host buffer PJRT allocates anew, and above
# glibc's largest mmap threshold (32 MiB) every such buffer is fresh
# pages, faulted in one by one: 0.7-0.9 GB/s against 5.6 GB/s below it.
# An offload larger than this goes in chunks of it, every chunk gathered
# and on its way to the host before the first is copied into the
# store's pool.
OFFLOAD_CHUNK_BYTES = 16 << 20
# The most bytes of offloads that may lie between their gathers'
# dispatch and their acknowledgement by the upload thread (each chunk
# lives in HBM until it is transferred, then on the host until its store
# batch is synced). Past it the engine thread waits for acknowledgements
# before it gathers more (stats "upload_backpressure_waits"); an offload
# larger than this alone goes when nothing else is in flight. The v5e
# cells peak at 11.7-12.95 of 16 GB of HBM, and the chunks in flight do
# not raise that peak. At 256 MiB granite-4.0-h-micro's finishes (a
# 78 MB snapshot each, 2.4 a second) made the engine thread wait 8-12
# times in a window of 51 s, at 512 MiB once (PERF.md, PR 39).
UPLOAD_INFLIGHT_BYTES = 512 << 20
# The most bytes of hits that may lie staged in HBM, read by the restore
# thread and not yet taken by their admissions, under the rule above: a
# hit larger than this alone is read when nothing else is staged. A
# staged hit is 85 MB in mistral7b-sessions, 127-260 MB in xing4-29b,
# 193-395 MB in keye-vl2 and 258-666 MB in evabyte, and the v5e cells
# peak at 11.7-12.95 of 16 GB of HBM. The same array exists without the
# thread for the length of its admission; new is that it lies beside a
# decode step's temporaries, and beside the hits of the requests queued
# behind it while every slot is taken: that is what this bounds.
RESTORE_STAGED_BYTES = 256 << 20
# How many pages below the matched depth a hit of a family with state
# looks for a snapshot, one single-key probe a page (some 0.1 ms
# each): the next turn of a conversation adds an answer and a message,
# a few hundred tokens, to where the last snapshot lies.
SNAPSHOT_WALK = 32


@jax.jit
def _gather_pages(k_pool, v_pool, ids):
    """Every (layer, kind) row of the pool pages `ids` as ONE flat
    array, page-major: element order [len(ids), L, 2 (k, v), page,
    n_kv, hd] — the offload's device program. The pools are read where
    they lie (not donated, no layer sliced out: a sliced layer is a
    copied layer) and the only temporaries are the gathered rows
    themselves, at most the output's size (tests/test_offload_batch.py
    holds that). Page-major, so that the rows of a padded tail of `ids`
    are a suffix the host drops as a view; flat, because PJRT hands a
    multi-dimensional array to the host in its tiled device layout
    (tpu.to_host) — here the flattening is part of the program, not a
    second dispatch."""
    with jax.named_scope("pool.gather"):
        k = k_pool.at[:, ids].get(mode="promise_in_bounds")
        if v_pool is None:  # a latent family's one pool: [ids, L, ...]
            return jnp.swapaxes(k, 0, 1).reshape(-1)
        v = v_pool.at[:, ids].get(mode="promise_in_bounds")
        rows = jnp.stack([k, v], axis=2)  # [L, len(ids), 2, page, kv, hd]
        return jnp.swapaxes(rows, 0, 1).reshape(-1)


def _offload_bucket(n, cap):
    """The page count a gather of `n` pages runs at: `n` rounded up to
    a grid of four steps an octave (1..8, 10, 12, 14, 16, 20, 24, ...),
    at most `cap`. Padding is under a fifth of the bytes moved, and the
    gather programs are as many as the grid's points up to `cap` (12 up
    to 16, 26 up to 192) however many distinct counts traffic
    produces."""
    step = 1 << max(0, (n - 1).bit_length() - 3)
    return min(cap, -(-n // step) * step)


def _pow2_bucket(n, cap):
    """`_offload_bucket` for gathers whose page counts traffic does
    not fix (the banded layers' pool: what a slot has shed by its
    finish depends on the slots beside it): `n` rounded up to a power
    of two, at most `cap`. Few enough programs that the engine builds
    them all when it is made (`_init_window_pools`), so none is built
    inside a step; the padding is under half of a gather of at most
    16 MiB."""
    return min(cap, 1 << max(0, (n - 1).bit_length()))


def _upload_loop(engine_ref, todo, acked):
    """An engine's upload thread: `_Upload`s off `todo` in order, each
    run by the engine's `_run_upload` and put on `acked` whatever came
    of it; None ends it (`ServingEngine.close`, or the engine was
    collected). It holds the engine only while an upload runs."""
    while True:
        up = todo.get()
        engine = None if up is None else engine_ref()
        if engine is None:
            return
        engine._run_upload(up)
        acked.put(up)
        del engine, up


def _restore_loop(engine_ref, todo):
    """An engine's restore thread: `_Stage`s off `todo` in the order
    their requests arrived, each run by the engine's `_run_stage`; None
    ends it, as `_upload_loop`."""
    while True:
        st = todo.get()
        engine = None if st is None else engine_ref()
        if engine is None:
            return
        engine._run_stage(st)
        del engine, st


def _join(thread):
    """Wait for an engine's upload or restore thread to end, its queue
    closed: `ServingEngine.close`."""
    thread.join(timeout=60)
    if thread.is_alive():
        raise RuntimeError(
            f"{thread.name} did not stop; the store connection must not "
            f"be destroyed while it is running")


class ServingEngine:
    """Continuous-batching engine over the store, serving any model
    family that exposes the shared surface (models.llama, models.moe —
    prefill / prefill_with_prefix / decode_step / verify_step over the
    common KV page contract; pass it as `model`).

    `store` is a TpuKVStore (or None for store-less serving). Decoding
    is greedy by default; per-request seeded temperature/top-k sampling
    via Request(temperature=..., top_k=..., seed=...) — the RNG stream
    travels with the request, so sampled output reproduces across runs
    and across preemptions (with spec_k>0, reproducibility under
    preemption is at the distribution level — see _Work).
    """

    def __init__(self, params, cfg: llama.LlamaConfig, sconfig=None,
                 store=None, proposer=None, model=llama):
        # An engine lives where its weights live. Weights on ONE device
        # pin the pool, every restore and every step input to it — a
        # replica on chip 1 must not stage through chip 0, jax's default
        # — and are committed there, so steps run on it whichever thread
        # drives them (jax.default_device is thread-local, and
        # uncommitted arrays follow the default). Mesh-sharded weights
        # leave placement to GSPMD (device None).
        devs = {d for leaf in jax.tree_util.tree_leaves(params)
                for d in leaf.devices()}
        self.device = devs.pop() if len(devs) == 1 else None
        if self.device is not None:
            params = jax.device_put(params, self.device)
        elif jax.default_backend() == "tpu":
            # Seen on four v5e chips (PR 21): every step program fails to
            # lower, and the HTTP engine thread would only go down at its
            # first request. The attention calls need the shard_map form
            # (ops.pallas_paged_attention.decode_attention_tp) and a
            # pool sharded on kv heads first — ROADMAP D9.
            raise NotImplementedError(
                "ServingEngine over mesh-sharded weights is not brought "
                "up on TPU: its step programs call Pallas kernels under "
                "GSPMD, which the compiler rejects (\"Mosaic kernels "
                "cannot be automatically partitioned. Please wrap the "
                "call in a shard_map.\"). Run one engine per chip "
                "(weights on one device) instead."
            )
        self.params = params
        # What maps an engine's spans to its replica: the index of its
        # chip in the process (None over a mesh).
        self._device_index = getattr(self.device, "id", None)
        self.cfg = cfg
        # The model family: any module exposing the llama serving
        # surface (prefill, prefill_with_prefix, decode_step,
        # verify_step over the shared KV page contract) — models.moe
        # is the second family. Fused jits key on it statically.
        self.model = model
        self.sc = sconfig or ServingConfig()
        self.store = store
        self.proposer = proposer if proposer is not None \
            else prompt_lookup_propose
        # The cache manager's pools, by kind. Pages: K and V of the
        # layers that attend (all of them, for most families).
        # Which layers (by their rank among those that keep pages:
        # the L of a page's store keys) each pool holds. One kind of
        # attention layer: one pool of them all. Full AND banded
        # layers: the full ones here, the banded ones in a second pair
        # of pools under a short table a slot (`_init_window_pools`).
        spec = decoder.attn_layers(cfg)
        self._full_layers = [i for i, (*_, pool, _) in enumerate(spec)
                             if pool == "full"]
        self._win_layers = [i for i, (*_, pool, _) in enumerate(spec)
                            if pool == "window"]
        shape = (len(self._full_layers), self.sc.total_pages,
                 *cfg.kv_page_shape())
        self.k_pages = jnp.zeros(shape, dtype=cfg.jdtype,
                                 device=self.device)
        # A latent family keeps ONE page a layer (`cfg.page_kinds` one
        # letter): its rows live in `k_pages` [layers, pages, page,
        # width] and there is no second pool. Every program takes the
        # pair as it is: None is an empty pytree to jit.
        self._latent = "latent" in cfg.layer_kinds
        if self._latent:
            self._check_latent_family()
        # ... unless some of its layers own an indexer: the index keys
        # they cache are a further kind of page, of a width of its own
        # on those layers alone, held in a pool of its own under the
        # same page ids ([index layers, pages, page, index width]): a
        # latent family's second pool (models/glm.py), a K and V
        # family's third, beside V in the pair's second place
        # (models/keye.py; `_kind_pools`).
        self._index_kind = _index_kind(cfg)
        self._index_layers = list(cfg.page_layers(self._index_kind)) \
            if self._index_kind else []
        self.v_pages = None if self._latent else jnp.zeros_like(self.k_pages)
        if self._index_kind:
            ipool = jnp.zeros(
                (len(self._index_layers), self.sc.total_pages,
                 *cfg.page_shape(self._index_kind)),
                dtype=cfg.jdtype, device=self.device)
            if not self._latent:
                self._check_index_family()
            self.v_pages = ipool if self._latent else (self.v_pages, ipool)
        # A family whose finished windows fold into summary rows that
        # take their pages' place (models/evabyte.py): the window, in
        # positions (0: rows are positions, every other family), and in
        # pages what a window holds before and after its fold.
        self._fold = getattr(cfg, "fold_window", 0)
        if self._fold:
            self._check_fold_family()
            self._fold_in = self._fold // cfg.page_size
            self._fold_out = self._fold // cfg.fold_chunk // cfg.page_size
        self.wk_pages = self.wv_pages = None
        if self._win_layers:
            self._init_window_pools()
        # State: for a family with recurrent layers, what its model
        # declares (`model.state_pools`), a row a slot, and the same
        # again for the copies taken at each slot's last page edge:
        # where its stored pages can end, so what an offload writes
        # beside them. None for every other family.
        self.state = self.bstate = None
        if getattr(cfg, "n_state_layers", 0):
            self._check_state_family()
            self.state = model.state_pools(cfg, self.sc.max_slots,
                                           self.device)
            self.bstate = model.state_pools(cfg, self.sc.max_slots,
                                            self.device)
        # Page 0 is the scratch page: inactive decode slots scatter their
        # garbage KV there; sequences never own it.
        self.free_pages = list(range(1, self.sc.total_pages))
        self.page_table = np.zeros(
            (self.sc.max_slots, self.sc.max_pages_per_seq), dtype=np.int32
        )
        # whether a decode step over this table selects the rows it
        # attends (a table of index_topk rows or fewer is the dense path)
        self._selects = bool(self._index_kind) and decoder.indexed(
            cfg, self.page_table.shape[1] * cfg.page_size)
        self.slots = [None] * self.sc.max_slots
        self.queue = []
        self.outputs = {}
        self.stats = {
            "requests": 0, "prefix_hit_pages": 0, "restored_pages": 0,
            "prefill_tokens": 0, "decode_steps": 0, "decoded_tokens": 0,
            "offloaded_pages": 0, "preemptions": 0, "store_errors": 0,
            "restore_misses": 0, "spec_proposed": 0, "spec_accepted": 0,
            # hit pages this engine did not itself offload: another
            # engine over the same store wrote them
            "foreign_hit_pages": 0,
            # restores' page reads: contiguous runs of the store's pool
            # they spanned, bytes copied on the host (0 for one run)
            "restore_runs": 0, "restore_copied_bytes": 0,
            "prefetched_pages": 0,
            # admissions that returned for want of pool pages
            "admit_retries": 0,
            # XLA programs built (or read from the persistent cache)
            # inside a step: 0 once every shape is warm
            "compilations": 0,
            # families with state: snapshots an offload wrote / a hit
            # restored, page hits refused because the snapshot at
            # their end was not in the store, and boundary copies
            # dispatched behind decode steps
            "snapshots_written": 0, "snapshots_restored": 0,
            "snapshot_misses": 0, "boundary_copies": 0,
            # ... over the decode steps, the slots whose state a step
            # moved and the sequences it decoded (the same, since PR 52)
            "state_rows_run": 0, "state_rows_active": 0,
            # the admission programs' token-layer rows: those they ran,
            # of padded tokens x layers (fewer where the layers above
            # the last cache run on the kept row alone:
            # decoder.stack_rows), and over the decode steps the cache
            # rows of ANOTHER layer's pages that borrowing layers read
            # (live rows x such layers)
            "stack_rows_run": 0, "stack_rows_all": 0,
            "shared_kv_rows_read": 0,
            # hits whose snapshot lay below the pages' matched depth
            "snapshot_walkbacks": 0,
            # two kinds of attention layer: banded layers' pages that
            # left the band during decode (freed / of those written to
            # the store first), pages of banded layers a hit did not
            # transfer because its band spared them, and banded
            # layers' pages an admission wrote to the store without a
            # pool page (sequence pages, each over every banded layer)
            "window_pages_released": 0, "window_pages_offloaded": 0,
            "restore_trimmed_pages": 0, "subfloor_pages_written": 0,
            # the paged-decode kernel's work, summed over decode steps
            # and attention layers: table entries that held a key of an
            # active row's band, of all the entries of every row's
            # table (what a grid of one step an entry walked)
            "attn_pages_live": 0, "attn_pages_table": 0,
            # routed experts whose weights the single decode steps
            # fetched (models/moe.py:experts_gathered), of those their
            # layers hold (layers x experts a step)
            "moe_experts_fetched": 0, "moe_experts_held": 0,
            # a model whose layers hold a SHARE of the experts their
            # routers score (MoEConfig.holds_share), over admissions
            # and decode steps: the (token, chosen expert) pairs its
            # routers made for real tokens, those that fell on experts
            # held here (counted by the programs), and the rows the
            # admission programs' expert matmuls ran
            "moe_pairs_routed": 0, "moe_pairs_held": 0,
            "moe_rows_computed": 0,
            # offloads handed to the upload thread, times the engine
            # thread waited for room under UPLOAD_INFLIGHT_BYTES, and
            # what `done` waited for acknowledgements (a request's
            # finish to its tokens in `outputs`, summed, ms)
            "uploads": 0, "upload_backpressure_waits": 0,
            "done_held_ms": 0.0,
            # hits whose pages an admission took as the restore thread
            # had staged them, those of them taken before they were
            # done with a sequence in a slot (its steps waited: the
            # scheduler leaves such a head queued, so 0), and what the
            # engine thread waited for stagings in all, ms
            "restores_staged": 0, "restores_staged_late": 0,
            "restore_stage_wait_ms": 0.0,
            # plain decode steps (of decode_steps) whose program was
            # dispatched before the step before it had landed, and the
            # rows of such steps that were dropped unseen: their
            # sequence had ended at an EOS in the step before
            "decode_steps_ahead": 0, "decode_rows_dropped": 0,
            # admission in pieces: pieces run; a latent family: its
            # pages (a sequence page of one layer each) that offloads
            # wrote to the store and hits restored from it
            "admit_pieces": 0, "latent_pages_written": 0,
            "latent_pages_restored": 0,
            # a cache whose finished windows fold (models/evabyte.py):
            # windows folded and the pool pages that freed, summary
            # pages the folds wrote, those offloads sent to the store
            # and hits brought back, the exact pages (of the window a
            # prefix ends in) hits brought back, hits that found their
            # window's exact pages gone and began at its edge; and over
            # the decode steps, layers and active sequences, the cache
            # rows the tables held and the positions they stood for
            "windows_folded": 0, "fold_pages_freed": 0,
            "summary_pages_written": 0, "summary_pages_offloaded": 0,
            "summary_pages_restored": 0, "exact_pages_restored": 0,
            "hits_cut_to_window_edge": 0, "attn_rows_read": 0,
            "attn_positions_live": 0,
            # a learned selection (models/glm.py), over the decode
            # steps: index keys in every slot's table a layer that
            # owns an indexer, the slots the selection ran over (the
            # share of those keys it scored) and those of them that
            # held a sequence, cache rows the attention layers read and
            # rows that were live there (counted from the lengths held
            # here); index pages (a sequence page of one owner layer
            # each) that offloads wrote and hits restored
            "index_keys_scored": 0, "select_rows_run": 0,
            "select_rows_active": 0, "attn_rows_selected": 0,
            "attn_rows_live": 0, "index_pages_offloaded": 0,
            "index_pages_restored": 0,
            # the gaps between two tokens of one request, as `_emit`
            # counts them: how many, their ns on the engine's clock,
            # what the engine thread spent of them under a span of
            # each cause (GAP_CAUSES; the rest, `other`, is the loop
            # around the steps), and the gaps in which a cause other
            # than `step` ran at all
            "gap_tokens": 0, "gap_ns": 0, **dict.fromkeys(_GAP_KEYS, 0),
            "gaps_stalled": 0,
        }
        # Requests finished: in `outputs`, or held for their offload's
        # acknowledgement. What a driver reads as progress.
        self.finished = 0
        # The upload thread (started by the first offload), its queue
        # and the one acknowledgements come back on; uploads put and
        # not yet collected, and their bytes. Engine thread only, but
        # `_upload_failed`, which is the upload thread's.
        self._upload_thread = None
        self._todo = queue.SimpleQueue()
        self._acked = queue.SimpleQueue()
        self.uploads_pending = 0
        self._upload_bytes = 0
        self._upload_failed = False
        # The restore thread (started by the first request staged) and
        # its queue; the bytes staged and not yet taken, which both
        # threads move under the condition the restore thread waits
        # for room on.
        self._restore_thread = None
        self._to_stage = queue.SimpleQueue()
        self._stage_cv = threading.Condition()
        self._staged_bytes = 0
        # (pool, band) -> attention layers of that kind, and the entries
        # of every row's table over every attention layer
        self._attn_kinds = collections.Counter(
            (pool, band) for band, _, pool, _ in spec)
        # ... and the layers that attend ANOTHER layer's full pages
        # ("cross": they walk the page table as its owner does)
        self._borrowers = sum(k == "cross" for k in cfg.layer_kinds)
        if self._borrowers:
            self._attn_kinds[("full", 0)] += self._borrowers
        self._attn_table = self.sc.max_slots * sum(
            layers * (self.wtable if pool == "window"
                      else self.page_table).shape[1]
            for (pool, _), layers in self._attn_kinds.items())
        # routed experts the layers hold (0: a family without any);
        # where there are some, a decode program also says how many it
        # fetched
        self._experts_held = sum(
            layer["e_gate"].shape[0] for layer in params.get("layers", ())
            if "e_gate" in layer)
        # ... and those layers, where they hold a share of the experts
        # their routers score (0: every expert scored is held)
        self._share_layers = sum(
            "e_gate" in layer for layer in params.get("layers", ())
        ) if getattr(cfg, "holds_share", False) else 0
        self.engine_id = profiling.next_engine_id()
        # The gaps between tokens. ns the engine thread has spent under
        # spans of each cause (GAP_CAUSES), as of the last one that
        # closed, and the outermost such span open now (`_CauseSpan`);
        # the gaps `_emit` has seen and `_count_gaps` has not counted
        # yet, {mark they began at: how many}; and the mark at which a
        # plain decode step last landed its tokens (`_land`).
        self._cause_ns = [0] * len(GAP_CAUSES)
        self._cause_open = None
        self._gaps = {}
        self._landed_at = None
        self._own_digests = {}  # insertion-ordered, at most OWN_DIGESTS
        # One sequence page over every layer and kind the page pools
        # hold, and one state snapshot, in bytes.
        kinds = len(cfg.page_kinds)
        self._page_objects = kinds * self.k_pages.shape[0]
        self._page_bytes = kinds * self.k_pages.nbytes // self.sc.total_pages
        # The store keys that stand for a page in a hit's probe: one,
        # attention layer 0's. (Three kinds in a slot: the ONE full
        # layer's, whose pages a hit needs from page 0 on; attention
        # layer 0 there is banded, and what it computes below an
        # admission's band is never written.)
        self._probe_kinds = [(self._full_layers[0] if self._three_kinds()
                              else 0, cfg.page_kinds[0])]
        self._kinds_field = {}  # what a pool a kind adds to the spans
        if self._index_kind:
            # ... but kinds of different sizes on different layers, a
            # pool each: one sequence page's bytes by kind
            # (`_kind_bytes`); a page is a hit only with every kind, so
            # the probe asks for the LAST object an offload writes of
            # each kind.
            self._kind_bytes = [
                pool.nbytes // self.sc.total_pages
                for pool in _kind_pools(self.k_pages, self.v_pages)]
            self._page_objects = sum(
                len(cfg.page_layers(kind)) for kind in cfg.page_kinds)
            self._page_bytes = sum(self._kind_bytes)
            self._probe_kinds = [(cfg.page_layers(kind)[-1], kind)
                                 for kind in cfg.page_kinds]
            # `kinds` on istpu.cache.offload and .restore: the calls
            # (gather, transfer, store batch; get) each makes
            self._kinds_field = {"kinds": len(cfg.page_kinds)}
        if self._fold:
            # summary pages and exact pages: two kinds of store key of
            # one shape ("sk" / "sv" beside "k" / "v"), probed by
            # attention layer 0's first
            self._kinds_field = {"kinds": 2}
        if self.sc.admit_piece % cfg.page_size:
            raise ValueError(
                f"admit_piece {self.sc.admit_piece} is no multiple of the "
                f"page ({cfg.page_size} tokens)")
        self._piece_ran = False  # a piece ran in the step under way
        self._snapshot_bytes = 0
        self._snapshot_fields = {}  # what its spans carry beyond pages'
        if self.state is not None:
            self._snapshot_row = _snapshot_row_elems(cfg)
            self._snapshot_bytes = (cfg.n_state_layers * self._snapshot_row
                                    * cfg.state_jdtype.itemsize)
            self._snapshot_fields = {"snapshot_bytes": self._snapshot_bytes}
        # The store is an accelerator, never a dependency: after the
        # first store failure the engine downgrades itself to store-less
        # serving (full prefills, no offload) instead of failing
        # requests on a cache.
        self._store_ok = True
        # Steady-state decode device cache: (key, token_dev, lens_dev,
        # rows_dev) left by the previous fused step. While the active
        # set, page tables and emitted tokens are exactly what the
        # device already holds (pure-greedy lockstep decode — the
        # common serving state), the next step re-uses them and issues
        # ONE dispatch + one tiny D2H instead of re-uploading host
        # state. _pages_rev is bumped by every page-table mutation so
        # staleness is structural, not heuristic.
        self._steady = None
        self._pages_rev = 0
        # The plain decode step that is dispatched and not landed
        # (`_step` sends the next one behind it), or None.
        self._flight = None
        # The operand of _tick, when idle() last sent it, and when an
        # admission last found no sequence running (_settle).
        self._tick_x = self._to_device(np.int32(0))
        self._ticked = time.monotonic()
        self._left_idle = -SETTLE_S
        # Everything that shapes page BYTES goes into the key namespace:
        # engines differing in any of these must never cross-hit. When
        # the caller left model_id at its default AND a store is
        # attached, derive a weights fingerprint so two engines with
        # different checkpoints (but identical KV geometry) sharing one
        # store can never silently cross-hit each other's cached KV.
        model_id = self.sc.model_id
        if store is not None and model_id == "default":
            model_id = f"wf{self._weights_fingerprint()}"
        wire = "q8" if self.sc.quantized_store else cfg.dtype
        self._ns = (
            f"{model_id}/p{cfg.page_size}/l{cfg.n_layers}"
            f"/kv{cfg.n_kv_heads}x{cfg.head_dim}/{wire}"
        )
        if self._latent:
            # ... a latent row is no K page, whatever its bytes
            self._ns += f"/latent{cfg.kv_lora_rank}+{cfg.qk_rope}" \
                f"w{cfg.latent_width}"
        if self._index_kind:
            # ... and which layers keep index keys, how wide
            self._ns += (f"/index{cfg.index_dim}@"
                         + ".".join(map(str, self._index_layers)))
            if not self._latent:  # ... in how many lanes a key
                self._ns += f"w{cfg.index_width}"
        if self._fold:
            # ... a row of a folded window is no position's
            self._ns += f"/fold{self._fold}c{cfg.fold_chunk}"
        if self._win_layers:
            # ... of every cache kind: which layers are banded, and how
            # widely (a page of a banded layer is not a page of a full
            # one, whatever the weights).
            self._ns += (f"/band{cfg.window_band}@"
                         + ".".join(map(str, self._win_layers)))
        if self.state is not None:
            # ... of every cache kind: which layers keep pages, and the
            # state's geometry and dtype.
            shapes = "+".join("x".join(map(str, shape))
                              for shape in cfg.state_shapes().values())
            self._ns += (f"/kvl{cfg.n_kv_layers}/st{cfg.n_state_layers}"
                         f"x{shapes}/{cfg.state_dtype}")
        if store is not None and self.sc.quantized_store:
            self._get_pages = partial(store.get_kv_pages_quantized,
                                      device=self.device)
            self._put_pages = store.put_kv_pages_quantized
        elif store is not None:
            self._get_pages = partial(store.get_kv_pages,
                                      device=self.device)
            self._put_pages = store.put_kv_pages

    def _init_window_pools(self):
        """The banded layers' pools, short tables and free list (a
        model with full and banded attention layers). Sized from the
        slots, the band and the page, not configured: a sequence never
        attends more than `band` positions of a banded layer, which
        lie in at most band / page + 1 pages; the table has room for
        one more (the page being written) and is rounded up to 8
        entries, and that slack is what lets the pages that left the
        band go a few at a time and several slots' together
        (`_shed_windows`). Page 0 is scratch, as in
        the full pools. What is not built over two kinds is refused
        here, not found at the first request: verify and burst steps,
        a piece's prefix, the int8 wire and packed rows address ONE
        page pool, and state layers beside them are built for ONE
        combination (`_three_kinds`)."""
        cfg, sc = self.cfg, self.sc
        bands = {w for w, *_ in decoder.attn_layers(cfg) if w}
        three = self._three_kinds()
        self._refuse("full and banded attention layers", {
            "spec_k": sc.spec_k > 0, "host_steps": sc.host_steps > 1,
            "admit_piece": sc.admit_piece > 0,
            "quantized_store": sc.quantized_store,
            "kv_pack": cfg.kv_pack > 1 and not three,
            "state layers": bool(getattr(cfg, "n_state_layers", 0))
            and not three,
            "more than one band": len(bands) > 1,
            "a band that is no page multiple":
                cfg.window_band % cfg.page_size != 0})
        self._band_pages = cfg.window_band // cfg.page_size
        self._wtable_w = -(-(self._band_pages + 2) // 8) * 8
        self._shed_pages = max(
            1, (self._wtable_w - self._band_pages - 1) // 2)
        self._wpool_pages = sc.max_slots * self._wtable_w + 1
        shape = (len(self._win_layers), self._wpool_pages,
                 *cfg.kv_page_shape())
        self.wk_pages = jnp.zeros(shape, dtype=cfg.jdtype,
                                  device=self.device)
        self.wv_pages = jnp.zeros_like(self.wk_pages)
        self.wfree = list(range(1, self._wpool_pages))
        self.wtable = np.zeros((sc.max_slots, self._wtable_w), np.int32)
        # One sequence page over the banded layers, K and V, in bytes.
        self._wpage_bytes = 2 * len(self._win_layers) * cfg.kv_page_bytes()
        # Every gather program over these pools, built now
        # (`_pow2_bucket`): the counts they run at are decided by what
        # the slots shed and when, not by the traffic's shapes.
        cap = self._chunk_pages(self._wpage_bytes)
        for n in sorted({_pow2_bucket(1 << i, cap)
                         for i in range(cap.bit_length() + 1)}):
            jax.block_until_ready(_gather_pages(
                self.wk_pages, self.wv_pages,
                self._to_device(np.zeros(n, np.int32))))

    def _check_latent_family(self):
        """What is not built over a latent pool is refused at
        construction, not found at the first request: verify and burst
        steps address K and V pages by head, the int8 wire quantizes a
        row a head, and packed rows are heads side by side."""
        sc, cfg = self.sc, self.cfg
        self._refuse("a latent cache", {
            "spec_k": sc.spec_k > 0, "host_steps": sc.host_steps > 1,
            "quantized_store": sc.quantized_store,
            "kv_pack": cfg.kv_pack > 1, "window": bool(cfg.window_band),
            "state layers": bool(getattr(cfg, "n_state_layers", 0))})
        if _index_kind(cfg):
            self._check_index_family()

    def _check_index_family(self):
        """What is not built over an index pool (a family whose layers
        select the rows they attend: models/glm.py over a latent
        cache, models/keye.py over K and V pages) is refused at
        construction too. Beside what a latent cache refuses (a
        verify or burst step would have to make and carry a selection
        a drafted token, and the int8 wire knows one page shape): a
        spec that is not one entry a layer or whose first layer
        borrows a selection (none is made below it; so some layer owns
        an indexer and the index pool has a layer), and several
        residual streams (the
        indexer reads ONE normalised stream; which, no published model
        says). Over K and V pages, three pools, the same options are
        refused by name (speculation, bursts, the int8 wire, packed
        rows, a window, state layers: none is built over three pools),
        and a layer that borrows its selection (an attention layer
        makes its own)."""
        sc, cfg = self.sc, self.cfg
        kinds = getattr(cfg, "indexer_kinds", ())
        over_kv = {} if self._latent else {
            "spec_k": sc.spec_k > 0, "host_steps": sc.host_steps > 1,
            "quantized_store": sc.quantized_store,
            "kv_pack": cfg.kv_pack > 1, "window": bool(cfg.window_band),
            "state layers": bool(getattr(cfg, "n_state_layers", 0)),
            "a layer that borrows its selection":
                any(k != "full" for k in kinds)}
        self._refuse("an index pool", {
            "an indexer spec that is not one entry a layer":
                len(kinds) != cfg.n_layers,
            "a first layer that borrows its selection":
                kinds[:1] != ("full",),
            "hc_mult": cfg.hc_mult > 1, **over_kv})

    def _three_kinds(self):
        """THE rule of what runs over three kinds of cache in one
        sequence (full pages, banded pages, a recurrent state): state
        layers beside full and banded attention layers whose cache
        rows are packed BY PAIR (`cfg.pair_rows`: a row is the two kv
        heads one differential pair reads, so both pairs of pools take
        the one `kv_page_shape` and no call unpacks a row). Every
        other mix of these stays refused by name: packed rows over two
        kinds for any other family (`unpack_heads` over a second pool
        is not built), state layers beside two kinds without them, and
        every option either family refuses."""
        cfg = self.cfg
        return bool(cfg.two_kinds and getattr(cfg, "n_state_layers", 0)
                    and getattr(cfg, "pair_rows", False))

    def _check_state_family(self):
        """What is not built over a recurrent state is refused at
        construction, not found at the first request: a rejected draft
        cannot be rolled back out of a state, bursts would need the
        boundary copy inside the scan, a piece would need the state its
        predecessor left as a hit needs a snapshot, and the int8 wire is
        defined for pages. ONE sliding window over every layer is
        refused too: its release (`_release_windowed`) frees the ONE
        pool's pages by one global band, and a family whose state layers
        carry the long range has none. Banded layers BESIDE full ones
        are no such window: their pages live in pools of their own
        under a short table (`_init_window_pools`, which refuses state
        layers for every combination but `_three_kinds`), the full
        layers' pages stay, and the state's boundary copy and snapshot
        follow the page edges of the sequence, which both kinds
        share."""
        sc = self.sc
        self._refuse("state layers", {
            "spec_k": sc.spec_k > 0, "host_steps": sc.host_steps > 1,
            "admit_piece": sc.admit_piece > 0,
            "quantized_store": sc.quantized_store,
            "window": bool(self.cfg.window)})

    def _check_fold_family(self):
        """What is not built over a cache whose finished windows fold
        (a table of rows, not positions) is refused at construction,
        by name: a verify or burst step writes several rows a sequence
        across what may be a window's edge, the int8 wire and packed
        rows know one kind of row, a sliding band would release pages
        by position under a table that is not by position, and a
        second pool (banded layers', a recurrent state's, a latent or
        an index pool) would need a fold, or a rule against one, of
        its own. The window is a whole number of pages and a chunk is
        the page (a summary page stands for whole pages of positions:
        models/evabyte.py's config refuses the rest too)."""
        sc, cfg = self.sc, self.cfg
        self._refuse("a folded cache", {
            "spec_k": sc.spec_k > 0, "host_steps": sc.host_steps > 1,
            "quantized_store": sc.quantized_store,
            "kv_pack": cfg.kv_pack > 1,
            "window": bool(cfg.window_band),
            "state layers": bool(getattr(cfg, "n_state_layers", 0)),
            "a latent pool": self._latent,
            "an index pool": bool(self._index_kind),
            "a fold_chunk that is not the page":
                cfg.fold_chunk != cfg.page_size,
            "a fold_window that is no multiple of whole summary pages":
                self._fold % (cfg.fold_chunk * cfg.page_size) != 0,
            "an admit_piece longer than the window":
                sc.admit_piece > self._fold})

    def _refuse(self, over, options):
        """Raise for the first of `options` (name: whether it is on)
        that is not built for a model with `over`."""
        for name, on in options.items():
            if on:
                raise ValueError(
                    f"{name} is not supported for a model with {over} "
                    f"({type(self.cfg).__name__})")

    def _to_device(self, host):
        """Host array -> the engine's device (None: jax's default)."""
        return jax.device_put(host, self.device)

    def _span(self, name, request=None, **fields):
        """A span of this engine in the program's ring
        (utils/profiling.py); docs/serving.md lists the names. One
        that is a cause of a gap between tokens adds its time to it
        (`_CauseSpan`): those names are the engine thread's alone."""
        if name in _CAUSE_OF_SPAN:
            span = _CauseSpan(name, request, self.engine_id, **fields)
            span.owner = self
            return span
        return profiling.span(name, request, self.engine_id, **fields)

    def _weights_fingerprint(self):
        """Cheap checkpoint identity for the store-key namespace: sha256
        over every leaf's (shape, dtype) plus a fused POSITION-WEIGHTED
        per-leaf float32 checksum (ONE device program + one tiny
        transfer at engine init). The position weights matter: a plain
        sum is permutation-invariant, so two checkpoints that are
        element-permutations of each other (the same model exported
        with different head/QKV layouts) would collide — exactly the
        cross-hit this fingerprint exists to prevent. Computed only
        when the caller left model_id at its default with a store
        attached. Backend-specific reduction order means the same
        checkpoint may fingerprint differently on different backends —
        a cache MISS, never a cross-hit."""
        leaves = jax.tree_util.tree_leaves(self.params)
        h = hashlib.sha256()
        for leaf in leaves:
            h.update(str((tuple(leaf.shape), str(leaf.dtype))).encode())

        def _checksum(x):
            f = jnp.ravel(x).astype(jnp.float32)
            w = (jnp.arange(f.shape[0], dtype=jnp.float32) % 251.0) + 1.0
            return jnp.sum(f * w, dtype=jnp.float32)

        sums = jax.jit(
            lambda ls: jnp.stack([_checksum(x) for x in ls])
        )(leaves)
        h.update(np.asarray(sums, dtype=np.float32).tobytes())
        return h.hexdigest()[:16]

    def _digests(self, tokens, n_pages):
        return content_page_digests(
            tokens, self.cfg.page_size, n_pages, namespace=self._ns
        )

    def _slot_digests(self, slot, n_pages):
        """content_page_digests, amortized per slot: the chain only ever
        APPENDS as generation grows (page i's digest depends only on
        tokens < (i+1)*page_size), so each page is hashed once per slot
        instead of restarting the sha chain at token 0 on every offload
        — windowed release fires every page_size tokens, which would
        otherwise make cumulative digest work O(seq^2). Token chunks
        come straight from prompt/generated slices (no O(seq) list
        concatenation per call)."""
        if len(slot.digests) >= n_pages:
            return slot.digests[:n_pages]
        if slot.digest_h is None:
            slot.digest_h = hashlib.sha256(self._ns.encode())
        ps = self.cfg.page_size
        prompt = slot.work.prompt
        n_p = len(prompt)

        def tok_slice(a, b):
            if b <= n_p:
                return prompt[a:b]
            if a >= n_p:
                return slot.generated[a - n_p:b - n_p]
            return list(prompt[a:]) + list(slot.generated[:b - n_p])

        _extend_digest_chain(
            slot.digest_h, slot.digests,
            lambda i: tok_slice(i * ps, (i + 1) * ps), n_pages,
        )
        return slot.digests[:n_pages]

    # ---- admission -----------------------------------------------------

    def submit(self, req: Request):
        if len(req.prompt) < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            # Admission always derives one token from the prompt's last
            # logits; a 0-token budget would still generate (and stream)
            # it, so reject the request up front instead.
            raise ValueError("max_new_tokens must be >= 1")
        n = len(req.prompt) + req.max_new_tokens
        need = self._pages_on_the_way(n)
        if need > self.sc.max_pages_per_seq:
            what = "cache rows" if self._fold else "positions"
            raise ValueError(
                f"request needs {need} pages of {what} ({n} positions) "
                f"> max_pages_per_seq {self.sc.max_pages_per_seq}"
            )
        work = _Work(req=req, prompt=list(req.prompt),
                     queue_len=len(self.queue))
        self.queue.append(work)
        self.stats["requests"] += 1
        # (a prompt within one page has no full page to hit: `_probe`)
        if self._store_chain(work) and len(work.prompt) > self.cfg.page_size:
            self._stage(work)

    def _pages_on_the_way(self, n):
        """The most pool pages a sequence holds on its way to `n`
        positions: their pages, where rows are positions. Over a
        folded cache, the summary pages of the windows before the last
        position's and either that window's pages so far or, just
        before the fold before it, a whole window's beside one window's
        summaries fewer."""
        page = self.cfg.page_size
        if not self._fold:
            return -(-n // page)
        w = (n - 1) // self._fold
        here = self._fold_out * w - (-(n - w * self._fold) // page)
        return max(here, self._fold_out * (w - 1) + self._fold_in) \
            if w else here

    def _alloc(self, n):
        if len(self.free_pages) < n:
            return None
        ids, self.free_pages = self.free_pages[:n], self.free_pages[n:]
        return ids

    def _pad_ids(self, ids, offset=0):
        """Pad a page-id list to the fixed arity max_pages_per_seq with
        the total_pages sentinel (mode=\"drop\" discards those writes) —
        the ONE place the fixed-arity convention lives. `offset` places
        the ids at [offset, offset+len): the windowed cold path drops
        its dead leading pages by leaving [0, offset) at the
        sentinel."""
        ids_p = np.full(self.sc.max_pages_per_seq, self.sc.total_pages,
                        dtype=np.int32)
        ids_p[offset:offset + len(ids)] = ids
        return ids_p

    def _store_failed(self, what, exc):
        """First store failure downgrades to store-less serving: the
        cache accelerates, it must never fail a request."""
        self._store_ok = False
        self.stats["store_errors"] += 1
        logging.getLogger("infinistore_tpu.serving").warning(
            "store %s failed (%s: %s) — continuing store-less",
            what, type(exc).__name__, exc,
        )

    def _probe_hit(self, work):
        """Page-granular prefix hit, capped so at least one prompt token
        remains to prefill (the engine needs its logits). Returns
        (hit, digests[:hit]) so the restore reuses the hash chain. ON
        THE ENGINE THREAD: `_probe`, counted, a store failure taken
        home."""
        if not self._store_chain(work):
            return 0, []
        try:
            hit, digests, counts = self._probe(work.prompt,
                                               work.req.request_id)
        except Exception as e:
            self._store_failed("probe", e)
            return 0, []
        for name, n in counts.items():
            self.stats[name] += n
        return hit, digests

    def _probe(self, prompt, request):
        """`_probe_hit` as either thread runs it, the engine's or its
        restore thread: it touches nothing of the engine that changes
        and raises what the store raises. Returns (hit, digests[:hit],
        {stat: what the probe adds to it once it is taken})."""
        cap = (len(prompt) - 1) // self.cfg.page_size
        counts = {}
        if cap == 0:
            return 0, [], counts
        with self._span("istpu.cache.probe", request, pages=cap) as f:
            digests = self._digests(prompt, cap)
            if self._fold:
                hit = self._probe_folded(digests, cap)
            else:
                # ONE call over the probed key of every kind of each
                # page, page-major: a page counts with all of them
                per = len(self._probe_kinds)
                found = self.store.cached_prefix_len(
                    [key for d in digests[:cap]
                     for layer, kind in self._probe_kinds
                     for key in content_page_keys_by_page([d], [layer],
                                                          kind)])
                hit = min(found // per, cap)
                if hit > 0 and self.state is not None:
                    hit = self._probe_snapshot(hit, digests, counts)
            f["hit_pages"] = hit
            if hit > 0:
                counts["prefetched_pages"] = self._prefetch_chain(
                    prompt, hit, digests[:hit])
        return hit, digests[:hit], counts

    def _fold_split(self, hit):
        """(summary pages, exact pages) a folded prefix of `hit` token
        pages is made of: every window that ends at or below it as its
        summary pages, the rest exactly."""
        w = hit // self._fold_in
        return w * self._fold_out, hit - w * self._fold_in

    def _summary_digests(self, digests, lo, hi):
        """The digests that key summary pages [lo, hi): a summary page
        stands for `page_size` chunks = `page_size` token pages, and is
        keyed by the LAST of them (so by every token it covers and all
        before it)."""
        page = self.cfg.page_size
        return [digests[(j + 1) * page - 1] for j in range(lo, hi)]

    def _probe_folded(self, digests, cap):
        """The depth, in token pages, a folded cache can restore of the
        chain `digests[:cap]`: for a prefix of P pages the store must
        hold the SUMMARY pages of every window that ends at or below P
        and the EXACT pages from that window's edge to P (what a finish
        wrote: `_offload_folded`). One call over the summary pages'
        probe keys of the `cap // window` whole windows and, behind
        them, the exact pages' of the window `cap` lies in: where a
        finished turn of the same sequence ended in that window, the
        common case, it answers both. Where the summaries end earlier
        (the stored sequence ended a window below), a second call over
        THAT window's exact pages. Exact pages missing: the hit is cut
        back to its window's edge, where summaries alone suffice
        (`cut_to_window_edge` on its admission's span); summaries
        missing from the first
        window on: cold. Never a whole window's exact pages: its fold
        would have to run before the first piece, so such a hit ends a
        page short."""
        (layer, kind), = self._probe_kinds
        per_w, out = self._fold_in, self._fold_out

        def exact_keys(w):
            return content_page_keys_by_page(
                digests[w * per_w:min(cap, (w + 1) * per_w)], [layer], kind)

        w_cap = cap // per_w
        sums = content_page_keys_by_page(
            self._summary_digests(digests, 0, w_cap * out), [layer],
            ("s" + kind,))
        found = self.store.cached_prefix_len(sums + exact_keys(w_cap))
        if found >= len(sums):
            w, n_exact = w_cap, found - len(sums)
        else:
            w = found // out
            n_exact = self.store.cached_prefix_len(exact_keys(w))
        return min(w * per_w + n_exact, (w + 1) * per_w - 1, cap)

    def _probe_snapshot(self, hit, digests, counts):
        """The depth a family with state can restore, given `hit`
        matched pages (`counts`: `_probe`'s, which this adds to): the
        deepest d <= hit at which a snapshot lies in
        the store, 0 if none within SNAPSHOT_WALK pages. Pages without
        the state at their end are no prefix. A finish writes pages and
        snapshot at ONE depth, so the common case is one probe, of the
        snapshot at `hit` (its last row's key: rows are written in
        order). Where that is not there (the pages matched deeper than
        the snapshot lies: a prompt that shares only part of a stored
        sequence, or that sequence's own earlier turn asked again), the
        walk goes back a page a probe; the store's match over a key
        list stops at the first hole, so sparse snapshot keys cannot be
        found by one call over the chain. Pages beyond d are prefilled
        again. Nothing found within the bound (or the snapshot was
        evicted and its pages not): admitted cold, and counted."""
        last = self.cfg.n_state_layers - 1
        for d in range(hit, max(hit - SNAPSHOT_WALK, 0), -1):
            if self.store.cached_prefix_len(
                    snapshot_keys(digests[d - 1], last, last + 1)):
                counts["snapshot_walkbacks"] = int(d < hit)
                return d
        counts["snapshot_misses"] = 1
        return 0

    def _prefetch_chain(self, prompt, hit, digests):
        """Fire-and-forget OP_PREFETCH for the matched page chain —
        every (layer, kind) page the restore will read. The probe just
        told us the engine's exact future reads; the store's async read
        pipeline promotes any disk-resident pages on ITS worker thread,
        so by the time prefill_with_prefix (or a preemption resume,
        which re-admits through this same probe path) pins the pages
        they are pool-resident and the restore pays zero inline disk
        reads. Purely advisory: failures are swallowed — a broken hint
        must never fail (or even slow) an admission. Returns the pages
        it asked for (`_probe` counts them)."""
        fn = getattr(self.store, "prefetch", None)
        if fn is None:
            return 0
        cfg = self.cfg
        try:
            if self._win_layers or self._fold:
                # what the restore will read, no more
                keys = self._restore_keys(hit, digests,
                                          self._first_live(hit))
            elif self._index_kind:
                keys = sum(self._restore_keys(hit, digests, 0), [])
            else:
                keys = [key for li in range(cfg.n_kv_layers)
                        for kind in cfg.page_kinds
                        for key in content_page_keys(
                            prompt, cfg.page_size, hit, li, kind,
                            digests=digests)]
            if self.state is not None:
                keys.extend(snapshot_keys(digests[hit - 1], 0,
                                          self.cfg.n_state_layers))
            return len(keys) if fn(keys) else 0
        except Exception:
            return 0

    def _admit(self, slot_idx, work):
        n_prompt = len(work.prompt)
        n_pages = -(-n_prompt // self.cfg.page_size)
        rid = work.req.request_id
        t0_ns = time.time_ns()
        fresh = work.gap_at is None
        with self._span("istpu.sched.admit", rid, slot=slot_idx,
                        prompt_tokens=n_prompt, hit_pages=0,
                        foreign_pages=0, staged_ns=0,
                        staged_wait_ns=0) as f:
            admitted = self._do_admit(slot_idx, work, n_prompt, n_pages, f)
        if fresh and work.gap_at is not None:
            # Its first token left inside the span: the causes are
            # marked anew behind it, so that a request's OWN admission
            # is in none of its gaps (what is left of the span behind
            # the token reads as `other`).
            work.gap_at = (work.gap_at[0], *self._cause_ns)
        if admitted:
            # Known only now that an admission went through: arrival
            # (or swap-out) to the start of that admission.
            profiling.record(
                "istpu.sched.queue_wait", work.queued_ns,
                t0_ns - work.queued_ns, rid, self.engine_id,
                slot=slot_idx, queue_len=work.queue_len,
            )
        return admitted

    def _do_admit(self, slot_idx, work, n_prompt, n_pages, f):
        """`f`: the fields of the admission's span; every way out
        leaves its `outcome` there."""
        cfg = self.cfg
        page = cfg.page_size
        window = cfg.window
        store_chain = self._store_chain(work)
        if work.staged is not None:
            if not store_chain:
                self.unstage(work)  # the store went while it waited
            elif not work.staged.done.is_set():
                self.stats["restores_staged_late"] += self._occupied()
        if work.probe is None:
            work.probe = self._staged_probe(work, f) \
                or self._probe_hit(work)
        hit, digests = work.probe
        if self._win_layers:
            return self._do_admit_two(slot_idx, work, n_prompt, n_pages,
                                      hit if store_chain else 0, digests, f)
        if self._fold:
            return self._do_admit_folded(slot_idx, work,
                                         hit if store_chain else 0,
                                         digests, f)
        if not store_chain and hit:
            # The probe is cached on work while the request waits under
            # pool pressure, so it can OUTLIVE the store: another slot's
            # store failure latching _store_ok=False between the probe
            # and this (re)admission would leave hit > 0 while skip is
            # computed store-less (p0, not first_live), and the restore
            # would trip the pool-placement `assert skip == first_live`.
            # A dead store chain means a cache MISS, not a smaller hit.
            hit, digests = 0, []
        # Windowed admission floors. Two distinct boundaries:
        #   first_live — earliest page the SUFFIX PREFILL can attend
        #     (the first suffix query sits at hit*page; its band floor
        #     is hit*page - window + 1), so restore transfers only
        #     [first_live, hit);
        #   p0 — earliest page anything can attend AFTER admission
        #     (floor of the last prompt position), so the one-shot path
        #     allocates pool pages only for [p0, n_pages) — this is
        #     what makes preemption re-admission of an over-pool grown
        #     prompt possible at all: the pool cost is O(window), not
        #     O(prompt).
        first_live = self._first_live(hit)
        p0 = max(0, n_prompt - window) // page if window else 0
        # How many leading pages never get a pool page:
        #   - with a store (and caching on), only pages the store
        #     ALREADY holds ([0, first_live) ⊆ the hit) can be skipped
        #     — un-cached sub-floor pages must be materialized once so
        #     release can offload them and keep the prefix chain
        #     gap-free for future hits;
        #   - an admission in pieces needs pool pages from first_live
        #     too: a later piece attends POOL pages of an earlier one
        #     that lie below p0 (its floor rises as pieces consume the
        #     prompt, and _release_windowed frees on the way);
        #   - else store-less (or cache=False), nothing is ever
        #     offloaded, so every page below p0 is droppable outright.
        if self._in_pieces(n_prompt - hit * page) or store_chain:
            skip = min(first_live, hit)
        else:
            skip = p0
        # Allocate BEFORE restoring: under pool pressure a queued
        # request retries admission every step, and paying the store
        # transfer just to throw it away on a failed _alloc (and
        # inflating the hit/restore stats each retry) would make
        # waiting quadratically expensive. skip depends only on the
        # probe, never on the restore.
        ids = self._alloc(n_pages - skip)
        if ids is None:
            self.stats["admit_retries"] += 1
            f["outcome"] = "no_pages"
            return False  # pool pressure: stay queued
        return self._admit_with_pages(
            slot_idx, work, ids, n_prompt, n_pages, hit, digests,
            skip, first_live, f,
        )

    def _admit_with_pages(self, slot_idx, work, ids, n_prompt, n_pages,
                          hit, digests, skip, first_live, f):
        """Everything after a successful allocation, wrapped so that
        ANY escaping exception (restore-side OOM building prefix_kvs,
        prefill failure, connection loss) refunds the pages — `ids`
        may be rebound by the restore-failure top-up, and the handler
        sees the latest binding."""
        try:
            return self._admit_restore_and_prefill(
                slot_idx, work, ids, n_prompt, n_pages, hit, digests,
                skip, first_live, f,
            )
        except _AdmitPagesRefunded:
            self.stats["admit_retries"] += 1
            f["outcome"] = "refunded"
            return False
        except BaseException:
            self.free_pages.extend(self._admit_ids_view)
            raise

    def _restore_size(self, hit, first_live):
        """(pages, bytes, the pages by kind) of a hit's restore as its
        spans count them."""
        n = hit - first_live
        if self._win_layers:
            # Two kinds: the full layers' [0, hit) and the banded
            # layers' [first_live, hit) in the one call, in the order
            # `_admit_fused_px_wf` takes them; `pages` stays the hit's
            # depth, `bytes` what crosses over.
            return (hit, hit * self._page_bytes + n * self._wpage_bytes
                    + self._snapshot_bytes,
                    {"full_pages": hit, "window_pages": n,
                     "trimmed_pages": first_live})
        if self._fold:
            # A folded prefix: the summary pages of its finished
            # windows and the exact pages behind them, one shape, ONE
            # call; `pages` counts what crosses over.
            n_sum, n_exact = self._fold_split(hit)
            return (n_sum + n_exact, (n_sum + n_exact) * self._page_bytes,
                    {"summary_pages": n_sum, "exact_pages": n_exact})
        return n, n * self._page_bytes + self._snapshot_bytes, {}

    def _read_hit(self, hit, digests, first_live):
        """Pages [first_live, hit) of the probed chain `digests`, store ->
        HBM in ONE batched call over every layer and kind, as the store
        call returns them: page-major [(hit - first_live) * L * 2, page,
        n_kv, hd] (decoder.restored_to_pages splits that; page-major is
        the order an offload allocated the keys in, so what one offload
        wrote is one zero-copy view of the store's pool). Digests are
        layer/kind-independent and come from the probe — the prompt is
        hashed ONCE per admission. For a family with state, a second
        call brings the snapshot taken at the end of page `hit`, [state
        layers, row]. On the thread that calls it, the engine's or its
        restore thread, and touching nothing of the engine that
        changes. Returns (pages, snapshot or None, how the pages lay
        in the store's pool: the contiguous runs the read spanned and
        the bytes it copied on the host, 0 for one run transferred from
        the pool itself; None from a store that does not say)."""
        keys = self._restore_keys(hit, digests, first_live)
        if self._index_kind:
            # a call a kind: a store call carries pages of ONE shape
            pages = tuple(
                self._get_pages(ks, self.cfg.page_shape(kind),
                                self.cfg.jdtype)
                for ks, kind in zip(keys, self.cfg.page_kinds))
        else:
            pages = self._get_pages(keys, self.cfg.kv_page_shape(),
                                    self.cfg.jdtype)
        read = getattr(self.store, "last_read", None)  # this thread's
        if self.state is None:
            return pages, None, read
        # The snapshot's way in: its store call (store -> HBM); it
        # is placed into the slot inside the hit program.
        with self._span("istpu.cache.state_in", bytes=self._snapshot_bytes):
            return pages, self._get_pages(
                snapshot_keys(digests[hit - 1], 0, self.cfg.n_state_layers),
                (self._snapshot_row,), self.cfg.state_jdtype), read

    def _restore(self, hit, digests, first_live=0, foreign=0, st=None,
                 fa=None):
        """A hit's pages and snapshot in HBM, (pages, snapshot or
        None), ON THE ENGINE THREAD under istpu.cache.restore, which
        times what this thread pays for them: with `st`, the request's
        `_Stage` (and `fa`, its admission's fields), the wait for what
        the restore thread read, near 0 where it is done; without, or
        where that thread read nothing, `_read_hit` here."""
        n, nbytes, kinds = self._restore_size(hit, first_live)
        with self._span("istpu.cache.restore", pages=n, bytes=nbytes,
                        foreign_pages=foreign, **kinds, **self._kinds_field,
                        **self._snapshot_fields) as f:
            got = None
            if st is not None:
                self._await_stage(st, st.done, fa)
                if st.error is not None:
                    raise st.error[1]
                if st.restored is not None:
                    got = st.restored, st.snap, st.read
                    fa["staged_ns"] = st.dur_ns
                    self.stats["restores_staged"] += 1
            pages, snap, read = got or self._read_hit(hit, digests,
                                                      first_live)
            if read is not None:
                f.update(read)
                self.stats["restore_runs"] += read["runs"]
                self.stats["restore_copied_bytes"] += read["copied_bytes"]
            return pages, snap

    def _store_chain(self, work):
        """Whether this request's pages go to and come from the
        store."""
        return (self.store is not None and self._store_ok
                and work.req.cache)

    def _try_restore(self, work, hit, digests, first_live, f):
        """`_restore`, counted, with a failure turned into a miss:
        (pages, snapshot, the hit that holds: 0 after a failure). `f`:
        the admission span's fields. What `work` has staged for this
        (hit, first_live) is taken; anything else staged is let go and
        the store call made here."""
        # hit pages this engine did not itself offload
        foreign = sum(d not in self._own_digests for d in digests[:hit])
        st = work.staged
        if st is not None and (st.hit, st.first_live) != (hit, first_live):
            self.unstage(work)
            st = None
        try:
            restored, snap = self._restore(hit, digests, first_live,
                                           foreign, st, f)
        except InfiniStoreKeyNotFound:
            # Routine eviction race: the page was LRU-dropped between
            # probe and restore. A cache MISS for this admission only —
            # the store stays in use.
            self.unstage(work)
            self.stats["restore_misses"] += 1
            return None, None, 0
        except Exception as e:
            # Connection-class failure: downgrade to store-less.
            self.unstage(work)
            self._store_failed("restore", e)
            return None, None, 0
        self.stats["prefix_hit_pages"] += hit
        self.stats["foreign_hit_pages"] += foreign
        f["foreign_pages"] = foreign
        rows, *more = restored if self._index_kind else (restored,)
        self.stats["restored_pages"] += rows.shape[0]
        if self._latent:
            self.stats["latent_pages_restored"] += rows.shape[0]
        for k in more:  # a call a further kind; the index keys' last
            self.stats["restored_pages"] += k.shape[0]
        if more:
            self.stats["index_pages_restored"] += more[-1].shape[0]
        self.stats["snapshots_restored"] += snap is not None
        if self._win_layers:
            self.stats["restore_trimmed_pages"] += first_live
        if self._fold:
            n_sum, n_exact = self._fold_split(hit)
            self.stats["summary_pages_restored"] += n_sum
            self.stats["exact_pages_restored"] += n_exact
        return restored, snap, hit

    def _first_live(self, hit):
        """The first page a banded layer's suffix prefill can attend
        over a hit of `hit` pages: the first suffix query sits at hit *
        page and its band floor at hit * page - band + 1."""
        band = self.cfg.window_band
        if not band:
            return 0
        return max(0, hit * self.cfg.page_size - band + 1) \
            // self.cfg.page_size

    def _restore_keys(self, hit, digests, first_live):
        """The keys of a hit's one store call. One kind of page: pages
        [first_live, hit) of every layer, page-major. Two kinds: the
        full layers' [0, hit) page-major, then the banded layers'
        [first_live, hit) page-major: each kind in the order its
        offloads wrote it, so each reads back in few runs."""
        if self._index_kind:
            # A kind of its own shape is a call of its own: [the rows'
            # keys, the index keys' keys], each page-major over the
            # layers that keep that kind.
            return [content_page_keys_by_page(
                digests[first_live:hit], self.cfg.page_layers(kind), kind)
                for kind in self.cfg.page_kinds]
        if self._fold:
            # A folded prefix in the slot's row order: its finished
            # windows' summary pages ("sk", "sv"), then the exact pages
            # from the last window's edge on.
            n_sum, n_exact = self._fold_split(hit)
            return content_page_keys_by_page(
                self._summary_digests(digests, 0, n_sum),
                self.cfg.n_kv_layers, ("sk", "sv")) \
                + content_page_keys_by_page(digests[hit - n_exact:hit],
                                            self.cfg.n_kv_layers,
                                            self.cfg.page_kinds)
        if not self._win_layers:
            return content_page_keys_by_page(digests[first_live:hit],
                                             self.cfg.n_kv_layers,
                                             self.cfg.page_kinds)
        return content_page_keys_by_page(digests[:hit], self._full_layers) \
            + content_page_keys_by_page(digests[first_live:hit],
                                        self._win_layers)

    def _admit_restore_and_prefill(self, slot_idx, work, ids, n_prompt,
                                   n_pages, hit, digests, skip,
                                   first_live, f):
        self._admit_ids_view = ids
        restored = snap = None
        if hit > 0:
            # Restore the in-window hit pages once (one HBM array, as
            # the store call returns it; pool placement follows in
            # _do_admit_paged).
            restored, snap, hit = self._try_restore(work, hit, digests,
                                                    first_live, f)
            if hit == 0 and skip > 0:
                # Restore failed after a skip-trimmed allocation: the
                # cold path needs the skipped pages after all. Top up
                # or put everything back and stay queued.
                extra = self._alloc(skip)
                if extra is None:
                    self.free_pages.extend(ids)
                    raise _AdmitPagesRefunded()
                ids = extra + ids
                self._admit_ids_view = ids
                first_live = 0
                skip = 0
        f["hit_pages"] = hit
        self._do_admit_paged(
            slot_idx, work, ids, n_prompt, n_pages, hit, skip,
            first_live, restored, snap,
        )
        work.probe = None  # consumed; a future re-admission re-probes
        self.unstage(work)
        f["outcome"] = "admitted"
        return True

    def _do_admit_paged(self, slot_idx, work, ids, n_prompt, n_pages,
                        hit, skip, first_live, restored, snap=None):
        """`restored`, `snap`: what _restore returned for pages
        [first_live, hit), or None on a miss."""
        page = self.cfg.page_size
        # page_ids[i] for i < skip are dead placeholders (page 0, the
        # scratch page): nothing after admission can attend positions
        # below the band floor, and _release/_offload honor
        # slot.released = skip so they are never freed or offloaded.
        full_ids = [0] * skip + ids
        # Pool placement for the restored pages. A hit implies the
        # store_chain branch chose skip = first_live, so the restored
        # pages ([first_live, hit)) and the pool targets ([skip, hit) =
        # ids[:hit - skip]) line up exactly.
        assert restored is None or skip == first_live, (skip, first_live)

        row = np.zeros(self.sc.max_pages_per_seq, dtype=np.int32)
        row[skip:n_pages] = ids
        self._pages_rev += 1  # admission rewrites this slot's row
        suffix = work.prompt[hit * page:]
        if self._in_pieces(len(suffix)):
            # Admission in pieces: the slot holds its pages and the
            # tokens still to admit; `_step_pieces` runs a piece an
            # engine step and the slot decodes once none is left. A
            # hit's restored pages go in with its first piece, here.
            self.page_table[slot_idx] = row
            slot = _Slot(
                work=work, page_ids=full_ids, seq_len=hit * page,
                cached_pages=hit, released=skip, index=slot_idx,
                todo=suffix)
            self.slots[slot_idx] = slot
            if restored is not None:
                self._run_piece(slot, restored)
                self._release_windowed(slot)
            return
        if restored is None:
            # Cold admission (hit == 0). Dead prompt pages [0, skip)
            # scatter to the drop sentinel: no pool page was allocated
            # for them.
            row_host = self._prefill_cold(
                suffix, self._pad_ids(ids, offset=skip), slot_idx)
        else:
            # A hit implies skip = first_live <= hit, so every suffix
            # page has a pool id; suffix pages below the post-admission
            # floor are materialized here and freed by the release
            # below, AFTER offloading: the prefix chain stays gap-free.
            row_host = self._prefill_hit(
                suffix, restored, first_live * page,
                ids[:hit - skip], ids[hit - skip:], snap, slot_idx)
        self.stats["prefill_tokens"] += len(suffix)
        self._settle_if_left_idle()

        self.page_table[slot_idx] = row

        slot = _Slot(
            work=work, page_ids=full_ids, seq_len=n_prompt,
            cached_pages=hit, released=skip, index=slot_idx,
        )
        self._emit(slot, [self._pick(work, row_host)])
        self.slots[slot_idx] = slot
        # Windowed models: any remaining pages wholly below the band
        # floor go straight back to the pool (with a store, un-cached
        # ones were materialized so this release can offload them).
        self._release_windowed(slot)

    # ---- a cache whose finished windows fold ----------------------------

    def _open_folded(self, work, hit, digests, slot_idx=-1, f=None):
        """A slot over a folded cache with a hit's prefix restored (not
        yet placed: its first piece does that) and the pool pages of
        that prefix and of the first piece taken, or None where the
        pool is out (nothing is held then). Returns (slot, restored or
        None). A restore that fails makes the admission cold. `f`: an
        admission span's fields (None: `first_token_logits`, which
        counts nothing)."""
        page = self.cfg.page_size
        restored = None
        if hit and f is None:
            try:
                restored, _ = self._restore(hit, digests)
            except InfiniStoreKeyNotFound:
                hit = 0  # evicted between probe and restore
        elif hit:
            if len(self.free_pages) < sum(self._fold_split(hit)):
                return None  # before the store call, as `_do_admit`
            restored, _, hit = self._try_restore(work, hit, digests, 0, f)
        n_sum, n_exact = self._fold_split(hit)
        slot = _Slot(work=work, page_ids=[], seq_len=hit * page,
                     cached_pages=hit, index=slot_idx,
                     todo=work.prompt[hit * page:],
                     folded=n_sum // self._fold_out, sum_stored=n_sum)
        first = min(self._piece_len(slot.seq_len), len(slot.todo))
        if slot_idx >= 0:
            self.page_table[slot_idx] = 0
            self._pages_rev += 1
        if not self._back_rows(slot, n_sum + n_exact - (-first // page)):
            return None
        return slot, restored

    def _do_admit_folded(self, slot_idx, work, hit, digests, f):
        """`_do_admit` for a family whose finished windows fold. The
        slot's table is of ROWS: a hit's prefix is the summary pages of
        its finished windows and the exact pages behind them
        (`_fold_split`), and the pool pages of the prompt are taken a
        PIECE at a time (`_back_rows`), never for all its positions: a
        prompt far longer than `max_pages_per_seq` pages of positions
        is admitted where its rows fit (`submit`). Every such prompt
        goes in pieces, each inside one window (`_run_piece`), one an
        engine step; a hit's first piece runs here, with the restore."""
        opened = self._open_folded(work, hit, digests, slot_idx, f)
        if opened is None:
            self.stats["admit_retries"] += 1
            f["outcome"] = "no_pages"
            return False
        slot, restored = opened
        hit = slot.cached_pages
        cut = hit > 0 and hit % self._fold_in == 0 \
            and (len(work.prompt) - 1) // self.cfg.page_size > hit
        f["hit_pages"], f["cut_to_window_edge"] = hit, cut
        self.stats["hits_cut_to_window_edge"] += cut
        self.slots[slot_idx] = slot
        try:
            if restored is not None:
                row = self._run_piece(slot, restored)
                if not slot.todo:
                    self._emit(slot, [self._pick(work, row)])
        except BaseException:
            self._release(slot_idx, slot)
            raise
        work.probe = None  # consumed; a future re-admission re-probes
        self.unstage(work)
        f["outcome"] = "admitted"
        return True

    def _fold_due(self, active, more=0):
        """Before a decode step of `active` whose lengths lie `more`
        beyond the slots' (1: behind the step in flight): every slot
        whose step would write the first row of a new window has the
        window before it folded first, behind the program that wrote
        its last row; the step's table goes up anew."""
        for _, s in active:
            if (s.seq_len + more) // self._fold > s.folded:
                self._fold_slot(s, "decode")

    def _fold_slot(self, slot, during):
        """The fold: the window `slot.folded` of the sequence (whose
        last row the last program dispatched wrote) becomes its
        summary rows. ONE program reads the window's `_fold_in` pool
        pages of every layer and writes `_fold_out` summary pages over
        the first of them; the others go back to the free list at
        once, WITHOUT an offload: nothing at that depth or deeper can
        attend them again, and a program that takes one is enqueued
        behind the fold. `during`: "decode" | "piece"."""
        w, out = slot.folded, self._fold_out
        lo = w * out
        ids = slot.page_ids[lo:lo + self._fold_in]
        assert len(ids) == self._fold_in \
            and len(slot.page_ids) == lo + self._fold_in, (w, len(ids))
        with self._span("istpu.cache.fold", slot.work.req.request_id,
                        slot=slot.index, window=w, pages_in=len(ids),
                        pages_out=out, during=during,
                        bytes=(len(ids) + out) * self._page_bytes):
            self.k_pages, self.v_pages = _fold_window(
                self.params, self.cfg, self.k_pages, self.v_pages,
                self._to_device(np.asarray(ids, np.int32)),
                model=self.model)
        self.free_pages.extend(ids[out:])
        del slot.page_ids[lo + out:]
        slot.folded += 1
        if slot.index >= 0:
            self.page_table[slot.index] = 0
            self.page_table[slot.index, :len(slot.page_ids)] = slot.page_ids
            self._pages_rev += 1
        self.stats["windows_folded"] += 1
        self.stats["fold_pages_freed"] += len(ids) - out
        self.stats["summary_pages_written"] += out

    def _offload_folded(self, slot, reason):
        """`_offload_full_pages` for a folded slot: the SUMMARY pages
        the store lacks (of every window folded here: `sum_stored` on)
        and the EXACT full pages of the window the sequence ends in,
        from the deeper of the window's edge and what a hit brought.
        A folded window's exact pages were freed at its fold and are
        never written. One offload, a gather-transfer-put a kind."""
        page = self.cfg.page_size
        n_sum = slot.folded * self._fold_out
        base = slot.folded * self._fold_in  # the last window's edge
        n_full = slot.seq_len // page
        lo = max(slot.cached_pages, base)
        n_exact, new_sum = max(0, n_full - lo), n_sum - slot.sum_stored
        n = new_sum + n_exact
        if n <= 0:
            return None
        nbytes = n * self._page_bytes
        digests = self._slot_digests(slot, max(n_full, base))
        rid = slot.work.req.request_id
        up = _Upload(reason, rid, n, nbytes,
                     digests=digests[slot.sum_stored * page:],
                     counts={"offloaded_pages": n_exact,
                             "summary_pages_offloaded": new_sum})
        with self._span("istpu.cache.offload", rid, reason=reason, pages=n,
                        bytes=nbytes, padded_pages=0, puts=0,
                        summary_pages=new_sum, exact_pages=n_exact,
                        kinds=2) as f:
            if not self._upload_room(nbytes):
                return None
            if new_sum:
                self._gather_pool_pages(
                    up, f, self.k_pages, self.v_pages,
                    slot.page_ids[slot.sum_stored:n_sum],
                    self._summary_digests(digests, slot.sum_stored, n_sum),
                    self._full_layers, self._page_bytes,
                    kind=("sk", "sv"))
            if n_exact:
                self._gather_pool_pages(
                    up, f, self.k_pages, self.v_pages,
                    slot.page_ids[n_sum + lo - base:n_sum + n_full - base],
                    digests[lo:n_full], self._full_layers,
                    self._page_bytes)
            f["puts"] = len(up.chunks)
            self._enqueue_upload(up)
        slot.sum_stored = n_sum
        return up

    # ---- admission in pieces --------------------------------------------

    def _run_piece(self, slot, restored=None):
        """The next piece of `slot`'s prompt (`admit_piece` tokens, or
        the tail; over a folded cache it ends no later than the window
        it begins in, `_piece_len`, and the window folds behind it,
        `_fold_slot`; None where the pool has no page for it): ONE
        program call. A cold prompt's first piece is the
        cold program; every other is the prefix program (`_prefill_hit`,
        the one a hit runs) over the pages the slot holds from its
        band's floor on (`_first_live`; all of them without a window):
        `restored`, a hit's pages as its store call returned them (they
        go into the pool here), or the slot's own pool pages, gathered
        into that same form. What a window leaves behind is the
        caller's to release: it owns the slot's pages. Returns the
        piece's last logits row (the first token's, once `slot.todo` is
        empty)."""
        page = self.cfg.page_size
        # pieces and hits end on page edges; over a folded cache the
        # pages held are of ROWS, and the piece's own are taken here
        held = decoder.cache_rows(self.cfg, slot.seq_len) // page
        tokens = slot.todo[:self._piece_len(slot.seq_len)]
        if self._fold and not self._back_rows(
                slot, held - (-len(tokens) // page)):
            return None
        ids = slot.page_ids[held:held - (-len(tokens) // page)]
        with self._span("istpu.sched.admit_piece",
                        slot.work.req.request_id, tokens=len(tokens),
                        prefix_pages=held, piece=slot.pieces + 1,
                        of=slot.pieces + self._pieces_left(slot)):
            if restored is None and held == 0:
                row = self._prefill_cold(tokens, self._pad_ids(ids),
                                         slot.index)
            else:
                lo = self._first_live(held)
                r_ids = slot.page_ids[lo:held]
                if restored is None:
                    with self._span("istpu.cache.pool_read",
                                    pages=held - lo):
                        at = self._to_device(np.asarray(r_ids, np.int32))
                        if self._index_kind:
                            restored = tuple(
                                _gather_pages(pool, None, at).reshape(
                                    -1, *self.cfg.page_shape(kind))
                                for pool, kind in zip(
                                    _kind_pools(self.k_pages,
                                                self.v_pages),
                                    self.cfg.page_kinds))
                        else:
                            restored = _gather_pages(
                                self.k_pages, self.v_pages, at
                            ).reshape(-1, *self.cfg.kv_page_shape())
                    r_ids = [self.sc.total_pages] * len(r_ids)  # in place
                # the prefix's first row stands at lo * page where rows
                # are positions; over a folded cache at the piece's
                # first position less the rows below it
                row = self._prefill_hit(
                    tokens, restored, slot.seq_len - (held - lo) * page,
                    r_ids, ids)
        slot.todo = slot.todo[len(tokens):]
        slot.seq_len += len(tokens)
        slot.pieces += 1
        self.stats["admit_pieces"] += 1
        self.stats["prefill_tokens"] += len(tokens)
        self._piece_ran = True
        if self._fold and slot.seq_len // self._fold > slot.folded:
            self._fold_slot(slot, "piece")  # the piece ended its window
        return row

    def _piece_len(self, pos):
        """How many tokens the piece that begins at position `pos` may
        take: `admit_piece`; over a folded cache no further than the
        end of the window `pos` lies in, so that inside a piece every
        prefix row is visible to every suffix row (and `admit_piece`
        0 means a window)."""
        if not self._fold:
            return self.sc.admit_piece
        return min(self.sc.admit_piece or self._fold,
                   self._fold - pos % self._fold)

    def _pieces_left(self, slot):
        """Pieces `slot.todo` still makes, the next one counted."""
        if not self._fold:
            return -(-len(slot.todo) // self.sc.admit_piece)
        n, pos, left = 0, slot.seq_len, len(slot.todo)
        while left > 0:
            step = min(self._piece_len(pos), left)
            n, pos, left = n + 1, pos + step, left - step
        return n

    def _back_rows(self, slot, n_pages):
        """Pool pages under the first `n_pages` entries of a folded
        slot's table of rows (a piece's own, a decode step's next):
        taken as they are needed, never for the whole prompt. False,
        and nothing taken, where the pool is out."""
        held = len(slot.page_ids)
        if held >= n_pages:
            return True
        ids = self._alloc(n_pages - held)
        if ids is None:
            return False
        if slot.index >= 0:
            self.page_table[slot.index, held:n_pages] = ids
            self._pages_rev += 1
        slot.page_ids.extend(ids)
        return True

    def _step_pieces(self):
        """One piece of the first slot that is still being admitted,
        unless this step ran one already (an admission's own first).
        After the last piece the slot has its first token and decodes
        from this step on."""
        for s in self.slots:
            if s is None or not s.todo or self._piece_ran:
                continue
            row = self._run_piece(s)
            if row is None:  # (a folded cache) no pool page for it yet
                self.stats["admit_retries"] += 1
                continue
            if not s.todo:
                self._emit(s, [self._pick(s.work, row)])
            self._release_windowed(s)
            self._settle_if_left_idle()

    def _in_pieces(self, n_tokens):
        """Whether `n_tokens` uncached prompt tokens go in pieces."""
        return 0 < self.sc.admit_piece < n_tokens

    def _active(self):
        """(index, slot) of the slots that decode: occupied and
        admitted whole."""
        return [(i, s) for i, s in enumerate(self.slots)
                if s is not None and not s.todo]

    # ---- admission over two kinds of attention layer -------------------

    def _walloc(self, n):
        """`n` pages of the banded layers' pools, or None."""
        if len(self.wfree) < n:
            return None
        ids, self.wfree = self.wfree[:n], self.wfree[n:]
        return ids

    def _wbase_after(self, n_pages):
        """The first page of a sequence whose banded layers' pages are
        held after an admission of `n_pages` prompt pages: the page the
        band floor of the prompt's last position can lie in at the
        lowest (the prompt's last page may hold one token). What lies
        below is attended by the admission's own queries alone."""
        return max(0, n_pages - self._band_pages - 1)

    def _do_admit_two(self, slot_idx, work, n_prompt, n_pages, hit,
                      digests, f):
        """`_do_admit` from the allocation on, for a model with full
        and banded attention layers. The full layers take a pool page
        for every page of the prompt, as a model without a window does;
        the banded layers for pages [wbase, n_pages) alone. A hit of P
        pages restores the full layers' [0, P) and the banded layers'
        [first_live(P), P) in one store call; what a banded layer
        computes below wbase goes from the program to the store
        (`_put_subfloor`). Both allocations before the restore, both
        refunded on any way out but the admitted one."""
        wbase = self._wbase_after(n_pages)
        ids = self._alloc(n_pages)
        wids = self._walloc(n_pages - wbase) if ids is not None else None
        if wids is None:
            if ids is not None:
                self.free_pages.extend(ids)
            self.stats["admit_retries"] += 1
            f["outcome"] = "no_pages"
            return False
        try:
            restored = snap = None
            if hit > 0:
                restored, snap, hit = self._try_restore(
                    work, hit, digests, self._first_live(hit), f)
            f["hit_pages"] = hit
            row_host, sub = self._prefill_two(work.prompt, hit, restored,
                                              ids, wids, wbase, snap,
                                              slot_idx)
        except BaseException:
            self.free_pages.extend(ids)
            self.wfree.extend(wids)
            raise
        page = self.cfg.page_size
        self.stats["prefill_tokens"] += n_prompt - hit * page
        self._settle_if_left_idle()
        self._pages_rev += 1  # admission rewrites this slot's rows
        self.page_table[slot_idx] = 0
        self.page_table[slot_idx, :n_pages] = ids
        self.wtable[slot_idx] = 0
        self.wtable[slot_idx, :len(wids)] = wids
        slot = _Slot(work=work, page_ids=ids, seq_len=n_prompt,
                     cached_pages=hit, index=slot_idx, wpage_ids=wids,
                     wbase=wbase, wstored=hit)
        self._emit(slot, [self._pick(work, row_host)])
        self.slots[slot_idx] = slot
        f["subfloor_pages"] = 0
        if sub and self._store_chain(work):
            f["subfloor_pages"] = self._put_subfloor(slot, sub, hit, wbase)
        work.probe = None  # consumed; a future re-admission re-probes
        self.unstage(work)
        f["outcome"] = "admitted"
        return True

    def _prefill_two(self, prompt, hit, restored, ids, wids, wbase,
                     snap=None, slot_idx=None):
        """The admission program of a model with two kinds of attention
        layer: cold (`hit` 0) or prefix (`restored`: what `_restore`
        returned for a hit of `hit` pages). `ids`: the full pools' ids
        of the prompt's pages [0, n_pages); `wids`: the banded pools'
        of [wbase, n_pages); None for either: nothing is written
        (`first_token_logits`). With state layers too, `snap` (the
        restored snapshot) is where they continue from, and the
        sequence's state and boundary copy land in row `slot_idx`
        (None: dropped). Returns (the last real position's
        logits row on the host, `sub`: the banded layers' pages [hit,
        wbase) as device chunks, see `_page_out_two`)."""
        cfg, sc = self.cfg, self.sc
        page = cfg.page_size
        suffix = prompt[hit * page:]
        toks = self._pad_tokens(suffix)
        n_pages = hit + toks.shape[1] // page
        # pool ids by page of the sequence; the sentinels drop
        m = sc.max_pages_per_seq
        fa = np.full(hit + m, sc.total_pages, np.int32)
        wa = np.full(hit + m, self._wpool_pages, np.int32)
        if ids is not None:
            fa[:n_pages] = ids
        if wids is not None:
            wa[wbase:n_pages] = wids
        # What the banded layers compute below the band goes to the
        # store from the program, unless the model has state layers
        # too: a prefix is used only with the snapshot at its end,
        # snapshots lie at finishes' page edges alone, and a finish
        # writes the band it ends in, so no hit can ever read a page
        # from below an ADMISSION's band (491 MB of them a cold prompt
        # of 12.4k tokens at phi4-mini-flash's widths, which alone
        # filled UPLOAD_INFLIGHT_BYTES and made the next finish wait).
        n_sub = max(0, wbase - hit) if self.state is None else 0
        s_real = self._to_device(np.int32(len(suffix)))
        pools = (self.k_pages, self.v_pages, self.wk_pages, self.wv_pages)
        fields = {"restored_pages": hit} if hit else {}
        with self._span("istpu.model.prefill",
                        program="prefix" if hit else "cold",
                        tokens=len(suffix), padded_tokens=toks.shape[1],
                        **fields, **self._rows_fields(toks.shape[1])) as f:
            if self.state is None:
                admit, admit_px, snap_in, where = (
                    _admit_fused_wf, _admit_fused_px_wf, (), ())
            else:  # ... and the state pools, their boundary copies, the
                # snapshot a hit continues from, the slot's row in them
                pools += (self.state, self.bstate)
                admit, admit_px, snap_in, where = (
                    _admit_fused_wf_st, _admit_fused_px_wf_st, (snap,),
                    (self._slot_dev(slot_idx),))
            if hit:
                first_live = self._first_live(hit)
                out = admit_px(
                    self.params, cfg, toks, restored, *snap_in, *pools,
                    self._to_device(fa[:hit]),
                    self._to_device(wa[first_live:hit]),
                    self._to_device(fa[hit:]), self._to_device(wa[hit:]),
                    s_real, *where, model=self.model, n_sub=n_sub)
            else:
                out = admit(
                    self.params, cfg, toks, *pools, self._to_device(fa),
                    self._to_device(wa), s_real, *where, model=self.model,
                    n_sub=n_sub)
            (row_dev, self.k_pages, self.v_pages, self.wk_pages,
             self.wv_pages, sub, *states) = out
            if states:
                self.state, self.bstate = states
            f["dispatch_ns"] = profiling.elapsed_ns()
            return self._pull_row(row_dev, len(suffix), f), sub

    def _put_subfloor(self, slot, sub, lo, hi):
        """The banded layers' pages [lo, hi) of the slot's sequence,
        which its admission computed below the band (`sub`, device
        chunks of `_sub_chunk_pages` pages), to the store: they never
        had a pool page, and the store's contract wants every full
        page of every layer. Behind the first token the engine thread
        starts every chunk's device-to-host transfer and hands them to
        the upload thread as one upload (one store batch a chunk, one
        sync). Returns the pages on their way."""
        digests = self._slot_digests(slot, hi)[lo:hi]
        c = _sub_chunk_pages(self.cfg)
        n = hi - lo
        nbytes = n * self._wpage_bytes
        rid = slot.work.req.request_id
        up = _Upload("subfloor", rid, n, nbytes,
                     counts={"subfloor_pages_written": n})
        with self._span("istpu.cache.offload", rid, reason="subfloor",
                        pages=n, bytes=nbytes, padded_pages=n,
                        puts=len(sub)):
            if not self._upload_room(nbytes):
                return 0
            for i, flat in enumerate(sub):
                flat.copy_to_host_async()
                up.chunks.append((
                    flat, self.cfg.kv_page_shape(),
                    content_page_keys_by_page,
                    (digests[i * c:(i + 1) * c], self._win_layers)))
            self._enqueue_upload(up)
        slot.wstored = max(slot.wstored, hi)
        return n

    def idle(self):
        """For whoever drives an engine that has nothing to step
        (serving_http's loop, on every pass that finds no work): one
        trivial program every IDLE_TICK_S, so that an admission's
        program is not the first thing the device runs after an idle
        spell. Of the admissions that followed under 1.0 s of idle
        none started the slow mode _settle describes, of those after
        1.0-4.0 s three in four did (PERF.md, PR 29). Returns whether
        this call sent one (the loop's no_work span counts them)."""
        self.land()
        now = time.monotonic()
        if now - self._ticked < IDLE_TICK_S:
            return False
        self._ticked = now
        jax.block_until_ready(_tick(self._tick_x))
        return True

    def _settle_if_left_idle(self):
        """Behind a one-shot admission's program: `_settle`, for
        SETTLE_S after an admission that found no sequence running."""
        now = time.monotonic()
        if not any(s is not None for s in self.slots):
            self._left_idle = now
        if now - self._left_idle < SETTLE_S:
            self._settle()

    def _settle(self):
        """SETTLE_PROGRAMS trivial programs behind a one-shot
        admission's program, after the row pull. Seen on the v5e host
        (PERF.md, PR 29): when a long program is the first thing the
        device runs after an idle spell of a second or more, every
        later wait for the device (decode steps, admissions, offload
        gathers alike) may come back some 2.5 ms late, for 3-13 s, and
        the engine thread's own host work takes twice as long. What
        ends it is a burst of short programs: the hundreds of eager
        dispatches a hit admission was before it became one program
        ended it every time, which is why only cold admissions showed
        it then. The cause lies under this program and is not known;
        the cure is what those bursts did, in under a millisecond. It
        holds some nine times in ten, so it runs behind EVERY
        admission (as the old hit's burst did) for SETTLE_S after one
        that found the engine idle, not only behind that one: a slow
        spell that does start ends with one of the next admissions,
        not seconds later. idle() is the other half: it keeps most
        spells from starting."""
        with self._span("istpu.engine.settle", programs=SETTLE_PROGRAMS):
            x = self._tick_x
            for _ in range(SETTLE_PROGRAMS):
                x = _tick(x)
            jax.block_until_ready(x)

    def _pad_tokens(self, tokens):
        """Prompt tokens as the [1, s_pad] device array the prefill
        programs take: bucketed to a page multiple (causal attention
        makes tail padding inert for the positions we read; a
        recurrence is told the real length and masks the rest)."""
        page = self.cfg.page_size
        toks = np.zeros((1, -(-len(tokens) // page) * page), dtype=np.int32)
        toks[0, :len(tokens)] = tokens
        return self._to_device(toks)

    def _scan_fields(self, padded_tokens):
        """What istpu.model.prefill carries for a family with state:
        `chunks`, the chunks its scan of `padded_tokens` runs in."""
        if self.state is None:
            return {}
        return {"chunks": -(-padded_tokens // self.cfg.ssm_chunk)}

    def _rows_fields(self, padded_tokens):
        """An admission program's token-layer rows, counted as they
        are read (decoder.stack_rows: the rows the program ran, of
        padded tokens x layers), and what istpu.model.prefill carries
        of them where the two differ (a model whose upper layers run on
        the kept row alone): `rows_run` of `rows_all`."""
        run, every = decoder.stack_rows(self.cfg, padded_tokens)
        self.stats["stack_rows_run"] += run
        self.stats["stack_rows_all"] += every
        return {"rows_run": run, "rows_all": every} if run < every else {}

    def _prefill_cold(self, tokens, ids_padded, slot_idx=None):
        """The cold program: ONE fused device program does prefill +
        page-out + pool scatter at `ids_padded` (_pad_ids form) +
        logits-row slice; for a family with state it also leaves the
        sequence's state and boundary copy in row `slot_idx` of the
        state pools (None: dropped, nothing is admitted). Returns the
        last real position's logits row, on the host."""
        toks = self._pad_tokens(tokens)
        with self._span("istpu.model.prefill", program="cold",
                        tokens=len(tokens), padded_tokens=toks.shape[1],
                        **self._scan_fields(toks.shape[1]),
                        **self._rows_fields(toks.shape[1])) as f:
            ids = self._to_device(ids_padded)
            s_real = self._to_device(np.int32(len(tokens)))
            if self.state is None:
                row_dev, self.k_pages, self.v_pages = _admit_fused(
                    self.params, self.cfg, toks, self.k_pages,
                    self.v_pages, ids, s_real, model=self.model,
                )
            else:
                (row_dev, self.k_pages, self.v_pages, self.state,
                 self.bstate) = _admit_fused_st(
                    self.params, self.cfg, toks, self.k_pages,
                    self.v_pages, self.state, self.bstate, ids, s_real,
                    self._slot_dev(slot_idx), model=self.model,
                )
            f["dispatch_ns"] = profiling.elapsed_ns()
            return self._pull_row(row_dev, len(tokens), f)

    def _pull_row(self, row_dev, n_tokens, f):
        """An admission program's logits row on the host. Behind the
        row of a model whose layers hold a share of their experts lie
        the program's two counts (`_last_row`): they go to the span
        `f` and the counters, the row comes back alone."""
        row = np.asarray(row_dev)
        if not self._share_layers:
            return row
        held, rows = int(row[-2]), int(row[-1])
        f["pairs_held"], f["rows_computed"] = held, rows
        self.stats["moe_pairs_routed"] += (n_tokens * self.cfg.top_k
                                           * self._share_layers)
        self.stats["moe_pairs_held"] += held
        self.stats["moe_rows_computed"] += rows
        return row[:-2]

    def _slot_dev(self, slot_idx):
        """A state-pool row index on the device; None is max_slots,
        which the admission programs drop."""
        return self._to_device(np.int32(
            self.sc.max_slots if slot_idx is None else slot_idx))

    def _prefill_hit(self, suffix, restored, pos0, restored_ids,
                     suffix_ids, snap=None, slot_idx=None):
        """The prefix program: ONE fused device program scatters the
        `restored` pages (what _restore returned) into the pool at
        `restored_ids`, prefills the suffix over them, and pages its KV
        out into the pool at `suffix_ids`. pos0 anchors the trimmed
        prefix's absolute rope positions; the band mask is relative, so
        local indices inside the kernel stay correct
        (decoder.forward_stack). Ids at the drop sentinel are not
        written. Between the store call's return and this row pull the
        engine thread dispatches that one program and nothing else.
        For a family with state, `snap` (the restored snapshot) is
        where its state layers continue from, and the sequence's state
        and boundary copy land in row `slot_idx` (None: dropped).
        Returns the last real position's logits row, on the host."""
        toks = self._pad_tokens(suffix)
        with self._span("istpu.model.prefill", program="prefix",
                        tokens=len(suffix), padded_tokens=toks.shape[1],
                        restored_pages=len(restored_ids),
                        **self._scan_fields(toks.shape[1]),
                        **self._rows_fields(toks.shape[1])) as f:
            r_ids = self._to_device(np.asarray(restored_ids, np.int32))
            s_ids = self._to_device(self._pad_ids(suffix_ids))
            s_real = self._to_device(np.int32(len(suffix)))
            if self.state is None:
                row_dev, self.k_pages, self.v_pages = _admit_fused_px(
                    self.params, self.cfg, toks, restored,
                    self.k_pages, self.v_pages, r_ids, s_ids, s_real,
                    self._to_device(np.int32(pos0)),
                    model=self.model,
                )
            else:
                (row_dev, self.k_pages, self.v_pages, self.state,
                 self.bstate) = _admit_fused_px_st(
                    self.params, self.cfg, toks, restored, snap,
                    self.k_pages, self.v_pages, self.state, self.bstate,
                    r_ids, s_ids, s_real, self._slot_dev(slot_idx),
                    model=self.model,
                )
            f["dispatch_ns"] = profiling.elapsed_ns()
            return self._pull_row(row_dev, len(suffix), f)

    def first_token_logits(self, prompt):
        """First-token logits of `prompt` through the programs an
        admission dispatches, without admitting anything: on a miss
        the cold program with every page id at the drop sentinel (the
        pool is untouched), on a hit probe + restore + the prefix
        program, likewise with every id at the sentinel (and, for a
        family with state, the state-pool row: no slot is written).
        Returns (float32 row [vocab], hit pages): the depth that RAN.
        The engine must be idle, and the caller on the thread that
        steps it."""
        if self.queue or any(s is not None for s in self.slots):
            raise RuntimeError("first_token_logits needs an idle engine")
        prompt = [int(t) for t in prompt]
        page = self.cfg.page_size
        work = _Work(req=Request("first-token-logits", prompt),
                     prompt=prompt)
        hit, digests = self._probe_hit(work)
        if self._fold:
            # What an admission over a folded cache runs, piece by
            # piece with its folds, on pool pages taken for the call
            # and given back.
            slot, restored = self._open_folded(work, hit, digests) \
                or (None, None)
            row = None
            try:
                if slot is not None:
                    row = self._run_piece(slot, restored)
                    while slot.todo and row is not None:
                        row = self._run_piece(slot)
            finally:
                if slot is not None:
                    self.free_pages.extend(slot.page_ids)
            if row is None:
                raise RuntimeError("first_token_logits: the prompt needs "
                                   "more pool pages than are free")
            return np.asarray(row, np.float32), slot.cached_pages
        if self._win_layers:
            restored = snap = None
            if hit > 0:
                try:
                    restored, snap = self._restore(hit, digests,
                                                   self._first_live(hit))
                except InfiniStoreKeyNotFound:
                    hit = 0  # evicted between probe and restore
            row, _ = self._prefill_two(
                prompt, hit, restored, None, None,
                self._wbase_after(-(-len(prompt) // page)), snap)
            return np.asarray(row, np.float32), hit
        if hit > 0:
            first_live = self._first_live(hit)
            try:
                restored, snap = self._restore(hit, digests, first_live)
            except InfiniStoreKeyNotFound:
                hit = 0  # evicted between probe and restore
        if self._in_pieces(len(prompt) - hit * page):
            # What an admission in pieces runs, on pool pages taken for
            # the call and given back (the engine is idle: they are
            # free, and free again after).
            ids = self._alloc(-(-len(prompt) // page))
            if ids is None:
                raise RuntimeError("first_token_logits: the prompt needs "
                                   "more pool pages than are free")
            slot = _Slot(work=work, page_ids=ids, seq_len=hit * page,
                         todo=prompt[hit * page:])
            try:
                row = self._run_piece(slot, restored if hit > 0 else None)
                while slot.todo:
                    row = self._run_piece(slot)
            finally:
                self.free_pages.extend(ids)
        elif hit > 0:
            row = self._prefill_hit(
                prompt[hit * page:], restored, first_live * page,
                [self.sc.total_pages] * (hit - first_live), [], snap)
        else:
            row = self._prefill_cold(prompt, self._pad_ids([]))
        return np.asarray(row, np.float32), hit

    # ---- decode --------------------------------------------------------

    def _emit(self, slot, tokens, at=None):
        """The ONE place generated tokens enter a slot: appends and
        fires the request's streaming callback once per token (callback
        failures are the caller's bug — they propagate). And where the
        gaps between a request's tokens are seen: from its second token
        on, each call closes the gap that began at the request's mark
        (`_Work.gap_at`), and k tokens at once are k gaps, the first
        the interval and the rest 0, as a client sees them. `at`: the
        mark of this moment, which a step takes ONCE for all the slots
        it emits to and hands to `_count_gaps` behind them; None: a
        token on its own (an admission's first), marked and counted
        here."""
        slot.generated.extend(tokens)
        work = slot.work
        last, work.gap_at = work.gap_at, at or self._gap_mark()
        if last is not None:
            # (no call a token here: under a profiler session every
            # one, a builtin's too, is an event of its Python tracer)
            try:
                self._gaps[last] += 1
            except KeyError:
                self._gaps[last] = 1
            if tokens[1:]:
                self.stats["gap_tokens"] += len(tokens) - 1
            if at is None:
                self._count_gaps(work.gap_at)
        cb = work.req.on_token
        if cb is not None:
            rid = work.req.request_id
            for t in tokens:
                cb(rid, t)

    def _gap_mark(self):
        """(now, the five causes' ns as of now): the engine's clock
        (`perf_counter_ns`, the spans') and `_cause_ns` with what the
        outermost cause span open now has run so far. Marks are
        compared by their differences alone."""
        now = time.perf_counter_ns()
        span = self._cause_open
        if span is None:
            return (now, *self._cause_ns)
        mark = [now, *self._cause_ns]
        mark[1 + _cause_of(span)] += now - span._p0
        return tuple(mark)

    def _count_gaps(self, at, df=None):
        """The gaps that `_emit` saw end at the mark `at`, into the
        counters: the slots that emitted together last time share one
        mark, so one difference serves them all. `df`: the fields of
        the decode span a step lands under, which gain `waiting` (the
        slots that emitted here and at the land before) and, where
        another cause than `step` ran between the two lands, its ns
        (`stall_ns`) and the largest of them (`stall_cause`)."""
        st = self.stats
        for last, n in self._gaps.items():
            interval, step, *stalls = map(operator.sub, at, last)
            st["gap_tokens"] += n
            st["gap_ns"] += n * interval
            st["gap_ns_step"] += n * step
            stall_ns = sum(stalls)
            if stall_ns:
                st["gaps_stalled"] += n
                for key, ns in zip(_GAP_KEYS[1:], stalls):
                    st[key] += n * ns
            if df is not None and last is self._landed_at:
                df["waiting"] = n
                if stall_ns:
                    df["stall_ns"] = stall_ns
                    df["stall_cause"] = GAP_CAUSES[
                        1 + stalls.index(max(stalls))]
        self._gaps.clear()

    @staticmethod
    def _probs(req, row):
        """The request's sampling distribution over one logits row
        (temperature + top-k transform, normalized float64)."""
        z = np.asarray(row, dtype=np.float64)
        # Subtract the max BEFORE dividing: z/T with a pathologically
        # tiny T overflows to inf and inf-inf = NaN probabilities; with
        # the max at 0 first, scaling can only push losers to -inf
        # (exp -> 0, i.e. greedy), never produce NaN.
        with np.errstate(over="ignore"):
            z = (z - z.max()) / req.temperature
        if 0 < req.top_k < len(z):  # top_k >= vocab = full distribution
            kth = np.partition(z, -req.top_k)[-req.top_k]
            z = np.where(z >= kth, z, -np.inf)
        p = np.exp(z)
        p /= p.sum()
        return p

    def _pick(self, work, row):
        """Next token from one logits row: greedy by default, seeded
        temperature/top-k sampling when the request asked for it (one
        RNG draw per token on the non-speculative paths; see _Work for
        the spec_k reproducibility contract)."""
        req = work.req
        if req.temperature <= 0:
            return int(np.argmax(row))
        p = self._probs(req, row)
        return int(work.rng.choice(len(p), p=p))

    def _ensure_pages(self, slot_idx, slot, last_pos):
        """Allocate pages on demand (vLLM-style growth) so positions up
        to and including `last_pos` are backed. Partial progress is
        kept: pages allocated before a failure stay owned by the slot."""
        need_idx = decoder.cache_rows(self.cfg, last_pos) \
            // self.cfg.page_size
        while len(slot.page_ids) <= need_idx:
            ids = self._alloc(1)
            if ids is None:
                return False
            self.page_table[slot_idx, len(slot.page_ids)] = ids[0]
            slot.page_ids.extend(ids)
            self._pages_rev += 1
        while self._win_layers and \
                slot.wbase + len(slot.wpage_ids) <= need_idx:
            # The short table has room: `_shed_windows` ran before.
            ids = self._walloc(1)
            if ids is None:
                return False
            self.wtable[slot_idx, len(slot.wpage_ids)] = ids[0]
            slot.wpage_ids.extend(ids)
            self._pages_rev += 1
        return True

    def _offload_full_pages(self, slot, hi=None, reason="finish"):
        """Send the slot's NEW full pages [lo, hi) to the store (shared
        by finish, preemption and windowed release). Offloads FULL
        pages only — partial tail pages would poison page-granular
        prefix matching — and skips [0:cached_pages) which the store
        already holds (first-writer-wins makes re-putting them wasted
        transfer) plus [0:released) which was offloaded when the pages
        left the window. Keys hash prompt + generated tokens (page i's
        key depends only on tokens < (i+1)*page_size, so release-time
        and finish-time keys agree), so a future request whose prompt
        extends this sequence hits these pages. A family with state
        writes, behind the pages and before the sync, the slot's
        boundary copy as the snapshot at the end of page n_full
        (`_gather_snapshot_rows`): pages and snapshot at ONE depth.

        Which thread does what. The ENGINE thread pays the digests,
        the bucketed page ids and the dispatch of EVERY gather program
        of the offload, each result's device-to-host transfer started,
        and one put on the upload queue: the `istpu.cache.offload`
        span. The engine's UPLOAD thread (`_run_upload`) waits for
        each transfer, formats its keys and makes the store batch
        (allocate, the copy into the store's pool), then ONE sync an
        offload, and acknowledges: `istpu.cache.upload`.

        Who owns the pages. A gather takes the pools undonated and its
        value is fixed at the call: a later program that writes a page
        (a decode step or an admission that took it off the free list)
        is enqueued behind the gather on the same device. So the
        caller frees the pool pages, the slot and its boundary copy as
        soon as this returns; what must wait for the store is `done`
        (`_finish`) and a preemption's re-admission (`_preempt`).

        Returns the upload on its way, or None for nothing to send."""
        if not self._store_chain(slot.work):
            return None
        if self._fold:
            return self._offload_folded(slot, reason)
        n_full = slot.seq_len // self.cfg.page_size
        if hi is not None:
            n_full = min(n_full, hi)
        lo = max(slot.cached_pages, slot.released)
        if n_full <= lo:
            return None
        # Digests come from the slot's incremental chain and only the
        # [lo, n_full) keys are ever formatted (on the upload thread) —
        # windowed release calls this every page_size tokens, so
        # per-call work must stay O(pages released), not O(seq).
        n = n_full - lo
        # Two kinds of attention layer: the banded layers' part of the
        # same offload, what the store lacks of the pages their pool
        # still holds ([0, wstored) it has; [wstored, wbase) cannot be:
        # a page leaves that pool through the store).
        wlo = max(slot.wstored, slot.wbase) if self._win_layers else n_full
        nw = max(0, n_full - wlo)
        nbytes = n * self._page_bytes + self._snapshot_bytes \
            + (nw * self._wpage_bytes if nw else 0)
        new_digests = self._slot_digests(slot, n_full)[lo:]
        rid = slot.work.req.request_id
        up = _Upload(reason, rid, n, nbytes, digests=new_digests,
                     counts={"offloaded_pages": n, "snapshots_written":
                             int(self.state is not None),
                             "latent_pages_written":
                             n * self.k_pages.shape[0] * self._latent,
                             "index_pages_offloaded":
                             n * len(self._index_layers)})
        two = {"full_pages": n, "window_pages": nw} if self._win_layers \
            else {}
        with self._span("istpu.cache.offload", rid, reason=reason, pages=n,
                        bytes=nbytes, padded_pages=0, puts=0, **two,
                        **self._kinds_field, **self._snapshot_fields) as f:
            if not self._upload_room(nbytes):
                return None
            if self._index_kind:
                # a gather, a transfer and a store batch a kind: the
                # rows of every layer (or K, then V), then the owners'
                # index keys
                for pool, kind, kbytes in zip(
                        _kind_pools(self.k_pages, self.v_pages),
                        self.cfg.page_kinds, self._kind_bytes):
                    self._gather_pool_pages(
                        up, f, pool, None, slot.page_ids[lo:n_full],
                        new_digests, self.cfg.page_layers(kind), kbytes,
                        kind=kind)
            else:
                self._gather_pool_pages(
                    up, f, self.k_pages, self.v_pages,
                    slot.page_ids[lo:n_full], new_digests,
                    self._full_layers, self._page_bytes)
            if nw:
                self._gather_pool_pages(
                    up, f, self.wk_pages, self.wv_pages,
                    slot.wpage_ids[wlo - slot.wbase:n_full - slot.wbase],
                    self._slot_digests(slot, n_full)[wlo:],
                    self._win_layers, self._wpage_bytes, _pow2_bucket)
            if self.state is not None:
                self._gather_snapshot_rows(up, slot, new_digests[-1])
            f["puts"] = len(up.chunks)
            self._enqueue_upload(up)
        if nw:
            slot.wstored = n_full
        return up

    def _chunk_pages(self, page_bytes):
        """Pages of `page_bytes` each that one chunk of an offload
        holds: at most OFFLOAD_CHUNK_BYTES, and a page table's
        width."""
        return min(self.sc.max_pages_per_seq,
                   max(1, OFFLOAD_CHUNK_BYTES // page_bytes))

    def _gather_pool_pages(self, up, f, k_pool, v_pool, page_ids, digests,
                           layers, page_bytes, bucket=_offload_bucket,
                           kind=None):
        """Pages `page_ids` of one pair of pools onto the upload `up`,
        page i under `digests[i]`'s keys for `layers` (the pool's
        layers, by their rank among those that keep pages). In chunks
        of at most OFFLOAD_CHUNK_BYTES, each one gather program and
        one device-to-host transfer, all dispatched here and now; each
        is one store batch on the upload thread. `f`: the offload
        span's fields (`padded_pages`). `kind`: the one kind of page
        the pool holds (a pool of its own shape: `cfg.page_shape`);
        None: every kind of the family, of the first kind's shape."""
        kinds = kind or self.cfg.page_kinds
        shape = self.cfg.page_shape(kinds[0])
        c = self._chunk_pages(page_bytes)
        for a in range(0, len(page_ids), c):
            # The chunk's ids, padded to a bucket with the scratch
            # page 0: those rows are the tail of the array and never
            # reach the store.
            part = page_ids[a:a + c]
            ids = np.zeros(bucket(len(part), c), np.int32)
            ids[:len(part)] = part
            f["padded_pages"] += len(ids)
            flat = _gather_pages(k_pool, v_pool, self._to_device(ids))
            # Quantized pages stay on the device: the store call
            # quantizes there, so only packed int8 crosses over.
            if not self.sc.quantized_store:
                flat.copy_to_host_async()
            up.chunks.append((flat, shape, content_page_keys_by_page,
                              (digests[a:a + c], layers, kinds)))

    def _gather_snapshot_rows(self, up, slot, digest):
        """The slot's boundary copy onto the upload `up`, keyed by
        `digest` (of the last full page: the copy IS the state at its
        end), in the offload's form: ONE gather program, its result in
        chunks of at most OFFLOAD_CHUNK_BYTES, each a device-to-host
        transfer started here and a store batch on the upload
        thread."""
        layers = self.cfg.n_state_layers
        c = max(1, OFFLOAD_CHUNK_BYTES // (self._snapshot_bytes // layers))
        with self._span("istpu.cache.state_out", slot.work.req.request_id,
                        bytes=self._snapshot_bytes):
            chunks = _gather_snapshot(self.cfg, self.bstate,
                                      self._slot_dev(slot.index), c)
            for i, flat in enumerate(chunks):
                flat.copy_to_host_async()
                up.chunks.append((flat, (self._snapshot_row,), snapshot_keys,
                                  (digest, i * c, min(i * c + c, layers))))

    # ---- the upload thread and its acknowledgements --------------------

    def _upload_room(self, nbytes):
        """Before an offload of `nbytes` gathers anything, inside its
        span: wait (and count it) while uploads are in flight and this
        one would take them past UPLOAD_INFLIGHT_BYTES. Returns
        whether the store is still in use: a failure may have come
        home meanwhile."""
        def full():
            return self.uploads_pending and \
                self._upload_bytes + nbytes > UPLOAD_INFLIGHT_BYTES

        if full():
            self.stats["upload_backpressure_waits"] += 1
            with self._span("istpu.cache.upload_backpressure",
                            bytes=self._upload_bytes):
                while full():
                    self._await_ack()
        return self._store_ok

    def _enqueue_upload(self, up):
        """One put on the upload queue; the thread starts with the
        first. It holds no reference to the engine between uploads,
        and ends when the engine is closed or collected."""
        if self._upload_thread is None:
            self._upload_thread = threading.Thread(
                target=_upload_loop,
                args=(weakref.ref(self), self._todo, self._acked),
                name=f"istpu-upload-{self.engine_id}", daemon=True)
            self._stop_uploads = weakref.finalize(self, self._todo.put, None)
            self._upload_thread.start()
        self.uploads_pending += 1
        self._upload_bytes += up.nbytes
        self.stats["uploads"] += bool(up.chunks)
        up.put_ns = time.perf_counter_ns()
        self._todo.put(up)

    def _run_upload(self, up):
        """ON THE UPLOAD THREAD, one upload at a time in the order they
        were put: per chunk the keys, the wait for its transfer
        (`to_host`) and the store batch, through the same calls by the
        same names as ever (`store.put_kv_pages[_quantized]`), then ONE
        `store.conn.sync()`. An exception stays in `up.error` for the
        engine thread (`_acknowledge`), and every later upload comes
        back untried: the engine serves store-less from then on."""
        up.skipped = self._upload_failed
        if up.skipped or not up.chunks:  # ... or a marker: nothing to do
            up.chunks = []
            return
        try:
            with self._span("istpu.cache.upload", up.request,
                            reason=up.reason, pages=up.pages,
                            bytes=up.nbytes, puts=0,
                            queued_ns=time.perf_counter_ns() - up.put_ns
                            ) as f:
                while up.chunks:
                    flat, row, keys_of, args = up.chunks.pop(0)
                    keys = keys_of(*args)
                    rows = flat if self.sc.quantized_store else to_host(flat)
                    rows = rows.reshape(-1, *row)
                    self._put_pages(keys, rows[:len(keys)])
                    f["puts"] += 1
                    del flat, rows  # the chunk's HBM goes with them
                with self._span("istpu.cache.offload_sync"):
                    self.store.conn.sync()
        except Exception as e:
            # The sequence's OUTPUT does not depend on the offload;
            # losing it only costs future cache hits.
            up.error = e
            up.chunks = []
            self._upload_failed = True

    def collect_uploads(self, wait_s=0.0):
        """On the engine thread (every step starts with it, and a
        driver calls it while `uploads_pending` and nothing to step):
        take the acknowledgements that have come back, waiting up to
        `wait_s` for the first. Each counts what its upload wrote,
        brings a failure home, and puts the tokens of the request
        whose `done` it held into `outputs`. Returns how many."""
        n = 0
        while self.uploads_pending:
            try:
                if wait_s and not n:
                    up = self._acked.get(timeout=wait_s)
                else:
                    up = self._acked.get_nowait()
            except queue.Empty:
                break
            n += 1
            self._acknowledge(up)
        return n

    def _acknowledge(self, up):
        self.uploads_pending -= 1
        self._upload_bytes -= up.nbytes
        if up.error is not None:
            self._store_failed("offload", up.error)
        elif not up.skipped:
            for name, n in up.counts.items():
                self.stats[name] += n
            own = self._own_digests
            own.update(dict.fromkeys(up.digests))
            while len(own) > OWN_DIGESTS:
                del own[next(iter(own))]
        if up.done is not None:
            rid, tokens, finished_at = up.done
            self.outputs[rid] = tokens
            self.stats["done_held_ms"] += \
                (time.perf_counter() - finished_at) * 1e3

    def _await_ack(self):
        """Block until one more acknowledgement is collected."""
        while not self.collect_uploads(wait_s=1.0):
            if not self._upload_thread.is_alive():
                raise RuntimeError(
                    f"the upload thread ended with {self.uploads_pending} "
                    f"uploads unacknowledged")

    # ---- the restore thread and what it stages --------------------------

    def _stage(self, work):
        """`work`'s probe and store read, handed to the restore thread
        (which starts with the first, and ends as the upload thread
        does). Requests are staged as they are submitted and the queue
        is FIFO, so the head's staging never waits for room behind a
        request that can only be admitted after it. (A preempted
        request goes to the queue's FRONT: it is not staged again, and
        its re-admission makes the store call itself.)"""
        if self._restore_thread is None:
            self._restore_thread = threading.Thread(
                target=_restore_loop,
                args=(weakref.ref(self), self._to_stage),
                name=f"istpu-restore-{self.engine_id}", daemon=True)
            self._stop_restores = weakref.finalize(
                self, self._to_stage.put, None)
            self._restore_thread.start()
        work.staged = _Stage(work.req.request_id, work.prompt,
                             time.perf_counter_ns())
        self._to_stage.put(work.staged)

    def _run_stage(self, st):
        """ON THE RESTORE THREAD, one request at a time in the order
        they arrived: the digests, the probe and its prefetch hint
        (`_probe`), then, for a hit and once it has room under
        RESTORE_STAGED_BYTES, the keys, the store call or calls and the
        transfer to the engine's device (`_read_hit`), by the same
        calls under the same span names as the engine thread makes
        them. Nothing else: no slot, no pool page, no `stats`, no
        `_own_digests`; what went wrong stays in `st.error` for the
        engine thread (`_staged_probe`, `_restore`)."""
        if st.dropped:
            st.probed.set()
            st.done.set()
            return
        span = self._span("istpu.cache.stage", st.request, hit_pages=0,
                          pages=0, bytes=0,
                          queued_ns=time.perf_counter_ns() - st.put_ns)
        try:
            with span as f:
                try:
                    st.hit, st.digests, st.counts = self._probe(
                        st.prompt, st.request)
                except Exception as e:
                    st.error = ("probe", e)
                    return
                finally:
                    st.probed.set()
                if not st.hit:
                    return
                f["hit_pages"] = st.hit
                st.first_live = self._first_live(st.hit)
                pages, nbytes, _ = self._restore_size(st.hit, st.first_live)
                if not self._stage_room(st, nbytes):
                    return
                f.update(pages=pages, bytes=nbytes)
                try:
                    got = self._read_hit(st.hit, st.digests, st.first_live)
                except Exception as e:
                    st.error = ("restore", e)
                    got = None
                with self._stage_cv:
                    if got is None:
                        self._let_go(st)
                    elif not st.dropped:  # (else its bytes went with it)
                        st.restored, st.snap, st.read = got
        finally:
            st.dur_ns = span.dur_ns
            st.done.set()

    def _stage_room(self, st, nbytes):
        """On the restore thread, before it reads a hit of `nbytes`:
        wait while hits are staged and this one would take them past
        RESTORE_STAGED_BYTES, then count it. False where the engine
        thread let `st` go meanwhile."""
        with self._stage_cv:
            while not st.dropped and self._staged_bytes \
                    and self._staged_bytes + nbytes > RESTORE_STAGED_BYTES:
                self._stage_cv.wait()
            if st.dropped:
                return False
            st.nbytes = nbytes
            self._staged_bytes += nbytes
            return True

    def _let_go(self, st):
        """Under `_stage_cv`: what `st` holds staged is no longer."""
        self._staged_bytes -= st.nbytes
        st.nbytes = 0
        st.restored = st.snap = None
        self._stage_cv.notify_all()

    def unstage(self, work):
        """On the engine thread: `work` is through with what it had
        staged (its admission took it, or will do without): the arrays
        go and their bytes make room. A read still under way is
        dropped where it ends."""
        st, work.staged = work.staged, None
        if st is not None:
            with self._stage_cv:
                st.dropped = True
                self._let_go(st)

    def _await_stage(self, st, event, f):
        """On the engine thread, inside an admission (`f`: its span's
        fields): block until the restore thread has set `event` of
        `st`, and count the wait."""
        if event.is_set():
            return
        t0 = time.perf_counter_ns()
        while not event.wait(1.0):
            if not self._restore_thread.is_alive():
                raise RuntimeError(
                    f"the restore thread ended with request {st.request} "
                    f"not staged")
        waited = time.perf_counter_ns() - t0
        f["staged_wait_ns"] += waited
        self.stats["restore_stage_wait_ms"] += waited / 1e6

    def _staged_probe(self, work, f):
        """The probe `work` has staged, waited for and counted as
        `_probe_hit` counts its own: (hit, digests), or None where
        nothing is staged."""
        st = work.staged
        if st is None:
            return None
        self._await_stage(st, st.probed, f)
        if st.error is not None and st.error[0] == "probe":
            self.unstage(work)
            self._store_failed(*st.error)
            return 0, []
        for name, n in st.counts.items():
            self.stats[name] += n
        st.counts = {}
        return st.hit, st.digests

    def _head_ready(self):
        """Whether what the queue's head has staged is done (or it has
        nothing staged): its admission then waits for no store call."""
        st = self.queue[0].staged
        return st is None or st.done.is_set()

    def _occupied(self):
        """Whether any slot holds a sequence."""
        return any(s is not None for s in self.slots)

    def drain_uploads(self):
        """Block until every upload is acknowledged and collected:
        what was offloaded is in the store, what was held is in
        `outputs`. A decode step in flight lands first."""
        self.land()
        while self.uploads_pending:
            self._await_ack()

    def close(self):
        """Drain the uploads and stop the upload thread and the
        restore thread; what queued requests had staged goes (their
        admissions make the store call themselves). Whoever
        closes the store's connection calls this first: a native call
        on a closed handle is a use-after-free (`LayerStreamer.close`
        has the note). The engine stays usable; its next offload and
        its next request staged start a thread anew."""
        self.land()
        for work in self.queue:
            self.unstage(work)
        if self._restore_thread is not None:
            self._stop_restores()
            _join(self._restore_thread)
            self._restore_thread = None
        if self._upload_thread is not None:
            self.drain_uploads()
            self._stop_uploads()
            _join(self._upload_thread)
            self._upload_thread = None

    def _shed_windows(self, active, more=0):
        """Before a decode step of a model with two kinds of attention
        layer (`more` = 1: the step BEHIND the one in flight, whose
        lengths lie one beyond the slots'): the banded layers' pages
        that lie wholly below a slot's
        band floor (seq_len - band: decode masks below it) are shed,
        written to the store where the store lacks them, then freed,
        and the slot's short table moved up. Not a page an edge a
        slot: nothing is shed until some slot has `_shed_pages` such
        pages (half the table's slack) or a full table, and then EVERY
        slot that has any sheds them in ONE offload
        (`_offload_window`). The full layers' pages stay
        (`_release`)."""
        page = self.cfg.page_size
        band = self.cfg.window_band
        due = [(i, s, (s.seq_len + more - band) // page) for i, s in active]
        due = [(i, s, dead) for i, s, dead in due if dead > s.wbase]
        if not any(dead - s.wbase >= self._shed_pages
                   or (s.seq_len + more) // page - s.wbase >= self._wtable_w
                   for _, s, dead in due):
            return
        self._offload_window(due)
        for i, s, dead in due:
            n = dead - s.wbase
            self.wfree.extend(s.wpage_ids[:n])
            s.wpage_ids = s.wpage_ids[n:]
            s.wbase = dead
            s.wstored = max(s.wstored, dead)
            self.wtable[i] = 0
            self.wtable[i, :len(s.wpage_ids)] = s.wpage_ids
            self.stats["window_pages_released"] += n
        self._pages_rev += 1

    def _offload_window(self, due):
        """The banded layers' pages that `due` slots are about to shed
        ([(slot index, slot, first page that stays)]), what the store
        lacks of them, as one offload: one gather over all the slots'
        pages, one upload. The caller frees the pool pages at once
        (`_offload_full_pages` has the rule)."""
        ids, digests, slots = [], [], 0
        for _, s, dead in due:
            lo = max(s.wstored, s.wbase)
            if dead <= lo or not self._store_chain(s.work):
                continue
            ids += s.wpage_ids[lo - s.wbase:dead - s.wbase]
            digests += self._slot_digests(s, dead)[lo:dead]
            slots += 1
        nbytes = len(ids) * self._wpage_bytes
        if not ids:
            return
        up = _Upload("window", None, len(ids), nbytes,
                     counts={"window_pages_offloaded": len(ids)})
        with self._span("istpu.cache.offload", reason="window",
                        pages=len(ids), slots=slots, bytes=nbytes,
                        padded_pages=0, puts=0) as f:
            if not self._upload_room(nbytes):
                return
            self._gather_pool_pages(up, f, self.wk_pages, self.wv_pages, ids,
                                    digests, self._win_layers,
                                    self._wpage_bytes, _pow2_bucket)
            f["puts"] = len(up.chunks)
            self._enqueue_upload(up)

    def _release(self, slot_idx, slot):
        # [0:released) already went back to the pool when those pages
        # left the sliding window — freeing them twice would hand the
        # same pool page to two slots.
        self.free_pages.extend(slot.page_ids[slot.released:])
        if self._win_layers:
            self.wfree.extend(slot.wpage_ids)
        self.slots[slot_idx] = None
        self._pages_rev += 1

    def _release_windowed(self, slot):
        """Sliding-window KV bound (the rolling-buffer property): pages
        whose every position is below the band floor (seq_len - window)
        can never be attended again — decode, verify and suffix prefill
        all mask below the floor — so their pool pages go back to the
        free list and live KV stays O(window) per slot however long the
        generation runs. The page-table ENTRIES keep pointing at the
        freed (possibly reused) pages: the attention kernels skip
        sub-floor pages for compute, and the XLA fallbacks mask their
        logits before the softmax, so reused contents are never
        observable. Each page is offloaded to the store first (content
        keys are stable as generation grows), keeping the prefix-hash
        chain intact for future cache hits and for preemption
        re-admission."""
        window = getattr(self.cfg, "window", 0)
        if not window:
            return
        dead = (slot.seq_len - window) // self.cfg.page_size
        if dead <= slot.released:
            return
        self._offload_full_pages(slot, hi=dead, reason="window")
        self.free_pages.extend(slot.page_ids[slot.released:dead])
        slot.released = dead

    def _finish(self, slot_idx, slot):
        """The request's pages go on their way to the store and its
        slot and pool pages are free at once. Its tokens reach
        `outputs` (what a driver sends `done` from) only when the
        upload thread has acknowledged its offload's sync, and so
        every earlier write of the request, the queue being FIFO: the
        next turn of the conversation may not overtake them. With
        nothing to wait for (no store chain, nothing new to write and
        nothing in flight) they are there when this returns. Every
        token was streamed as it was made (`_emit`), so no gap between
        tokens holds the wait."""
        work = slot.work
        rid, tokens = work.req.request_id, work.done + slot.generated
        up = self._offload_full_pages(slot)
        if up is None and self.uploads_pending and self._store_chain(work):
            # Nothing new of its own, but writes still in flight (a
            # window's shed pages, an admission's sub-floor ones): a
            # marker behind them.
            up = _Upload("finish", rid, 0, 0)
            self._enqueue_upload(up)
        if up is None:
            self.outputs[rid] = tokens
        else:
            up.done = (rid, tokens, time.perf_counter())
        self.finished += 1
        self._release(slot_idx, slot)

    def _preempt(self, slot_idx, slot):
        """Swap the sequence OUT through the store (vLLM's preemption
        with the disaggregated pool as the swap device): persist its new
        full pages, free its pool pages, and requeue it at the FRONT;
        re-admission travels the normal prefix-HIT path — restore the
        cached pages, recompute only the partial tail page — and decoding
        resumes exactly where it left off. Its re-admission needs the
        pages in the store, so this alone waits for its upload."""
        self._offload_full_pages(slot, reason="preempt")
        self.drain_uploads()
        work = slot.work
        work.done.extend(slot.generated)
        work.prompt = list(work.prompt) + slot.generated
        work.probe = None  # prompt changed: stale probe
        self._release(slot_idx, slot)
        work.queued_ns, work.queue_len = time.time_ns(), 0
        self.queue.insert(0, work)
        self.stats["preemptions"] += 1

    def step(self):
        """One engine iteration: admit into free slots, then decode one
        token for every active slot. Returns #active slots decoded.

        A plain decode step runs one step AHEAD wherever the host can
        prove the next batch from what it holds (`_proven`): step N+1
        is dispatched, with step N's device outputs as its tokens and
        lengths, BEFORE the host waits for N's tokens, so the host's
        round between two steps runs beside a program and the device
        does not stand idle for it. The contract holds either way: a call lands one token for every active slot, and
        `decode_steps` / `decoded_tokens` move when a step LANDS."""
        with self._span("istpu.engine.step", kind="idle", active=0,
                        k=0, device=self._device_index) as f:
            c0 = profiling.compilations()
            try:
                return self._step(f)
            finally:
                f["compiled"] = profiling.compilations() - c0
                self.stats["compilations"] += f["compiled"]

    def _step(self, f):
        """step() proper; `f` holds the step span's fields (kind,
        active slots, k)."""
        self.collect_uploads()
        if self._flight is not None:
            # A plain step N is on the device: step N+1 behind it
            # first, where proven and backed, THEN N's tokens, so that
            # the host's whole round (the wait's return lag, the emit,
            # the loop, the bookkeeping, the dispatch) lies beside a
            # running program. What `_proven` refuses (a finish, a
            # sampling slot, a piece...) lands here and finds, in the
            # next call, the synchronous order below; but an admission
            # keeps the place that order gives it, BEFORE the step's
            # tokens: its probe, store call and transfer run beside
            # the program in flight and its own program queues behind
            # it, so an arrival waits for what is left of one program
            # and not for its return as well.
            flight, self._flight = self._flight, None
            active = flight.active
            f.update(kind="decode", active=len(active), k=1)
            if self._proven(active):
                self._send_behind(active, f)
            else:
                self._admit_queued()
            return self._land_span(flight)
        self._admit_queued()
        if self.sc.admit_piece:
            self._step_pieces()

        active = self._active()
        if not active:
            return 0

        # Sequences at max_new_tokens finish BEFORE the step (their last
        # sampled token never needs its KV appended).
        for i, s in list(active):
            if self._done(s):
                self._finish(i, s)
        active = self._active()
        if not active:
            return 0

        if self.sc.spec_k > 0:
            proposals = {}
            for i, s in active:
                ctx = list(s.work.prompt) + s.generated
                allowed = s.work.req.max_new_tokens - s.total_generated()
                p = list(self.proposer(ctx, self.sc.spec_k))
                p = p[: max(0, allowed - 1)]
                # A buggy/hostile proposer must not index out of vocab.
                proposals[i] = [int(t) % self.cfg.vocab_size for t in p]
            if any(proposals.values()):
                f.update(kind="spec", active=len(active),
                         k=self.sc.spec_k + 1)
                return self._spec_decode(active, proposals)
            # Every draft is empty: the plain single-token path below is
            # strictly cheaper (pallas decode kernel, no (k+1)-wide
            # verify FLOPs) — the common case on non-repetitive text.

        # Burst size for multi-step host scheduling: every active slot
        # greedy and within budget for k more tokens; power-of-2
        # bucketed so _decode_scan compiles O(log host_steps) variants.
        greedy = all(s.work.req.temperature <= 0 for _, s in active)
        k = 1
        if greedy and self.sc.host_steps > 1:
            k = min(
                self.sc.host_steps,
                min(s.work.req.max_new_tokens - s.total_generated()
                    for _, s in active),
            )
            k = max(k, 1)
            while k & (k - 1):
                k &= k - 1

        if self._win_layers:
            self._shed_windows(active)
        if self._fold:
            self._fold_due(active)
        for i, s in active:
            if not self._ensure_pages(i, s, s.seq_len + k - 1):
                if k > 1 and self._ensure_pages(i, s, s.seq_len):
                    # Burst not backable but a single step is: drop the
                    # whole batch to k=1 (pages ensured for other slots
                    # beyond 1 step stay owned and get used later).
                    k = 1
                else:
                    # Pool exhausted mid-decode. If other sequences are
                    # running, swap this one out through the store and
                    # let them drain — it resumes via the prefix-HIT
                    # path when pages free up. Alone, preemption can't
                    # help (the whole pool is already ours): finish
                    # early with the tokens produced so far rather than
                    # deadlock.
                    if len(active) > 1:
                        self._preempt(i, s)
                    else:
                        self._finish(i, s)
                    continue
        active = self._active()
        if not active:
            return 0

        f.update(kind="burst" if k > 1 else "decode", active=len(active),
                 k=k)
        if k > 1:
            key, token_dev, lens_dev, rows_dev = self._step_inputs(
                active, greedy, f)
            live_pages = self._count_attn_pages(active, k)
            with self._span("istpu.model.decode", program="decode_scan",
                            live_pages=live_pages) as df:
                (toks_dev, lens_next, self.k_pages,
                 self.v_pages) = _decode_scan(
                    self.params, self.cfg, token_dev, lens_dev,
                    self.k_pages, self.v_pages, rows_dev, k,
                    model=self.model,
                )
                df["dispatch_ns"] = profiling.elapsed_ns()
                toks = np.asarray(toks_dev)  # [B, k] — the one D2H
            trimmed = False
            at = self._gap_mark()
            for i, s in active:
                burst = [int(t) for t in toks[i]]
                if self.sc.eos_id >= 0 and self.sc.eos_id in burst:
                    # Tokens past the EOS were computed but are never
                    # emitted; their KV beyond seq_len is masked and
                    # overwritten by any later occupant of the pages.
                    burst = burst[: burst.index(self.sc.eos_id) + 1]
                    trimmed = True
                self._emit(s, burst, at)
                s.seq_len += len(burst)
                self._release_windowed(s)
                self.stats["decoded_tokens"] += len(burst)
            self._count_gaps(at)
            self.stats["decode_steps"] += k
            # `key` is still valid here: nothing between its
            # computation and this point mutates the active set or
            # _pages_rev (the steady-key invariant lives in ONE place).
            self._steady = (
                None if trimmed else (key, toks_dev[:, -1], lens_next,
                                      rows_dev)
            )
            return len(active)

        # The plain step: its two halves, `_dispatch` and `_land`.
        # Synchronous, they lie under ONE span from the dispatch to the
        # tokens on the host. Where the step behind is proven (asked
        # BEFORE the dispatch, of the same host state) a run ahead
        # begins: the span ends at the dispatch, the step behind goes
        # out, and then this one lands.
        proven = self._proven(active)
        with self._span("istpu.model.decode", program="decode_fused") as df:
            flight = self._dispatch(active, greedy, f, df)
            if not proven:
                return self._land(flight, df)
        self._send_behind(active, f)
        return self._land_span(flight)

    def _admit_queued(self):
        """The queue's head into every free slot, while it admits. A
        head whose staging is not done stays queued while a slot holds
        a sequence (its steps or pieces go on beside the restore
        thread's read, and the next call looks again); with every slot
        free its admission waits for the staging there, so a caller's
        stall rule never sees a head that is only waiting for its
        pages."""
        self._piece_ran = False
        for i in range(self.sc.max_slots):
            if self.slots[i] is None and self.queue:
                if not self._head_ready() and self._occupied():
                    return
                if self._admit(i, self.queue[0]):
                    self.queue.pop(0)

    def _done(self, slot, more=0):
        """Whether `slot` has its last token once `more` further
        tokens have landed: `max_new_tokens` by count, or an EOS as
        the last one that landed."""
        return (slot.total_generated() + more
                >= slot.work.req.max_new_tokens
                or (self.sc.eos_id >= 0 and bool(slot.generated)
                    and slot.generated[-1] == self.sc.eos_id))

    def _proven(self, active):
        """THE predicate of the run ahead: whether, with a plain step N
        of `active` dispatched (or about to be) and NOT landed, the
        host can prove step N+1's batch from what it holds. Then N+1
        needs nothing of N but its device outputs: tokens and lengths
        are N's, the page tables follow from lengths the host counts.
        From what the engine observes alone:

        - no burst and no draft (`host_steps`, `spec_k`): a burst's
          size and a proposal follow N's tokens;
        - not a family with recurrent state under `eos_id >= 0`: an
          EOS that N shows drops the slot's row of N+1, and a boundary
          copy taken behind N+1 would hold the dropped token's state.
          A family without state runs ahead under an EOS all the same:
          the dropped row's KV lies beyond `seq_len` in a page the
          slot owns, and no offload reads it (`_land`);
        - the active set is every occupied slot (no admission in
          pieces under way) and no admission this call could make (a
          queue head whose pages are staged, where it stages any, and
          a free slot): the step behind an admission holds the new
          sequence too, and an arrival never finds two programs queued
          before its own. A head that still waits for its pages is no
          admission this call could make: the run ahead goes on beside
          the restore thread's read;
        - every slot greedy (a sampler needs N's logits row) and still
          short of its last token AFTER N's (a count; or an EOS
          landed).

        The pages of N+1 are `_send_behind`'s, behind N's dispatch."""
        sc = self.sc
        if sc.host_steps > 1 or sc.spec_k > 0 \
                or (sc.eos_id >= 0 and self.state is not None):
            return False
        if sum(s is not None for s in self.slots) != len(active) \
                or (self.queue and len(active) < sc.max_slots
                    and self._head_ready()):
            return False
        return not any(s.work.req.temperature > 0 or self._done(s, 1)
                       for _, s in active)

    def _send_behind(self, active, f):
        """Step N+1 behind the step N in flight (`_proven` said it is
        this batch's), with the lengths it will see, one beyond the
        slots': its pages as `_step` has them for a step at rest (the
        banded layers' shed, every slot's page of position `seq_len +
        1`), then its dispatch, alone under its span; the call is one
        that ran `ahead`. Where the pool is out nothing goes out: the
        caller lands N, and the next call's preemption path decides
        (the pages taken so far stay the slots')."""
        if self._win_layers:
            self._shed_windows(active, more=1)
        if self._fold:
            self._fold_due(active, more=1)
        if not all(self._ensure_pages(i, s, s.seq_len + 1)
                   for i, s in active):
            return
        with self._span("istpu.model.decode", program="decode_fused") as df:
            self._flight = self._dispatch(active, True, f, df, more=1)
        f["ahead"] = True

    def land(self):
        """The decode step in flight, if any, lands: for whoever stops
        stepping the engine (`drain_uploads`, `close` and `idle` call
        it), on the thread that steps it."""
        if self._flight is not None:
            flight, self._flight = self._flight, None
            self._land_span(flight)

    def _land_span(self, flight):
        """`_land` under a span of its own: all of it is the wait."""
        with self._span("istpu.model.decode", program="land",
                        dispatch_ns=0) as df:
            return self._land(flight, df)

    def _step_inputs(self, active, greedy, f, more=0):
        """(steady key, tokens, lengths, page tables) on the device
        for a decode step or burst of `active`.

        Steady-state fast path: if the device already holds exactly
        this step's inputs (previous fused step's outputs, same active
        set, no page-table mutation, pure-greedy slots), skip the
        host->device uploads entirely — one dispatch + one 32-byte
        D2H per decode step (or per k-step burst). The host-side
        input arrays are built ONLY on a cache miss: on the hit path
        they were pure per-step waste (built, then discarded for the
        cached device copies). `more` = 1: behind the step in flight,
        whose outputs the steady cache holds; where a slot crossed a
        page edge only the tables go up anew, tokens and lengths stay
        the device's (the host has not seen them yet)."""
        key = (tuple(i for i, _ in active), self._pages_rev)
        steady = (self._steady is not None and greedy
                  and self._steady[0] == key)
        # of any step the call dispatched (two, where a run begins)
        f["rows_uploaded"] = f.get("rows_uploaded", False) or not steady
        if steady:
            return self._steady
        if more:
            _, token_dev, lens_dev, _ = self._steady
        else:
            token = np.zeros(self.sc.max_slots, dtype=np.int32)
            seq_lens = np.zeros(self.sc.max_slots, dtype=np.int32)
            for i, s in active:
                token[i] = s.generated[-1]
                seq_lens[i] = s.seq_len
            token_dev = self._to_device(token)
            lens_dev = self._to_device(seq_lens)
        rows = np.zeros_like(self.page_table)  # inactive → scratch 0
        for i, _ in active:
            rows[i] = self.page_table[i]
        rows_dev = self._to_device(rows)
        if self._win_layers:
            # ... and the banded layers' short tables with their
            # bases (inactive rows: scratch page 0 from position 0)
            wrows = np.zeros_like(self.wtable)
            wbase = np.zeros(self.sc.max_slots, dtype=np.int32)
            for i, s in active:
                wrows[i] = self.wtable[i]
                wbase[i] = s.wbase * self.cfg.page_size
            rows_dev = (rows_dev, self._to_device(wrows),
                        self._to_device(wbase))
        return key, token_dev, lens_dev, rows_dev

    def _dispatch(self, active, greedy, f, df, more=0):
        """The first half of a plain decode step of `active`: choose
        the inputs and send the fused program (for a family with
        state, its boundary copies behind it). `more` = 1: behind a
        step in flight, so every length lies one beyond the slots'.
        Returns the step, in flight; `df` is its span's fields."""
        key, token_dev, lens_dev, rows_dev = self._step_inputs(
            active, greedy, f, more)
        df["live_pages"] = self._count_attn_pages(active, more=more)
        if self._fold:
            # the rows the step's tables hold, over its sequences, and
            # the positions they stand for
            df["positions"] = sum(s.seq_len + more + 1 for _, s in active)
            df["cache_rows"] = sum(
                decoder.cache_rows(self.cfg, s.seq_len + more) + 1
                for _, s in active)
            layers = self.k_pages.shape[0]
            self.stats["attn_rows_read"] += layers * df["cache_rows"]
            self.stats["attn_positions_live"] += layers * df["positions"]
        if self._index_kind:
            self._count_selected(active, df, more)
        sparse = self._experts_held > 0 or self._selects
        pulled = ()  # in place of nxt_dev, where the step counts experts
        if self._borrowers:
            # live rows of the shared pages x the layers that borrow them
            df["shared_rows"] = self._borrowers * sum(
                s.seq_len + more + 1 for _, s in active)
            self.stats["shared_kv_rows_read"] += df["shared_rows"]
        if self._win_layers and self.state is not None:
            (logits, nxt_dev, lens_next, self.k_pages, self.v_pages,
             self.wk_pages, self.wv_pages, self.state) = _decode_fused_wf_st(
                self.params, self.cfg, token_dev, lens_dev,
                self.k_pages, self.v_pages, self.wk_pages,
                self.wv_pages, self.state, rows_dev, model=self.model,
            )
            self._copy_boundaries(active, more)
        elif self._win_layers:
            (logits, nxt_dev, lens_next, self.k_pages, self.v_pages,
             self.wk_pages, self.wv_pages, *pulled) = _decode_fused_wf(
                self.params, self.cfg, token_dev, lens_dev,
                self.k_pages, self.v_pages, self.wk_pages,
                self.wv_pages, rows_dev, model=self.model,
                fetched=sparse,
            )
        elif self.state is None:
            (logits, nxt_dev, lens_next, self.k_pages, self.v_pages,
             *pulled) = _decode_fused(
                self.params, self.cfg, token_dev, lens_dev,
                self.k_pages, self.v_pages, rows_dev,
                model=self.model, fetched=sparse,
            )
        else:
            (logits, nxt_dev, lens_next, self.k_pages, self.v_pages,
             self.state) = _decode_fused_st(
                self.params, self.cfg, token_dev, lens_dev,
                self.k_pages, self.v_pages, self.state, rows_dev,
                model=self.model,
            )
            self._copy_boundaries(active, more)
        # Reusable next step iff every emitted token is the device's
        # argmax (greedy) — samplers/spec/finishes invalidate via key.
        self._steady = (
            (key, nxt_dev, lens_next, rows_dev) if greedy else None
        )
        # Dispatched; what is left of a synchronous step's span is the
        # wait.
        df["dispatch_ns"] = profiling.elapsed_ns()
        return _Flight(active, pulled[0] if pulled else nxt_dev,
                       bool(pulled), logits, bool(more))

    def _land(self, flight, df):
        """The second half of a plain decode step, and the ONE place
        its tokens reach the host: pull the token array with the
        counts that ride in it, emit, advance. A row whose sequence
        ended at an EOS while this step was in flight behind the one
        that showed it is dropped: never emitted, never counted.
        Returns the tokens landed."""
        nxt = np.asarray(flight.pull)
        active = flight.active
        if self.state is not None:
            # the slots whose state the step moved are the decoding
            # ones, whose count bounds the grid of `ssm.step_kernel`
            df["state_rows_run"] = df["state_rows_active"] = len(active)
            self.stats["state_rows_run"] += len(active)
            self.stats["state_rows_active"] += len(active)
        if flight.counted:
            fetched = int(nxt[self.sc.max_slots])
            df["experts_fetched"] = fetched
            self.stats["moe_experts_fetched"] += fetched
            self.stats["moe_experts_held"] += self._experts_held
        if flight.counted and self._selects:
            # the cache rows the step's attention took and the slots
            # its selections ran over (of which `active` held a
            # sequence), as the device counted them
            # (decoder.decode_step)
            df["rows_selected"] = int(nxt[self.sc.max_slots + 1])
            df["select_rows_run"] = int(nxt[self.sc.max_slots + 2])
            df["select_rows_active"] = len(active)
            self.stats["attn_rows_selected"] += df["rows_selected"]
            self.stats["select_rows_run"] += df["select_rows_run"]
            self.stats["select_rows_active"] += len(active)
        if self._share_layers:
            df["pairs_held"] = int(nxt[-1])
            self.stats["moe_pairs_routed"] += (
                len(active) * self.cfg.top_k * self._share_layers)
            self.stats["moe_pairs_held"] += int(nxt[-1])
        lhost = _LazyHost(flight.logits)
        landed = 0
        at = self._gap_mark()  # the one clock read a landed step
        for i, s in active:
            if self._done(s):
                self.stats["decode_rows_dropped"] += 1
                continue
            if s.work.req.temperature > 0:
                tok = self._pick(s.work, lhost()[i])
            else:
                tok = int(nxt[i])
            self._emit(s, [tok], at)
            s.seq_len += 1
            self._release_windowed(s)
            landed += 1
        df["waiting"] = 0
        self._count_gaps(at, df)
        self._landed_at = at
        self.stats["decoded_tokens"] += landed
        self.stats["decode_steps"] += 1
        self.stats["decode_steps_ahead"] += flight.ahead
        return landed

    def _count_attn_pages(self, active, k=1, more=0):
        """Count what the paged-decode kernel walks in `k` decode steps
        over `active` into `attn_pages_live` / `attn_pages_table`, from
        the lengths held here plus `more` (no device work); returns the
        live pages of the first step (a scan's k steps count as k of
        its first)."""
        page = self.cfg.page_size
        live = 0
        for (pool, band), layers in self._attn_kinds.items():
            for _, s in active:
                # keys the step attends from 0 (rows, where rows are
                # not positions)
                n = decoder.cache_rows(self.cfg, s.seq_len + more) + 1
                if pool == "window":
                    n -= s.wbase * page
                first = max(n - band, 0) // page if band else 0
                live += layers * ((n - 1) // page - first + 1)
        self.stats["attn_pages_live"] += k * live
        self.stats["attn_pages_table"] += k * self._attn_table
        return live

    def _count_selected(self, active, df, more=0):
        """Count what one decode step of `active` under a learned
        selection reads, from the lengths held here plus `more` (no
        device work): the rows live in the attention layers
        (`rows_live` of the step's span) and the index keys in the
        owners' reach, which is every entry of every slot's page table
        (ops/sparse_select.py `select_paged` scores a slot's table
        whole and masks what is not live). Of those the program scores
        the slots its selection runs over, `select_rows_run` of
        `max_slots`: that count and the rows the attention TOOK are
        the device's and arrive with the step's tokens (`_land`). A
        table no wider than `index_topk` runs the dense path: every
        live row is read, no key is scored, and nothing is counted on
        the device."""
        live = self.k_pages.shape[0] * sum(
            s.seq_len + more + 1 for _, s in active)
        df["rows_live"] = live
        self.stats["attn_rows_live"] += live
        if self._selects:
            self.stats["index_keys_scored"] += (
                len(self._index_layers) * self.page_table.size
                * self.cfg.page_size)
        else:
            df["rows_selected"] = live
            self.stats["attn_rows_selected"] += live

    def _copy_boundaries(self, active, more=0):
        """Behind a decode step of a family with state (whose lengths
        lay `more` beyond the slots'): the boundary
        copy of every slot whose sequence the step brought to a page
        edge (its state now is the state at the end of a full page,
        which is where its stored pages can end). One small program a
        crossing slot, dispatched and not waited for."""
        page = self.cfg.page_size
        for i, s in active:
            pos = s.seq_len + more + 1
            if pos % page == 0:
                with self._span("istpu.cache.snapshot",
                                s.work.req.request_id, slot=i,
                                pos=pos, reason="boundary"):
                    self.bstate = _copy_boundary(
                        self.state, self.bstate, self._slot_dev(i))
                self.stats["boundary_copies"] += 1

    def _sample_over_draft(self, work, draft, rows):
        """Rejection-sampling acceptance for a sampled request's draft
        (standard speculative sampling, specialized to a DETERMINISTIC
        proposer — a point-mass draft distribution): draft token t at
        position j is accepted with probability p_target_j(t); on
        rejection the replacement is drawn from the residual
        (p_target_j with t zeroed, renormalized), which leaves every
        emitted token exactly target-distributed — the same
        distribution as draft-less sampling, draw by draw. If the whole
        draft is accepted, a bonus token is sampled from the next row,
        so accepted drafts land several-per-step just like the greedy
        path. Returns (emitted_tokens, n_draft_accepted)."""
        req = work.req
        emitted = []
        for j, t in enumerate(draft):
            p = self._probs(req, rows[j])
            if work.rng.random() < p[t]:
                emitted.append(int(t))
                continue
            resid = p.copy()
            resid[t] = 0.0
            tot = resid.sum()
            if tot <= 0.0:
                # p was (numerically) a point mass AT the draft token;
                # the residual is empty, so the draw IS the draft token.
                emitted.append(int(t))
                continue
            resid /= tot
            emitted.append(int(work.rng.choice(len(resid), p=resid)))
            return emitted, j
        p = self._probs(req, rows[len(draft)])
        emitted.append(int(work.rng.choice(len(p), p=p)))
        return emitted, len(draft)

    def _spec_decode(self, active, proposals):
        """Speculative step: verify each slot's draft (`proposals`,
        precomputed by the caller) PLUS the mandatory current token in
        one multi-token pass. Greedy requests accept the longest
        argmax-matching prefix + the bonus token; sampled requests
        accept via rejection sampling (_sample_over_draft), so drafts
        speed them up WITHOUT changing their output distribution.
        Token-stream parity with plain decoding holds up to kernel
        numerics: verify runs the XLA multi-token attention while plain
        decode runs the pallas flash-decode kernel, so a logit near-tie
        within their accumulation-order difference can flip a greedy
        choice (same caveat class as quantized_store). Accepted drafts
        land several-per-step, amortizing the per-step weight reads
        that bound decode on TPU (HBM-bandwidth-limited)."""
        m = self.sc.spec_k + 1
        self._steady = None  # multi-token advance: device state stale
        props = {}
        for i, s in active:
            p = proposals[i]
            if not self._ensure_pages(i, s, s.seq_len + len(p)):
                # Shrink the draft to what the owned pages can back.
                avail = (
                    len(s.page_ids) * self.cfg.page_size - s.seq_len
                )
                if avail < 1:
                    if len(active) > 1:
                        self._preempt(i, s)
                    else:
                        self._finish(i, s)
                    continue
                p = p[: avail - 1]
            props[i] = p
        if not props:
            return 0
        # The padded [B, m] batch: drafts differ in length, and a ragged
        # row parks its padding in the scratch page (valid_len).
        token = np.zeros((self.sc.max_slots, m), dtype=np.int32)
        seq_lens, valid = np.zeros((2, self.sc.max_slots), dtype=np.int32)
        rows = np.zeros_like(self.page_table)
        active = [(i, s) for i, s in active if i in props]
        for i, s in active:
            toks = [s.generated[-1]] + props[i]
            token[i, : len(toks)] = toks
            valid[i] = len(toks)
            seq_lens[i] = s.seq_len
            rows[i] = self.page_table[i]
        with self._span("istpu.model.decode", program="verify") as df:
            logits, self.k_pages, self.v_pages = self.model.verify_step(
                self.params, self.cfg,
                self._to_device(token), self._to_device(seq_lens),
                self.k_pages, self.v_pages, self._to_device(rows),
                self._to_device(valid),
            )
            nxt_dev = jnp.argmax(logits, axis=-1)
            df["dispatch_ns"] = profiling.elapsed_ns()
            nxt = np.asarray(nxt_dev)
        lhost = _LazyHost(logits)  # ONE transfer if any slot samples
        at = self._gap_mark()
        for i, s in active:
            p = props[i]
            if s.work.req.temperature > 0:
                appended, a = self._sample_over_draft(
                    s.work, p, lhost()[i]
                )
            else:
                a = 0
                while a < len(p) and p[a] == int(nxt[i, a]):
                    a += 1
                appended = p[:a] + [int(nxt[i, a])]
            if self.sc.eos_id >= 0 and self.sc.eos_id in appended:
                # Nothing after the EOS may be emitted; the truncated
                # advance keeps the seq_len/history invariant (pages
                # beyond it hold stale KV that is masked and never
                # offloaded).
                appended = appended[: appended.index(self.sc.eos_id) + 1]
            self._emit(s, appended, at)
            s.seq_len += len(appended)
            self._release_windowed(s)
            self.stats["spec_proposed"] += len(p)
            # Draft tokens actually EMITTED (EOS truncation may drop
            # matched drafts; if the bonus was cut, every emitted token
            # came from the draft).
            self.stats["spec_accepted"] += min(a, len(appended))
            self.stats["decoded_tokens"] += len(appended)
        self._count_gaps(at)
        self.stats["decode_steps"] += 1
        return len(active)

    def run(self, requests=()):
        """Submit `requests`, drive the loop to completion (every
        upload acknowledged: what was offloaded is in the store), and
        return {request_id: generated token list}."""
        for r in requests:
            self.submit(r)
        while self.queue or any(s is not None for s in self.slots):
            before = (len(self.queue), self.finished)
            decoded = self.step()
            progressed = decoded > 0 or (
                (len(self.queue), self.finished) != before
            )
            if not progressed and not any(
                s is not None for s in self.slots
            ):
                # Every slot is free so the whole pool is free: the head
                # request still not admitting means it never will.
                work = self.queue[0]
                if work.done:
                    # A preempted request whose grown prompt (original
                    # prompt + generated tokens) outgrew the pool can
                    # never re-admit — finish it with the output it
                    # already produced (mirroring the alone-slot early
                    # finish) instead of losing every other request's
                    # completed output to a RuntimeError.
                    self.queue.pop(0)
                    self.outputs[work.req.request_id] = list(work.done)
                    continue
                self.unstage(work)
                self.drain_uploads()
                raise RuntimeError(
                    f"request {work.req.request_id} needs more pool "
                    f"pages than exist ({self.sc.total_pages - 1} usable); "
                    "completed outputs remain available in .outputs"
                )
        self.drain_uploads()
        return dict(self.outputs)
