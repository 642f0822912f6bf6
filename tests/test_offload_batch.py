"""The offload as one device program, one transfer and one store batch
a chunk (`ServingEngine._offload_full_pages`; most offloads are one
chunk): what reaches the store is the pool's rows bit for bit under the
keys the per-layer form wrote, the padding of a bucket never does, the
gather programs are bounded by the bucket grid, and a store failure
leaves no uncommitted key behind. The engine thread dispatches the
gathers and frees the pages; the engine's upload thread makes the
store batches and the sync (`_run_upload`), so a test that reads the
store drains the uploads first, as `run()` does.

CPU, tiny widths, the in-process loop-back store of conftest.py. The
engine's pool is filled with random rows and slots are built by hand,
so no model program runs: finish, preemption and windowed release are
driven through the methods the engine itself calls.
"""

import dataclasses
import itertools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import (
    ClientConfig,
    InfiniStoreServer,
    InfinityConnection,
    ServerConfig,
    TYPE_SHM,
)
from infinistore_tpu import serving
from infinistore_tpu._native import FAKE_TOKEN
from infinistore_tpu.models import decoder, llama, moe
from infinistore_tpu.serving import (
    Request, ServingConfig, ServingEngine, _Slot, _Work,
    content_page_keys, content_page_keys_by_page,
)
from infinistore_tpu.sharded import ShardedConnection
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

PAGE = 8
MAX_PAGES = 24   # the grid's points up to here: 1..8, 10, 12, 14, 16,
#                  20, 24
WINDOW = 2 * PAGE
# 1, one under a bucket edge, on it, one over it, max_pages_per_seq.
COUNTS = [1, 9, 10, 11, MAX_PAGES]
_ids = itertools.count()


def _families():
    geometry = dict(vocab_size=64, d_model=32, n_layers=3, n_heads=4,
                    n_kv_heads=2, d_ff=64, max_seq=256, page_size=PAGE)
    return {
        "llama": (llama, llama.LlamaConfig(dtype="bfloat16", **geometry)),
        "moe": (moe, moe.MoEConfig(n_experts=4, top_k=2, dtype="float32",
                                   **geometry)),
    }


@pytest.fixture(scope="module")
def families():
    out = {}
    for name, (model, cfg) in _families().items():
        out[name] = (model, cfg,
                     model.init_params(jax.random.PRNGKey(0), cfg))
    return out


class Recorder:
    """A TpuKVStore that notes every put batch: (method, keys, shape of
    the pages, blocks the store returned)."""

    def __init__(self, inner):
        self._inner = inner
        self.conn = inner.conn
        self.puts = []

    def _put(self, name, keys, pages, **kw):
        blocks = getattr(self._inner, name)(keys, pages, **kw)
        self.puts.append((name, list(keys), tuple(pages.shape), blocks))
        return blocks

    def put_kv_pages(self, keys, pages, **kw):
        return self._put("put_kv_pages", keys, pages, **kw)

    def put_kv_pages_quantized(self, keys, pages, **kw):
        return self._put("put_kv_pages_quantized", keys, pages, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _engine(families, family, conn, window=0, **sc):
    """An engine over a recording store whose pool holds random rows
    (page 0, the scratch page, too: a padded row that reached the store
    would not be zeros)."""
    model, cfg, params = families[family]
    if window:
        cfg = dataclasses.replace(cfg, window=window)
    sc.setdefault("model_id", f"offload-batch-{next(_ids)}")
    sc.setdefault("total_pages", 64)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=2, max_pages_per_seq=MAX_PAGES, **sc),
        store=Recorder(TpuKVStore(conn)), model=model)
    kk, kv = jax.random.split(jax.random.PRNGKey(next(_ids)))
    eng.k_pages = jax.random.normal(kk, eng.k_pages.shape, cfg.jdtype)
    eng.v_pages = jax.random.normal(kv, eng.v_pages.shape, cfg.jdtype)
    return eng


def _slot(eng, n_tokens, seed=0):
    """Slot 0 holding `n_tokens` of KV in pool pages it owns (scattered
    ids, not a run, so that a gather by the wrong ids shows)."""
    rng = np.random.default_rng(seed)
    prompt = [int(t) for t in rng.integers(0, eng.cfg.vocab_size, n_tokens)]
    ids = eng._alloc(-(-n_tokens // PAGE))[::-1]
    slot = _Slot(work=_Work(req=Request(f"r{next(_ids)}", prompt,
                                        max_new_tokens=1), prompt=prompt),
                 page_ids=ids, seq_len=n_tokens)
    eng.slots[0] = slot
    return slot


def _pool_rows(eng, page_ids):
    """{(layer, kind): [n, page, n_kv, hd] host rows} of the pool."""
    k, v = np.asarray(eng.k_pages), np.asarray(eng.v_pages)
    return {(li, kind): pool[li, page_ids]
            for li in range(eng.cfg.n_layers)
            for kind, pool in (("k", k), ("v", v))}


def _row_bytes(cfg):
    """One page of one layer and kind."""
    return int(np.prod(cfg.kv_page_shape())) * cfg.jdtype.itemsize


def _bytes(a):
    return np.ascontiguousarray(a).view(np.uint8)


def _assert_stored(eng, digests, want):
    """Every (layer, kind) of the pages reads back, through the keys of
    the per-layer scheme, equal to the pool rows in `want`."""
    cfg = eng.cfg
    for (li, kind), rows in want.items():
        keys = content_page_keys([], 0, 0, li, kind, digests=digests)
        back = eng.store.get_kv_pages_host(keys, cfg.kv_page_shape(),
                                           cfg.jdtype)
        assert np.array_equal(_bytes(back), _bytes(rows)), (li, kind)


def _chunk_by_pages(monkeypatch, eng, pages):
    """Make a chunk `pages` pages (at the tiny widths here a whole
    page table is far under OFFLOAD_CHUNK_BYTES)."""
    monkeypatch.setattr(serving, "OFFLOAD_CHUNK_BYTES",
                        pages * eng._page_bytes)


def _drive(eng, slot, reason):
    """... and the upload thread's part of it (a preemption waits for
    that itself)."""
    if reason == "finish":
        eng._finish(0, slot)
    elif reason == "preempt":
        eng._preempt(0, slot)
        assert not eng.uploads_pending
    else:
        eng._release_windowed(slot)
    eng.drain_uploads()


@pytest.mark.parametrize("chunk", [None, 4], ids=["one_chunk", "chunks_of_4"])
@pytest.mark.parametrize("n", COUNTS)
@pytest.mark.parametrize("reason", ["finish", "preempt", "window"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_rows_reach_the_store_bit_for_bit(families, shm_conn, monkeypatch,
                                          family, reason, n, chunk):
    """n full pages leave the pool, in one batch or in chunks of 4
    pages; each (layer, kind, page) is the pool's row, the partial tail
    page and the buckets' padding stay behind."""
    windowed = reason == "window"
    if windowed:
        # The pages below the band floor go; the window's own stay, and
        # the slot holds max_pages_per_seq at most.
        n = min(n, MAX_PAGES - WINDOW // PAGE)
    eng = _engine(families, family, shm_conn,
                  window=WINDOW if windowed else 0)
    if chunk:
        _chunk_by_pages(monkeypatch, eng, chunk)
    L = eng.cfg.n_layers
    tail = 0 if n == MAX_PAGES else 3  # a partial page, where one fits
    slot = _slot(eng, n * PAGE + (WINDOW if windowed else tail), seed=n)
    page_ids = list(slot.page_ids[:n])
    want = _pool_rows(eng, page_ids)
    free_before = len(eng.free_pages)
    t0 = time.time_ns()
    _drive(eng, slot, reason)

    sizes = [min(chunk, n - a) for a in range(0, n, chunk)] if chunk \
        else [n]
    assert [p[0] for p in eng.store.puts] == ["put_kv_pages"] * len(sizes)
    assert [p[2] for p in eng.store.puts] == [
        (2 * L * m, *eng.cfg.kv_page_shape()) for m in sizes]
    keys = [k for p in eng.store.puts for k in p[1]]
    assert len(keys) == len(set(keys)) == 2 * L * n
    digests = eng._digests(slot.work.prompt, n)
    assert keys == content_page_keys_by_page(digests, L)
    _assert_stored(eng, digests, want)
    # Nothing beyond the full pages is known to the store.
    beyond = eng._digests(slot.work.prompt + [1] * PAGE, n + 1)
    assert eng.store.cached_prefix_len(
        content_page_keys([], 0, 0, 0, "k", digests=beyond)) == n
    assert eng.stats["offloaded_pages"] == n
    assert eng.stats["store_errors"] == 0
    assert list(eng._own_digests) == digests
    assert len(eng.free_pages) == free_before + (
        n if windowed else len(slot.page_ids))
    assert eng.stats["uploads"] == 1
    if reason == "finish":
        assert eng.outputs == {slot.work.req.request_id: []}
        assert eng.stats["done_held_ms"] > 0
    spans = profiling.spans(since_ns=t0)
    rid = slot.work.req.request_id
    (off,) = [s for s in spans if s.name == "istpu.cache.offload"
              and s.request == rid]
    cap = chunk or MAX_PAGES
    buckets = [serving._offload_bucket(m, cap) for m in sizes]
    assert off.fields == {
        "reason": reason, "pages": n,
        "bytes": n * 2 * L * _row_bytes(eng.cfg),
        "padded_pages": sum(buckets), "puts": len(sizes)}
    # The engine thread's part: the gathers' dispatch alone, no wait
    # for a transfer and no store call. The upload thread's: one
    # transfer a chunk, of its bucket's rows, and one store batch
    # (allocate, then the copy into the pool) of its pages' rows; then
    # the one sync.
    assert not [s for s in spans if s.parent == off.id]
    (upl,) = [s for s in spans if s.name == "istpu.cache.upload"
              and s.request == rid]
    assert upl.tid != off.tid and upl.engine == eng.engine_id
    queued_ns = upl.fields.pop("queued_ns")
    assert 0 <= queued_ns <= upl.t0_ns - off.t0_ns
    assert upl.fields == {"reason": reason, "pages": n,
                          "bytes": off.fields["bytes"], "puts": len(sizes)}
    kids = sorted((s for s in spans if s.parent == upl.id),
                  key=lambda s: s.t0_ns)
    assert [k.name for k in kids] == (
        ["istpu.xfer.d2h", "istpu.store.allocate", "istpu.store.write"]
        * len(sizes) + ["istpu.cache.offload_sync"])
    assert [k.fields["bytes"] for k in kids[:-1:3]] == [
        b * 2 * L * _row_bytes(eng.cfg) for b in buckets]
    assert [k.fields for k in kids[1:-1:3]] == [
        {"keys": m * 2 * L, "bytes": m * 2 * L * _row_bytes(eng.cfg)}
        for m in sizes]
    assert [k.fields["bytes"] for k in kids[2:-1:3]] == [
        m * 2 * L * _row_bytes(eng.cfg) for m in sizes]


def _offload_per_layer(eng, slot):
    """The form the offload had before it became one batch, kept as the
    plain reference of the key scheme and the rows: per layer and kind,
    one eager gather out of a sliced layer and one put."""
    n_full = slot.seq_len // PAGE
    digests = eng._slot_digests(slot, n_full)
    sel = jnp.asarray(slot.page_ids[:n_full], jnp.int32)
    for li in range(eng.cfg.n_layers):
        for kind, pool in (("k", eng.k_pages), ("v", eng.v_pages)):
            eng.store.put_kv_pages(
                content_page_keys([], 0, 0, li, kind, digests=digests),
                jnp.take(pool[li], sel, axis=0))
    eng.store.conn.sync()


@pytest.mark.parametrize("first", ["per_layer", "batch"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_keys_are_the_per_layer_schemes(families, shm_conn, family, first):
    """A page the per-layer form wrote is a hit for the batch and the
    reverse: whichever writes second finds every key taken (first
    writer wins, no byte written again), and an engine that never
    offloaded them restores them as a prefix hit."""
    eng = _engine(families, family, shm_conn)
    n, L = 5, eng.cfg.n_layers
    slot = _slot(eng, n * PAGE + 2)
    want = _pool_rows(eng, slot.page_ids[:n])
    def batch():
        eng._offload_full_pages(slot)
        eng.drain_uploads()

    writers = [lambda: _offload_per_layer(eng, slot), batch]
    if first == "batch":
        writers.reverse()
    writers[0]()
    n_first = len(eng.store.puts)
    writers[1]()
    second = eng.store.puts[n_first:]
    assert n_first == (2 * L if first == "per_layer" else 1)
    assert len(second) == (1 if first == "per_layer" else 2 * L)
    for _, _, _, blocks in second:
        assert (blocks["token"] == FAKE_TOKEN).all()
    digests = eng._digests(slot.work.prompt, n)
    _assert_stored(eng, digests, want)
    # Another engine of the same namespace probes and restores them.
    other = _engine(families, family, shm_conn, model_id=eng.sc.model_id)
    work = _Work(req=Request("probe", slot.work.prompt, max_new_tokens=1),
                 prompt=slot.work.prompt)
    hit, got = other._probe_hit(work)
    assert hit == n and got == digests


def test_bucket_grid():
    """Every count fits its bucket, padding stays under a quarter of
    what moves, and the grid has four steps an octave."""
    grid = sorted({serving._offload_bucket(n, 192) for n in range(1, 193)})
    assert grid == [1, 2, 3, 4, 5, 6, 7, 8, 10, 12, 14, 16, 20, 24, 28, 32,
                    40, 48, 56, 64, 80, 96, 112, 128, 160, 192]
    for n in range(1, 193):
        b = serving._offload_bucket(n, 192)
        assert n <= b and (b - n) / b < 0.25
        assert serving._offload_bucket(b, 192) == b
    # A page table narrower than the next grid point caps the bucket.
    assert serving._offload_bucket(17, 18) == 18
    assert serving._offload_bucket(18, 18) == 18


def test_twenty_counts_compile_no_more_programs_than_buckets(
        families, shm_conn):
    # Another pool shape than every other test's, so that the programs
    # counted are this test's own.
    eng = _engine(families, "llama", shm_conn, total_pages=40)
    counts = list(range(1, 21))
    buckets = {serving._offload_bucket(n, MAX_PAGES) for n in counts}
    assert len(buckets) == 13
    before = serving._gather_pages._cache_size()
    for n in counts:
        eng._finish(0, _slot(eng, n * PAGE, seed=100 + n))
    eng.drain_uploads()
    assert len(eng.store.puts) == len(counts)
    assert serving._gather_pages._cache_size() - before == len(buckets)


@pytest.mark.parametrize("family", ["llama", "moe"])
def test_gather_program_holds_no_layer_of_the_pool(families, family):
    """Read off the compiled program: with a pool far larger than the
    rows gathered, its temporaries stay under the output's size (the
    gathered rows before they are laid out page-major) and far under
    one layer and kind of the pool, which `k_pages[li]` copied."""
    _, cfg, _ = families[family]
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, 8192, *cfg.kv_page_shape()), cfg.jdtype)
    ids = jax.ShapeDtypeStruct((MAX_PAGES,), jnp.int32)
    compiled = serving._gather_pages.lower(pool, pool, ids).compile()
    ma = compiled.memory_analysis()
    one_layer_and_kind = 8192 * _row_bytes(cfg)
    assert ma.output_size_in_bytes == (
        MAX_PAGES * 2 * cfg.n_layers * _row_bytes(cfg))
    assert ma.alias_size_in_bytes == 0  # the pools are not donated
    assert ma.temp_size_in_bytes <= 2 * ma.output_size_in_bytes
    assert ma.temp_size_in_bytes < one_layer_and_kind // 8


def test_restore_asks_in_the_order_the_offload_allocated(monkeypatch):
    """Pages one offload wrote, in chunks, lie in the store's pool in
    the order the restore asks for them (page-major on both sides), so
    the SHM read is one view of the pool: blocks of one 16 KB page each
    at consecutive offsets. The restored stacks are the pool's rows."""
    cfg = llama.LlamaConfig(vocab_size=64, d_model=256, n_layers=2,
                            n_heads=4, n_kv_heads=4, d_ff=64, max_seq=256,
                            page_size=16, dtype="float32")
    row_bytes = _row_bytes(cfg)
    assert row_bytes == 16 << 10  # one block of the store below
    srv = InfiniStoreServer(ServerConfig(
        service_port=0, prealloc_size=0.03125, minimal_allocate_size=16))
    srv.start()
    conn = InfinityConnection(ClientConfig(
        host_addr="127.0.0.1", service_port=srv.service_port,
        connection_type=TYPE_SHM))
    conn.connect()
    try:
        fam = {"llama": (llama, cfg,
                         llama.init_params(jax.random.PRNGKey(0), cfg))}
        eng = _engine(fam, "llama", conn)
        _chunk_by_pages(monkeypatch, eng, 4)
        n = 9
        prompt_len = n * cfg.page_size + 5
        rng = np.random.default_rng(7)
        prompt = [int(t) for t in rng.integers(0, 64, prompt_len)]
        ids = eng._alloc(n + 1)[::-1]
        slot = _Slot(work=_Work(req=Request("w", prompt, max_new_tokens=1),
                                prompt=prompt),
                     page_ids=ids, seq_len=prompt_len)
        eng.slots[0] = slot
        want = _pool_rows(eng, ids[:n])
        eng._finish(0, slot)
        eng.drain_uploads()
        assert len(eng.store.puts) == 3
        seen = []
        inner = eng.store._inner
        real = inner._pool_batch_view

        def spy(blocks, *a):
            seen.append(blocks.copy())
            return real(blocks, *a)
        monkeypatch.setattr(inner, "_pool_batch_view", spy)
        kp, vp = decoder.restored_to_pages(
            cfg, eng._restore(n, eng._digests(prompt, n))[0])
        (blocks,) = seen
        assert len(blocks) == 2 * cfg.n_layers * n
        assert (blocks["pool_idx"] == blocks["pool_idx"][0]).all()
        assert (np.diff(blocks["offset"].astype(np.int64))
                == row_bytes).all()
        for li in range(cfg.n_layers):
            assert np.array_equal(np.asarray(kp[li]), want[(li, "k")])
            assert np.array_equal(np.asarray(vp[li]), want[(li, "v")])
    finally:
        conn.close()
        srv.stop()


@pytest.fixture
def small_store():
    """A store of 64 blocks of 16 KB with nothing to grow into."""
    srv = InfiniStoreServer(ServerConfig(
        service_port=0, prealloc_size=(1 << 20) / (1 << 30),
        minimal_allocate_size=16))
    srv.start()
    conn = InfinityConnection(ClientConfig(
        host_addr="127.0.0.1", service_port=srv.service_port,
        connection_type=TYPE_SHM))
    conn.connect()
    yield srv, conn
    conn.close()
    srv.stop()


@pytest.mark.parametrize("fault", ["allocate_runs_out", "write_raises",
                                   "sync_raises"])
def test_a_failed_offload_leaves_no_uncommitted_key(
        families, small_store, monkeypatch, fault):
    """The store fails in the middle of an offload of four chunks: the
    engine goes store-less; the chunks before the fault are a shorter
    prefix any engine may hit (a chunk holds every layer and kind of
    its pages), and no key of the rest is taken or half there (an
    uncommitted key would count in a probe, swallow the next put and
    404 on a read)."""
    srv, conn = small_store
    eng = _engine(families, "llama", conn)
    L = eng.cfg.n_layers
    _chunk_by_pages(monkeypatch, eng, 4)
    # 16 pages in chunks of 4 x 2 x 3 = 24 blocks: the pool's 64 hold
    # two chunks, the third's allocate runs out part-way through it.
    n, stored = 16, 8
    slot = _slot(eng, n * PAGE)
    want = _pool_rows(eng, slot.page_ids[:stored])
    if fault == "write_raises":
        real_write, calls = conn.write_cache, []

        def third_write_raises(*a, **kw):
            calls.append(1)
            if len(calls) == 3:
                raise ConnectionError("injected write failure")
            return real_write(*a, **kw)
        monkeypatch.setattr(conn, "write_cache", third_write_raises)
        monkeypatch.setattr(serving, "OFFLOAD_CHUNK_BYTES",
                            2 * eng._page_bytes)  # 8 chunks of 12 blocks
        stored = 4
        want = _pool_rows(eng, slot.page_ids[:stored])
    elif fault == "sync_raises":
        real_sync = conn.sync

        def sync_then_raise():
            real_sync()
            raise ConnectionError("injected sync failure")
        monkeypatch.setattr(conn, "sync", sync_then_raise)
        monkeypatch.setattr(serving, "OFFLOAD_CHUNK_BYTES",
                            2 * eng._page_bytes)
        n = stored = 8
        slot.seq_len = n * PAGE
        want = _pool_rows(eng, slot.page_ids[:stored])
    eng._finish(0, slot)
    # The slot and its pages are free and the engine knows of no
    # failure yet: it comes home with the acknowledgement, once, and
    # the request is delivered all the same.
    assert eng.slots[0] is None and slot.page_ids[0] in eng.free_pages
    assert slot.work.req.request_id not in eng.outputs
    eng.drain_uploads()
    monkeypatch.undo()

    assert not eng._store_ok and eng.stats["store_errors"] == 1
    assert eng.stats["offloaded_pages"] == 0 and not eng._own_digests
    assert eng.outputs == {slot.work.req.request_id: []}
    digests = eng._digests(slot.work.prompt, n)
    keys = content_page_keys_by_page(digests, L)
    store = TpuKVStore(conn)
    conn.sync()
    # What was acknowledged before the fault is committed and whole.
    assert srv.kvmap_len() == stored * 2 * L
    assert store.cached_prefix_len(keys) == stored * 2 * L
    _assert_stored(eng, digests[:stored], want)
    other = _engine(families, "llama", conn, model_id=eng.sc.model_id)
    prompt = slot.work.prompt[:n * PAGE]
    assert other._probe_hit(_Work(
        req=Request("probe", prompt + [1], max_new_tokens=1),
        prompt=prompt + [1])) == (stored, digests[:stored])
    if stored < n:
        # The keys of the rest are free: a put that fits commits and
        # reads back.
        few = keys[stored * 2 * L:(stored + 2) * 2 * L]
        rows = np.arange(len(few) * PAGE * 2 * 8, dtype=np.float32).astype(
            eng.cfg.jdtype).reshape(len(few), *eng.cfg.kv_page_shape())
        blocks = store.put_kv_pages(few, rows, sync=True)
        assert (blocks["token"] != FAKE_TOKEN).all()
        back = store.get_kv_pages_host(few, eng.cfg.kv_page_shape(),
                                       eng.cfg.jdtype)
        assert np.array_equal(_bytes(back), _bytes(rows))
    # The next request runs without the store.
    assert eng._probe_hit(_Work(req=slot.work.req,
                                prompt=slot.work.prompt)) == (0, [])


@pytest.mark.parametrize("chunk", [None, 4], ids=["one_chunk", "chunks_of_4"])
@pytest.mark.parametrize("family", ["llama", "moe"])
def test_pages_overwritten_behind_the_gather_reach_the_store_as_they_were(
        families, shm_conn, monkeypatch, gated_transfers, family, chunk):
    """The ownership rule: a finished slot's pool pages are free once
    their gathers are dispatched. A program that overwrites every one
    of them (what the next admission's scatter does), dispatched behind
    the gathers and before the upload thread has waited for a single
    transfer, changes nothing of what the store gets."""
    eng = _engine(families, family, shm_conn)
    if chunk:
        _chunk_by_pages(monkeypatch, eng, chunk)
    n = 11
    slot = _slot(eng, n * PAGE + 3, seed=5)
    ids = list(slot.page_ids[:n])
    want = _pool_rows(eng, ids)
    eng._finish(0, slot)
    assert set(ids) <= set(eng.free_pages) and eng.uploads_pending == 1
    L, shape = eng.cfg.n_layers, eng.cfg.kv_page_shape()
    junk = jnp.full((L, n, *shape), 7, eng.cfg.jdtype)
    at = jnp.asarray(ids)
    # in place (the pools donated), as an admission's scatter writes
    eng.k_pages, eng.v_pages = jax.jit(
        lambda k, v: (k.at[:, at].set(junk), v.at[:, at].set(-junk)),
        donate_argnums=(0, 1))(eng.k_pages, eng.v_pages)
    got = _pool_rows(eng, ids)
    assert all((got[li, "k"] == 7).all() and (got[li, "v"] == -7).all()
               for li in range(L))
    assert eng.collect_uploads() == 0 and not eng.store.puts
    gated_transfers.set()
    eng.drain_uploads()
    _assert_stored(eng, eng._digests(slot.work.prompt, n), want)
    assert eng.stats["offloaded_pages"] == n


def test_uploads_behind_a_failed_one_come_back_untried(
        families, small_store, monkeypatch, gated_transfers):
    """Three finishes in a row, the first one's sync fails: the failure
    reaches `_store_failed` once; the second, put on the queue before
    the engine knew, is acknowledged untried (no store call, nothing
    counted); the third finds the store off and is delivered at once.
    All three are delivered."""
    srv, conn = small_store
    eng = _engine(families, "llama", conn)

    def sync_raises():
        raise ConnectionError("injected sync failure")
    monkeypatch.setattr(conn, "sync", sync_raises)
    slots = [_slot(eng, 2 * PAGE, seed=20 + i) for i in range(3)]
    rids = [s.work.req.request_id for s in slots]
    eng._finish(0, slots[0])
    eng._finish(0, slots[1])
    assert eng.uploads_pending == 2 and eng.outputs == {}
    gated_transfers.set()
    eng.drain_uploads()
    monkeypatch.undo()
    assert not eng._store_ok and eng.stats["store_errors"] == 1
    assert [len(p[1]) for p in eng.store.puts] == [2 * 2 * eng.cfg.n_layers]
    assert eng.stats["offloaded_pages"] == 0 and not eng._own_digests
    assert eng.stats["uploads"] == 2
    eng._finish(0, slots[2])
    assert eng.uploads_pending == 0 and eng.stats["uploads"] == 2
    assert eng.outputs == {rid: [] for rid in rids}
    assert eng.stats["store_errors"] == 1


def test_the_in_flight_cap_makes_the_engine_wait_and_counts_it(
        families, shm_conn, monkeypatch, gated_transfers):
    """UPLOAD_INFLIGHT_BYTES: an offload that would take the bytes in
    flight past the cap waits, before it gathers anything, for
    acknowledgements, and `upload_backpressure_waits` counts the wait;
    one that fits does not wait, and one larger than the cap alone goes
    when nothing else is in flight."""
    eng = _engine(families, "llama", shm_conn)
    monkeypatch.setattr(serving, "UPLOAD_INFLIGHT_BYTES",
                        5 * eng._page_bytes)
    eng._finish(0, _slot(eng, 3 * PAGE, seed=30))
    eng._finish(0, _slot(eng, 2 * PAGE, seed=31))  # 5 pages: at the cap
    assert eng.uploads_pending == 2
    assert eng.stats["upload_backpressure_waits"] == 0
    threading.Timer(0.2, gated_transfers.set).start()
    t0 = time.time_ns()
    eng._finish(0, _slot(eng, 8 * PAGE, seed=32))  # alone past the cap
    # It waited until BOTH were acknowledged, and only then gathered.
    assert eng.uploads_pending == 1 and eng._upload_bytes == \
        8 * eng._page_bytes
    assert eng.stats["upload_backpressure_waits"] == 1
    assert eng.stats["offloaded_pages"] == 5 and len(eng.outputs) == 2
    spans = profiling.spans(since_ns=t0)
    (off,) = [s for s in spans if s.name == "istpu.cache.offload"]
    (wait,) = [s for s in spans
               if s.name == "istpu.cache.upload_backpressure"]
    assert wait.fields == {"bytes": 5 * eng._page_bytes}
    # ... a child of the offload's own span: what the engine thread
    # pays for an offload, the wait for room included.
    assert wait.dur_ns > 0.1e9 and wait.parent == off.id
    assert off.fields["puts"] == 1 and off.dur_ns >= wait.dur_ns
    eng.drain_uploads()
    assert eng.stats["offloaded_pages"] == 13 and eng._upload_bytes == 0
    assert eng.stats["uploads"] == 3


@pytest.mark.parametrize("n,chunk", [(3, None), (11, None), (11, 4)])
def test_the_quantized_wire_takes_the_same_batches(
        families, shm_conn, monkeypatch, n, chunk):
    eng = _engine(families, "llama", shm_conn, quantized_store=True)
    if chunk:
        _chunk_by_pages(monkeypatch, eng, chunk)
    L = eng.cfg.n_layers
    slot = _slot(eng, n * PAGE + 1)
    want = _pool_rows(eng, slot.page_ids[:n])
    t0 = time.time_ns()
    eng._finish(0, slot)
    eng.drain_uploads()
    sizes = [min(chunk, n - a) for a in range(0, n, chunk)] if chunk \
        else [n]
    assert [(p[0], p[2]) for p in eng.store.puts] == [
        ("put_kv_pages_quantized", (2 * L * m, *eng.cfg.kv_page_shape()))
        for m in sizes]
    digests = eng._digests(slot.work.prompt, n)
    assert [k for p in eng.store.puts for k in p[1]] == (
        content_page_keys_by_page(digests, L))
    # Only the packed int8 pages and their scales cross to the host.
    d2h = [s.fields["bytes"] for s in profiling.spans(since_ns=t0)
           if s.name == "istpu.xfer.d2h"]
    assert sum(d2h) < n * eng._page_bytes
    for (li, kind), rows in want.items():
        back = eng.store.get_kv_pages_quantized(
            content_page_keys([], 0, 0, li, kind, digests=digests),
            eng.cfg.kv_page_shape(), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(back), rows.astype(np.float32), atol=0.05)


@pytest.fixture
def sharded_conn():
    servers = []
    for _ in range(3):
        s = InfiniStoreServer(ServerConfig(
            service_port=0, prealloc_size=0.03125,
            minimal_allocate_size=16))
        s.start()
        servers.append(s)
    conn = ShardedConnection([
        ClientConfig(host_addr="127.0.0.1", service_port=s.service_port)
        for s in servers])
    conn.connect()
    yield servers, conn
    conn.close()
    for s in servers:
        s.stop()


@pytest.mark.parametrize("n,chunk", [(2, None), (9, None), (9, 4)])
def test_a_sharded_connection_takes_the_same_batches(
        families, sharded_conn, monkeypatch, n, chunk):
    """A batch is one call, routed by key: every shard holds a part,
    and every row reads back bit for bit."""
    servers, conn = sharded_conn
    eng = _engine(families, "llama", conn)
    if chunk:
        _chunk_by_pages(monkeypatch, eng, chunk)
    L = eng.cfg.n_layers
    slot = _slot(eng, n * PAGE + 5)
    want = _pool_rows(eng, slot.page_ids[:n])
    eng._finish(0, slot)
    eng.drain_uploads()
    assert len(eng.store.puts) == (-(-n // chunk) if chunk else 1)
    assert {p[0] for p in eng.store.puts} == {"put_kv_pages"}
    assert sum(len(p[1]) for p in eng.store.puts) == 2 * L * n
    assert eng.stats["store_errors"] == 0
    assert all(s.kvmap_len() > 0 for s in servers)
    assert sum(s.kvmap_len() for s in servers) == 2 * L * n
    _assert_stored(eng, eng._digests(slot.work.prompt, n), want)
