"""TPU/JAX accelerator edge: move ``jax.Array`` KV pages to/from the store.

This is the TPU-native replacement for the reference's accelerator path,
which registers CUDA device pointers for GPUDirect RDMA (nv_peer_mem,
reference lib.py:244-251, libinfinistore.cpp:1166-1201) and moves bytes
with ``cudaMemcpyAsync`` through IPC-shared device memory
(infinistore.cpp:570-804). TPUs expose no device-pointer/IPC model, so the
equivalent design is explicit host staging through the server's pool:

- **get (store → TPU)**: pin the committed blocks and hand the pool's
  bytes to ``jax.device_put``. Blocks that lie in the pool as ONE
  contiguous run (what one put batch allocated) are a numpy view over
  the mapped SHM pool — XLA's host-to-device DMA reads straight out of
  the server pool, with no intermediate host copy, the moral equivalent
  of the GPUDirect zero-copy read. A read that spans several runs (a
  hit over more than one offload) is copied ONCE, one memcpy a run,
  into a staging buffer the store keeps and reuses, and transferred
  from there (``TpuKVStore._pool_batch_view``).
- **put (TPU → store)**: device-to-host transfer (``np.asarray`` /
  ``copy_to_host_async``) followed by a one-sided memcpy into the
  allocated pool blocks + commit. One host-side copy, matching the
  reference's D2H ``cudaMemcpyAsync`` into the pool.
- **per-layer overlap**: ``LayerStreamer.submit`` kicks off the layer's
  async device→host copy and enqueues it for a dedicated upload thread,
  which reaps the copy and hands the store write to the connection's IO
  thread — submit never blocks on D2H or the store, so compute of layer
  k+1 overlaps the transfer+write of layer k (the reference's prefill
  upload-thread pattern, demo_prefill.py:57-77, design.rst:56-59).

Everything works identically against the STREAM path (remote server) —
the staging buffer is then private memory and the client streams it over
TCP — so code written against this module is host-topology agnostic.
"""

import os
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

from .lib import InfinityConnection
from .utils import profiling


def enable_compile_cache():
    """Persistent XLA compilation cache for every entry point that
    compiles for the chip (chip_smoke.py, example/serve.py,
    benchmark/run.py through benchmark/lib/serve.py), so the processes of
    one command and successive runs on one machine share executables. Where
    JAX_COMPILATION_CACHE_DIR is set the environment owns the location —
    jax reads the variable itself and no directory is set in code;
    otherwise the cache lives at the fixed path <checkout>/.xla_cache
    (gitignored). Returns the directory in effect."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".xla_cache",
            ),
        )
    return jax.config.jax_compilation_cache_dir


def _flatten_on_device(arr):
    """Device-side flatten of a multi-dim jax.Array (no-op otherwise):
    the prefetch sites and to_host must flatten the SAME way or the
    async D2H and the blocking one hit different arrays (a wasted
    double transfer)."""
    if not isinstance(arr, np.ndarray) and getattr(arr, "ndim", 1) > 1:
        return arr.reshape(-1)
    return arr


def to_host(arr):
    """Device → host as a C-contiguous numpy array.

    The reference lands D2H bytes directly in pool blocks
    (cudaMemcpyAsync into mm->allocate'd memory, reference
    infinistore.cpp:728-748). PJRT exposes no D2H destination control
    from Python (probed: np.asarray of a pinned_host-resident array
    still transfers; dlpack export is unimplemented), so the floor here
    is ONE device->host DMA into jax's host buffer, then ONE native
    memcpy into the pool, and no staging copy between them.

    jax.Array: the transfer is issued on a device-side FLATTENED view.
    PJRT hands multi-dim TPU arrays to the host in their device (tiled)
    layout — observed: a [64,2048,8,8] uint16 transfer arrives
    dim-permuted (strides (262144,2,32768,4096)) — and fixing that up
    host-side is exactly the full-size staging copy this path exists to
    avoid. The flattening reshape is a device relayout (HBM-speed, part
    of the transfer like the reference's cudaMemcpyAsync setup), the
    1-D transfer lands C-contiguous, and the reshape back to the
    caller's shape is a free view — so the bytes go from the D2H buffer
    straight into the pool via the native client's memcpy. A
    non-contiguous numpy input is the only case that still pays a
    staging copy."""
    if isinstance(arr, np.ndarray):
        return arr if arr.flags["C_CONTIGUOUS"] else np.ascontiguousarray(arr)
    if not hasattr(arr, "shape"):  # plain array-likes (lists, scalars)
        return np.ascontiguousarray(arr)
    shape = arr.shape
    flat = _flatten_on_device(arr)
    # Also waits for whatever produces `arr` (the engine's page gather).
    with profiling.span("istpu.xfer.d2h", bytes=arr.nbytes):
        host = np.asarray(flat)
    if not host.flags["C_CONTIGUOUS"]:  # defensive: 1-D should be flat
        host = np.ascontiguousarray(host)
    return host.reshape(shape)


def _device_put_owned(view, device):
    """device_put from a pinned pool view, complete on return and never
    aliasing `view`'s memory: the caller releases the view's pin lease
    the moment this returns, after which the server may reuse the
    bytes. On accelerator targets the transfer is a real DMA copy, so
    the pool view is handed over zero-copy and block_until_ready means
    the bytes have left the pool (chip_smoke.py overwrites the source
    after it returns and checks the device copy on every run); on CPU
    targets PJRT may alias an aligned contiguous host buffer
    (kImmutableZeroCopy) — force a private copy there."""
    platform = device.platform if device is not None else jax.default_backend()
    with profiling.span("istpu.xfer.h2d", bytes=view.nbytes):
        if platform == "cpu":
            view = np.array(view, copy=True)
        return jax.block_until_ready(jax.device_put(view, device))


def _abort_uncommitted(conn, blocks, keys=None):
    """Best-effort rollback of an allocate whose write failed: leaving
    the tokens uncommitted would dedup-poison the keys for EVERY client
    of the store (get_match_last_index counts uncommitted entries;
    re-puts silently skip; reads 404 — native/src/kv_index.h). If the
    connection itself is dead the abort can't be sent, but then the
    server's dead-connection cleanup aborts them for us. A sharded
    connection needs `keys` to route the aborts (tokens alone name no
    shard)."""
    import numpy as _np

    from ._native import FAKE_TOKEN, OK as _OK

    if keys is not None and hasattr(conn, "abort_for_keys"):
        try:
            conn.abort_for_keys(keys, blocks)
        except Exception:
            pass
        return
    toks = blocks["token"][
        (blocks["status"] == _OK) & (blocks["token"] != FAKE_TOKEN)
    ]
    if len(toks):
        try:
            conn.abort(_np.asarray(toks, dtype=_np.uint64))
        except Exception:
            pass


# A read of more runs than this is gathered (np.take) instead of copied
# run by run, where every offset is a multiple of a unit this large: a
# gather pays per unit, a run copy per run.
_GATHER_RUNS = 256
_GATHER_UNIT = 4096
_PAGE_ALIGN = 4096  # the staging buffer starts on a memory page


def _gather_blocks(pools, pool_idx, offs, page_bytes, unit, dst):
    """dst[i * page_bytes :][: page_bytes] = pools[pool_idx[i]][offs[i] :]
    for every block i, by np.take: a pool as rows of `unit` bytes (every
    offset and page_bytes are multiples of it), a block as page_bytes //
    unit consecutive rows. One take for each stretch of blocks in the
    same pool, which is one where the store has not grown."""
    n = len(offs)
    per = page_bytes // unit
    rows = (offs // unit)[:, None] + np.arange(per)
    dst = dst.reshape(n, per, unit)
    turns = (np.flatnonzero(pool_idx[1:] != pool_idx[:-1]) + 1).tolist()
    for a, b in zip([0, *turns], [*turns, n]):
        pool = pools[int(pool_idx[a])]
        table = pool[: pool.size // unit * unit].reshape(-1, unit)
        # mode: "raise" would gather into a buffer and copy that out
        np.take(table, rows[a:b], axis=0, out=dst[a:b], mode="clip")


class TpuKVStore:
    """High-level KV-page interface over an :class:`InfinityConnection`.

    Pages are fixed-size byte blocks addressed by content keys, exactly
    like the reference's vLLM integration (design.rst:54-63): the engine
    derives keys from token-prefix hashes, calls
    :meth:`get_match_last_index` to find the cached prefix, reads those
    pages, and writes back the new ones layer by layer.
    """

    def __init__(self, conn: InfinityConnection):
        self.conn = conn
        # A sharded connection routes by key, so writes must carry the
        # key list and aborts route through abort_for_keys; everything
        # else on the surface is signature-compatible (shm_connected is
        # False there, selecting the staged read path).
        self._sharded = hasattr(conn, "shard_of")
        # Per calling thread: the staging buffer of a read that spans
        # several runs of the pool, and what the last read did.
        self._tls = threading.local()

    @property
    def last_read(self):
        """What this thread's last SHM read of pages did: ``{"runs":
        contiguous runs of the pool it spanned, "copied_bytes": bytes
        copied on the host on their way out (0: one zero-copy view)}``.
        None before the first."""
        return getattr(self._tls, "last_read", None)

    def _write(self, cache, offsets, page_size, blocks, keys):
        if self._sharded:
            return self.conn.write_cache(
                cache, offsets, page_size, blocks, keys
            )
        return self.conn.write_cache(cache, offsets, page_size, blocks)

    def _put_batch(self, keys, flat, page_elems):
        """One store batch of uniform pages: allocate, then the copy
        into the store's pool and the commit's submission, each under
        a span of its own (istpu.store.allocate / .write). Returns the
        blocks."""
        n = len(keys)
        nbytes = page_elems * flat.itemsize
        with profiling.span("istpu.store.allocate", keys=n,
                            bytes=n * nbytes):
            blocks = self.conn.allocate(keys, nbytes)
        try:
            with profiling.span("istpu.store.write", bytes=n * nbytes):
                self._write(
                    flat, [i * page_elems for i in range(n)], page_elems,
                    blocks, keys,
                )
        except BaseException:
            _abort_uncommitted(self.conn, blocks, keys)
            raise
        return blocks

    # -- generic arrays --------------------------------------------------

    def put_arrays(self, items, sync=False):
        """Store [(key, array)] pairs. Arrays may be jax.Arrays (device)
        or numpy arrays (host); each array becomes one page.

        Aliasing: callers may mutate their input arrays as soon as this
        returns. Device arrays write from the fresh D2H buffer; a numpy
        input on the ``sync=False`` path is privately copied first —
        this convenience surface keeps the historical copy semantics
        rather than silently adopting write_cache's post-until-sync
        contract (round-4 advisor finding). The zero-staging-copy
        offload path is :meth:`put_kv_pages`, whose pipelined contract
        is documented there."""
        if not items:
            return
        host = []
        for k, a in items:
            h = to_host(a)
            if not sync and h is a:
                h = h.copy()  # caller-owned numpy buffer: detach from it
            host.append((k, h))
        # Group by nbytes so each allocate/write batch has a uniform page
        # size (protocol pages are uniform per request).
        by_size = {}
        for k, a in host:
            by_size.setdefault(a.nbytes, []).append((k, a))
        for nbytes, group in by_size.items():
            keys = [k for k, _ in group]
            blocks = self.conn.allocate(keys, nbytes)
            # One pipelined write per array, straight from its host
            # buffer — no concatenation staging copy (the writes share
            # the connection's IO thread, so per-call cost amortizes).
            for i, (k, a) in enumerate(group):
                try:
                    self._write(a, [0], a.size, blocks[i:i + 1], [k])
                except BaseException:
                    # Submitted writes ([:i]) commit via the IO thread;
                    # roll back only the blocks never written.
                    _abort_uncommitted(self.conn, blocks[i:], keys[i:])
                    raise
        if sync:
            self.conn.sync()

    def get_array(self, key, shape, dtype, device=None):
        """Fetch one array. On the SHM path the device transfer reads
        directly from the pinned server pool (zero host copy)."""
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        if self.conn.shm_connected:
            lease, blocks = self.conn.pin([key])
            try:
                pool = self.conn.pool_view(int(blocks["pool_idx"][0]))
                off = int(blocks["offset"][0])
                view = pool[off : off + nbytes].view(dtype).reshape(shape)
                out = _device_put_owned(view, device)
            finally:
                self.conn.release(lease)
            return out
        buf = np.empty(nbytes, dtype=np.uint8)
        self.conn.read_cache(buf, [(key, 0)], nbytes)
        self.conn.sync()
        return jax.device_put(buf.view(dtype).reshape(shape), device)

    # -- paged KV --------------------------------------------------------

    def put_kv_pages(self, keys, pages, sync=False):
        """Store a batch of uniform KV pages.

        ``pages``: array of shape [n_pages, ...] (jax or numpy); page i is
        stored under keys[i]. One allocate + one write round-trip for the
        whole batch (the reference's batched multi-block op,
        lib.py:439-475).

        Aliasing (the zero-staging-copy offload path): a device input
        writes from its fresh D2H buffer; a NUMPY input is written
        in-place, pipelined — with ``sync=False`` do not mutate it until
        :meth:`InfinityConnection.sync`, the same post-until-sync
        contract as ``write_cache``.
        """
        host = to_host(pages)
        n = host.shape[0]
        if n != len(keys):
            raise ValueError("len(keys) must equal pages.shape[0]")
        page_elems = int(np.prod(host.shape[1:]))
        blocks = self._put_batch(keys, host.reshape(n * page_elems),
                                 page_elems)
        if sync:
            self.conn.sync()
        return blocks

    def get_kv_pages(self, keys, page_shape, dtype, device=None):
        """Fetch pages for ``keys``; returns a device array of shape
        [len(keys), *page_shape]. SHM path, one pin and ONE device_put:
        keys whose blocks are one contiguous run of the pool (what one
        put batch wrote, asked for in its order) transfer straight from
        the pinned pool, zero host copies; a read across several runs is
        copied once, run by run, into this store's reused staging buffer
        and transferred from there (:meth:`_pool_batch_view`;
        :attr:`last_read` says which it was)."""
        dtype = np.dtype(dtype)
        page_elems = int(np.prod(page_shape))
        page_bytes = page_elems * dtype.itemsize
        n = len(keys)
        if n == 0:
            return jnp.zeros((0, *page_shape), dtype=dtype)
        if self.conn.shm_connected:
            with profiling.span("istpu.store.pin", keys=n):
                lease, blocks = self.conn.pin(keys)
            try:
                with profiling.span("istpu.store.view") as f:
                    stacked = self._pool_batch_view(
                        blocks, n, page_bytes, dtype, page_shape
                    )
                    f.update(self.last_read)
                out = _device_put_owned(stacked, device)
            finally:
                self.conn.release(lease)
            return out
        buf = np.empty(n * page_bytes, dtype=np.uint8)
        self.conn.read_cache(
            buf, [(k, i * page_bytes) for i, k in enumerate(keys)], page_bytes
        )
        self.conn.sync()
        return jax.device_put(buf.view(dtype).reshape(n, *page_shape), device)

    def get_kv_pages_host(self, keys, page_shape, dtype):
        """Fetch pages as a host numpy array ([len(keys), *page_shape])
        the caller owns, no device transfer: one copy out of the pinned
        pool, run by run, straight into the array returned (SHM) or the
        socket scatter (STREAM). For consumers that stage placement
        themselves (e.g. IciKVPool injection)."""
        dtype = np.dtype(dtype)
        page_elems = int(np.prod(page_shape))
        page_bytes = page_elems * dtype.itemsize
        n = len(keys)
        if n == 0:
            return np.zeros((0, *page_shape), dtype=dtype)
        if self.conn.shm_connected:
            out = np.empty(n * page_bytes, dtype=np.uint8)
            lease, blocks = self.conn.pin(keys)
            try:
                return self._pool_batch_view(
                    blocks, n, page_bytes, dtype, page_shape, out=out
                )
            finally:
                self.conn.release(lease)
        buf = np.empty(n * page_bytes, dtype=np.uint8)
        self.conn.read_cache(
            buf, [(k, i * page_bytes) for i, k in enumerate(keys)], page_bytes
        )
        self.conn.sync()
        return buf.view(dtype).reshape(n, *page_shape)

    # -- quantized paged KV (int8 + per-token-per-head scales) ----------

    def put_kv_pages_quantized(self, keys, pages, sync=False):
        """Store KV pages int8-quantized: halves store capacity use and
        host/DCN transfer bytes vs bf16 (~0.4% relative error; see
        ops/kv_quant.py). Quantization runs on the device under jit, so
        only packed int8 bytes ever cross to the host.

        ``pages``: [n_pages, page, n_kv, hd] float array (jax or numpy).
        Read back with :meth:`get_kv_pages_quantized`.
        """
        from .ops import kv_quant

        n = pages.shape[0]
        if n != len(keys):
            raise ValueError("len(keys) must equal pages.shape[0]")
        page_shape = tuple(pages.shape[1:])
        q, scales = kv_quant.quantize_kv_pages(pages)
        packed = kv_quant.pack_pages_host(to_host(q), to_host(scales))
        block = kv_quant.packed_page_bytes(page_shape)
        blocks = self._put_batch(keys, packed.reshape(-1), block)
        if sync:
            self.conn.sync()
        return blocks

    def get_kv_pages_quantized(self, keys, page_shape, dtype, device=None):
        """Fetch int8-quantized pages and dequantize on the device;
        returns [len(keys), *page_shape] in ``dtype``."""
        from .ops import kv_quant

        n = len(keys)
        if n == 0:
            return jnp.zeros((0, *page_shape), dtype=dtype)
        block = kv_quant.packed_page_bytes(page_shape)
        if self.conn.shm_connected:
            # Same read as get_kv_pages: packed pages are viewed in the
            # pinned server pool (one run) or its staging copy (several)
            # under a lease.
            lease, blocks = self.conn.pin(keys)
            try:
                packed = self._pool_batch_view(
                    blocks, n, block, np.uint8, (block,)
                )
                q, scales = kv_quant.unpack_pages_host(packed, page_shape)
                q = _device_put_owned(q, device)
                scales = jax.device_put(scales, device)  # .copy()'d in unpack
            finally:
                self.conn.release(lease)
        else:
            buf = np.empty(n * block, dtype=np.uint8)
            self.conn.read_cache(
                buf, [(k, i * block) for i, k in enumerate(keys)], block
            )
            self.conn.sync()
            q, scales = kv_quant.unpack_pages_host(
                buf.reshape(n, block), page_shape
            )
            q = jax.device_put(q, device)
            scales = jax.device_put(scales, device)
        return kv_quant.dequantize_kv_pages(q, scales, jnp.dtype(dtype))

    def _pool_batch_view(self, blocks, n, page_bytes, dtype, page_shape,
                         out=None):
        """The pinned ``blocks`` (what ``conn.pin`` returned for n keys)
        as one [n, *page_shape] array, valid until the lease is released.

        The blocks are split into contiguous RUNS of one pool, found
        vectorised (a break wherever the pool changes or an offset is
        not its predecessor's plus page_bytes); nothing here costs
        Python work per block. First-fit allocation lays one put batch
        down as one run, so:

        - one run, no ``out``: ONE zero-copy view of the pool — XLA's
          host→device DMA then reads straight out of the server pool;
        - several runs (a read across put batches), or ``out`` given:
          each run is copied once, one slice assignment (a memcpy,
          outside the GIL), into ``out`` or else into this thread's
          staging buffer, which grows geometrically to the largest read
          seen, is touched when it grows and is reused by the next read:
          the caller is done with it before then (_device_put_owned
          returns with the transfer complete, and copies on a CPU
          target);
        - very many short runs (a pool fragmented down to blocks): one
          C-level gather a pool (np.take over the pool as rows of the
          unit every offset is a multiple of) instead of a copy a run.

        ``last_read`` records the runs and the bytes copied."""
        pool_idx = blocks["pool_idx"]
        offs = blocks["offset"].astype(np.int64)
        cuts = np.flatnonzero(
            (pool_idx[1:] != pool_idx[:-1])
            | (offs[1:] != offs[:-1] + page_bytes)
        ) + 1
        n_runs = len(cuts) + 1
        copied = nbytes = n * page_bytes
        if n_runs == 1 and out is None:
            pool = self.conn.pool_view(int(pool_idx[0]))
            dst = pool[offs[0] : offs[0] + nbytes]
            copied = 0
        else:
            dst = self._staging(nbytes) if out is None else out
            pools = {
                int(p): self.conn.pool_view(int(p))
                for p in np.unique(pool_idx)
            }
            if n_runs > _GATHER_RUNS and (
                unit := int(np.gcd.reduce(offs, initial=page_bytes))
            ) >= _GATHER_UNIT:
                _gather_blocks(pools, pool_idx, offs, page_bytes, unit, dst)
            else:
                lo = np.concatenate(([0], cuts))
                lens = (np.diff(lo, append=n) * page_bytes).tolist()
                srcs = offs[lo].tolist()
                at = 0
                for p, src, ln in zip(pool_idx[lo].tolist(), srcs, lens):
                    dst[at : at + ln] = pools[p][src : src + ln]
                    at += ln
        self._tls.last_read = {"runs": n_runs, "copied_bytes": copied}
        return dst.view(dtype).reshape(n, *page_shape)

    def _staging(self, nbytes):
        """This thread's staging buffer, at least ``nbytes`` long: grown
        to twice its size or the read's, whichever is larger, and written
        once when it grows so that no read pays its first touch."""
        buf = getattr(self._tls, "staging", None)
        if buf is None or buf.size < nbytes:
            size = max(nbytes, 2 * (0 if buf is None else buf.size))
            raw = np.empty(size + _PAGE_ALIGN, dtype=np.uint8)
            skip = -raw.ctypes.data % _PAGE_ALIGN
            buf = self._tls.staging = raw[skip : skip + size]
            buf.fill(0)
        return buf[:nbytes]

    def prefetch(self, keys):
        """Advisory fire-and-forget promotion kick (OP_PREFETCH) for
        pages a caller KNOWS it will read soon — the serving engine
        fires this for the matched prefix chain right after its
        admission probe, so disk-resident pages are pool-resident by
        the time the restore asks for them. Returns True when the kick
        was issued, False when the connection does not support it (or
        has it disabled); never raises — a failed hint must not fail
        the read that follows."""
        fn = getattr(self.conn, "prefetch", None)
        if fn is None or not keys:
            return False
        try:
            fn(keys)
            return True
        except Exception:
            return False

    def cached_prefix_len(self, keys):
        """How many leading pages of ``keys`` are already cached
        (get_match_last_index + 1; 0 if none). Uses the raw variant —
        a clean miss is 0, not an exception (get_match_last_index raises
        on no-match for reference parity). Connection failures PROPAGATE
        — swallowing them would make a dead store indistinguishable from
        a cold one, so callers with a fallback (e.g. the serving
        engine's store-less downgrade) could never trigger it at probe
        time."""
        return self.conn._match_last_index_raw(keys) + 1


class LayerStreamer:
    """Overlap per-layer KV upload with compute (reference
    demo_prefill.py:57-77: per-layer CUDA event + upload thread feeding
    local_gpu_write_cache).

    Usage::

        streamer = LayerStreamer(conn)
        for layer in range(n_layers):
            kv = compute_layer(layer)          # jax.Array
            streamer.submit(f"{prefix}_{layer}", kv)
        streamer.finish()                       # barriers all writes

    ``submit`` is NON-BLOCKING: it kicks off the async device→host copy
    and enqueues the layer for a dedicated upload thread (the reference's
    upload-thread pattern). The upload thread waits out the D2H copy,
    allocates, and hands the store write to the connection's IO thread —
    compute for the next layer never waits on the device transfer or the
    store. ``finish`` drains the queue, barriers the connection, and
    surfaces any per-layer errors; the streamer stays usable afterwards
    for the next sequence.
    """

    _STOP = object()

    def __init__(self, conn: InfinityConnection):
        self.conn = conn
        self._q = queue.Queue()
        self._errors = []  # list.append is atomic; drained in finish()
        self._thread = threading.Thread(
            target=self._upload_loop, name="layer-streamer", daemon=True
        )
        self._thread.start()

    def submit(self, key, array):
        """Queue one array (one page) for upload under ``key``."""
        # Flatten ON DEVICE before the async D2H so the prefetch and
        # to_host hit the SAME (contiguous-landing) array — see
        # to_host for the device-layout story.
        array = _flatten_on_device(array)
        if hasattr(array, "copy_to_host_async"):
            array.copy_to_host_async()  # start D2H now; thread reaps it
        self._q.put((key, array, False))

    def submit_pages(self, keys, pages):
        """Queue a [n_pages, ...] page batch; page i goes under keys[i]
        (one allocate + one pipelined write for the batch, like
        :meth:`TpuKVStore.put_kv_pages`)."""
        if len(keys) != pages.shape[0]:
            raise ValueError("len(keys) must equal pages.shape[0]")
        if len(keys) == 0:  # no truthiness: keys may be a numpy array
            return  # nothing to upload; avoid a 0-division in the worker
        pages = _flatten_on_device(pages)  # same flatten-before-prefetch
        if hasattr(pages, "copy_to_host_async"):
            pages.copy_to_host_async()
        self._q.put((keys, pages, True))

    def _upload_loop(self):
        while True:
            item = self._q.get()
            try:
                if item is LayerStreamer._STOP:
                    return
                key, arr, batched = item
                try:
                    host = to_host(arr)  # waits only for the async D2H
                    if batched:
                        # Device inputs arrive pre-flattened (submit_pages);
                        # numpy inputs keep their [n, ...] shape — derive
                        # the page size from the key count either way.
                        n = len(key)
                        page_elems = host.size // n
                        blocks = self.conn.allocate(
                            key, page_elems * host.itemsize
                        )
                        self.conn._write_async_native(
                            host.reshape(-1),
                            [i * page_elems for i in range(n)],
                            page_elems, blocks, _ErrSink(self._errors, key),
                        )
                    else:
                        blocks = self.conn.allocate([key], host.nbytes)
                        self.conn._write_async_native(
                            host.reshape(-1), [0], host.size, blocks,
                            _ErrSink(self._errors, key),
                        )
                except Exception as e:  # allocate / submit failure
                    self._errors.append((key, e))
            finally:
                self._q.task_done()

    def finish(self):
        """Barrier: every submitted layer written and committed. Waits
        for the upload queue to drain, then for the connection's inflight
        writes (conn.sync); raises if any layer failed. The error list is
        always drained, so a failed sequence never leaks stale errors
        into the next sequence's finish()."""
        self._q.join()
        sync_exc = None
        try:
            self.conn.sync()
        except Exception as e:
            sync_exc = e
        errs, self._errors = self._errors, []
        if errs:
            raise RuntimeError(f"layer uploads failed: {errs}") from sync_exc
        if sync_exc is not None:
            raise sync_exc

    def close(self):
        """Stop the upload thread (queued layers still drain first).
        Raises if the thread will not stop — in that case it is still
        inside native calls on ``conn``, and the caller must NOT destroy
        the connection (freeing the handle under a live native call is a
        use-after-free; a closed-but-undestroyed one fails safely)."""
        self._q.put(LayerStreamer._STOP)
        # Native ops are themselves bounded (rpc timeout + one reconnect
        # retry), so a healthy-but-slow store still lets the thread exit
        # within this window.
        self._thread.join(timeout=60)
        if self._thread.is_alive():
            raise RuntimeError(
                "layer-streamer upload thread did not stop; the store "
                "connection must not be destroyed while it is running"
            )

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _ErrSink:
    def __init__(self, errors, key):
        self.errors = errors
        self.key = key

    def __call__(self, status):
        from ._native import OK

        if status != OK:
            self.errors.append((self.key, status))
