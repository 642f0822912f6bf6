"""Server-side safety: read backpressure (bounded per-connection send
queue) and token-connection binding (a client cannot commit, abort, or
write another client's in-flight allocations).

Reference discipline being matched: the reference bounds its push path
with signal/32 and a 4096-WR window (libinfinistore.cpp:898-987) and keys
inflight write state per client (infinistore.cpp:63,361-371). Round-1
review found both missing here; these tests pin the fixes.
"""

import socket
import struct
import uuid

import numpy as np
import pytest

from infinistore_tpu import (
    ClientConfig,
    InfiniStoreServer,
    InfinityConnection,
    ServerConfig,
    TYPE_SHM,
    TYPE_STREAM,
)

MAGIC = 0x49535450
WIRE_VERSION = 1
OP_READ = 4
HDR = struct.Struct("<IBBHQIQ")  # magic, ver, op, flags, seq, body, payload

OK = 200
BUSY = 429


def key():
    return str(uuid.uuid4())


def _connect(port, ctype):
    c = InfinityConnection(
        ClientConfig(
            host_addr="127.0.0.1", service_port=port, connection_type=ctype
        )
    )
    c.connect()
    return c


# ---------------------------------------------------------------------------
# Token-connection binding
# ---------------------------------------------------------------------------


def test_foreign_commit_fails_closed(server):
    """Client B committing client A's token must not make the key visible,
    and must not consume A's inflight state (A's own commit still lands)."""
    a = _connect(server.service_port, TYPE_STREAM)
    b = _connect(server.service_port, TYPE_STREAM)
    try:
        k = key()
        blocks = a.allocate([k], 4096)
        assert blocks["token"][0] != 0
        # Forged commit: returns without error (idempotent wire op) but the
        # key stays uncommitted — and A's token survives.
        b.commit(blocks["token"])
        assert not a.check_exist(k)
        src = np.arange(4096, dtype=np.uint8)
        a.write_cache(src, [0], 4096, blocks)
        a.sync()
        assert a.check_exist(k)
        dst = np.zeros_like(src)
        a.read_cache(dst, [(k, 0)], 4096)
        a.sync()
        assert np.array_equal(src, dst)
    finally:
        a.close()
        b.close()


def test_foreign_write_lands_in_sink(server):
    """Client B streaming payload against client A's tokens must not write
    A's pool block: A's subsequent write wins verbatim."""
    a = _connect(server.service_port, TYPE_STREAM)
    b = _connect(server.service_port, TYPE_STREAM)
    try:
        k = key()
        blocks = a.allocate([k], 4096)
        forged = np.full(4096, 0xEE, dtype=np.uint8)
        # B pushes payload with A's token; the server must sink it (and its
        # commit-on-receipt must be refused for the foreign owner).
        b.write_cache(forged, [0], 4096, blocks)
        b.sync()
        assert not a.check_exist(k)
        real = np.arange(4096, dtype=np.uint8)
        a.write_cache(real, [0], 4096, blocks)
        a.sync()
        dst = np.zeros_like(real)
        a.read_cache(dst, [(k, 0)], 4096)
        a.sync()
        assert np.array_equal(dst, real)
    finally:
        a.close()
        b.close()


def test_foreign_abort_is_noop(server):
    """Client B aborting client A's token must leave A's allocation
    intact — A can still write and commit it."""
    a = _connect(server.service_port, TYPE_STREAM)
    b = _connect(server.service_port, TYPE_STREAM)
    try:
        k = key()
        blocks = a.allocate([k], 4096)
        b.abort(blocks["token"])
        src = np.arange(4096, dtype=np.uint8)
        a.write_cache(src, [0], 4096, blocks)
        a.sync()
        assert a.check_exist(k)
    finally:
        a.close()
        b.close()


def test_own_abort_still_works(server):
    """Sanity: the owner's own abort still releases the key for
    reallocation (the owner check must not break the legitimate path)."""
    a = _connect(server.service_port, TYPE_STREAM)
    try:
        k = key()
        blocks = a.allocate([k], 4096)
        a.abort(blocks["token"])
        blocks2 = a.allocate([k], 4096)
        assert blocks2["token"][0] != 0  # real allocation, not dedup FAKE
        a.abort(blocks2["token"])
    finally:
        a.close()


def test_foreign_lease_release_fails_closed(server, rng):
    """Lease ids are sequential, so client B must not be able to release
    client A's pin lease (which would unpin blocks under A's one-sided
    copy). The owner's release still works."""
    from infinistore_tpu import InfiniStoreError

    a = _connect(server.service_port, TYPE_SHM)
    b = _connect(server.service_port, TYPE_SHM)
    try:
        k = key()
        src = rng.random(256).astype(np.float32)
        a.put_cache(src, [(k, 0)], 256)
        a.sync()
        lease, _ = a.pin([k])
        with pytest.raises(InfiniStoreError):
            b.release(lease)  # forged: KEY_NOT_FOUND, lease intact
        assert server.stats()["leases"] >= 1
        a.release(lease)  # owner's release still lands
        assert server.stats()["leases"] == 0
    finally:
        a.close()
        b.close()


def test_pin_hoarder_gets_busy():
    """A client that pins without releasing must hit BUSY at the byte cap
    instead of pinning the whole pool; releasing frees budget again."""
    import infinistore_tpu._native as _native
    from infinistore_tpu import InfiniStoreError

    bs = 64 << 10
    srv = InfiniStoreServer(
        ServerConfig(
            service_port=0,
            prealloc_size=0.0625,  # 64 MB
            minimal_allocate_size=64,
            max_outq_size=1,  # 1 MB pin budget
        )
    )
    srv.start()
    conn = _connect(srv.service_port, TYPE_SHM)
    try:
        keys = [f"pin_{i}" for i in range(64)]
        src = np.zeros(64 * bs, dtype=np.uint8)
        conn.put_cache(src, [(k, i * bs) for i, k in enumerate(keys)], bs)
        conn.sync()
        # First pin (empty budget) is admitted even though 4 MB > 1 MB cap.
        lease1, _ = conn.pin(keys)
        # Second pin exceeds the budget → BUSY (after client-side retries
        # exhaust the short timeout we set below).
        conn.config.timeout_ms = 200
        with pytest.raises(InfiniStoreError) as ei:
            conn.pin(keys)
        assert ei.value.status == _native.BUSY
        assert srv.stats()["pins_busy"] > 0
        assert srv.stats()["lease_bytes"] == 64 * bs
        # Releasing restores budget: the same pin now succeeds.
        conn.release(lease1)
        assert srv.stats()["lease_bytes"] == 0
        lease2, _ = conn.pin(keys)
        conn.release(lease2)
    finally:
        conn.close()
        srv.stop()


# ---------------------------------------------------------------------------
# Slow-reader backpressure
# ---------------------------------------------------------------------------


def _read_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("eof")
        buf += chunk
    return buf


def _read_request(seq, keys, block_size):
    body = struct.pack("<I", block_size) + struct.pack("<I", len(keys))
    for k in keys:
        kb = k.encode()
        body += struct.pack("<I", len(kb)) + kb
    return HDR.pack(MAGIC, WIRE_VERSION, OP_READ, 0, seq, len(body), 0) + body


def _read_response(sock):
    h = _read_exact(sock, HDR.size)
    magic, ver, op, flags, seq, body_len, payload_len = HDR.unpack(h)
    assert magic == MAGIC
    body = _read_exact(sock, body_len)
    status = struct.unpack_from("<I", body)[0]
    if payload_len:
        _read_exact(sock, payload_len)
    return status, payload_len


def test_slow_reader_gets_busy_and_server_stays_bounded():
    """A reader that issues many large OP_READs without draining responses
    must get BUSY (retryable) past the per-connection outq cap instead of
    pinning unbounded pool memory; after draining, reads succeed again."""
    nkeys, bs = 64, 64 << 10  # 4 MB per read request
    srv = InfiniStoreServer(
        ServerConfig(
            service_port=0,
            prealloc_size=0.0625,  # 64 MB
            minimal_allocate_size=64,
            max_outq_size=1,  # 1 MB cap → every 4 MB read is over-cap
        )
    )
    srv.start()
    writer = _connect(srv.service_port, TYPE_SHM)
    try:
        keys = [f"bp_{i}" for i in range(nkeys)]
        src = np.arange(nkeys * bs, dtype=np.uint8)
        writer.put_cache(src, [(k, i * bs) for i, k in enumerate(keys)], bs)
        writer.sync()

        raw = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # Tiny receive window: the server cannot dump responses into our
        # kernel buffer, so its outq genuinely fills.
        raw.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        raw.settimeout(30)
        raw.connect(("127.0.0.1", srv.service_port))
        n_requests = 16  # 64 MB of requested payload vs the 1 MB cap
        for seq in range(n_requests):
            raw.sendall(_read_request(seq, keys, bs))
        statuses = [_read_response(raw)[0] for _ in range(n_requests)]
        raw.close()

        assert statuses.count(BUSY) > 0, statuses
        # Progress guarantee: the first (empty-queue) read is admitted even
        # though it alone exceeds the cap.
        assert statuses[0] == OK
        st = srv.stats()
        assert st["reads_busy"] == statuses.count(BUSY)
        assert st["outq_cap"] == 1 << 20
        assert st["outq_bytes"] == 0  # fully drained, nothing leaked
        # BUSY is retryable: a normal reader succeeds afterwards.
        dst = np.zeros(bs, dtype=np.uint8)
        writer.read_cache(dst, [(keys[0], 0)], bs)
        writer.sync()
        assert np.array_equal(dst, src[:bs])
    finally:
        writer.close()
        srv.stop()


def test_fast_reader_never_sees_busy(server):
    """Ordinary request/response readers (drain before next read) must
    never hit the cap even with large batches."""
    conn = _connect(server.service_port, TYPE_STREAM)
    try:
        nkeys, bs = 32, 16 << 10
        keys = [f"fast_{i}" for i in range(nkeys)]
        src = np.arange(nkeys * bs, dtype=np.uint8)
        conn.put_cache(src, [(k, i * bs) for i, k in enumerate(keys)], bs)
        conn.sync()
        dst = np.zeros_like(src)
        for _ in range(4):
            conn.read_cache(dst, [(k, i * bs) for i, k in enumerate(keys)], bs)
            conn.sync()
        assert np.array_equal(src, dst)
    finally:
        conn.close()
