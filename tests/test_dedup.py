"""Content-addressed dedup (ISSUE 16).

Covers the refcounted-block index and the hash-first zero-byte put
path end to end:
  - a duplicate put (plain client) adopts the canonical block at
    commit: one physical block, byte-exact reads, exact saved-bytes
    accounting;
  - the hash-first path (use_dedup client, OP_PUT_HASH): a duplicate
    put transfers ZERO payload bytes — dedup_wire_bytes_saved equals
    the duplicate bytes, pinned exactly;
  - refcount conservation: used_bytes == logical_bytes -
    dedup_saved_live through delete / re-put / purge churn, ending at
    zero;
  - shared blocks under eviction pressure (a sharer is an ordinary
    victim; the bytes stay while a sharer does) and
    the spill -> promote round trip once a block goes solo;
  - snapshot round-trip: restore re-deduplicates byte-identical
    payloads (zero-alloc adoption), physical == distinct contents;
  - estimator cross-validation: the workload profiler's sampled
    dedup_ratio prediction within 0.1 of the index's measured
    multiplier on a deterministic delete-free trace;
  - chaos: clients killed by socket faults mid hash-first put leak
    zero blocks (byte-audited against the conservation invariant);
  - kill switch (ISTPU_DEDUP=0): no sharing, the bench denominator.

All servers ride ephemeral ports; STREAM connections only (the dedup
probe is transport-agnostic — it rides the same framed socket).
"""

import ctypes as ct
import os
import threading
import time

import numpy as np
import pytest

from infinistore_tpu import (
    ClientConfig,
    InfiniStoreServer,
    InfinityConnection,
    ServerConfig,
    TYPE_STREAM,
)
from infinistore_tpu import _native

BLOCK = 4 << 10


def start_server(pool_mb=8, ssd_mb=0, eviction=False, tmpdir=None,
                 env=None, **kw):
    # Arm dedup explicitly: conftest defaults ISTPU_DEDUP=0 for the
    # legacy pressure suites; this suite exists to test sharing ON.
    env = {"ISTPU_DEDUP": "1", **(env or {})}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        cfg = ServerConfig(
            service_port=0,
            prealloc_size=pool_mb / 1024,
            minimal_allocate_size=4,
            enable_eviction=eviction,
            **kw,
        )
        if ssd_mb:
            assert tmpdir is not None
            cfg.ssd_path = str(tmpdir)
            cfg.ssd_size = ssd_mb / 1024
        srv = InfiniStoreServer(cfg)
        srv.start()
        return srv
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def connect(port, use_dedup=False, **kw):
    c = InfinityConnection(
        ClientConfig(
            host_addr="127.0.0.1", service_port=port,
            connection_type=TYPE_STREAM, timeout_ms=5000,
            use_dedup=use_dedup, **kw,
        )
    )
    c.connect()
    return c


def content(v):
    """Deterministic 4 KB page per content id (distinct ids never
    collide byte-wise)."""
    return ((np.arange(BLOCK, dtype=np.uint32) * 2654435761 + v * 7919)
            % 251).astype(np.uint8)


def put(conn, key, buf):
    conn.put_cache(buf, [(key, 0)], BLOCK)


def read(conn, key):
    dst = np.zeros(BLOCK, dtype=np.uint8)
    conn.read_cache(dst, [(key, 0)], BLOCK)
    return dst


def dedup_stats(srv):
    return srv.stats().get("dedup", {})


def assert_conserved(srv):
    """The leak audit: with no inflight writes, every allocated pool
    byte is a committed entry's — physical == logical - shared
    savings. A leaked block (orphaned ref) breaks the equality from
    the left; a dangling sharer from the right."""
    st = srv.stats()
    dd = st.get("dedup", {})
    assert st["inflight"] == 0
    assert st["used_bytes"] == (
        dd["logical_bytes"] - dd["dedup_saved_live"]
    ), (st["used_bytes"], dd)


# ---------------------------------------------------------------------------
# Commit-time adoption (plain client: payload arrives, pool bytes don't
# stay).


def test_duplicate_put_shares_one_block():
    srv = start_server()
    try:
        conn = connect(srv.service_port)
        try:
            put(conn, "a", content(1))
            conn.sync()
            used1 = srv.stats()["used_bytes"]
            assert used1 == BLOCK
            for i in range(7):
                put(conn, f"dup{i}", content(1))
            conn.sync()
            st = srv.stats()
            dd = st["dedup"]
            assert dd["enabled"] == 1
            # All 7 duplicates adopted the canonical block: zero pool
            # growth, exact saved-byte accounting.
            assert st["used_bytes"] == used1
            assert dd["dedup_hits"] == 7
            assert dd["dedup_bytes_saved"] == 7 * BLOCK
            assert dd["dedup_saved_live"] == 7 * BLOCK
            assert dd["logical_bytes"] == 8 * BLOCK
            assert dd["dedup_measured_milli"] == 8000
            for i in range(7):
                assert np.array_equal(read(conn, f"dup{i}"), content(1))
            assert_conserved(srv)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_distinct_contents_do_not_share():
    srv = start_server()
    try:
        conn = connect(srv.service_port)
        try:
            for i in range(8):
                put(conn, f"d{i}", content(i))
            conn.sync()
            st = srv.stats()
            assert st["used_bytes"] == 8 * BLOCK
            assert st["dedup"]["dedup_hits"] == 0
            assert st["dedup"]["dedup_measured_milli"] == 1000
            assert_conserved(srv)
        finally:
            conn.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Hash-first path: a duplicate put ships zero payload bytes.


def test_hash_first_duplicate_put_transfers_zero_payload():
    srv = start_server()
    try:
        seed = connect(srv.service_port)
        try:
            put(seed, "orig", content(5))
            seed.sync()
        finally:
            seed.close()
        used1 = srv.stats()["used_bytes"]
        conn = connect(srv.service_port, use_dedup=True)
        try:
            for i in range(4):
                put(conn, f"h{i}", content(5))
            conn.sync()
            st = srv.stats()
            dd = st["dedup"]
            # ISSUE 16 acceptance pin: dedup_wire_bytes_saved equals
            # the duplicate bytes exactly — the payload for every HAVE
            # verdict never crossed the transport.
            assert dd["dedup_wire_hits"] == 4
            assert dd["dedup_wire_bytes_saved"] == 4 * BLOCK
            assert dd["dedup_hash_hits"] == 4
            assert st["used_bytes"] == used1
            # Client-side telemetry saw the same verdicts.
            cs = conn.client_stats()
            assert cs["dedup"]["have_verdicts"] == 4
            assert cs["counters"].get("dedup_have_pages", 0) == 4
            for i in range(4):
                assert np.array_equal(read(conn, f"h{i}"), content(5))
            assert_conserved(srv)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_hash_first_miss_falls_through_to_payload_path():
    srv = start_server()
    try:
        conn = connect(srv.service_port, use_dedup=True)
        try:
            # Fresh content: the probe answers NEED, the payload path
            # ships it, and the content is registered for the NEXT
            # writer.
            put(conn, "n0", content(9))
            conn.sync()
            dd = dedup_stats(srv)
            assert dd["dedup_hash_misses"] == 1
            assert dd["dedup_wire_hits"] == 0
            put(conn, "n1", content(9))
            conn.sync()
            dd = dedup_stats(srv)
            assert dd["dedup_wire_hits"] == 1
            assert srv.stats()["used_bytes"] == BLOCK
            assert np.array_equal(read(conn, "n0"), content(9))
            assert np.array_equal(read(conn, "n1"), content(9))
        finally:
            conn.close()
    finally:
        srv.stop()


def test_hash_first_existing_key_is_first_writer_wins():
    srv = start_server()
    try:
        conn = connect(srv.service_port, use_dedup=True)
        try:
            put(conn, "k", content(1))
            conn.sync()
            # Same key again (duplicate content): EXISTS — the put
            # succeeds as a no-op, the same outcome the payload path
            # reports under first-writer-wins.
            put(conn, "k", content(1))
            conn.sync()
            assert srv.stats()["kvmap_len"] == 1
            assert np.array_equal(read(conn, "k"), content(1))
        finally:
            conn.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Refcount conservation under churn.


def test_refcount_conservation_delete_reput_purge():
    srv = start_server()
    try:
        conn = connect(srv.service_port)
        try:
            # 16 sharers of one content.
            for i in range(16):
                put(conn, f"c{i}", content(2))
            conn.sync()
            assert srv.stats()["used_bytes"] == BLOCK
            assert_conserved(srv)
            # Delete half — including c0, the first writer whose
            # entry registered the canonical block.
            conn.delete_keys([f"c{i}" for i in range(8)])
            conn.sync()
            dd = dedup_stats(srv)
            assert dd["logical_bytes"] == 8 * BLOCK
            assert dd["dedup_saved_live"] == 7 * BLOCK
            assert_conserved(srv)
            # Survivors still byte-exact (the block outlives the
            # first writer).
            for i in range(8, 16):
                assert np.array_equal(read(conn, f"c{i}"), content(2))
            # Re-put deleted keys: they re-adopt the still-live block.
            for i in range(8):
                put(conn, f"c{i}", content(2))
            conn.sync()
            assert srv.stats()["used_bytes"] == BLOCK
            assert_conserved(srv)
            # Purge drops everything: zero logical, zero physical.
            conn.purge()
            conn.sync()
            st = srv.stats()
            assert st["used_bytes"] == 0
            assert st["dedup"]["logical_bytes"] == 0
            assert st["dedup"]["dedup_saved_live"] == 0
            # Re-put after full purge: the weak canonical expired, so
            # the first put re-allocates and re-registers.
            for i in range(4):
                put(conn, f"p{i}", content(2))
            conn.sync()
            assert srv.stats()["used_bytes"] == BLOCK
            assert_conserved(srv)
            for i in range(4):
                assert np.array_equal(read(conn, f"p{i}"), content(2))
        finally:
            conn.close()
    finally:
        srv.stop()


def test_delete_last_sharer_frees_the_block():
    srv = start_server()
    try:
        conn = connect(srv.service_port)
        try:
            for i in range(3):
                put(conn, f"s{i}", content(3))
            conn.sync()
            conn.delete_keys(["s0", "s1", "s2"])
            conn.sync()
            st = srv.stats()
            assert st["used_bytes"] == 0
            assert st["dedup"]["dedup_saved_live"] == 0
            assert_conserved(srv)
        finally:
            conn.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Shared blocks vs eviction and the disk tier.


def test_eviction_pressure_never_tears_shared_blocks():
    # Pool of 64 pages, eviction on, no disk tier. Every committed
    # sharer's entry holds the shared block once; those holds are no
    # pins, so a sharer is an ordinary LRU victim (until PR 35 it was
    # skipped as pinned and could never leave). What may not happen:
    # the block's bytes going out from under a sharer that is still
    # there. The sharers touched during the pressure stay, byte-exact;
    # the others go whole, key by key.
    srv = start_server(pool_mb=64 * BLOCK / (1 << 20), eviction=True,
                       reclaim_high=1.0)
    try:
        conn = connect(srv.service_port)
        try:
            for i in range(8):
                put(conn, f"sh{i}", content(7))
            conn.sync()
            # ~3 pools' worth of distinct filler drives eviction.
            for i in range(192):
                put(conn, f"f{i}", content(100 + i))
                if i % 8 == 0:  # keep four of the sharers hot
                    for j in range(4):
                        assert np.array_equal(read(conn, f"sh{j}"),
                                              content(7))
            conn.sync()
            assert srv.stats()["evictions"] > 0
            for i in range(4):
                assert np.array_equal(read(conn, f"sh{i}"), content(7))
            for i in range(4, 8):  # cold sharers: gone, or whole
                if conn.check_exist(f"sh{i}"):
                    assert np.array_equal(read(conn, f"sh{i}"), content(7))
            assert not all(conn.check_exist(f"sh{i}") for i in range(4, 8))
            assert_conserved(srv)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_a_few_shared_blocks_do_not_turn_eviction_on_fresh_entries():
    """Seen on the chip (PERF.md, PR 35): a model whose first layer has
    no positional encoding writes identical pages wherever a page of
    tokens repeats. Each pair of sharers was skipped by eviction as
    pinned and stayed at its stripe's cold tail with a stale age; once
    every stripe had one, the strict pass found nothing and the relaxed
    pass swept whole stripes, so a third of the keys written a moment
    before were gone. Here: rounds of 48 fresh keys with two duplicate
    pairs each through a pool of 128 pages; every key of the round just
    written reads back."""
    srv = start_server(pool_mb=128 * BLOCK / (1 << 20), eviction=True)
    try:
        conn = connect(srv.service_port)
        try:
            for r in range(24):
                keys = [f"r{r}k{i}" for i in range(48)]
                for i, k in enumerate(keys):
                    v = 10_000 * r + i
                    if i in (40, 41):          # copies of keys 0 and 1
                        v = 10_000 * r + i - 40
                    put(conn, k, content(v))
                conn.sync()
                gone = [k for k in keys if not conn.check_exist(k)]
                assert not gone, (r, gone)
            assert srv.stats()["evictions"] > 500
            assert_conserved(srv)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_spill_promote_roundtrip_after_block_goes_solo(tmp_path):
    srv = start_server(pool_mb=64 * BLOCK / (1 << 20), ssd_mb=16,
                       eviction=True, tmpdir=tmp_path,
                       reclaim_high=0.9, reclaim_low=0.7)
    try:
        conn = connect(srv.service_port)
        try:
            put(conn, "solo0", content(11))
            put(conn, "solo1", content(11))
            conn.sync()
            # Drop one sharer: the block goes solo and becomes
            # spillable (a SHARED block is never spilled — the
            # adopt-at-refcount-2 guard abandons it).
            conn.delete_keys(["solo1"])
            conn.sync()
            # Cold-start LRU position + pressure pushes it to disk.
            for i in range(192):
                put(conn, f"f{i}", content(200 + i))
            conn.sync()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if srv.stats()["spills"] > 0:
                    break
                time.sleep(0.02)
            assert srv.stats()["spills"] > 0
            # Read back through the tier (inline promote if spilled).
            assert np.array_equal(read(conn, "solo0"), content(11))
            # A re-put of the same content after the round trip still
            # commits correctly (whether it adopts or re-allocates
            # depends on where the block lives — both are legal).
            put(conn, "again", content(11))
            conn.sync()
            assert np.array_equal(read(conn, "again"), content(11))
        finally:
            conn.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Snapshot round-trip.


def test_snapshot_roundtrip_restores_sharing(tmp_path):
    snap = str(tmp_path / "dedup.snap")
    srv = start_server()
    try:
        conn = connect(srv.service_port)
        try:
            # 24 keys over 4 distinct contents.
            for i in range(24):
                put(conn, f"r{i}", content(i % 4))
            conn.sync()
            assert srv.stats()["used_bytes"] == 4 * BLOCK
        finally:
            conn.close()
        assert srv.snapshot(snap) == 24
    finally:
        srv.stop()
    srv2 = start_server()
    try:
        assert srv2.restore(snap) == 24
        st = srv2.stats()
        # Restore re-deduplicated: byte-identical payloads adopted the
        # first restored block (zero-alloc), so physical occupancy is
        # the DISTINCT contents, not the key count.
        assert st["used_bytes"] == 4 * BLOCK
        assert st["dedup"]["logical_bytes"] == 24 * BLOCK
        assert st["dedup"]["dedup_hits"] == 20
        assert_conserved(srv2)
        conn = connect(srv2.service_port)
        try:
            for i in range(24):
                assert np.array_equal(read(conn, f"r{i}"),
                                      content(i % 4))
        finally:
            conn.close()
    finally:
        srv2.stop()


# ---------------------------------------------------------------------------
# Estimator cross-validation (ISSUE 16 satellite 2).


def test_estimator_prediction_matches_measured_multiplier():
    """Delete-free deterministic trace: 96 keys over 8 contents. The
    PR-13 workload estimator (sampled bounded-FNV fingerprints)
    PREDICTS the capacity multiplier; the dedup index MEASURES it
    exactly. They must agree within 0.1."""
    srv = start_server()
    try:
        conn = connect(srv.service_port)
        try:
            for i in range(96):
                put(conn, f"x{i}", content(i % 8))
            conn.sync()
        finally:
            conn.close()
        predicted = float(srv.workload()["dedup"]["ratio"])
        measured = srv.stats()["dedup"]["dedup_measured_milli"] / 1000.0
        assert measured == pytest.approx(12.0)
        assert abs(predicted - measured) <= 0.1, (predicted, measured)
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Chaos: killed clients mid hash-first put leak nothing.


def test_chaos_killed_clients_mid_hash_first_put_leak_zero_blocks():
    srv = start_server(pool_mb=8)
    port = srv.service_port
    try:
        # Seed the canonical contents on a clean connection.
        seed = connect(port)
        try:
            for v in range(4):
                put(seed, f"seed{v}", content(50 + v))
            seed.sync()
        finally:
            seed.close()
        srv.fault("sock.recv=prob(0.02):err(104);"
                  "sock.send=prob(0.02):err(32)")
        committed = [set() for _ in range(4)]

        def hammer(t):
            for attempt in range(10):
                try:
                    conn = connect(port, use_dedup=True,
                                   auto_reconnect=True,
                                   retry_backoff_ms=5)
                    break
                except Exception:
                    if attempt == 9:
                        raise
                    time.sleep(0.02)
            try:
                for i in range(64):
                    k = f"cz{t}_{i}"
                    try:
                        # Every put is a duplicate: the hash-first
                        # probe rides (and dies on) the faulted
                        # socket constantly.
                        put(conn, k, content(50 + (i % 4)))
                        conn.sync()
                        committed[t].add(k)
                    except Exception:
                        continue
            finally:
                conn.close()

        threads = [threading.Thread(target=hammer, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive(), "hammer wedged under socket faults"
        assert srv.stats()["failpoints_fired"] > 0
        srv.fault("off")
        # Byte audit on a clean connection: every synced key exact...
        conn = connect(port)
        try:
            for t in range(4):
                for k in sorted(committed[t]):
                    v = 50 + (int(k.rsplit("_", 1)[1]) % 4)
                    assert np.array_equal(read(conn, k), content(v)), k
        finally:
            conn.close()
        # ...and zero leaked blocks: once inflight drains, physical
        # == logical - shared savings, and physical is exactly the 4
        # distinct contents (every committed key adopted one of
        # them).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if srv.stats()["inflight"] == 0:
                break
            time.sleep(0.02)
        assert_conserved(srv)
        assert srv.stats()["used_bytes"] == 4 * BLOCK
    finally:
        try:
            srv.fault("off")
        except Exception:
            pass
        srv.stop()


@pytest.fixture(autouse=True)
def _disarm_failpoints():
    yield
    _native.get_lib().ist_server_fault(ct.c_void_p(1), b"off", None, 0)


# ---------------------------------------------------------------------------
# Kill switch + hash primitive.


def test_kill_switch_disables_sharing():
    srv = start_server(env={"ISTPU_DEDUP": "0"})
    try:
        conn = connect(srv.service_port)
        try:
            for i in range(8):
                put(conn, f"k{i}", content(1))
            conn.sync()
            st = srv.stats()
            assert st["dedup"]["enabled"] == 0
            assert st["dedup"]["dedup_hits"] == 0
            # Every duplicate paid full pool bytes: the bench
            # denominator.
            assert st["used_bytes"] == 8 * BLOCK
            for i in range(8):
                assert np.array_equal(read(conn, f"k{i}"), content(1))
        finally:
            conn.close()
    finally:
        srv.stop()


def test_content_hash_is_deterministic_and_discriminating():
    lib = _native.get_lib()

    def h(buf):
        a = ct.c_uint64(0)
        b = ct.c_uint64(0)
        lib.ist_content_hash(
            buf.ctypes.data_as(ct.c_void_p), buf.nbytes,
            ct.byref(a), ct.byref(b))
        return a.value, b.value

    x = content(1)
    assert h(x) == h(x.copy())
    assert h(x) != h(content(2))
    # A single flipped byte anywhere changes the hash (both lanes are
    # full-payload).
    y = x.copy()
    y[BLOCK // 2] ^= 1
    assert h(x) != h(y)
    z = x.copy()
    z[-1] ^= 1
    assert h(x) != h(z)
