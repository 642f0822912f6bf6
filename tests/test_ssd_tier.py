"""Disk spill tier tests.

The reference names an SSD tier as a feature goal
(/root/reference/docs/source/design.rst:36) but ships no code; this tier
is beyond-parity. Semantics under test: cold committed entries spill to
disk under pool pressure, reads promote them back transparently on both
data paths, spill-only mode never drops data, and eviction mode drops
only when pool AND disk are full.
"""

import itertools
import time
import uuid

import numpy as np
import pytest

from infinistore_tpu import (
    ClientConfig,
    InfiniStoreError,
    InfiniStoreServer,
    InfinityConnection,
    ServerConfig,
    TYPE_SHM,
    TYPE_STREAM,
)

BLOCK_KB = 16
BLOCK = BLOCK_KB << 10
POOL_BLOCKS = 8  # tiny pool: 8 x 16 KB


def make_server(ssd_blocks=64, eviction=False, tmp_path="/tmp"):
    srv = InfiniStoreServer(
        ServerConfig(
            service_port=0,
            prealloc_size=(POOL_BLOCKS * BLOCK) / (1 << 30),
            minimal_allocate_size=BLOCK_KB,
            enable_eviction=eviction,
            ssd_path=str(tmp_path),
            ssd_size=(ssd_blocks * BLOCK) / (1 << 30),
        )
    )
    srv.start()
    return srv


def wait_for_counter(srv, name, poke=None, deadline_s=120.0):
    """The server's stats once its background work has moved counter
    `name` off zero: returns as soon as it has, and a loaded box (six
    test workers on shared cores) has two minutes for it. `poke` runs
    between polls: what the counter's work is queued by, where one
    request may be refused under pool pressure and the next not."""
    deadline = time.monotonic() + deadline_s
    while srv.stats()[name] == 0 and time.monotonic() < deadline:
        if poke is not None:
            poke()
        time.sleep(0.02)
    return srv.stats()


def connect(srv, ctype=TYPE_SHM):
    c = InfinityConnection(
        ClientConfig(
            host_addr="127.0.0.1",
            service_port=srv.service_port,
            connection_type=ctype,
        )
    )
    c.connect()
    return c


@pytest.mark.parametrize("ctype", [TYPE_SHM, TYPE_STREAM])
def test_spill_and_promote_roundtrip(tmp_path, ctype):
    """Write 4x pool capacity; every key must read back intact. Under
    the async read pipeline (PR 5) the FIRST cold get serves straight
    from the disk extent without promoting (disk_reads_inline grows,
    promotes stays 0 — one-shot scans must not churn the pool); a
    SECOND touch queues the async promotion, after which the key reads
    back pool-resident."""
    srv = make_server(tmp_path=tmp_path)
    try:
        conn = connect(srv, ctype)
        rng = np.random.default_rng(7)
        n = POOL_BLOCKS * 4
        pages = rng.integers(0, 255, size=(n, BLOCK), dtype=np.uint8)
        keys = [f"sp{i}" for i in range(n)]
        for i in range(n):
            conn.put_cache(pages[i], [(keys[i], 0)], BLOCK)
            conn.sync()
        stats = wait_for_counter(srv, "spills")
        assert stats["spills"] > 0, stats
        assert stats["kvmap_len"] == n  # nothing dropped
        # First cold pass: every key intact, served from disk with ZERO
        # promotions (second-touch policy).
        for i in range(n):
            dst = np.zeros(BLOCK, dtype=np.uint8)
            conn.read_cache(dst, [(keys[i], 0)], BLOCK)
            conn.sync()
            assert np.array_equal(dst, pages[i]), f"key {i} corrupted"
        stats = srv.stats()
        assert stats["disk_reads_inline"] > 0, stats
        assert stats["promotes"] == 0, stats
        # Second touch on a cold key: the async promote is queued and
        # eventually adopted; the data stays intact throughout.
        def touch(i):
            dst = np.zeros(BLOCK, dtype=np.uint8)
            conn.read_cache(dst, [(keys[i], 0)], BLOCK)
            conn.sync()
            assert np.array_equal(dst, pages[i]), f"key {i} corrupted (2)"

        for i in range(n):
            touch(i)
        # A second touch that meets a pool without headroom queues
        # nothing (it kicks the reclaimer): the wait touches again.
        again = itertools.count()
        stats = wait_for_counter(srv, "promotes_async",
                                 poke=lambda: touch(next(again) % n))
        assert stats["promotes_async"] > 0, stats
        assert stats["promotes"] >= stats["promotes_async"]
        conn.close()
    finally:
        srv.stop()


def test_spill_only_mode_never_drops(tmp_path):
    """Without enable_eviction, pool+disk exhaustion returns OOM but no
    committed entry is ever dropped (first-writer-wins preserved)."""
    srv = make_server(ssd_blocks=8, tmp_path=tmp_path)  # pool 8 + disk 8
    try:
        conn = connect(srv)
        written = []
        with pytest.raises(InfiniStoreError):
            for i in range(40):
                k = f"full{i}"
                conn.put_cache(
                    np.full(BLOCK, i % 251, dtype=np.uint8), [(k, 0)], BLOCK
                )
                conn.sync()
                written.append((k, i % 251))
        # Every successful write survives and reads back correctly.
        assert 8 <= len(written) <= 16
        assert srv.stats()["kvmap_len"] == len(written)
        for k, v in written:
            dst = np.zeros(BLOCK, dtype=np.uint8)
            conn.read_cache(dst, [(k, 0)], BLOCK)
            conn.sync()
            assert (dst == v).all()
        conn.close()
    finally:
        srv.stop()


def test_eviction_mode_drops_only_when_disk_full(tmp_path):
    """With eviction on, writes keep succeeding past pool+disk capacity;
    victims disappear coldest-first, hot keys survive."""
    srv = make_server(ssd_blocks=16, eviction=True, tmp_path=tmp_path)
    try:
        conn = connect(srv)
        n = 64
        for i in range(n):
            conn.put_cache(
                np.full(BLOCK, i % 251, dtype=np.uint8), [(f"ev{i}", 0)], BLOCK
            )
            conn.sync()
        stats = srv.stats()
        assert stats["evictions"] > 0
        assert stats["kvmap_len"] < n
        # The most recent key is hot and must be present.
        dst = np.zeros(BLOCK, dtype=np.uint8)
        conn.read_cache(dst, [(f"ev{n - 1}", 0)], BLOCK)
        conn.sync()
        assert (dst == (n - 1) % 251).all()
        conn.close()
    finally:
        srv.stop()


def test_spilled_keys_count_for_match_and_exist(tmp_path):
    """check_exist and get_match_last_index must see disk-resident keys
    without promoting them."""
    srv = make_server(tmp_path=tmp_path)
    try:
        conn = connect(srv)
        n = POOL_BLOCKS * 3
        chain = [f"pref{i}" for i in range(n)]
        for k in chain:
            conn.put_cache(np.zeros(BLOCK, dtype=np.uint8), [(k, 0)], BLOCK)
            conn.sync()
        assert srv.stats()["spills"] > 0
        promotes_before = srv.stats()["promotes"]
        # Oldest key is certainly spilled by now.
        assert conn.check_exist(chain[0])
        assert conn.get_match_last_index(chain + [str(uuid.uuid4())]) == n - 1
        # Metadata ops must not have promoted anything.
        assert srv.stats()["promotes"] == promotes_before
        conn.close()
    finally:
        srv.stop()


def test_purge_frees_disk(tmp_path):
    srv = make_server(tmp_path=tmp_path)
    try:
        conn = connect(srv)
        for i in range(POOL_BLOCKS * 2):
            conn.put_cache(
                np.zeros(BLOCK, dtype=np.uint8), [(f"pg{i}", 0)], BLOCK
            )
            conn.sync()
        assert srv.stats()["disk_used"] > 0
        srv.purge()
        stats = srv.stats()
        assert stats["disk_used"] == 0
        assert stats["used_bytes"] == 0
        conn.close()
    finally:
        srv.stop()
