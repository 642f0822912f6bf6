"""Workload observability plane (ISSUE 13).

Covers the four estimators end to end plus their export planes:
  - SHARDS reuse-distance sampler: deterministic (pure hash
    admission), and its predicted miss ratio at the real pool size
    matches both the native miss counters and an exact stack-distance
    simulation on a deterministic Zipfian trace;
  - ghost ring: a get-miss on a recently hard-evicted key counts
    premature_evictions under a forced-small pool; explicit deletes
    and purge clear the ring while the cumulative counters survive;
  - thrash: a spill -> promote round trip counts thrash_cycles, and a
    sustained premature-eviction rate fires exactly one
    watchdog.thrash verdict whose bundle carries workload.json;
  - dedup estimator: a known-duplicate key set reports the exact
    ratio; heat classes expose hot-key skew;
  - kill switch (ISTPU_WORKLOAD=0): recording fully off;
  - export: GET /workload over the manage plane, the stats "workload"
    section, /metrics families, history-ring demand deltas, and the
    istpu_top workload panel (live shape + bundle workload.json +
    graceful pre-v13 degrade).

All servers ride ephemeral ports and tmp dirs; the suite also runs
under the ISTPU_TSAN/ASAN smoke legs (run_test.sh).
"""

import importlib.util
import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import scenario
from infinistore_tpu import InfiniStoreServer, ServerConfig
from infinistore_tpu.config import ClientConfig
from infinistore_tpu.lib import InfinityConnection
from infinistore_tpu.server import _prometheus_metrics, make_control_plane

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK_KB = 4
BLOCK = BLOCK_KB << 10


def _istpu_top_module():
    spec = importlib.util.spec_from_file_location(
        "istpu_top_for_workload", os.path.join(REPO, "tools",
                                               "istpu_top.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _server(pool_keys, env=None, **kw):
    """Boot a server whose pool holds exactly pool_keys BLOCK-sized
    entries; env (if given) is set around start() only — the workload
    knobs are read at server start."""
    env = env or {}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        srv = InfiniStoreServer(
            ServerConfig(
                service_port=0,
                prealloc_size=pool_keys * BLOCK / (1 << 30),
                minimal_allocate_size=BLOCK_KB,
                **kw,
            )
        )
        srv.start()
        return srv
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _connect(srv):
    conn = InfinityConnection(
        ClientConfig(host_addr="127.0.0.1",
                     service_port=srv.service_port,
                     connection_type="STREAM")
    )
    conn.connect()
    return conn


def _put(conn, key, buf):
    conn.put_cache(buf, [(key, 0)], BLOCK)


def _read(conn, key, dst):
    conn.read_cache(dst, [(key, 0)], BLOCK)


SRC = np.arange(BLOCK, dtype=np.uint8) % 251
DST = np.zeros(BLOCK, dtype=np.uint8)


def _replay(conn, trace, prefix="z"):
    """Replay a key-index GET trace, re-putting every missed key (the
    re-reference stream every cache sees). Returns client-side miss
    count."""
    misses = 0
    for idx in trace:
        try:
            _read(conn, f"{prefix}{idx}", DST)
        except Exception:
            misses += 1
            _put(conn, f"{prefix}{idx}", SRC)
    conn.sync()
    return misses


def test_workload_endpoint_stats_and_metrics():
    srv = _server(64)
    try:
        conn = _connect(srv)
        try:
            for i in range(32):
                _put(conn, f"a{i}", SRC)
            conn.sync()
            for i in range(32):
                _read(conn, f"a{i}", DST)
        finally:
            conn.close()
        # Programmatic blob.
        wl = srv.workload()
        assert wl["enabled"] == 1
        assert wl["accesses"] == 32 and wl["misses"] == 0
        assert wl["commits"] == 32
        assert len(wl["mrc"]) == 5
        scales = [m["scale"] for m in wl["mrc"]]
        assert scales == [0.25, 0.5, 1.0, 2.0, 4.0]
        assert wl["wss_bytes"] > 0
        # Stats section mirrors the headline.
        st = srv.stats()
        assert st["workload"]["enabled"] == 1
        assert st["workload"]["accesses"] == 32
        # /metrics families render from the section.
        text = _prometheus_metrics(st)
        for fam in ("infinistore_workload_enabled",
                    "infinistore_workload_wss_bytes",
                    "infinistore_workload_predicted_miss_1x",
                    "infinistore_workload_premature_evictions_total",
                    "infinistore_workload_thrash_cycles_total",
                    "infinistore_workload_dedup_ratio"):
            assert fam in text, fam
        # HTTP manage plane serves the same blob on GET /workload.
        srv.config.manage_port = 0
        httpd = make_control_plane(srv)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/workload", timeout=5) as r:
                over_http = json.loads(r.read().decode())
            assert over_http["accesses"] == 32
            assert over_http["mrc"] == wl["mrc"]
        finally:
            httpd.shutdown()
            httpd.server_close()
    finally:
        srv.stop()


def test_sampler_deterministic_across_servers():
    # Admission is a pure hash of the key and the trace is fixed, so
    # two servers fed the same stream must land the same sampler
    # state bit for bit.
    trace = scenario.zipf_trace(96, 1024, seed=7)
    snaps = []
    for _ in range(2):
        srv = _server(48, enable_eviction=True, reclaim_high=1.0,
                      env={"ISTPU_EXACT_LRU": "1"})
        try:
            conn = _connect(srv)
            try:
                for i in range(96):
                    _put(conn, f"z{i}", SRC)
                conn.sync()
                _replay(conn, trace)
            finally:
                conn.close()
            wl = srv.workload()
            snaps.append((wl["sampler"], wl["accesses"], wl["misses"]))
        finally:
            srv.stop()
    assert snaps[0] == snaps[1]


def test_mrc_accuracy_vs_exact_sim_and_measured():
    # ISSUE 13 acceptance shape, in-suite: deterministic Zipfian trace
    # against a pool holding half the keys, exact inline LRU, sampler
    # at rate 1.0 (the sampling-noise-free contract: the Fenwick
    # byte-stack itself must be exact) — predicted-vs-measured and
    # predicted-vs-exact-sim both within 0.05.
    nkeys, cap = 128, 64
    trace = scenario.zipf_trace(nkeys, 3000, seed=11)
    srv = _server(cap, enable_eviction=True, reclaim_high=1.0,
                  env={"ISTPU_EXACT_LRU": "1",
                       "ISTPU_WORKLOAD_RATE": "1.0"})
    try:
        conn = _connect(srv)
        try:
            for i in range(nkeys):
                _put(conn, f"z{i}", SRC)
            conn.sync()
            before = srv.workload()
            _replay(conn, trace)
            after = srv.workload()
        finally:
            conn.close()
    finally:
        srv.stop()

    def delta(field, sub=None):
        if sub is None:
            return after[field] - before[field]
        return after[sub][field] - before[sub][field]

    d_acc = delta("accesses")
    d_miss = delta("misses")
    d_samp = delta("sampled_accesses", "sampler")
    d_hit = (after["sampler"]["hits"][2] - before["sampler"]["hits"][2])
    assert d_acc == len(trace)
    measured = d_miss / d_acc
    predicted = 1.0 - d_hit / d_samp
    exact = scenario.exact_lru_miss_ratio(trace, cap)
    assert abs(predicted - measured) <= 0.05, (predicted, measured)
    assert abs(predicted - exact) <= 0.05, (predicted, exact)
    # The curve is monotone non-increasing in pool size.
    mrc = [m["miss_ratio"] for m in after["mrc"]]
    assert all(a >= b - 1e-9 for a, b in zip(mrc, mrc[1:]))


def test_ghost_ring_counts_premature_evictions():
    srv = _server(32, enable_eviction=True, reclaim_high=1.0)
    try:
        conn = _connect(srv)
        try:
            # 64 keys through a 32-key pool: the first half is evicted
            # by the time the puts finish.
            for i in range(64):
                _put(conn, f"g{i}", SRC)
            conn.sync()
            misses = 0
            for i in range(64):
                try:
                    _read(conn, f"g{i}", DST)
                except Exception:
                    misses += 1
            wl = srv.workload()
            assert misses > 0
            assert wl["misses"] == misses
            # Every miss was on an evicted key; collisions in the
            # fixed ring can only lose a few.
            prem = wl["ghost"]["premature_evictions"]
            assert prem > 0
            assert prem <= misses
            assert prem >= misses * 0.9
            assert wl["ghost"]["evictions_noted"] > 0
        finally:
            conn.close()
    finally:
        srv.stop()


def test_delete_clears_ghost_slot():
    srv = _server(32, enable_eviction=True, reclaim_high=1.0)
    try:
        conn = _connect(srv)
        try:
            for i in range(40):
                _put(conn, f"d{i}", SRC)
            conn.sync()
            # d0..d7 were evicted (ghosted). Deleting an ALREADY
            # evicted key is a no-op; delete a resident one, then
            # miss on it — the miss is the client's own delete, never
            # a premature eviction.
            conn.delete_keys(["d30"])
            with pytest.raises(Exception):
                _read(conn, "d30", DST)
            wl = srv.workload()
            assert wl["ghost"]["premature_evictions"] == 0
            # An evicted (ghosted) key still counts.
            with pytest.raises(Exception):
                _read(conn, "d0", DST)
            assert (srv.workload()["ghost"]["premature_evictions"]
                    == 1)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_purge_counters_survive_ghost_clears():
    srv = _server(32, enable_eviction=True, reclaim_high=1.0)
    try:
        conn = _connect(srv)
        try:
            for i in range(64):
                _put(conn, f"p{i}", SRC)
            conn.sync()
            for i in range(16):
                try:
                    _read(conn, f"p{i}", DST)
                except Exception:
                    pass
            wl = srv.workload()
            prem = wl["ghost"]["premature_evictions"]
            acc = wl["accesses"]
            assert prem > 0
            srv.purge()
            wl2 = srv.workload()
            # Cumulative counters SURVIVE the purge...
            assert wl2["ghost"]["premature_evictions"] == prem
            assert wl2["accesses"] == acc
            # ...but the reuse stacks and ghost rings cleared: misses
            # on previously-ghosted (now purged) keys add no premature
            # evictions.
            assert wl2["sampler"]["live_keys"] == 0
            for i in range(16, 32):
                with pytest.raises(Exception):
                    _read(conn, f"p{i}", DST)
            assert (srv.workload()["ghost"]["premature_evictions"]
                    == prem)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_dedup_estimator_known_duplicates(tmp_path):
    # 96 keys carrying 8 distinct contents: the content-deterministic
    # sampler must report samples/distinct == 12 exactly (mask starts
    # at admit-all and the set stays far under the cap).
    srv = _server(128)
    try:
        conn = _connect(srv)
        try:
            bufs = [(np.arange(BLOCK, dtype=np.uint8) + 3 * v) % 251
                    for v in range(8)]
            for i in range(96):
                _put(conn, f"dd{i}", bufs[i % 8])
            conn.sync()
        finally:
            conn.close()
        wl = srv.workload()
        assert wl["dedup"]["samples"] == 96
        assert wl["dedup"]["distinct"] == 8
        assert wl["dedup"]["ratio"] == pytest.approx(12.0)
    finally:
        srv.stop()


def test_heat_classes_expose_hot_key_skew():
    srv = _server(64)
    try:
        conn = _connect(srv)
        try:
            for i in range(16):
                _put(conn, f"h{i}", SRC)
            conn.sync()
            # One hot key read 512 times vs 15 cold keys once each.
            for _ in range(512):
                _read(conn, "h0", DST)
            for i in range(1, 16):
                _read(conn, f"h{i}", DST)
        finally:
            conn.close()
        heat = srv.workload()["heat"]
        assert sum(heat["buckets"]) > 0
        # One bucket holds ~all the mass: skew well above uniform.
        assert heat["skew"] > 4.0, heat
    finally:
        srv.stop()


def test_kill_switch_records_nothing():
    srv = _server(64, env={"ISTPU_WORKLOAD": "0"})
    try:
        conn = _connect(srv)
        try:
            for i in range(32):
                _put(conn, f"k{i}", SRC)
            conn.sync()
            for i in range(32):
                _read(conn, f"k{i}", DST)
            with pytest.raises(Exception):
                _read(conn, "missing", DST)
        finally:
            conn.close()
        wl = srv.workload()
        assert wl["enabled"] == 0
        assert wl["accesses"] == 0 and wl["misses"] == 0
        assert wl["commits"] == 0
        assert wl["sampler"]["sampled_accesses"] == 0
        assert wl["dedup"]["samples"] == 0
        assert sum(wl["heat"]["buckets"]) == 0
        assert srv.stats()["workload"]["enabled"] == 0
    finally:
        srv.stop()


def test_thrash_cycles_count_spill_promote_round_trips(tmp_path):
    # Spill-only tier, inline reclaim, inline promotion: pushing the
    # working set past the pool spills the cold half; reading a
    # spilled key promotes it straight back — a round trip the
    # spill ring turns into thrash_cycles.
    srv = _server(16, ssd_path=str(tmp_path), ssd_size=1 / 1024,
                  reclaim_high=1.0, promote=False)
    try:
        conn = _connect(srv)
        try:
            for i in range(32):
                _put(conn, f"t{i}", SRC)
            conn.sync()
            st = srv.stats()
            assert st["spills"] > 0
            # Oldest keys are on disk now; reading them promotes.
            for i in range(4):
                _read(conn, f"t{i}", DST)
            wl = srv.workload()
            assert wl["ghost"]["spills_noted"] > 0
            assert wl["ghost"]["thrash_cycles"] > 0
            assert srv.stats()["workload"]["thrash_cycles"] > 0
        finally:
            conn.close()
    finally:
        srv.stop()


def test_thrash_verdict_fires_once_with_workload_bundle(tmp_path):
    # ISSUE 13 acceptance: the chaos-style small-pool re-read loop
    # fires EXACTLY ONE watchdog.thrash verdict (threshold crossed on
    # two consecutive 100 ms samples; the cooldown absorbs the rest)
    # whose bundle contains workload.json with a nonzero
    # premature_evictions count.
    bundle_dir = tmp_path / "bundles"
    srv = _server(
        32, enable_eviction=True, reclaim_high=1.0,
        bundle_dir=str(bundle_dir),
        env={
            "ISTPU_WATCHDOG_INTERVAL_MS": "100",
            "ISTPU_WATCHDOG_THRASH": "5",
            # Keep the other verdict kinds out of the way: this loop
            # legitimately drives slow-op-sized latencies on a loaded
            # box and the test must isolate the thrash kind.
            "ISTPU_WATCHDOG_P99_US": "60000000",
        },
    )
    try:
        conn = _connect(srv)
        try:
            for i in range(64):
                _put(conn, f"w{i}", SRC)
            conn.sync()
            ev_floor = srv.stats()["events"]["recorded"]
            deadline = time.time() + 8.0
            while time.time() < deadline:
                # Cycle a 2x-pool working set: every read of the
                # evicted half is a premature eviction; the re-put
                # evicts the other half.
                for i in range(64):
                    try:
                        _read(conn, f"w{i}", DST)
                    except Exception:
                        _put(conn, f"w{i}", SRC)
                trips = srv.stats()["watchdog"]["thrash_trips"]
                if trips:
                    break
            # The trip is counted before its bundle is written: wait for
            # the capture to finish before reading the bundle below.
            deadline = time.time() + 10
            while (srv.stats()["watchdog"]["bundles"] < 1
                   and time.time() < deadline):
                time.sleep(0.02)
            st = srv.stats()
            assert st["watchdog"]["thrash_trips"] == 1, st["watchdog"]
            assert st["workload"]["premature_evictions"] > 0
            # The verdict landed in the flight recorder...
            evs = srv.events(since_seq=ev_floor)["events"]
            thrash = [e for e in evs if e["name"] == "watchdog.thrash"]
            assert len(thrash) == 1
            assert thrash[0]["a0"] >= 5  # premature delta >= threshold
        finally:
            conn.close()
        # ...and the bundle carries the demand model.
        bundles = sorted(
            d for d in os.listdir(bundle_dir) if "thrash" in d
        )
        assert len(bundles) == 1, os.listdir(bundle_dir)
        bpath = bundle_dir / bundles[0]
        manifest = json.loads((bpath / "manifest.json").read_text())
        assert manifest["trigger"] == "thrash"
        assert "workload.json" in manifest["files"]
        wl = json.loads((bpath / "workload.json").read_text())
        assert wl["ghost"]["premature_evictions"] > 0
        # istpu_top renders the bundle (workload panel included).
        top = _istpu_top_module()
        frame = top.render_frame(
            json.loads((bpath / "stats.json").read_text()),
            json.loads((bpath / "debug_state.json").read_text()),
            json.loads((bpath / "events.json").read_text()),
            history=json.loads((bpath / "history.json").read_text()),
            workload=wl,
        )
        assert "workload:" in frame and "MRC" in frame
    finally:
        srv.stop()


def test_history_samples_carry_workload_deltas():
    srv = _server(32, enable_eviction=True, reclaim_high=1.0,
                  env={"ISTPU_WATCHDOG_INTERVAL_MS": "100"})
    try:
        conn = _connect(srv)
        try:
            for i in range(64):
                _put(conn, f"hh{i}", SRC)
            conn.sync()
            deadline = time.time() + 6.0
            seen = False
            while time.time() < deadline and not seen:
                for i in range(64):
                    try:
                        _read(conn, f"hh{i}", DST)
                    except Exception:
                        _put(conn, f"hh{i}", SRC)
                hist = srv.history()["history"]
                assert all("premature_evictions_delta" in s
                           and "thrash_cycles_delta" in s
                           and "wss_bytes" in s for s in hist)
                seen = any(s["premature_evictions_delta"] > 0
                           for s in hist)
            assert seen, "no sample saw a premature-eviction delta"
            assert any(s["wss_bytes"] > 0 for s in hist)
        finally:
            conn.close()
    finally:
        srv.stop()


def test_istpu_top_degrades_without_workload_blob():
    # Pre-v13 bundles lack workload.json: the panel must simply be
    # absent, never a crash; the ISTPU_WORKLOAD=0 denominator blob
    # renders the disabled notice.
    top = _istpu_top_module()
    assert top.render_workload({}) == []
    assert top.render_workload(None) == []
    off = top.render_workload({"enabled": 0, "accesses": 0})
    assert any("disabled" in ln for ln in off)
    frame = top.render_frame({}, {}, {}, workload={})
    assert "workload:" not in frame
