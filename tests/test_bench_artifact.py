"""The bench artifact must be un-killable: a driver timeout that kills
bench.py before an end-of-run print would zero a round's evidence.
These tests pin the two properties that make that impossible:

  1. under a tight wall-clock budget the run still exits quickly with a
     complete, parseable artifact whose device legs carry explicit
     *_skipped markers;
  2. a SIGKILL mid-run (the driver-timeout failure mode, un-catchable
     by python) leaves a tail whose last line is already a complete,
     parseable artifact carrying the primary metric.

Both bench subprocesses run in their own process GROUP and are
group-killed on every exit path: at kill time bench may have live
children (sharded-leg servers, gated_leg subprocesses) that must not
outlive the test.
"""

import json
import os
import signal
import subprocess
import sys
import threading


BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _env(budget):
    env = dict(os.environ)
    env["BENCH_BUDGET_S"] = str(budget)
    env["JAX_PLATFORMS"] = "cpu"  # tier-1 runs without a chip
    return env


def _killpg(p):
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass  # already exited (group reaped)


def _parse_artifacts(lines):
    """JSON-parse every candidate line, keeping the parseable ones —
    the line the kill interrupted may be a fragment."""
    outs = []
    for ln in lines:
        try:
            outs.append(json.loads(ln))
        except ValueError:
            pass
    return outs


def test_tiny_budget_run_completes_with_markers():
    p = subprocess.Popen(
        [sys.executable, BENCH], env=_env(30), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
    )
    try:
        stdout, stderr = p.communicate(timeout=420)
    finally:
        _killpg(p)  # reap surviving children on EVERY exit path
    assert p.returncode == 0, stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in stdout.splitlines() if ln.startswith("{")]
    )
    assert len(outs) >= 3, "cumulative line must be printed per leg"
    out = outs[-1]
    # Primary metric present and sane.
    assert out["metric"] == "kv_put_get_4KBx4096_agg_throughput"
    assert out["value"] > 0
    # Over-budget legs degrade to explicit markers, never hang.
    assert any(k.endswith("_skipped") for k in out), sorted(out)


def test_leg_timeout_salvages_partial_output(tmp_path, monkeypatch):
    """A leg that wedges mid-phase still contributes its completed
    phases: bench_subprocess must salvage the last JSON line the killed
    child printed and merge it with the timeout marker (r05 lesson —
    the transfer leg burned 900 s and lost its finished restore
    numbers)."""
    sys.path.insert(0, os.path.dirname(BENCH))
    try:
        import bench
    finally:
        sys.path.pop(0)

    stub = tmp_path / "stub.py"
    stub.write_text(
        "import json, time\n"
        "print(json.dumps({'phase1_GBps': 1.5}), flush=True)\n"
        "time.sleep(120)\n"
    )
    wrapper = tmp_path / "fakepython"
    wrapper.write_text(
        f"#!/bin/sh\nexec {sys.executable} {stub} \"$@\"\n"
    )
    wrapper.chmod(0o755)
    monkeypatch.setattr(bench.sys, "executable", str(wrapper))
    res = bench.bench_subprocess("--any-leg", 0, "tpu_error", timeout_s=5)
    assert res["phase1_GBps"] == 1.5  # salvaged
    assert "timed out" in res["tpu_error"]
    assert res["tpu_error_partial"] is True


def test_evict_leg_emits_pressure_keys():
    """The eviction-pressure leg (ISSUE 3) must land its keys in the
    artifact: put p50 under 2x-pool pressure, the ratio against the
    no-pressure p50, and the hard-stall counter that shows whether the
    background reclaimer kept reclaim off the put path."""
    env = _env(600)
    env["ISTPU_EVICT_KEYS"] = "256"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--evict-leg", "0"], env=env,
        capture_output=True, text=True, timeout=180,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert out["evict_put_p50_us"] > 0
    assert out["evict_nopress_put_p50_us"] > 0
    assert out["evict_put_p50_ratio"] > 0
    assert "hard_stalls" in out
    assert "evict_reclaim_runs" in out


def test_cold_leg_emits_prefetch_keys():
    """The cold-read leg (ISSUE 5) must land its keys in the artifact:
    cold-read p99 with the async read pipeline on vs off, the
    post-prefetch hit rate (acceptance: disk_reads_inline stops growing
    after warmup) and the warm-vs-resident p50 ratio (acceptance: a
    promoted key reads like a pool-resident one). Ratios are asserted
    only as sane (>0) here — CI noise is checked at the acceptance
    level, not per test run."""
    env = _env(600)
    env["ISTPU_COLD_KEYS"] = "256"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--cold-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert out["cold_get_p99_us"] > 0
    assert out["cold_get_p99_off_us"] > 0
    assert out["cold_get_p99_ratio"] > 0
    assert 0.0 <= out["prefetch_hit_rate"] <= 1.0
    assert out["prefetch_hit_rate"] > 0  # prefetch actually promoted
    assert out["cold_promotes_async"] > 0
    assert out["cold_warm_vs_resident_p50"] > 0


def test_trace_leg_emits_overhead_keys():
    """The tracing-overhead leg (ISSUE 4) must land its keys in the
    artifact: traced vs untraced stream-shape read p50 and the ratio
    the <=1.05 acceptance gate reads. The ratio itself is asserted only
    as sane (>0) here — CI noise is checked at the acceptance level,
    not per test run."""
    env = _env(600)
    env["ISTPU_TRACE_KEYS"] = "128"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--trace-leg", "0"], env=env,
        capture_output=True, text=True, timeout=180,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert out["trace_p50_read_us"] > 0
    assert out["notrace_p50_read_us"] > 0
    assert out["trace_overhead_p50_ratio"] > 0
    assert out["trace_spans"] > 0  # the traced leg actually traced


def test_engine_ab_leg_emits_keys():
    """The transport-engine A/B leg (ISSUES 8 + 12, now three-way)
    must land its keys in the artifact: the epoll aggregates + raw
    denominator always; either the uring side (uring_stream_agg_GBps /
    uring_vs_epoll / recomputed *_vs_raw) or an explicit uring_skipped
    reason on hosts without io_uring; and either the fabric side
    (fabric_stream_agg_GBps / fabric_vs_epoll / fabric_stream_vs_raw
    plus the one-sided acceptance signals fabric_one_sided_puts and
    fabric_put_server_cpu_per_byte with its epoll RPC contrast) or an
    explicit fabric_skipped reason — never an error, never silence."""
    env = _env(600)
    env["ISTPU_ENGINE_AB_KEYS"] = "512"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--engine-ab-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert "engine_ab_error" not in out, out
    assert out["epoll_stream_agg_GBps"] > 0
    assert out["epoll_stream_64k_agg_GBps"] > 0
    assert out["engine_raw_tcp_GBps"] > 0
    if "uring_skipped" in out:
        assert "io_uring" in out["uring_skipped"] or "selected" in (
            out["uring_skipped"]
        )
    else:
        assert out["uring_stream_agg_GBps"] > 0
        assert out["uring_vs_epoll"] > 0
        assert out["uring_stream_vs_raw"] > 0
    if "fabric_skipped" in out:
        assert out["fabric_skipped"], out
    else:
        assert out["fabric_stream_agg_GBps"] > 0
        assert out["fabric_vs_epoll"] > 0
        assert out["fabric_stream_vs_raw"] > 0
        # One-sided acceptance: every put rode the ring, and the
        # server's CPU-per-byte on the fabric path does not exceed the
        # RPC path's beyond clock-tick noise (/proc utime+stime ticks
        # are 10 ms; over this leg's 2 MB that is ~4.8 ns/B of
        # quantization, and unrelated server threads can cross a tick
        # boundary — the absolute ~0 claim is asserted at the
        # acceptance level on a quiet host, not on a loaded CI box).
        assert out["fabric_one_sided_puts"] == 512
        tick_ns_per_byte = 0.01 * 1e9 / (512 * 4096)
        assert (out["fabric_put_server_cpu_per_byte"]
                <= out["epoll_put_server_cpu_per_byte"]
                + tick_ns_per_byte)


def test_chaos_leg_emits_overhead_keys():
    """The failpoints-disarmed overhead leg (ISSUE 6) must land its
    keys in the artifact: read p50 with the failpoint registry
    populated-but-disarmed vs untouched, and the ratio the <=1.02
    acceptance gate reads. The ratio itself is asserted only as sane
    (>0) here — CI noise is checked at the acceptance level, not per
    test run."""
    env = _env(600)
    env["ISTPU_CHAOS_KEYS"] = "128"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--chaos-leg", "0"], env=env,
        capture_output=True, text=True, timeout=180,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert out["chaos_off_p50_read_us"] > 0
    assert out["chaos_baseline_p50_read_us"] > 0
    assert out["chaos_off_overhead_p50_ratio"] > 0


def test_events_leg_emits_overhead_keys():
    """The always-on flight-recorder overhead leg (ISSUE 10) must land
    its keys in the artifact: read p50 with the recorder on (default)
    vs ISTPU_EVENTS=0, plus the <=1.02 acceptance ratio. The ratio is
    asserted only as sane (>0) here — CI noise is checked at the
    acceptance level, not per test run."""
    env = _env(600)
    env["ISTPU_EVENTS_KEYS"] = "128"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--events-leg", "0"], env=env,
        capture_output=True, text=True, timeout=180,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert out["events_on_p50_read_us"] > 0
    assert out["events_off_p50_read_us"] > 0
    assert out["events_overhead_p50_ratio"] > 0
    # The on-leg really recorded (always-on contract): at least the
    # server.start / engine.selected / conn.accept transitions.
    assert out["events_recorded"] >= 3


def test_obs_leg_emits_overhead_keys():
    """The observability overhead leg (ISSUE 11) must land its keys in
    the artifact: client-telemetry on vs ISTPU_CLIENT_STATS=0 and
    history on vs ISTPU_HISTORY=0 read p50s, plus the two <=1.02
    acceptance ratios. The ratios are asserted only as sane (>0) here —
    CI noise is checked at the acceptance level, not per test run."""
    env = _env(600)
    env["ISTPU_OBS_KEYS"] = "128"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--obs-leg", "0"], env=env,
        capture_output=True, text=True, timeout=240,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert out["client_stats_on_p50_read_us"] > 0
    assert out["client_stats_off_p50_read_us"] > 0
    assert out["client_telemetry_overhead_p50_ratio"] > 0
    assert out["history_on_p50_read_us"] > 0
    assert out["history_off_p50_read_us"] > 0
    assert out["history_overhead_p50_ratio"] > 0
    # The on-leg really recorded: every read of every pass landed in
    # the client histogram (warmup + measured passes)...
    assert out["client_stats_recorded"] >= out["obs_nkeys"]
    # ...and the history sampler demonstrably ran DURING the measured
    # window (baseline + >= 1 timed sample) — a ratio over a sampler
    # that never ticked would certify nothing.
    assert out["history_recorded"] >= 2


def test_workload_leg_emits_accuracy_and_overhead_keys():
    """The workload-observability leg (ISSUE 13) must land its keys in
    the artifact: the profiler-on vs ISTPU_WORKLOAD=0 read p50s plus
    the <=1.02 acceptance ratio (asserted only as sane here — CI noise
    is checked at the acceptance level), and the Zipfian accuracy
    numbers, which ARE asserted here because the trace, the hash
    admission and the exact-LRU eviction order are all deterministic:
    the sampler's predicted miss ratio at the real pool size must be
    within 0.05 of both the measured miss rate and the exact
    stack-distance simulation."""
    env = _env(600)
    env["ISTPU_WORKLOAD_KEYS"] = "256"   # small: keep the test fast
    env["ISTPU_WORKLOAD_TRACE"] = "4096"
    p = subprocess.run(
        [sys.executable, BENCH, "--workload-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert "workload_error" not in out, out
    assert out["workload_on_p50_read_us"] > 0
    assert out["workload_off_p50_read_us"] > 0
    assert out["workload_overhead_p50_ratio"] > 0
    # The on-leg really recorded; the off-leg (kill switch) did not.
    assert out["workload_accesses"] > 0
    assert out["workload_off_accesses"] == 0
    # Deterministic accuracy pins (ISSUE 13 acceptance).
    assert 0.0 < out["workload_measured_miss_ratio"] < 1.0
    assert out["workload_accuracy_err"] <= 0.05, out
    assert out["workload_vs_exact_err"] <= 0.05, out
    assert out["workload_wss_bytes"] > 0
    assert out["workload_premature_evictions"] > 0


def test_iosched_leg_emits_keys():
    """The background-IO scheduler leg (ISSUE 17) must land its keys
    in the artifact: the on vs ISTPU_IOSCHED=0 overhead p50s and
    <=1.02 acceptance ratio (asserted only as sane here — CI noise is
    checked at the acceptance level), plus the phase-scenario scores
    for the auto-tuned variant and the best static variant. What IS
    deterministic at this scale: the spill-pressured scenario drives
    real scheduler traffic (iosched_served > 0) and the promote class
    never pays a deadline miss on an unthrottled box
    (iosched_deadline_misses == 0 with no budget set on the auto
    variant's default env... the auto variant runs budget-free)."""
    env = _env(600)
    env["ISTPU_IOSCHED_KEYS"] = "96"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--iosched-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert "iosched_error" not in out, out
    assert out["iosched_on_p50_read_us"] > 0
    assert out["iosched_off_p50_read_us"] > 0
    assert out["iosched_overhead_p50_ratio"] > 0
    assert out["iosched_auto_interactive_p99_us"] > 0
    assert out["iosched_static_best_interactive_p99_us"] > 0
    assert out["iosched_auto_GBps"] > 0
    assert out["iosched_static_best_GBps"] > 0
    # The scenario really exercised the scheduler: background IO was
    # class-accounted, and with no budget the promote class can never
    # wait past its bound.
    assert out["iosched_served"] > 0
    assert out["iosched_deadline_misses"] == 0
    # The leg settle-waits for the auto variant's first calm-server
    # controller step, so >= 1 decision is structural (the CI smoke
    # pins the same) and the per-class breakdown carries the classes.
    assert out["iosched_decisions"] >= 1
    # >= not ==: the aggregate and the per-class rows serialize at
    # slightly different instants inside one stats snapshot, so a
    # background grant between them can skew the sum by a grant.
    assert sum(out["iosched_class_served"].values()) >= \
        out["iosched_served"] > 0
    assert out["iosched_class_served"].get("spill", 0) > 0


def test_conn_scale_leg_emits_keys():
    """The connection-scale leg (ISSUE 18) must land its keys in the
    artifact: the accept-burst rate, the base vs max-conns interactive
    percentiles with the 1.3x acceptance ratio (asserted only as
    present/sane here — the full-ramp acceptance runs at CI scale),
    and the bounded-memory pins that ARE deterministic at any scale:
    RSS per idle conn and the server's staging-buffer accounting both
    <= the 64 KB ISSUE budget, no sheds, and — when the fabric engine
    actually runs — every distinct-payload put on the one-sided ring
    path with a pool that never denied an attach."""
    env = _env(600)
    env["ISTPU_CONN_SCALE_TARGET"] = "300"  # small: keep the test fast
    env["ISTPU_CONN_SCALE_KEYS"] = "64"
    p = subprocess.run(
        [sys.executable, BENCH, "--conn-scale-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert "conn_scale_error" not in out, out
    assert out["conn_scale_max_conns"] >= out["conn_scale_target"] == 300
    assert out["conn_scale_accepts_per_sec"] > 0
    assert out["conn_scale_p99_us_base"] > 0
    assert out["conn_scale_p99_us_max"] > 0
    assert out["conn_scale_p99_ratio"] > 0
    # Bounded memory (ISSUE 18 acceptance): idle conns must cost well
    # under the 64 KB/conn budget in both process RSS and the server's
    # own staging-buffer accounting.
    assert out["conn_scale_rss_per_idle_conn_bytes"] <= 64 << 10
    assert 0 <= out["conn_scale_bytes_per_conn"] <= 64 << 10
    assert out["conn_scale_conns_shed"] == 0
    if out.get("conn_scale_engine") == "fabric":
        # Active writers kept their rings under full idle-conn load.
        assert out["conn_scale_ring_hit_rate"] == 1.0
        assert (out["conn_scale_one_sided_puts"]
                >= out["conn_scale_active_puts"] > 0)


def test_cluster_obs_leg_emits_overhead_keys():
    """The cluster-observability leg (ISSUE 15) must land its keys in
    the artifact: the aggregator-scraping vs idle read p50s, the
    <=1.02 acceptance ratio (asserted only as sane here — CI noise is
    checked at the acceptance level), and proof the on-leg's
    aggregator actually scraped the fleet with divergence digests
    (a ratio over an aggregator that never ran certifies nothing)."""
    env = _env(600)
    env["ISTPU_CLUSTER_OBS_KEYS"] = "128"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--cluster-obs-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert "cluster_obs_error" not in out, out
    assert out["cluster_obs_off_p50_read_us"] > 0
    assert out["cluster_obs_on_p50_read_us"] > 0
    assert out["cluster_obs_overhead_p50_ratio"] > 0
    # The on-leg's aggregator demonstrably scraped (>= one pass per
    # interleaved pair) and had real replica pairs to digest.
    assert out["cluster_obs_scrapes"] >= 1
    assert out["cluster_obs_digest_ranges"] > 0


def test_dedup_leg_emits_keys():
    """The content-addressed dedup leg (ISSUE 16) must land its keys
    in the artifact and pin the acceptance numbers that are
    deterministic at this scale: a duplicate put transfers ZERO
    payload bytes (dedup_hit_put_bytes == 0 — the HAVE verdicts'
    wire-bytes-saved delta covers the duplicate pass exactly), the
    MEASURED capacity multiplier is at least the PR-18 estimator's
    prediction and within 0.1 of it (same deterministic trace), and
    the dedup'd store packs strictly more users per GB than the
    ISTPU_DEDUP=0 denominator. The read p50 ratio is asserted only as
    sane here — CI noise is checked at the acceptance level."""
    env = _env(600)
    env["ISTPU_DEDUP_KEYS"] = "256"  # small: keep the test fast
    p = subprocess.run(
        [sys.executable, BENCH, "--dedup-leg", "0"], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 0, p.stderr[-400:]
    outs = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert outs, p.stdout[-400:]
    out = outs[-1]
    assert "dedup_error" not in out, out
    assert out["dedup_on_p50_read_us"] > 0
    assert out["dedup_off_p50_read_us"] > 0
    assert out["dedup_read_p50_ratio"] > 0
    # Zero-byte duplicate puts: the whole point of the hash-first path.
    assert out["dedup_dup_logical_bytes"] > 0
    assert out["dedup_hit_put_bytes"] == 0, out
    # Measured >= predicted, and the estimator cross-validates within
    # 0.1 on the deterministic trace (ISSUE 16 acceptance).
    assert out["dedup_capacity_multiplier"] >= out["dedup_estimator_ratio"]
    assert abs(out["dedup_capacity_multiplier"]
               - out["dedup_estimator_ratio"]) <= 0.1, out
    assert out["dedup_capacity_multiplier"] > 1.5
    # The capacity story: physical bytes shrank, users/GB grew.
    assert out["dedup_hits"] > 0
    assert out["dedup_bytes_saved"] > 0
    assert out["dedup_physical_bytes"] < out["dedup_physical_bytes_nodedup"]
    assert out["dedup_logical_bytes"] > out["dedup_physical_bytes"]
    assert out["users_per_gb"] > out["users_per_gb_nodedup"]


def test_device_leg_without_tpu_fails():
    """A device leg measures the chip or fails: on a CPU backend its
    child says what it found and exits non-zero (bench.py's parent then
    exits non-zero too, after the host legs have published) — no
    *_skipped marker, no CPU number under a device metric's name."""
    p = subprocess.run(
        [sys.executable, BENCH, "--engine-leg", "0"], env=_env(600),
        capture_output=True, text=True, timeout=180,
    )
    assert p.returncode != 0
    (out,) = _parse_artifacts(
        [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    )
    assert "needs a TPU" in out["engine_error"], out
    assert not any(k.endswith("_skipped") for k in out), out


def test_sigkill_mid_run_leaves_valid_artifact():
    p = subprocess.Popen(
        [sys.executable, BENCH], env=_env(3600),
        stdout=subprocess.PIPE, text=True, start_new_session=True,
    )
    # ONE reader owns p.stdout for its whole life (a second reader —
    # e.g. communicate() — would race the iterator's readahead buffer):
    # it collects every JSON line until EOF and flags when two
    # cumulative lines have landed, which is the mid-run moment we
    # KILL — the exact driver-timeout shape.
    lines = []
    two_seen = threading.Event()

    def reader():
        for ln in p.stdout:
            if ln.startswith("{"):
                lines.append(ln)
                if len(lines) >= 2:
                    two_seen.set()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    two_seen.wait(timeout=300)  # wedge-proof: kill fires regardless
    _killpg(p)
    t.join(timeout=60)  # EOF after the group kill ends the reader
    p.wait(timeout=60)
    outs = _parse_artifacts(lines)
    assert outs, "bench printed no parseable artifact before the kill"
    out = outs[-1]
    assert out["metric"] == "kv_put_get_4KBx4096_agg_throughput"
    assert out["value"] > 0  # primary metric survived the kill
