"""The reduction from trace events to busy share, program times and
gap attribution: synthetic cases worked by hand, and a small recorded
trace with its known answers."""

import json
import os

import pytest

from benchmark.lib import trace

MS = 1_000_000


def synthetic():
    ops = [["fusion.1", 0 * MS, 10 * MS], ["fusion.2", 10 * MS, 5 * MS],
           ["copy.3", 30 * MS, 10 * MS], ["fusion.1", 35 * MS, 10 * MS],
           ["fusion.1", 90 * MS, 30 * MS]]     # runs past the window
    modules = [["jit__decode_fused(1)", 0, 15 * MS],
               ["jit__admit_fused(2)", 30 * MS, 15 * MS],
               ["jit__decode_fused(1)", 90 * MS, 30 * MS]]
    host = [[trace.WINDOW_SPAN, 0, 100 * MS],
            ["bench.step", 14 * MS, 20 * MS],
            ["bench.store.get_kv_pages", 16 * MS, 12 * MS],
            ["bench.step", 44 * MS, 30 * MS],
            ["bench.store.put_kv_pages", 46 * MS, 4 * MS]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_busy_is_the_union_clipped_to_the_window():
    r = trace.reduce(synthetic())
    # [0,15) + [30,45) + [90,100) = 40 ms of a 100 ms window
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["devices"] == 1


def test_top_ops_sum_by_name_inside_the_window():
    r = trace.reduce(synthetic())
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.030)   # 10 + 10 + 10
    assert ops["copy.3"] == pytest.approx(0.010)
    assert r["device_ops"][0][0] == "fusion.1"


def test_gaps_go_to_the_innermost_span_that_covers_them():
    gaps = dict(trace.reduce(synthetic())["idle_gaps"])
    # gap [15,30): get_kv_pages covers 12 of 15 ms -> innermost
    assert gaps["bench.store.get_kv_pages"] == pytest.approx(0.015)
    # gap [45,90): the step covers 29 of 45 ms, the put only 4
    assert gaps["bench.step"] == pytest.approx(0.045)
    assert sum(gaps.values()) == pytest.approx(0.060)


def test_a_gap_under_no_span_is_named_so():
    ev = synthetic()
    ev["host"] = ev["host"][:1]
    gaps = dict(trace.reduce(ev)["idle_gaps"])
    assert gaps == {"outside_any_bench_span": pytest.approx(0.060)}


def test_program_times_by_module_name():
    r = trace.reduce(synthetic())
    dec = trace.program_times(r, "decode_fused")
    assert sorted(dec) == pytest.approx([0.010, 0.015])
    assert trace.program_times(r, "admit_fused", "prefill_px") \
        == pytest.approx([0.015])
    assert trace.program_times(r, "nothing") == []


def test_busy_is_averaged_over_the_chips_that_ran():
    ev = synthetic()
    ev["devices"]["/device:TPU:1"] = {
        "ops": [["fusion.9", 0, 80 * MS]], "modules": []}
    ev["devices"]["/device:TPU:2"] = {"ops": [], "modules": []}
    r = trace.reduce(ev)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.040 + 0.080) / 2)


def test_without_the_window_span_the_device_events_bound_it():
    ev = synthetic()
    ev["host"] = []
    assert trace.window_of(ev) == (0, 120 * MS)


@pytest.mark.parametrize("intervals,want", [
    ([], []), ([(0, 5), (5, 9)], [[0, 9]]), ([(3, 4), (0, 1)], [[0, 1], [3, 4]]),
    ([(0, 10), (2, 3)], [[0, 10]]), ([(1, 1)], []),
])
def test_union(intervals, want):
    assert trace._union(intervals) == want


RECORDED = "benchmark/data/recorded_trace.json"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the tree")
def test_recorded_trace_reduces_to_its_known_answers():
    ev = trace.load_recorded(RECORDED)
    with open("benchmark/data/recorded_trace.expected.json") as f:
        want = json.load(f)
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(want["window_s"]) == 0.45
    # known answers from a 100 ns boolean timeline made apart from
    # lib/trace.py: they agree to within that resolution
    assert r["busy_s"] == pytest.approx(want["busy_s_by_100ns_timeline"],
                                        abs=5e-4)
    assert r["busy_s"] / r["window_s"] == pytest.approx(0.300, abs=2e-3)
    dec = trace.program_times(r, "decode_fused")
    assert len(dec) == want["decode_runs"] == 2
    assert sorted(dec)[len(dec) // 2] == pytest.approx(
        want["decode_median_s"])
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == set(want["idle_gaps_by_timeline"])
    for name, secs in want["idle_gaps_by_timeline"].items():
        assert gaps[name] == pytest.approx(secs, abs=5e-4)
    # the device idles under the offload's puts, not under the step
    assert gaps["bench.store.put_kv_pages"] > 10 * gaps["bench.step"]
    assert r["device_ops"][0][0] == want["top_op"]
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(0.45)


def test_op_names_are_cut_to_the_instruction():
    assert trace.short("%fusion.3 = (bf16[8]{0}) fusion(%p), kind=kLoop") \
        == "fusion.3"
    assert trace.short("copy-done") == "copy-done"
    assert len(trace.short("x" * 500)) == 80
