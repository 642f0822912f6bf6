"""What the metric readers read, and every reader on a synthetic
window: each returns its number, and nothing when there is nothing."""

import json

import pytest

from benchmark.lib import costs, manifest
from benchmark.lib.cell import Observations, _hist_delta
from benchmark.lib.serve import Step
from benchmark.lib.store import Span


def rec(due, first=None, n=4, gap=0.01, want=4, error=None, turn=1,
        ended=True):
    times = [] if first is None else [first + i * gap for i in range(n)]
    return {"due": due, "sent": due + 0.001, "token_times": times,
            "want_tokens": want, "error": error, "turn": turn,
            "ended": ended, "done": times[-1] if times else None}


def window(records, drain=5.0):
    obs = Observations()
    obs.window, obs.seconds, obs.drain_s = (100.0, 110.0), 10.0, drain
    obs.records = records
    with open("benchmark/configs/mistral7b.json") as f:
        obs.conf = json.load(f)
    obs.max_slots = 16
    return obs


def test_ttft_is_taken_from_due_and_only_in_the_window():
    obs = window([rec(99.0, 99.5), rec(100.0, 100.25), rec(109.9, 111.0),
                  rec(110.0, 110.1)])
    assert obs.ttfts_ms() == pytest.approx([250.0, 1100.0])
    assert len(obs.due_in_window()) == 2


@pytest.mark.parametrize("r,failed", [
    (rec(101, 101.1), False),
    (rec(101, error="ConnectionRefusedError"), True),
    (rec(101, 101.1, n=2), True),            # short answer
    (rec(101, 101.1, n=2, ended=False), False),  # still streaming at cut
    (rec(101, ended=False), True),           # never got a token, drain on
])
def test_failed(r, failed):
    assert window([r]).failed(r) is failed


def test_a_request_still_queued_at_the_cut_is_not_failed_without_drain():
    r = rec(101, ended=False)
    assert window([r], drain=0.0).failed(r) is False


def test_a_failed_request_ranks_last_in_the_tail():
    obs = window([rec(101, 101.2), rec(102, error="refused"),
                  rec(103, 103.1)])
    assert sorted(obs.ttfts_ms()) == pytest.approx(
        [100.0, 200.0, (115.0 - 102) * 1e3])


def test_gaps_and_tokens_count_what_ends_in_the_window():
    obs = window([rec(99.9, 99.99, n=4, gap=0.01),   # tokens .99 1.00 ..
                  rec(109.0, 109.98, n=4, gap=0.01)])
    # first request: gaps ending at 100.00, 100.01, 100.02 are in
    # second: gap ending 109.99 in; 110.00 and 110.01 out
    assert len(obs.gaps_ms()) == 4
    assert obs.tokens_in_window() == 3 + 2
    assert obs.gaps_ms()[0] == pytest.approx(10.0)


def test_hist_delta_subtracts_per_op():
    before = {"op_stats": {"PIN": {"hist": [1, 2, 0]}}}
    after = {"op_stats": {"PIN": {"hist": [1, 5, 1]},
                          "COMMIT": {"hist": [0, 3, 0]}}}
    assert _hist_delta(after, before) == {"PIN": [0, 3, 1],
                                          "COMMIT": [0, 3, 0]}


def full_window():
    recs = []
    for i in range(40):
        turn = 1 + i % 3
        first = 100.2 + i * 0.2 + (0.3 if turn == 1 else 0.05)
        recs.append(rec(100.2 + i * 0.2, first, n=8, gap=0.02, want=8,
                        turn=turn))
    obs = window(recs)
    obs.setup_s = 61.5
    obs.chips = 1
    obs.counters = {"prefix_hit_pages": 300, "prefill_tokens": 3200,
                    "decode_steps": 100, "decoded_tokens": 1200}
    obs.spans = [
        Span("get_kv_pages", 101.0, 0.010, 50_000_000, 100, None),
        Span("get_kv_pages", 102.0, 0.030, 150_000_000, 300, None),
        Span("put_kv_pages", 103.0, 0.020, 40_000_000, 10, None),
        Span("sync", 103.1, 0.020, 0, 0, None),
        Span("probe", 104.0, 0.001, 0, 80, 73),
        Span("probe", 104.5, 0.001, 0, 70, 0),
    ]
    obs.trace_window = (103.5, 107.5)
    obs.steps = [Step(104.0 + i * 0.02, 0.015, 16, 16 * 1800, {
        "prefill_tokens": 128 if i == 0 else 0, "decoded_tokens": 16,
        "decode_steps": 1}) for i in range(50)]
    obs.store_hist = {"PIN": [0, 0, 0, 98, 0, 0, 2] + [0] * 13,
                      "COMMIT": [0, 0, 0, 0, 100] + [0] * 15}
    obs.trace = {"busy_s": 3.0, "window_s": 4.0, "programs": {
        "jit__decode_fused(123)": [0.0125] * 50,
        "jit__admit_fused(7)": [0.100],
        "jit__prefill_px_jit(9)": [0.020]}}
    obs.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return obs


def expected(obs):
    conf = obs.conf
    flops = costs.prefill_flops(conf, (80 + 1 - 73) * 16, 73 * 16) \
        + costs.prefill_flops(conf, 71 * 16, 0)
    least = costs.decode_bytes(conf, 16, 16 * 1800, 16) / 819e9
    return {
        "ttft_p50_ms": 50.0, "ttft_p95_ms": 300.0, "itl_p95_ms": 20.0,
        "tokens_per_s": 40 * 8 / 10.0, "setup_s": 61.5,
        "ttft_hit_p50_ms": 50.0, "ttft_miss_p50_ms": 300.0,
        "prefix_hit_share": 100.0 * 4800 / 8000,
        "batch_occupancy": 75.0, "decode_step_ms": 12.5,
        "prefill_ms_per_ktok": 120.0 / 0.128,
        "decode_roofline_share": 100.0 * least / 0.0125,
        "prefill_mfu": 100.0 * flops / 197e12 / 0.120,
        "restore_gbps": 0.2 / 0.040, "offload_gbps": 0.04 / 0.040,
        "store_read_p99_us": 96.0, "store_write_p99_us": 24.0,
    }


BENCH = manifest.load()
ALL = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", ALL)
def test_reader_gives_the_number_worked_by_hand(name):
    obs = full_window()
    assert manifest.reader(name).read(obs) == pytest.approx(
        expected(obs)[name], rel=1e-6)


@pytest.mark.parametrize("name", ALL)
def test_reader_with_nothing_to_read_returns_nothing(name):
    obs = window([])
    assert manifest.reader(name).read(obs) is None


@pytest.mark.parametrize("name", [
    "decode_roofline_share", "prefill_mfu"])
def test_shares_stay_under_100_on_the_synthetic_window(name):
    assert 0 < manifest.reader(name).read(full_window()) < 100
