"""What the metric readers read, and every reader on a synthetic
window: each returns its number, and nothing when there is nothing."""

import collections
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


def test_the_mean_gap_weighs_a_stall_by_its_length_and_the_p95_does_not():
    """Twenty gaps, one of them a stall: the mean moves with the stall's
    length; the nearest-rank p95 (the 19th of 20) stays a plain step, and
    with two stalls it is a stall: the edge itl_p95_ms stood on."""
    def obs_with(stalls):
        times = [100.0]
        for i in range(20):
            times.append(times[-1] + (0.2 if i in stalls else 0.02))
        r = rec(99.9, 100.0)
        r["token_times"] = times
        return window([r])
    mean, tail = (manifest.reader(n).read for n in
                  ("itl_mean_ms", "itl_tail_p95_ms"))
    assert mean(obs_with(())) == pytest.approx(20.0)
    assert mean(obs_with((5,))) == pytest.approx((19 * 20 + 200) / 20)
    assert tail(obs_with((5,))) == pytest.approx(20.0)
    assert tail(obs_with((5, 9))) == pytest.approx(200.0)
    assert tail(obs_with((5,))) == manifest.reader("itl_p95_ms").read(
        obs_with((5,)))


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


# The program's own span ring (infinistore_tpu/utils/profiling.py), made
# by hand for the five readers that read it in-process.
S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


STEP, DECODE = "istpu.engine.step", "istpu.model.decode"
ADMIT, OFFLOAD = "istpu.sched.admit", "istpu.cache.offload"
# The synthetic window is [100, 110) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    span(2, "istpu.sched.queue_wait", 101.0, 5.0),
    span(3, "istpu.sched.queue_wait", 102.0, 9.0),
    span(4, "istpu.sched.queue_wait", 103.0, 7.0),
    span(5, ADMIT, 101.0, 380.0, outcome="admitted", hit_pages=80),
    span(6, ADMIT, 102.0, 440.0, outcome="admitted", hit_pages=120),
    span(7, ADMIT, 103.0, 400.0, outcome="admitted", hit_pages=90),
    span(8, ADMIT, 104.0, 62.0, outcome="admitted", hit_pages=0),
    span(9, ADMIT, 105.0, 70.0, outcome="admitted", hit_pages=0),
    span(10, ADMIT, 106.0, 60.0, outcome="admitted", hit_pages=0),
    span(11, ADMIT, 106.5, 0.2, outcome="no_pages", hit_pages=0),
    span(12, OFFLOAD, 107.0, 150.0, reason="finish"),
    span(13, OFFLOAD, 107.5, 120.0, reason="finish"),
    span(14, OFFLOAD, 108.0, 146.0, reason="finish"),
    span(15, OFFLOAD, 108.5, 3.0, reason="window"),
    span(20, STEP, 109.0, 50.0, kind="decode"),
    span(21, DECODE, 109.001, 48.6, parent=20),   # host 1.4 ms
    span(30, STEP, 109.1, 52.0, kind="decode"),
    span(31, DECODE, 109.101, 50.5, parent=30),   # host 1.5 ms
    span(40, STEP, 109.2, 51.0, kind="decode"),
    span(41, DECODE, 109.201, 49.8, parent=40),   # host 1.2 ms
    span(50, STEP, 109.3, 500.0, kind="decode"),  # holds an admission
    span(51, ADMIT, 109.3, 440.0, parent=50, outcome="refunded"),
    span(52, DECODE, 109.75, 50.0, parent=50),
]
# Medians by nearest rank, as every p50 of the benchmark.
BY_HAND = {"queue_wait_p50_ms": 7.0, "admit_hit_p50_ms": 400.0,
           "admit_miss_p50_ms": 62.0, "offload_stall_p50_ms": 146.0,
           "decode_host_p50_ms": 1.4}


def expected(obs):
    conf = obs.conf
    flops = costs.prefill_flops(conf, (80 + 1 - 73) * 16, 73 * 16) \
        + costs.prefill_flops(conf, 71 * 16, 0)
    least = costs.decode_bytes(conf, 16, 16 * 1800, 16) / 819e9
    return {
        "ttft_p50_ms": 50.0, "ttft_p95_ms": 300.0, "itl_p95_ms": 20.0,
        "itl_mean_ms": 20.0, "itl_tail_p95_ms": 20.0,
        "tokens_per_s": 40 * 8 / 10.0, "setup_s": 61.5,
        "ttft_hit_p50_ms": 50.0, "ttft_miss_p50_ms": 300.0,
        "prefix_hit_share": 100.0 * 4800 / 8000,
        "batch_occupancy": 75.0, "decode_step_ms": 12.5,
        "prefill_ms_per_ktok": 120.0 / 0.128,
        "decode_roofline_share": 100.0 * least / 0.0125,
        "prefill_mfu": 100.0 * flops / 197e12 / 0.120,
        "restore_gbps": 0.2 / 0.040, "offload_gbps": 0.04 / 0.040,
        "store_read_p99_us": 96.0, "store_write_p99_us": 24.0,
        **BY_HAND,
    }


BENCH = manifest.load()
ALL = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("name", ALL)
def test_reader_gives_the_number_worked_by_hand(name, monkeypatch):
    from infinistore_tpu.utils import profiling

    if name in BY_HAND:  # PR 26's three get theirs from tests/conftest.py
        monkeypatch.setattr(profiling, "spans", lambda: RING)
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


ACCEPTED = {c["name"]: c["file"] for c in BENCH["configs"]}


@pytest.mark.parametrize("config", sorted(ACCEPTED))
@pytest.mark.parametrize("what", ["costs", "decode", "prefill",
                                  "tolerances", "no_new_key"])
def test_an_accepted_configuration_resolves_to_todays_defaults(config, what):
    """`program.costs`, `.programs` and `.tolerances` are optional; a
    file without them (every accepted one) gets lib/costs.py, the
    engine's fused programs' names and the dense / moe rule."""
    from benchmark.lib import correct, serve

    conf = serve.load_config(ACCEPTED[config])
    if what == "costs":
        assert serve.costs_module(conf) is costs
        assert costs.snapshot_bytes(conf) == 0
        assert costs.store_block_bytes(conf, 16, 2) == 16 * 8 * 128 * 2
    elif what == "decode":
        assert serve.program_names(conf, "decode") == ["decode_fused"]
    elif what == "prefill":
        assert serve.program_names(conf, "prefill") == ["admit_fused",
                                                        "prefill_px"]
    elif what == "tolerances":
        family = "moe" if config == "mixtral8x7b" else "dense"
        assert correct.tolerances_for(conf) == correct.tolerances(family)
    else:
        assert sorted(conf["program"]) == ["bridge", "model", "reference"]


@pytest.mark.parametrize("programs,found", [
    ({"jit__decode_fused(1)": [0.01]}, True),
    ({"jit__admit_fused_px(2)": [0.01]}, False),
    ({"jit_decode_step(3)": [0.01]}, False),
])
def test_the_decode_reader_finds_the_default_name_only(programs, found):
    obs = full_window()
    obs.trace = dict(obs.trace, programs=programs)
    got = manifest.reader("decode_step_ms").read(obs)
    assert (got == pytest.approx(10.0)) if found else got is None
