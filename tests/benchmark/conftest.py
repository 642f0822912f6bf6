"""test_bench_observations.py checks every metric of BENCHMARK.json
against a number worked by hand on one synthetic window, from a table
inside that file. PR 24 (tracing) could add benchmark files and edit
none, so the hand-made ring and the hand-worked numbers of the five
metrics it added are given here, and handed to that one test. The next
`benchmark` issue moves them into the test's own table and deletes
this file."""

import collections

import pytest

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


STEP, DECODE = "istpu.engine.step", "istpu.model.decode"
ADMIT, OFFLOAD = "istpu.sched.admit", "istpu.cache.offload"
# The synthetic window of test_bench_observations.py is [100, 110) s.
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


@pytest.fixture(autouse=True)
def program_spans_worked_by_hand(request, monkeypatch):
    node = request.node
    if getattr(node, "originalname", None) != \
            "test_reader_gives_the_number_worked_by_hand" \
            or node.callspec.params.get("name") not in BY_HAND:
        return
    from infinistore_tpu.utils import profiling

    table = request.module.expected
    monkeypatch.setattr(request.module, "expected",
                        lambda obs: {**table(obs), **BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: RING)
