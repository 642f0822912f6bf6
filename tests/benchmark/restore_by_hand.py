"""A hand-built ring for the per-layer metric restore_staged_share
(PR 56) and the number worked from it by hand; tests/conftest.py hands
both to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `perf_opt` PR may not edit (as
state_by_hand.py)."""

from glm_by_hand import STEP, span

ADMIT = "istpu.sched.admit"

# The synthetic window of test_bench_observations.py is [100, 110) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # a hit before the window; inside it a hit whose pages were in HBM
    # when its admission looked (staged in 48 ms, nothing waited for),
    # a hit that arrived at an empty engine (staged in 50 ms, 40 of
    # them waited for inside the admission), one whose wait of 12 ms
    # holds the hand-over between the threads beside a staging of 10
    # (counted to the staging's length), a hit whose admission made
    # the store call itself (nothing staged, nothing waited for), a
    # miss that waited for its probe (no hit: not counted) and a hit
    # that came back for want of pages (not admitted: not counted)
    span(2, ADMIT, 99.5, 70.0, outcome="admitted", hit_pages=96,
         staged_ns=60_000_000, staged_wait_ns=60_000_000),
    span(3, ADMIT, 101.0, 62.0, outcome="admitted", hit_pages=128,
         staged_ns=48_000_000, staged_wait_ns=0),
    span(4, ADMIT, 104.0, 105.0, outcome="admitted", hit_pages=160,
         staged_ns=50_000_000, staged_wait_ns=40_000_000),
    span(8, ADMIT, 105.0, 30.0, outcome="admitted", hit_pages=16,
         staged_ns=10_000_000, staged_wait_ns=12_000_000),
    span(5, ADMIT, 106.0, 110.0, outcome="admitted", hit_pages=96,
         staged_ns=0, staged_wait_ns=0),
    span(6, ADMIT, 107.0, 5.0, outcome="admitted", hit_pages=0,
         staged_ns=0, staged_wait_ns=2_000_000),
    span(7, ADMIT, 108.0, 1.0, outcome="no_pages", hit_pages=0,
         staged_ns=0, staged_wait_ns=0),
]
# 40 + 10 ms waited for of 48 + 50 + 10 ms staged
BY_HAND = {"restore_staged_share": 100.0 * (1 - 50 / 108)}
