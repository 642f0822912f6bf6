"""A hand-built ring for the per-layer metric select_active_share
(PR 47) and the number worked from it by hand; tests/conftest.py hands
both to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `perf_opt` PR may not edit (as
glm_by_hand.py). The next `benchmark` issue moves the tables into that
test."""

from glm_by_hand import DECODE, STEP, span

# The synthetic window of test_bench_observations.py is [100, 110) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # a step before the window; inside it three sequences over the rung
    # of 4, two over 2, a landing with the counts of the step it lands
    # (one over 1), and a dispatch alone (its counts land later)
    span(2, DECODE, 99.5, 10.0, program="decode_fused",
         select_rows_active=8, select_rows_run=8),
    span(3, DECODE, 101.0, 10.0, program="decode_fused",
         select_rows_active=3, select_rows_run=4),
    span(4, DECODE, 105.0, 10.0, program="decode_fused",
         select_rows_active=2, select_rows_run=2),
    span(5, DECODE, 105.1, 2.0, program="land", dispatch_ns=0,
         select_rows_active=1, select_rows_run=1),
    span(6, DECODE, 105.2, 0.3, program="decode_fused"),
]
# 3 + 2 + 1 sequences over 4 + 2 + 1 slots
BY_HAND = {"select_active_share": 100.0 * 6 / 7}
