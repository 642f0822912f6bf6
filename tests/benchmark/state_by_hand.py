"""A hand-built ring for the per-layer metric state_active_share
(PR 52) and the number worked from it by hand; tests/conftest.py hands
both to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `perf_opt` PR may not edit (as
select_by_hand.py)."""

from glm_by_hand import DECODE, STEP, span

# The synthetic window of test_bench_observations.py is [100, 110) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # a step before the window; inside it a step that moved four slots'
    # state for three sequences (a form that runs a rung of slots would;
    # the tree's kernel runs the decoding ones alone), one that moved
    # two for two, a landing with the counts of the step it lands, and
    # a dispatch alone (its counts land later)
    span(2, DECODE, 99.5, 10.0, program="decode_fused",
         state_rows_active=16, state_rows_run=16),
    span(3, DECODE, 101.0, 10.0, program="decode_fused",
         state_rows_active=3, state_rows_run=4),
    span(4, DECODE, 105.0, 10.0, program="decode_fused",
         state_rows_active=2, state_rows_run=2),
    span(5, DECODE, 105.1, 2.0, program="land", dispatch_ns=0,
         state_rows_active=5, state_rows_run=5),
    span(6, DECODE, 105.2, 0.3, program="decode_fused"),
]
# 3 + 2 + 5 sequences over 4 + 2 + 5 slots
BY_HAND = {"state_active_share": 100.0 * 10 / 11}
