"""A hand-built window for the four per-layer metrics PR 55 adds for the
cell evabyte-docs24k-bytes (`folded_attn_roofline_share`,
`fold_roofline_share`, `cache_rows_share`, `fold_p50_ms`), the numbers
worked from it by hand, and the costs module's counts worked by hand.
test_bench_evabyte.py checks the readers and the costs against them;
tests/conftest.py hands the table to test_bench_observations.py's table
test, which runs every metric of BENCHMARK.json and which a
`model_config` PR may not edit (as phi_by_hand.py). The next
`benchmark` issue moves the tables into that test."""

from glm_by_hand import DECODE, STEP, span

FOLD = "istpu.cache.fold"
# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # decode steps: one before the traced seconds, three inside (5
    # sequences of 2,400-3,700 rows: 14,000, 15,000 and 16,000 rows a
    # step), a landing without rows, one after
    span(2, DECODE, 101.0, 9.0, program="decode_fused",
         positions=90_000, cache_rows=13_000),
    span(3, DECODE, 104.0, 9.0, program="decode_fused",
         positions=100_000, cache_rows=14_000),
    span(4, DECODE, 105.0, 9.0, program="decode_fused",
         positions=101_000, cache_rows=15_000),
    span(5, DECODE, 105.1, 2.0, program="land", dispatch_ns=0),
    span(6, DECODE, 106.0, 9.0, program="decode_fused",
         positions=102_000, cache_rows=16_000),
    span(7, DECODE, 108.0, 9.0, program="decode_fused",
         positions=103_000, cache_rows=17_000),
    # folds: one before the window, three inside
    span(10, FOLD, 99.5, 0.9, slot=0, window=5, pages_in=128,
         pages_out=8, during="piece", bytes=427_819_008),
    span(11, FOLD, 102.0, 0.4, slot=1, window=6, pages_in=128,
         pages_out=8, during="decode", bytes=427_819_008),
    span(12, FOLD, 105.5, 0.6, slot=2, window=12, pages_in=128,
         pages_out=8, during="piece", bytes=427_819_008),
    span(13, FOLD, 109.0, 0.5, slot=1, window=7, pages_in=128,
         pages_out=8, during="decode", bytes=427_819_008),
]
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds: in 50 decode programs the paged
# kernel's 12 calls took 0.24 s, 4.8 ms a step; in 2 fold programs the
# gathers, the summaries and the scatters took 1.6 ms, 0.8 ms a fold.
SCOPED = {("decode", ("attn.kernel",)): (0.24, 50, 0.6)}
FOLDS = (0.0016, 2, 0.0017)
# The window's counters: the decode steps' tables held 18 M rows where
# their positions were 120 M, over the 12 layers.
COUNTERS = {"attn_rows_read": 18_000_000, "attn_positions_live": 120_000_000}

# By hand, at the published widths cut to 12 layers.
D, FF, H, HD, V, HEADS = 4096, 11008, 32, 128, 320, 8
ATTN = 4 * D * D + 2 * H * HD                                  # 67,117,056
MLP = 3 * D * FF                                               # 135,266,304
LAYER = ATTN + MLP + 2 * D                                     # 202,391,552
OUTSIDE = V * D + D * HEADS * V + D                            # 11,800,576
PARAMS = 12 * LAYER + OUTSIDE                               # 2,440,499,200
PARAMS_WHOLE = 32 * LAYER + OUTSIDE                         # 6,488,330,240
ROW = 2 * H * HD * 2                    # 16,384 B: K and V, 32 heads, bf16
K_PAGE = 16 * H * HD * 2                                       # 131,072 B
POOL_PAGE = 12 * 16 * ROW                                    # 3,145,728 B
FOLD_BYTES = (128 + 8) * POOL_PAGE                         # 427,819,008 B
POOLS = 2 * 12 * 2049 * K_PAGE                            # 6,445,596,672 B

# the median traced step held 15,000 rows: x 16,384 B x 12 layers =
# 2,949,120,000 B, 3.6009 ms at 819 GB/s, of 4.8 ms
BY_HAND = {
    "folded_attn_roofline_share":
        100.0 * (12 * 15_000 * ROW / 819e9) / (0.24 / 50),     # 75.018 %
    "fold_roofline_share":
        100.0 * (FOLD_BYTES / 819e9) / (0.0016 / 2),           # 65.296 %
    "cache_rows_share": 15.0,
    "fold_p50_ms": 0.5,
}
