"""A hand-built window for the per-layer metric this PR adds for the
cell keye-vl2-30b-a3b-docs32k-answers (PR 49), `sparse_prefill_mfu`,
the number worked from it by hand, and the costs module's counts worked
by hand. test_bench_keye.py checks the reader and the costs against
them; tests/conftest.py hands the table to test_bench_observations.py's
table test, which runs every metric of BENCHMARK.json and which a
`model_config` PR may not edit (as glm_by_hand.py). The next
`benchmark` issue moves the tables into that test."""

from glm_by_hand import PREFILL, RING as GLM_RING, STEP

# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s. glm_by_hand.py's admissions: three
# programs in the traced seconds (a cold piece of 4,096, a piece over
# 256 pages, a tail of 128 over 1,046 pages), one after; and the step
# that makes the ring reach back before the window.
RING = [s for s in GLM_RING if s.name in (STEP, PREFILL)]
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds: in 3 admission programs the
# attention under the selection, with its mask, took 0.25 s.
SCOPED = {
    ("prefill", ("attn.kernel", "attn.gather", "attn.mask")):
        (0.25, 3, 0.89),
}

# By hand, at the published widths cut to 5 layers.
ATTN = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 2 * 128     # 18,874,624
INDEXER = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16 + 2 * 64     # 2,261,120
EXPERT = 3 * 2048 * 768                                        # 4,718,592
ROUTER = 2048 * 128                                            # 262,144
NORMS = 2 * 2048
LAYER = ATTN + INDEXER + ROUTER + 128 * EXPERT + NORMS         # 625,381,760
PARAMS = 2 * 151_936 * 2048 + 2048 + 5 * LAYER                 # 3,749,240,704
KV_PAGE = 16 * 4 * 128 * 2                                     # 16,384 B
INDEX_PAGE = 16 * 128 * 2                                      # 4,096 B

# pairs the selection leaves: the cold piece's 4,096 queries see 1 ..
# 4,096 keys (2,048 under topk, then 2,048 rows each), the piece over
# 256 pages 2,048 rows each, the tail of 128 over 1,046 pages too
PAIRS = (2048 * 2049 // 2 + 2048 * 2048) + 4096 * 2048 + 128 * 2048
# ... at 4 x 128 FLOPs a query head a pair a layer, 32 heads, 5 layers
FLOPS = PAIRS * 32 * 4 * 128 * 5
BY_HAND = {
    "sparse_prefill_mfu": 100.0 * FLOPS / 197e12 / 0.25,      # 2.4856 %
}
