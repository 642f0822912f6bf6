"""A hand-built window for the five per-layer metrics of the cell
glm-5.2-docs32k-answers (PR 46), the numbers worked from it by hand,
and the costs module's counts worked by hand. test_bench_glm.py checks
the readers and the costs against them; tests/conftest.py hands the
table to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `model_config` PR may not edit (as
xing_by_hand.py and command_a_by_hand.py). The next `benchmark` issue
moves the tables into that test."""

import collections

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6
STEP, DECODE = "istpu.engine.step", "istpu.model.decode"
PREFILL = "istpu.model.prefill"


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s, every decode step at 16 active
# sequences with 16 x 1,800 live tokens between them.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # decode steps: one before the window, two inside, one a landing
    # (no rows of its own)
    span(2, DECODE, 99.5, 10.0, program="decode_fused",
         rows_selected=81_920, rows_live=900_000),
    span(3, DECODE, 101.0, 10.0, program="decode_fused",
         rows_selected=81_920, rows_live=1_000_000),
    span(4, DECODE, 105.0, 10.0, program="decode_fused",
         rows_selected=81_920, rows_live=1_048_000),
    span(5, DECODE, 105.1, 2.0, program="land", dispatch_ns=0),
    # admission programs in the traced seconds, one after
    span(20, PREFILL, 104.0, 300.0, program="cold", tokens=4096,
         padded_tokens=4096),
    span(21, PREFILL, 104.5, 500.0, program="prefix", tokens=4096,
         padded_tokens=4096, restored_pages=256),
    span(22, PREFILL, 106.0, 90.0, program="prefix", tokens=128,
         padded_tokens=128, restored_pages=1046),
    span(23, PREFILL, 109.0, 140.0, program="prefix", tokens=112,
         padded_tokens=112, restored_pages=1024),
]
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds, by (kind of program, scopes): in
# 50 decode steps the gathers and the attention over the selected rows
# took 0.15 s, the indexers 0.05 s, the top-k 0.04 s; in 3 admission
# programs the index scores took 0.20 s.
SCOPED = {
    ("decode", ("attn.kernel", "attn.gather")): (0.15, 50, 0.85),
    ("decode", ("attn.index",)): (0.05, 50, 0.85),
    ("decode", ("attn.topk",)): (0.04, 50, 0.85),
    ("prefill", ("attn.index",)): (0.20, 3, 0.41),
}

# By hand, at the published widths cut to 5 layers of which 2 own an
# indexer (ranks 2048 / 512, rope 64, 32 index heads x 128, index_topk
# 2,048, bf16):
#   a selected row: 512 + 64 = 576 values = 1,152 B a layer; 16
#   sequences with 28,800 live tokens between them read at the least
#   min(28,800, 16 x 2,048) = 28,800 rows a layer:
#   5 x 28,800 x 1,152 B = 165,888,000 B; a step's gathers and
#   attention took 0.15 s / 50 = 3 ms
#   an indexer: 2,048 x 4,096 + 6,144 x 128 + 6,144 x 32 + 256
#   = 9,371,904 parameters; a live token's index key 128 values:
#   2 x (28,800 x 128 + 9,371,904) x 2 B = 52,233,216 B; a step's
#   indexers took 0.05 s / 50 = 1 ms; its top-k 0.04 s / 50 = 0.8 ms
#   index scores of an admission: 2 layers x pairs x 2 x 32 x 128
#   = 16,384 x pairs
#     4,096 cold: pairs 4,096 x 4,097 / 2 = 8,390,656
#     4,096 over 256 pages (4,096 tokens): 4,096 x 4,096 + 8,390,656
#       = 25,167,872
#     128 over 1,046 pages (16,736 tokens): 128 x 16,736 + 8,256
#       = 2,150,464
#     together 35,708,992 pairs = 585,056,124,928 FLOPs in 0.20 s
#   rows read of rows live in the window's two decode steps:
#   163,840 of 2,048,000
BY_HAND = {
    "sparse_attn_roofline_share": 100.0 * (165_888_000 / 819e9) / 0.003,
    "index_score_roofline_share": 100.0 * (52_233_216 / 819e9) / 0.001,
    "index_topk_ms": 0.8,
    "index_prefill_mfu": 100.0 * 585_056_124_928 / 197e12 / 0.20,
    "sparse_attn_rows_share": 8.0,
}

# The costs module by hand (tests/benchmark/test_bench_glm.py), the
# arithmetic of ISSUE 46.
ATTN = (12_582_912 + 33_554_432 + 3_538_944 + 14_680_064 + 100_663_296
        + 2_560)                               # 165,022,208
INDEXER = 8_388_608 + 786_432 + 196_608 + 256  # 9,371,904
EXPERT = 3 * 6144 * 2048                       # 37,748,736
ROUTER = 6144 * 256 + 256                      # 1,573,120
DENSE_MLP = 3 * 6144 * 12288                   # 226,492,416
NORMS = 2 * 6144
EXPERT_LAYER = ATTN + NORMS + 17 * EXPERT + ROUTER      # 808,336,128
DENSE_LAYER = ATTN + NORMS + DENSE_MLP + INDEXER        # 400,898,816
HEAD = 2 * 19_360 * 6144 + 6144                # 237,901,824
PARAMS = HEAD + DENSE_LAYER + 3 * EXPERT_LAYER + (EXPERT_LAYER + INDEXER)
F32_PARAMS = 4 * ROUTER
LATENT_PAGE = 16 * 640 * 2                     # 20,480 B
INDEX_PAGE = 16 * 128 * 2                      # 4,096 B
