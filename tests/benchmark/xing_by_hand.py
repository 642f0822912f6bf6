"""A hand-built window for the four per-layer metrics of the cell
xing4-29b-docs32k (PR 40), and the numbers worked from it by hand, and
the costs module's counts worked by hand. test_bench_xing.py checks
the readers and the costs against them; tests/conftest.py hands the
table to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `model_config` PR may not edit (as
PR 26's, PR 31's and PR 35's: replicas4_by_hand.py,
granite4h_by_hand.py, smallthinker_by_hand.py). The next `benchmark`
issue moves all four tables into that test."""

import collections

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6
STEP, PIECE = "istpu.engine.step", "istpu.sched.admit_piece"
PREFILL = "istpu.model.prefill"


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s, every decode step at 16 active
# sequences with 16 x 1,800 live tokens between them.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # three pieces in the window, one before it
    span(2, PIECE, 99.5, 400.0, tokens=8192, prefix_pages=0, piece=1, of=5),
    span(3, PIECE, 104.0, 210.0, tokens=8192, prefix_pages=0, piece=1,
         of=3),
    span(4, PIECE, 104.5, 180.0, tokens=8192, prefix_pages=512, piece=2,
         of=3),
    span(5, PIECE, 109.0, 150.0, tokens=112, prefix_pages=1024, piece=3,
         of=3),
    # their program calls and a hit's in the traced seconds, one after
    span(20, PREFILL, 104.0, 200.0, parent=3, program="cold", tokens=8192,
         padded_tokens=8192),
    span(21, PREFILL, 104.5, 170.0, parent=4, program="prefix",
         tokens=8192, padded_tokens=8192, restored_pages=512),
    span(22, PREFILL, 106.0, 40.0, program="prefix", tokens=128,
         padded_tokens=128, restored_pages=1033),
    span(23, PREFILL, 109.0, 140.0, parent=5, program="prefix", tokens=112,
         padded_tokens=112, restored_pages=1024),
]
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds, by (kind of program, scopes): in
# 50 decode steps the latent kernel took 0.10 s; in 3 admission
# programs attention and its expansion took 0.30 s, the residual path
# 0.08 s.
SCOPED = {
    ("decode", ("attn.kernel",)): (0.10, 50, 0.85),
    ("prefill", ("attn.kernel", "attn.expand")): (0.30, 3, 0.41),
    ("prefill", ("hc.",)): (0.08, 3, 0.41),
}

# By hand, at the published widths cut to 6 layers (d 3584, 32 heads,
# ranks 768 / 512, head widths 128 + 64 / 128, 4 streams, bf16):
#   a cached token: 512 + 64 = 576 values = 1,152 B a layer
#   28,800 live tokens x 6 layers x 1,152 B = 199,065,600 B; a step's
#   kernels took 0.10 s / 50 = 2 ms
#   attention of an admission, a layer: 32 heads x pairs x 2 x (192 +
#   128) = 20,480 x pairs, and K, V of every row: (prefix + suffix) x 2
#   x 512 x 32 x 256 = 8,388,608 a row
#     8,192 cold: pairs 8,192 x 8,193 / 2 = 33,558,528;
#       6 x (687,278,653,440 + 68,719,476,736) = 4,535,988,781,056
#     8,192 over 512 pages: pairs 8,192 x 8,192 + 33,558,528
#       = 100,667,392; 6 x (2,061,668,188,160 + 137,438,953,472)
#       = 13,194,642,849,792
#     128 over 1,033 pages (16,528 tokens): pairs 128 x 16,528 + 8,256
#       = 2,123,840; 6 x (43,496,243,200 + 139,720,654,848)
#       = 1,099,301,388,288
#     together 18,829,933,019,136 FLOPs in 0.30 s
#   the residual path: (8,192 + 8,192 + 128) tokens x 12 sublayers x 12
#   stream reads and writes x 3,584 x 2 B = 17,043,554,304 B in 0.08 s
BY_HAND = {
    "latent_attn_roofline_share": 100.0 * (199_065_600 / 819e9) / 0.002,
    "latent_prefill_mfu": 100.0 * 18_829_933_019_136 / 197e12 / 0.30,
    "hc_mix_roofline_share": 100.0 * 17_043_554_304 / 819e9 / 0.08,
    "admit_piece_p50_ms": 180.0,
}

# The costs module by hand (tests/benchmark/test_bench_xing.py).
ATTN = 2_752_512 + 4_718_592 + 2_064_384 + 4_194_304 + 14_680_064 + 1_280
EXPERT = 3 * 3584 * 1024                       # 11,010,048
ROUTER = 3584 * 64 + 64                        # 229,440
HC_F32 = 14_336 * 24 + 24 + 3                  # 344,091 a sublayer
HC = HC_F32 + 14_336                           # 358,427
DENSE_MLP = 3 * 3584 * 9216                    # 99,090,432
SPARSE_LAYER = ATTN + 2 * 3584 + 2 * HC + 65 * EXPERT + ROUTER
DENSE_LAYER = ATTN + 2 * 3584 + 2 * HC + DENSE_MLP
PARAMS = 2 * 131_072 * 3584 + 3584 + DENSE_LAYER + 5 * SPARSE_LAYER
F32_PARAMS = 5 * ROUTER + 12 * HC_F32
