"""A hand-built window for the two per-layer metrics PR 53 adds for the
cell phi4-mini-flash-traces12k (`shared_kv_attn_roofline_share`,
`admit_rows_run_share`), the numbers worked from it by hand, and the
costs module's counts worked by hand. test_bench_phi4flash.py checks
the readers and the costs against them; tests/conftest.py hands the
table to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `model_config` PR may not edit (as
keye_by_hand.py). The next `benchmark` issue moves the tables into that
test."""

# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s: 50 decode steps of 16 active slots
# and 16 x 1,800 live tokens each.
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds: in 50 decode programs the seven
# cross layers' kernel calls took 0.1 s, 2 ms a step.
SCOPED = {("decode", ("attn.kernel.cross",)): (0.1, 50, 0.6)}
# The window's counters: the admission programs ran 53,000 token-layer
# rows of 100,000.
COUNTERS = {"stack_rows_run": 53_000, "stack_rows_all": 100_000}

# By hand, at the published widths, whole.
D, FF, C, N, R, V = 2560, 10240, 5120, 16, 160, 200_064
MLP = 3 * D * FF                                               # 78,643,200
NORMS = 4 * D                              # two LayerNorms, weight + bias
MAMBA = (D * 2 * C + C * (R + 2 * N) + R * C + C * D       # the four matmuls
         + C * 4 + C                                   # convolution, its bias
         + C + N * C + C)                              # dt_bias, A_log, D
CROSS = 2 * D * D + D + D + 128 + 4 * 64     # W_q, W_o, biases, norm, lambdas
ATTN = CROSS + 2 * D * 1280 + 2 * 1280                # ... W_k, W_v, biases
GMU = 2 * D * C
LAYERS = (9 * (MAMBA + MLP + NORMS), 9 * (ATTN + MLP + NORMS),
          7 * (GMU + MLP + NORMS), 7 * (CROSS + MLP + NORMS))
PARAMS = V * D + 2 * D + sum(LAYERS)                        # 3,852,562,944
# float32 leaves: 9 x (dt_bias, A_log, D) and 16 x four lambda vectors
F32 = 9 * (C + N * C + C) + 16 * 4 * 64
WEIGHT_BYTES = 2 * PARAMS + 2 * F32                         # 7,706,792,960
KV_TOKEN_LAYER = 2 * 20 * 64 * 2                  # 5,120 B: K and V, bf16
PAGE_ALL_LAYERS = 9 * 16 * KV_TOKEN_LAYER                      # 737,280 B
K_PAGE = 16 * 10 * 128 * 2                                      # 40,960 B
STATE = 9 * (N * C + 3 * C) * 4                              # 3,502,080 B
SNAPSHOT = 9 * 10 * K_PAGE                # a row rounded up to 10 K pages

# the seven cross layers read 16 x 1,800 live tokens of ONE layer's K
# and V each: 1,032,192,000 B, 1.2603 ms at 819 GB/s, of 2 ms
SHARED = 7 * 16 * 1800 * KV_TOKEN_LAYER
BY_HAND = {
    "shared_kv_attn_roofline_share":
        100.0 * (SHARED / 819e9) / (0.1 / 50),                 # 63.0154 %
    "admit_rows_run_share": 53.0,
}
