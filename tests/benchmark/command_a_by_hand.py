"""A hand-built window for the two per-layer metrics of the cell
command-a-plus-mixed12k (PR 42), the numbers worked from it by hand,
and the costs module's counts worked by hand. test_bench_command_a.py
checks the readers and the costs against them; tests/conftest.py hands
the table to test_bench_observations.py's table test, which runs every
metric of BENCHMARK.json and which a `model_config` PR may not edit (as
PRs 26, 31, 35 and 40: replicas4_by_hand.py, granite4h_by_hand.py,
smallthinker_by_hand.py, xing_by_hand.py). The next `benchmark` issue
moves all five tables into that test."""

import collections

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6
STEP, PREFILL = "istpu.engine.step", "istpu.model.prefill"


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


# The synthetic window of test_bench_observations.py is [100, 110) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # an admission before the window: not counted
    span(2, PREFILL, 99.5, 400.0, program="cold", tokens=12528,
         padded_tokens=12528, pairs_held=50_000, rows_computed=63_488),
    # a cold prompt of 12,528 tokens: 4 layers x one pass of 15,872 rows
    # (12,528 x 8 x 16 / 128 x 1.25 = 15,660 -> 31 tiles of 512)
    span(3, PREFILL, 104.0, 600.0, program="cold", tokens=12528,
         padded_tokens=12528, pairs_held=50_400, rows_computed=63_488),
    # a hit's suffix of 128 tokens through the dense form: 4 layers x
    # 128 x 16 held experts
    span(4, PREFILL, 106.0, 30.0, program="prefix", tokens=128,
         padded_tokens=128, restored_pages=86, pairs_held=520,
         rows_computed=8_192),
    # a program without the fields (a model that holds every expert)
    span(5, PREFILL, 107.0, 30.0, program="prefix", tokens=128,
         padded_tokens=128, restored_pages=86),
]
# engine counter deltas of the window: 10,000 real tokens x 8 chosen
# experts, of which 10,400 pairs fell on the 16 held (even routing:
# 10,000)
COUNTERS = {"moe_pairs_routed": 80_000, "moe_pairs_held": 10_400,
            "moe_rows_computed": 71_680}

BY_HAND = {
    # 13.0 % held against the even 16 of 128
    "moe_held_pair_skew": abs(100.0 * 10_400 / 80_000 - 12.5),   # 0.5
    "moe_held_rows_share": 100.0 * (50_400 + 520) / (63_488 + 8_192),
}

# The costs module by hand (tests/benchmark/test_bench_command_a.py), at
# the published widths as one of 8 chips' share of 4 layers.
ATTN = 4096 * 16384 * 2 + 4096 * 1024 * 2          # 142,606,336
EXPERT = 3 * 4096 * 4096                           # 50,331,648
SHARED = 4 * EXPERT                                # 201,326,592
ROUTER = 4096 * 128                                # 524,288
LAYER = ATTN + SHARED + ROUTER + 4096 + 16 * EXPERT  # 1,149,767,680
PARAMS = 4 * LAYER + 32768 * 4096 + 4096           # 4,733,292,544
