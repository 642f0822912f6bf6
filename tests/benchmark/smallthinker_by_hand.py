"""A hand-built window for the five per-layer metrics of the cell
smallthinker21b-sessions12k (PR 35), and the numbers worked from it by
hand. test_bench_smallthinker.py checks the readers against them;
tests/conftest.py hands them to test_bench_observations.py's table
test, which runs every metric of BENCHMARK.json and which a
`model_config` PR may not edit (as PR 26's three, replicas4_by_hand.py,
and PR 31's four, granite4h_by_hand.py). The next `benchmark` issue
moves all three tables into that test."""

import collections

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6
STEP, OFFLOAD = "istpu.engine.step", "istpu.cache.offload"
PREFILL = "istpu.model.prefill"


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s, every decode step at 16 active
# sequences with 16 x 1,800 live tokens between them.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # three batches of window pages shed in the window, one before it;
    # a finish offload, a sub-floor write at an admission and a
    # one-band model's release (no `slots`) are not such batches
    span(2, OFFLOAD, 99.5, 90.0, reason="window", pages=8, slots=1),
    span(3, OFFLOAD, 101.0, 6.0, reason="window", pages=8, slots=1),
    span(4, OFFLOAD, 103.0, 14.0, reason="window", pages=24, slots=3),
    span(5, OFFLOAD, 106.0, 9.0, reason="window", pages=16, slots=2),
    span(6, OFFLOAD, 104.0, 300.0, reason="finish", pages=780),
    span(7, OFFLOAD, 105.0, 70.0, reason="subfloor", pages=527),
    span(8, OFFLOAD, 107.0, 2.0, reason="window", pages=1),
    # a cold and a hit admission in the traced seconds, one after them
    span(20, PREFILL, 104.0, 300.0, program="cold", tokens=12400,
         padded_tokens=12400),
    span(21, PREFILL, 105.0, 40.0, program="prefix", tokens=128,
         padded_tokens=128, restored_pages=781),
    span(22, PREFILL, 108.0, 40.0, program="prefix", tokens=256,
         padded_tokens=256, restored_pages=405),
]
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds, by (kind of program, scopes): in
# 50 decode steps of 17 ms the banded layers' attention kernels took
# 0.10 s, the full layers' 0.05 s and the expert blocks 0.50 s; in 2
# admissions the expert blocks 0.15 s.
SCOPED = {
    ("decode", ("attn.kernel.window",)): (0.10, 50, 0.85),
    ("decode", ("attn.kernel.full",)): (0.05, 50, 0.85),
    ("decode", ("moe.",)): (0.50, 50, 0.85),
    ("prefill", ("moe.",)): (0.15, 2, 0.40),
}

# By hand, at the published widths (d 2560, 4 kv heads of 128, 8 layers
# of which 2 full and 6 banded, band 4096, 64 experts of 3 x 2560 x 768,
# 6 a token, bf16):
#   K and V of one layer: 2 x 4 x 128 x 2 B = 2,048 B a token
#   28,800 live tokens over 16 sequences are under 16 x 4,096, so the
#   banded layers read them all: 6 x 28,800 x 2,048 = 353,894,400 B;
#   a step's banded kernels took 0.10 s / 50 = 2 ms
#   the full layers: 2 x 28,800 x 2,048 = 117,964,800 B in 1 ms
#   an expert: 3 x 2560 x 768 = 5,898,240 parameters, 11,796,480 B;
#   16 tokens touch 64 x (1 - (58 / 64) ** 16) = 50.7608... of 64;
#   a router 2560 x 64 x 4 B = 655,360 B; 8 layers; in 10 ms
#   prefill: (12,400 + 128) tokens x 8 layers x 2 x (6 x 5,898,240
#     + 163,840) = 12,528 x 8 x 2 x 35,553,280 = 7,126,583,869,440
#     FLOPs in 0.15 s of expert-block operations
TOUCHED = 64 * (1 - (58 / 64) ** 16)
BY_HAND = {
    "window_attn_roofline_share": 100.0 * (353_894_400 / 819e9) / 0.002,
    "full_attn_roofline_share": 100.0 * (117_964_800 / 819e9) / 0.001,
    "moe_step_roofline_share": 100.0 * (
        8 * (TOUCHED * 11_796_480 + 655_360) / 819e9) / 0.010,
    "moe_prefill_mfu": 100.0 * 7_126_583_869_440 / 197e12 / 0.15,
    "window_release_p50_ms": 9.0,
}
