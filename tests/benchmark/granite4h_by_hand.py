"""A hand-built window for the four per-layer metrics of the cell
granite4h-micro-sessions4k (PR 31), and the numbers worked from it by
hand. test_bench_granite4h.py checks the readers against them;
tests/conftest.py hands them to test_bench_observations.py's table
test, which runs every metric of BENCHMARK.json and which a
`model_config` PR may not edit (as PR 26's three, replicas4_by_hand.py).
The next `benchmark` issue moves them into that table."""

import collections

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6
STEP, OFFLOAD = "istpu.engine.step", "istpu.cache.offload"
IN, OUT = "istpu.cache.state_in", "istpu.cache.state_out"
PREFILL = "istpu.model.prefill"


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


# The synthetic window of test_bench_observations.py is [100, 110) s,
# its traced part [103.5, 107.5) s, every decode step at 16 active.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode"),  # the ring reaches back
    # three hits' snapshots on their way in; one before the window
    span(2, IN, 99.5, 500.0, bytes=77856768),
    span(3, IN, 101.0, 60.0, bytes=77856768),
    span(4, IN, 102.0, 45.0, bytes=77856768),
    span(5, IN, 105.0, 80.0, bytes=77856768),
    # three finish offloads with the snapshot's part inside, and a
    # preemption's, which is not a finish
    span(10, OFFLOAD, 103.0, 140.0, reason="finish"),
    span(11, OUT, 103.03, 100.0, parent=10, bytes=77856768),
    span(12, OFFLOAD, 104.0, 120.0, reason="finish"),
    span(13, OUT, 104.02, 90.0, parent=12, bytes=77856768),
    span(14, OFFLOAD, 106.0, 170.0, reason="finish"),
    span(15, OUT, 106.04, 120.0, parent=14, bytes=77856768),
    span(16, OFFLOAD, 107.0, 30.0, reason="preempt"),
    span(17, OUT, 107.01, 10.0, parent=16, bytes=77856768),
    # a cold and a hit admission in the traced seconds, one after them
    span(20, PREFILL, 104.0, 400.0, program="cold", tokens=4336,
         padded_tokens=4336, chunks=17),
    span(21, PREFILL, 105.0, 40.0, program="prefix", tokens=160,
         padded_tokens=160, chunks=1),
    span(22, PREFILL, 108.0, 40.0, program="prefix", tokens=352,
         padded_tokens=352, chunks=2),
]
# (device seconds of the scoped operations, program runs, seconds of
# those runs) in the traced seconds: in 50 decode steps of 17 ms the
# stages that are not the mixers took 0.45 s; in 2 admissions the scans
# 0.05 s.
SCOPED = {"decode": (0.45, 50, 0.85), "prefill": (0.05, 2, 0.26)}

# By hand, at the published widths (d 2048, H 64, P 64, N 128, K 4,
# 36 state layers, chunk 256, state float32, weights bf16):
#   a sequence's state: 36 x (64 x 64 x 128 + 3 x 4352) x 4 B
#     = 36 x 537,344 x 4 = 77,377,536 B
#   a mixer's weights: in_proj 2048 x 8512 + out_proj 4096 x 2048
#     + conv 4352 x 5 + A_log, dt_bias, D 3 x 64 + norm 4096
#     = 25,847,232 parameters, x 36 x 2 B = 1,861,000,704 B
#   step bytes at 16 active: 2 x 16 x 77,377,536 + 1,861,000,704
#     = 4,337,081,856 B; / 819e9 B/s = 5.29558 ms; a step's mixers took
#     (0.85 - 0.45) s / 50 = 8 ms
#   scan FLOPs a layer: a chunk of q adds q (q + 1) (128 + 4096)
#     + 4 q x 128 x 4096 + 2 x 4096 x 128;
#     q = 256: 815,824,896; q = 240: 748,681,216; q = 160: 445,403,136
#     4,336 tokens = 16 x 256 + 240: 13,801,879,552 a layer
#     (4,336) + (160) over 36 layers: 36 x 14,247,282,688
#     = 512,902,176,768 FLOPs in 0.05 s of scan operations
BY_HAND = {
    "snapshot_restore_p50_ms": 60.0,
    "snapshot_offload_p50_ms": 100.0,
    "ssm_step_roofline_share": 100.0 * (4_337_081_856 / 819e9) / 0.008,
    "ssm_scan_mfu": 100.0 * 512_902_176_768 / 197e12 / 0.05,
}
