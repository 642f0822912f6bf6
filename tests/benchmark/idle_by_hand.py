"""The small recorded trace of benchmark/metrics/_idle_by_span.py
(benchmark/data/idle_by_span_trace.json: two engines on two chips,
device planes, the annotations, the runtime's launches and the ring),
and the numbers worked from it by hand. test_bench_idle_by_span.py
checks the join and the six readers (PR 38) against them;
tests/conftest.py hands them to test_bench_observations.py's table
test, which runs every metric of BENCHMARK.json and which this PR may
not edit (as replicas4_by_hand.py, granite4h_by_hand.py and
smallthinker_by_hand.py before it).

The timeline, in ms on the host's clock (the traced window is [1000,
5000), the synthetic window of test_bench_observations.py's [103.5,
107.5) s: the ring's clock is the trace's + 102.5 s; 40 spans are
recorded both ways, their annotations 0, 10 or 20 us to either side of
the ring's start, eight of each; plane 0's timestamps lie 1.5 ms early
and plane 1's 0.5 ms, each run enqueued 40 us before it starts and its
completion handled 40 us after it ends, so both skews are known to
40 us):

engine 1 on chip 0
  no_work     [-600, 2000)   open before the session: on the ring alone
  submit      [2000, 2000.2)
  ten plain decode steps of 20 ms, 0.5 ms apart, the first at 2001:
    step [S, S+20), istpu.model.decode [S+1, S+19.5) with its dispatch
    back at S+1.5; the program runs [S+2, S+18) in the first seven and
    [S+3, S+17) in the last three
  a step [2206, 2306) that finishes a sequence: offload [2207, 2297)
    with a gather program [2207.5, 2209.5), d2h [2208, 2218), allocate
    [2218, 2248) of 1,000 keys, write [2248, 2288) of 40 MB, sync
    [2288, 2296)
  no_work     [2306.5, 5200)
  (three ticks in the first spell: program runs without operations)
engine 2 on chip 1
  a step [950, 3040) with an admission [960, 2975) whose program runs
  [1000, 3000), and a sub-floor write [2978, 3040): allocate [2980,
  2985) of 100 keys, write [2985, 2990) of 10 MB, allocate [2996,
  3000) of 200 keys, write [3000, 3040) of 80 MB; nothing after it on
  the ring; the next program runs [3080, 5100)

Chip 0 is busy 7 x 16 + 3 x 14 + 2 = 156 ms of the 4,000, idle 3,844:
  no_work 1,000 + 2,693.5; submit 0.2; loop 0.8 + 10 x 0.5 + 0.5 = 6.3;
  a step's own time 10 x (1 + 0.5) + (1 + 9) = 25; decode:dispatch
  10 x 0.5 = 5; decode:wait 7 x (0.5 + 1.5) + 3 x (1.5 + 2.5) = 26;
  offload's own 0.5 + 1 = 1.5; d2h 10 - 1.5 = 8.5; allocate 30; write
  40; sync 8.
Chip 1 is idle [3000, 3080): 40 under the write, 40 under nothing.
Mean over the two planes: idle 1,962 ms.
"""

import collections
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")

with open(os.path.join(ROOT, "benchmark", "data",
                       "idle_by_span_trace.json")) as f:
    _recorded = json.load(f)
RING = [S(*s) for s in _recorded["ring"]]


def plain(names=None):
    """The recorded trace in _idle_by_span.read_plain's form; with
    `names`, only its `istpu.*` annotations of those names."""
    host = [e for e in _recorded["host"] if names is None
            or e[0] in names or not e[0].startswith("istpu.")]
    return {"devices": _recorded["devices"], "host": host,
            "launches": _recorded["launches"]}


# unix ns at which the traced window's span was left
CLOSED_NS = 107_500_000_000
CLOCK = {"offset_ns": 102_500_000_000, "quartile_distance_ns": 20_000,
         "pairs": 40,
         "device_skew": {
             "/device:TPU:0": {"skew_ns": 1_500_000, "halfwidth_ns": 40_000,
                               "runs": 14},
             "/device:TPU:1": {"skew_ns": 500_000, "halfwidth_ns": 40_000,
                               "runs": 2}}}
IDLE_S = (3.844 + 0.080) / 2
IDLE_BY = {  # seconds of the window, mean over the two planes
    "no_work": 3.6935 / 2,
    "istpu.store.write": (0.040 + 0.040) / 2,
    "unspanned": 0.040 / 2,
    "istpu.store.allocate": 0.030 / 2,
    "istpu.model.decode:wait": 0.026 / 2,
    "istpu.engine.step": 0.025 / 2,
    "istpu.xfer.d2h": 0.0085 / 2,
    "istpu.cache.offload_sync": 0.008 / 2,
    "loop": 0.0063 / 2,
    "istpu.model.decode:dispatch": 0.005 / 2,
    "istpu.cache.offload": 0.0015 / 2,
    "istpu.sched.submit": 0.0002 / 2,
}
LAG_P95_MS = 2.5
# Medians by nearest rank, as every p50 of the benchmark: the fifth of
# ten leads (seven of 2.0 ms, three of 3.0) and lags (seven of 1.5 ms,
# three of 2.5); the second of 20, 30 and 50 us a key; 130 MB in 85 ms.
BY_HAND = {
    "idle_no_work_share": 100.0 * (3.6935 / 2) / IDLE_S,
    "host_held_idle_share": 100.0 * (IDLE_S - 3.6935 / 2) / 4.0,
    "decode_dispatch_lead_p50_ms": 2.0,
    "decode_return_lag_p50_ms": 1.5,
    "store_allocate_us_per_key": 30.0,
    "store_write_gbps": 0.130 / 0.085,
}
