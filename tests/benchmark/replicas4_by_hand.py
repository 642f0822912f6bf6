"""A hand-built window for the three per-layer metrics of the cell
mistral7b-replicas4-sessions (PR 26), and the numbers worked from it by
hand. test_bench_replicas4.py checks the readers against them;
tests/conftest.py hands them to test_bench_observations.py's table
test, which runs every metric of BENCHMARK.json and which PR 26 could
not edit. The next `benchmark` issue moves them into that table."""

import collections

S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6
STEP, ADMIT = "istpu.engine.step", "istpu.sched.admit"
RESTORE = "istpu.cache.restore"


def span(i, name, t0_s, dur_ms, parent=0, engine=1, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), engine,
             None, engine, fields)


# The synthetic window of test_bench_observations.py is [100, 110) s.
RING = [
    span(1, STEP, 99.0, 50.0, kind="decode", device=0),  # reaches back
    # three cross-replica hits on three engines, restore inside each
    span(5, ADMIT, 101.0, 500.0, engine=1, outcome="admitted",
         hit_pages=80, foreign_pages=80),
    span(6, RESTORE, 101.01, 170.0, parent=5, engine=1, pages=80,
         foreign_pages=80),
    span(7, ADMIT, 102.0, 620.0, engine=2, outcome="admitted",
         hit_pages=120, foreign_pages=120),
    span(8, RESTORE, 102.01, 210.0, parent=7, engine=2, pages=120,
         foreign_pages=120),
    span(9, ADMIT, 109.9, 540.0, engine=3, outcome="admitted",
         hit_pages=90, foreign_pages=90),
    # ... whose restore began after the window's end, and still counts
    span(10, RESTORE, 110.01, 190.0, parent=9, engine=3, pages=90,
         foreign_pages=90),
    # a hit on the engine's own pages: the control's case, not this one
    span(11, ADMIT, 103.0, 400.0, engine=4, outcome="admitted",
         hit_pages=70, foreign_pages=0),
    span(12, RESTORE, 103.01, 150.0, parent=11, engine=4, pages=70,
         foreign_pages=0),
    # a miss, a refunded admission and one outside the window
    span(13, ADMIT, 104.0, 62.0, outcome="admitted", hit_pages=0,
         foreign_pages=0),
    span(14, ADMIT, 105.0, 900.0, outcome="refunded", hit_pages=50,
         foreign_pages=50),
    span(15, RESTORE, 105.01, 800.0, parent=14, pages=50,
         foreign_pages=50),
    span(16, ADMIT, 111.0, 900.0, outcome="admitted", hit_pages=10,
         foreign_pages=10),
    span(17, RESTORE, 111.01, 800.0, parent=16, pages=10,
         foreign_pages=10),
]
# test_bench_observations.full_window(): prefix_hit_pages 300,
# prefill_tokens 3200, pages of 16 tokens. Of the 300 hit pages 250
# foreign: 100 x 250 x 16 / (300 x 16 + 3200) = 50.
COUNTERS = {"foreign_hit_pages": 250}
# Medians by nearest rank: admissions 500, 540, 620; restores 170, 190,
# 210.
BY_HAND = {"xreplica_hit_share": 50.0,
           "xreplica_admit_hit_p50_ms": 540.0,
           "xreplica_restore_p50_ms": 190.0}
