"""The nine readers of the gap between tokens by cause (PR 51): each
against a number worked by hand from a window's counters or a small
ring, and their entries in BENCHMARK.json."""

import pytest

from benchmark.lib import manifest
from benchmark.metrics import _gap_by_cause, gap_stalled_p50_ms
from infinistore_tpu.utils import profiling

# For test_bench_observations.py's table of every metric (through
# tests/conftest.py): the window's counters, the ring, and the numbers
# by hand. 4,000 gaps of 10 ms in the mean: 6 a step's, 0.5 + 1.5 + 1
# another request's admission's, 0.25 an offload's, which leaves 0.75;
# 180 of the gaps held a stall.
COUNTERS = {
    "gap_tokens": 4000, "gap_ns": 40_000_000_000,
    "gap_ns_step": 24_000_000_000, "gap_ns_admit_miss": 2_000_000_000,
    "gap_ns_admit_hit": 6_000_000_000,
    "gap_ns_admit_piece": 4_000_000_000, "gap_ns_offload": 1_000_000_000,
    "gaps_stalled": 180}
MS = 10 ** 6
_ids = iter(range(1, 100))


def land(engine, t0_s, dur_ms, waiting, **stall):
    """A decode span under which a plain step landed."""
    return profiling.Span(
        next(_ids), 0, "istpu.model.decode", int(t0_s * 1e9), dur_ms * MS,
        1, None, engine, dict(program="land", dispatch_ns=0,
                              waiting=waiting, **stall))


# The synthetic window is [100, 110) s; a span enters the ring as it
# ends. Engine 1: a land that ended at 99 s (the ring reaches back),
# one at 101.004, a STALLED one at 101.302 (298 ms behind, 4 slots
# waited), one at 101.312, a stalled one at 101.412 (100 ms, 2 slots).
# Engine 2, between them: one at 101.101, a stalled one at 101.351
# (250 ms behind its own engine's, 1 slot). By the slots that waited:
# 100, 100, 250, 298, 298, 298, 298 ms: the median is 298 (of the three
# intervals alone it would be 250).
RING = [
    land(1, 98.990, 10, 0),
    land(1, 101.000, 4, 3),
    land(2, 101.100, 1, 1),
    land(1, 101.300, 2, 4, stall_ns=290 * MS, stall_cause="admit_hit"),
    land(1, 101.310, 2, 4),
    land(2, 101.350, 1, 1, stall_ns=240 * MS, stall_cause="admit_miss"),
    land(1, 101.400, 12, 2, stall_ns=80 * MS, stall_cause="offload"),
]
BY_HAND = {
    "gap_engine_mean_ms": 10.0, "gap_step_ms": 6.0,
    "gap_admit_miss_ms": 0.5, "gap_admit_hit_ms": 1.5,
    "gap_admit_piece_ms": 1.0, "gap_offload_ms": 0.25,
    "gap_other_ms": 0.75, "gap_stalled_share": 4.5,
    "gap_stalled_p50_ms": 298.0}
PARTS = ("gap_step_ms", "gap_admit_miss_ms", "gap_admit_hit_ms",
         "gap_admit_piece_ms", "gap_offload_ms", "gap_other_ms")
ALL_CELLS = {
    "mistral7b-sessions", "mixtral8x7b-sessions", "mistral7b-unshared",
    "mistral7b-replicas4-sessions", "granite4h-micro-sessions4k",
    "smallthinker21b-sessions12k", "xing4-29b-docs32k",
    "command-a-plus-mixed12k", "glm-5.2-docs32k-answers",
    "keye-vl2-30b-a3b-docs32k-answers"}
CELLS = {**dict.fromkeys(BY_HAND, ALL_CELLS),
         "gap_admit_hit_ms": ALL_CELLS - {"mistral7b-unshared"},
         "gap_admit_piece_ms": {"xing4-29b-docs32k",
                                "glm-5.2-docs32k-answers",
                                "keye-vl2-30b-a3b-docs32k-answers"}}
LAYERS = {**dict.fromkeys(BY_HAND, "Scheduler and cache manager"),
          "gap_step_ms": "Model step",
          "gap_offload_ms": "Device and host transfer",
          "gap_other_ms": "HTTP edge"}


class Obs:
    window = (100.0, 110.0)

    def __init__(self, **counters):
        self.counters = counters


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_reader_against_the_number_worked_by_hand(name, monkeypatch):
    monkeypatch.setattr(profiling, "spans", lambda: RING)
    assert manifest.reader(name).read(Obs(**COUNTERS)) \
        == pytest.approx(BY_HAND[name], rel=1e-9)


def test_the_six_parts_sum_to_the_mean_gap_and_none_is_negative():
    obs = Obs(**COUNTERS)
    parts = [manifest.reader(name).read(obs) for name in PARTS]
    assert sum(parts) == pytest.approx(
        manifest.reader("gap_engine_mean_ms").read(obs), abs=1e-9)
    assert min(parts) >= 0
    # counters as a run's window gives them, nothing round
    obs = Obs(gap_tokens=30977, gap_ns=556_839_112_003,
              gap_ns_step=139_213_377_411, gap_ns_admit_miss=9_120_047_113,
              gap_ns_admit_hit=201_733_900_017,
              gap_ns_admit_piece=190_111_222_333, gap_ns_offload=0,
              gaps_stalled=1404)
    parts = [manifest.reader(name).read(obs) for name in PARTS]
    assert abs(sum(parts)
               - manifest.reader("gap_engine_mean_ms").read(obs)) < 1e-6
    assert manifest.reader("gap_offload_ms").read(obs) == 0.0


@pytest.mark.parametrize("name", sorted(set(BY_HAND)
                                        - {"gap_stalled_p50_ms"}))
def test_a_program_without_the_counters_says_nothing(name):
    """The parent commit: no `gap_tokens`, so no reader divides; a
    cause that never ran in a window with gaps reads 0.0."""
    read = manifest.reader(name).read
    assert read(Obs()) is None
    assert read(Obs(decode_steps=640, decoded_tokens=5120)) is None
    assert read(Obs(gap_tokens=0, gap_ns=0)) is None
    alone = read(Obs(gap_tokens=10, gap_ns=50 * MS, gap_ns_step=50 * MS))
    assert alone == {"gap_engine_mean_ms": 5.0, "gap_step_ms": 5.0}.get(
        name, 0.0)


def test_the_stalled_gap_is_read_engine_by_engine_and_in_the_window():
    value = gap_stalled_p50_ms.value
    assert value(Obs(), RING) == 298.0
    # a program whose lands carry no `waiting` (the parent commit), and
    # a window without a stall: nothing to read
    bare = [s._replace(fields={"program": "land", "dispatch_ns": 0})
            for s in RING]
    assert value(Obs(), bare) is None
    assert value(Obs(), [s for s in RING
                         if "stall_ns" not in s.fields]) is None
    # the first land of an engine has none before it to be apart from
    assert value(Obs(), RING[3:4]) is None
    # one engine alone: 298 ms four times, 100 ms twice
    assert value(Obs(), [s for s in RING if s.engine == 1]) == 298.0
    assert value(Obs(), [s for s in RING if s.engine == 2]) == 250.0
    # a stalled land that started before the window is not its own
    early = Obs()
    early.window = (101.305, 110.0)
    assert value(early, RING) == 100.0


def test_the_helper_divides_by_the_gaps():
    obs = Obs(**COUNTERS)
    assert _gap_by_cause.ms_per_token(obs, "gap_ns") == 10.0
    assert _gap_by_cause.ms_per_token(obs, "gap_ns_nothing") == 0.0
    assert _gap_by_cause.ms_per_token(Obs(), "gap_ns") is None
    assert _gap_by_cause.per_gap(obs, "gaps_stalled") == 0.045
    assert _gap_by_cause.other_ms(obs) == pytest.approx(0.75)
    assert _gap_by_cause.other_ms(Obs()) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_the_metric_is_in_the_manifest_on_its_cells(name):
    bench = manifest.load()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert set(entry["workloads"]) == CELLS[name]
    span = name == "gap_stalled_p50_ms"
    share = name == "gap_stalled_share"
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (
        "%" if share else "ms", "lower",
        "program_span" if span else "program_counter", LAYERS[name],
        "itl_mean_ms")


def test_the_nine_are_the_last_per_layer_entries_and_the_manifest_is_sound():
    bench = manifest.load()
    assert [m["name"] for m in bench["per_layer"][-9:]] == [
        "gap_engine_mean_ms", *PARTS, "gap_stalled_share",
        "gap_stalled_p50_ms"]
    assert manifest.check(bench) == []
