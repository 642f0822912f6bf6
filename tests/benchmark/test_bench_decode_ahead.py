"""decode_ahead_share: its value from a window's counters, and its
entry in BENCHMARK.json."""

import pytest

from benchmark.lib import manifest


# For test_bench_observations.py's table of every metric (through
# tests/conftest.py): the window's counters, and the number by hand.
COUNTERS = {"decode_steps": 640, "decode_steps_ahead": 592}
BY_HAND = {"decode_ahead_share": 92.5}


class Obs:
    def __init__(self, **counters):
        self.counters = counters


def test_the_share_of_the_steps_that_ran_ahead():
    read = manifest.reader("decode_ahead_share").read
    assert read(Obs(decode_steps=200, decode_steps_ahead=170)) \
        == pytest.approx(85.0)
    assert read(Obs(decode_steps=7, decode_steps_ahead=7)) == 100.0
    assert read(Obs(**COUNTERS)) == BY_HAND["decode_ahead_share"]
    # a program without the counter (the parent commit)
    assert read(Obs(decode_steps=12)) == 0.0


def test_no_steps_no_share():
    read = manifest.reader("decode_ahead_share").read
    assert read(Obs(decode_steps=0, decode_steps_ahead=0)) is None
    assert read(Obs()) is None


def test_every_cell_reports_it():
    bench = manifest.load()
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "decode_ahead_share"]
    assert set(entry["workloads"]) >= {
        "mistral7b-sessions", "mixtral8x7b-sessions", "mistral7b-unshared",
        "mistral7b-replicas4-sessions", "granite4h-micro-sessions4k",
        "smallthinker21b-sessions12k", "xing4-29b-docs32k",
        "command-a-plus-mixed12k"}
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "program_counter",
                                "Scheduler and cache manager", "itl_mean_ms")
    assert manifest.check(bench) == []
