"""The readers of the program's own spans (benchmark/lib/
program_spans.py and the five metrics built on it) on hand-made rings,
against a program without a recorder, and through the one command."""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest, program_spans
from benchmark.lib.cell import Observations
from benchmark.metrics import (
    admit_hit_p50_ms, admit_miss_p50_ms, decode_host_p50_ms,
    offload_stall_p50_ms, queue_wait_p50_ms,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = {"queue_wait_p50_ms": ["mixtral8x7b-sessions"],
       "admit_hit_p50_ms": ["mistral7b-sessions", "mixtral8x7b-sessions"],
       "admit_miss_p50_ms": None, "offload_stall_p50_ms": None,
       "decode_host_p50_ms": None}
S = collections.namedtuple(
    "S", "id parent name t0_ns dur_ns tid request engine fields")
MS = 10 ** 6


def span(i, name, t0_s, dur_ms, parent=0, **fields):
    return S(i, parent, name, int(t0_s * 1e9), int(dur_ms * MS), 1, None,
             1, fields)


def window(w0=100.0, w1=110.0):
    obs = Observations()
    obs.window = (w0, w1)
    return obs


OLD = span(1, "istpu.engine.step", 90.0, 20.0, kind="decode")


def test_window_edges_are_start_inclusive_end_exclusive():
    ring = [OLD] + [span(10 + i, "istpu.sched.queue_wait", t, 5.0 + i)
                    for i, t in enumerate((99.999, 100.0, 105.0, 109.999,
                                           110.0, 111.0))]
    obs = window()
    got = program_spans.started_in_window(obs, ring,
                                          "istpu.sched.queue_wait")
    assert [s.id for s in got] == [11, 12, 13]
    # the median of 6, 7, 8 ms
    assert queue_wait_p50_ms.value(obs, ring) == pytest.approx(7.0)
    # a span that started inside counts whole, however late it ended
    late = ring + [span(30, "istpu.sched.queue_wait", 109.0, 9000.0)]
    assert queue_wait_p50_ms.value(obs, late) == pytest.approx(7.0)


@pytest.mark.parametrize("reader", [
    queue_wait_p50_ms, admit_hit_p50_ms, admit_miss_p50_ms,
    offload_stall_p50_ms, decode_host_p50_ms])
def test_an_empty_window_gives_none(reader):
    ring = [OLD, span(2, "istpu.sched.admit", 95.0, 3.0,
                      outcome="admitted", hit_pages=2),
            span(3, "istpu.cache.offload", 120.0, 3.0, reason="finish")]
    assert reader.value(window(), ring) is None


def test_a_wrapped_or_missing_ring_gives_none_and_says_so(capsys):
    obs = window()
    inside = span(5, "istpu.sched.queue_wait", 101.0, 1.0)
    # reaches back: its first record ENDED before the window began
    assert program_spans.ring(obs, [OLD, inside]) == [OLD, inside]
    assert capsys.readouterr().out == ""
    # wrapped: the oldest record left started after the window did
    assert program_spans.ring(obs, [inside]) is None
    assert "does not reach back" in capsys.readouterr().out
    # ... or started before it and ended inside: what the ring dropped
    # ended earlier still, but may have started inside
    straddles = span(6, "istpu.http.request", 99.0, 5000.0)
    assert program_spans.ring(obs, [straddles, inside]) is None
    assert program_spans.ring(obs, []) is None
    out = capsys.readouterr().out
    assert out.count("does not reach back") == 2


def test_a_program_without_a_recorder_gives_none(monkeypatch, capsys):
    """The parent commit has utils/profiling.py and no spans() in it:
    the readers must report nothing there, and raise nothing."""
    from infinistore_tpu.utils import profiling

    monkeypatch.delattr(profiling, "spans")
    for reader in (queue_wait_p50_ms, admit_hit_p50_ms, admit_miss_p50_ms,
                   offload_stall_p50_ms, decode_host_p50_ms):
        assert reader.read(window()) is None
    assert capsys.readouterr().out.count("records none") == 5


def test_the_live_ring_is_read_through_the_program(monkeypatch):
    from infinistore_tpu.utils import profiling

    ring = [OLD, span(7, "istpu.sched.queue_wait", 103.0, 12.5)]
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    assert queue_wait_p50_ms.read(window()) == pytest.approx(12.5)


def test_admissions_split_by_hit_and_outcome():
    ring = [OLD,
            span(2, "istpu.sched.admit", 101.0, 30.0, outcome="admitted",
                 hit_pages=0),
            span(3, "istpu.sched.admit", 102.0, 50.0, outcome="admitted",
                 hit_pages=0),
            span(4, "istpu.sched.admit", 103.0, 400.0, outcome="admitted",
                 hit_pages=120),
            span(5, "istpu.sched.admit", 104.0, 0.2, outcome="no_pages",
                 hit_pages=0),
            span(6, "istpu.sched.admit", 105.0, 0.3, outcome="refunded",
                 hit_pages=0),
            span(7, "istpu.sched.admit", 99.0, 7.0, outcome="admitted",
                 hit_pages=0)]
    obs = window()
    assert admit_miss_p50_ms.value(obs, ring) == pytest.approx(30.0)
    assert admit_hit_p50_ms.value(obs, ring) == pytest.approx(400.0)


def test_offload_stall_reads_finishes_only():
    ring = [OLD,
            span(2, "istpu.cache.offload", 101.0, 200.0, reason="finish"),
            span(3, "istpu.cache.offload", 102.0, 300.0, reason="finish"),
            span(4, "istpu.cache.offload", 103.0, 1.0, reason="window"),
            span(5, "istpu.cache.offload", 104.0, 2.0, reason="preempt"),
            span(6, "istpu.cache.offload_sync", 104.0, 900.0, parent=5)]
    assert offload_stall_p50_ms.value(window(), ring) == pytest.approx(200.0)


def test_decode_host_is_the_step_less_its_program_in_plain_steps():
    step = "istpu.engine.step"
    ring = [OLD,
            # plain decode steps: 50 - 48, 60 - 57, 40 - 36 ms of host
            span(10, step, 101.0, 50.0, kind="decode"),
            span(11, "istpu.model.decode", 101.001, 48.0, parent=10),
            span(20, step, 102.0, 60.0, kind="decode"),
            span(21, "istpu.model.decode", 102.001, 57.0, parent=20),
            span(30, step, 103.0, 40.0, kind="decode"),
            span(31, "istpu.model.decode", 103.001, 36.0, parent=30),
            # a wait that ended in the step is not work done in it
            span(32, "istpu.sched.queue_wait", 90.0, 13000.0, parent=30),
            # steps with an admission or an offload are not plain
            span(40, step, 104.0, 500.0, kind="decode"),
            span(41, "istpu.sched.admit", 104.0, 400.0, parent=40),
            span(42, "istpu.model.decode", 104.4, 50.0, parent=40),
            span(50, step, 105.0, 300.0, kind="decode"),
            span(51, "istpu.model.decode", 105.0, 50.0, parent=50),
            span(52, "istpu.cache.offload", 105.06, 200.0, parent=50),
            # other kinds, and steps outside the window, do not count
            span(60, step, 106.0, 90.0, kind="burst"),
            span(61, "istpu.model.decode", 106.0, 10.0, parent=60),
            span(70, step, 106.5, 1.0, kind="idle"),
            span(80, step, 110.5, 77.0, kind="decode"),
            # a step that began inside the window counts with a child
            # that began after its end
            span(90, step, 109.99, 50.0, kind="decode"),
            span(91, "istpu.model.decode", 110.001, 45.0, parent=90)]
    # host times 2, 3, 4 and 5 ms: nearest-rank median 3
    assert decode_host_p50_ms.value(window(), ring) == pytest.approx(3.0)


def test_the_new_metrics_are_in_the_manifest_and_it_is_sound():
    bench = manifest.load()
    assert manifest.check(bench) == []
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, cells in NEW.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "program_span")
        assert m.get("workloads") == cells
    # appended, in the order ISSUE 24 gives, after what was there
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)


def cells_of(name):
    bench = manifest.load()
    return NEW[name] or [w["name"] for w in bench["workloads"]]


@pytest.mark.parametrize("workload", [
    "mistral7b-sessions", "mixtral8x7b-sessions", "mistral7b-unshared"])
def test_the_traced_rehearsal_prints_the_new_metrics(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(2 ** 31 + 24),
         "--seconds", "4", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    want = {n for n in NEW if workload in cells_of(n)}
    assert want <= set(res["metrics"])
    assert not (set(NEW) - want) & set(res["metrics"])
    for n in want:
        assert res["metrics"][n] == {
            "value": pytest.approx(res["metrics"][n]["value"]),
            "unit": "ms"}
        assert res["metrics"][n]["value"] > 0
    # the program's counters of this PR ride the window line
    window_line = next(ln for ln in r.stdout.splitlines()
                       if ln.startswith("window: "))
    counters = json.loads(window_line[len("window: "):])["counters"]
    assert counters["compilations"] == 0 and "admit_retries" in counters
    # the correct: line still compares logits
    check = next(ln for ln in r.stdout.splitlines()
                 if ln.startswith("correct: "))
    assert json.loads(check[len("correct: "):])["logit_checked"] > 0
    assert "entry points are gone" not in r.stdout
