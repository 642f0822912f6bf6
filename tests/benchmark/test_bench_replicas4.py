"""The cell mistral7b-replicas4-sessions (PR 26): its entries in the
manifest, its configuration against its control's, its three per-layer
readers on a hand-built window, and its rehearsal end to end on four
forced host devices - through run.py and through the chip tool
benchmark/tools/replicas_check.py."""

import json
import os
import subprocess
import sys
import types

import pytest
import replicas4_by_hand as by_hand  # beside this file

from benchmark.lib import manifest, traffic
from benchmark.tools import replicas_check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mistral7b-replicas4-sessions"
NEW = {"xreplica_hit_share": ("%", "higher", "program_counter",
                              "Scheduler and cache manager"),
       "xreplica_admit_hit_p50_ms": ("ms", "lower", "program_span",
                                     "Scheduler and cache manager"),
       "xreplica_restore_p50_ms": ("ms", "lower", "program_span",
                                   "Store client and server")}
# Per-layer metrics without a `workloads` list: every cell reports them.
LIST_FREE = {"decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms"}



def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mistral7b-replicas4", "sessions-rr4-k", 4)
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == [CELL]
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer) in NEW.items():
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"], m["workloads"]) == (
            unit, better, source, layer, "itl_mean_ms", [CELL])
    # the other cells lack them (no place in a list is held: later PRs
    # append entries and may not edit this file)
    per = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                   "per_layer")}
    assert per == LIST_FREE | set(NEW)
    for w in (w for w in bench["workloads"] if w is not cell):
        others = {m["name"] for m in manifest.metrics_for(
            bench, w["name"], "per_layer")}
        assert not others & set(NEW) and LIST_FREE <= others
    assert {m["name"] for m in manifest.metrics_for(
        bench, CELL, "end_to_end")} == {"itl_p95_ms", "itl_mean_ms",
                                       "setup_s"}


def test_the_configuration_differs_from_its_control_in_the_layout_only():
    with open(os.path.join(ROOT, "benchmark/configs/mistral7b.json")) as f:
        control = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/configs/mistral7b-replicas4.json")) as f:
        conf = json.load(f)
    told = {"source", "assumed", "deployment", "guarantees", "reduced"}
    assert set(conf) == set(control) | {"hosts"}
    for k in set(control) - told:
        assert conf[k] == control[k], k
    # A configuration with the source AND the reduced keys of one that
    # is there is no new configuration (the driver refused the first
    # form of this one for it): this one names the served model's
    # repository and the reference's design document, and lists the
    # cut from several hosts to one beside the control's cut of depth.
    assert conf["source"].split()[0] != control["source"].split()[0]
    assert conf["source"].startswith(
        "https://huggingface.co/mistralai/Mistral-7B-Instruct-v0.3/")
    assert "infiniStore/blob/main/docs/source/design.rst" in conf["source"]
    assert len(conf["source"]) <= 200
    assert set(conf["reduced"]) == set(control["reduced"]) | {"hosts"}
    assert conf["reduced"]["num_hidden_layers"] \
        == control["reduced"]["num_hidden_layers"]
    assert conf["hosts"] == conf["reduced"]["hosts"]["here"] == 1
    bench = manifest.load()
    entries = {c["name"]: c for c in bench["configs"]}
    mine, ctl = entries["mistral7b-replicas4"], entries["mistral7b"]
    assert mine["source"] == conf["source"] != ctl["source"]
    assert mine["reduced"] == ctl["reduced"] + ["hosts"]
    # no guarantee of the control is dropped or reworded; four are added
    n = len(control["guarantees"])
    assert conf["guarantees"][:n] == control["guarantees"]
    assert len(conf["guarantees"]) == n + 4
    assert conf["assumed"][:len(control["assumed"])] == control["assumed"]
    spec = traffic.load("benchmark/traffic/sessions-rr4-k.json")
    one = traffic.load("benchmark/traffic/sessions.json")
    assert (spec["replicas"], spec["route"]) == (4, "rotate")
    assert (one["replicas"], one["route"]) == (1, "sticky")
    for k in ("turns", "classes", "think_s", "ramp_s", "drain_s",
              "store_pool_seconds", "loop", "arrivals"):
        assert spec[k] == one[k], k
    # the mix ISSUE 26 gave, but for the rate its sweep put at 0.8 x knee
    first = traffic.load("benchmark/traffic/sessions-rr4.json")
    told = {"name", "session_rate_per_s", "knee"}
    assert {k: v for k, v in spec.items() if k not in told} \
        == {k: v for k, v in first.items() if k not in told}
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(
        int(0.8 * knee / 0.05 + 1e-9) * 0.05)
    assert spec["session_rate_per_s"] < first["session_rate_per_s"]


def window(counters):
    return types.SimpleNamespace(
        window=(100.0, 110.0), counters=counters,
        conf={"serving": {"page_size": 16}})


@pytest.mark.parametrize("name", list(NEW))
def test_reader_on_the_hand_built_window(name, monkeypatch):
    from infinistore_tpu.utils import profiling

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    obs = window({"prefix_hit_pages": 300, "prefill_tokens": 3200,
                  **by_hand.COUNTERS})
    assert manifest.reader(name).read(obs) == pytest.approx(
        by_hand.BY_HAND[name])


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_nothing_on_a_program_without_the_counter_or_field(
        name, monkeypatch):
    """The parent commit, measured with this benchmark: no
    foreign_hit_pages in the stats, no foreign_pages on a span."""
    from infinistore_tpu.utils import profiling

    bare = [s._replace(fields={k: v for k, v in s.fields.items()
                               if k != "foreign_pages"})
            for s in by_hand.RING]
    monkeypatch.setattr(profiling, "spans", lambda: bare)
    obs = window({"prefix_hit_pages": 300, "prefill_tokens": 3200})
    assert manifest.reader(name).read(obs) is None


def test_the_schedule_implies_a_hit_for_every_prompt_length():
    spec = traffic.load("benchmark/traffic/sessions-rr4-k.json")
    implied = replicas_check.implied_hits(spec, 16)
    # 4 classes x 3 turns, every prompt length names one class and turn
    assert len(implied) == 12
    assert sorted(implied.items())[:3] == [(1136, 0), (1264, 0),
                                           (1296, 73)]
    assert sum(1 for h in implied.values() if h == 0) == 4


def run(cmd, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, *cmd], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def line(stdout, prefix):
    ln = next(ln for ln in stdout.splitlines() if ln.startswith(prefix))
    return json.loads(ln[len(prefix):])


def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    r = run([os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
             "--seed", str(2 ** 31 + 26), "--seconds", "5", "--trace", "1",
             "--rehearsal"])
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0 and res["device"]["count"] == 4
    # the three new metrics and the list-free program metrics print
    # (device_trace ones never do in a rehearsal)
    want = set(NEW) | {m for m in LIST_FREE
                       if not m.startswith("decode_") or "host" in m}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    for name, (unit, *_) in NEW.items():
        assert res["metrics"][name]["unit"] == unit
        assert res["metrics"][name]["value"] > 0
    w = line(r.stdout, "window: ")
    c = w["counters"]
    # every hit of the window crossed replicas, and none was lost
    assert c["foreign_hit_pages"] == c["prefix_hit_pages"] > 0
    assert c["restore_misses"] == 0 and w["store_errors"] == 0
    assert w["compilations_in_window"] == 0 and w["engine_ok"] is True
    page = 16
    share = 100.0 * c["foreign_hit_pages"] * page / (
        c["prefix_hit_pages"] * page + c["prefill_tokens"])
    assert res["metrics"]["xreplica_hit_share"]["value"] \
        == pytest.approx(share)
    check = line(r.stdout, "correct: ")
    # 4 classes x (cold + first hit), the hit on the replica the route
    # names; 16 sample sessions each wrote a first batch that read back
    assert check["logit_checked"] == 8 and check["failed"] == 0
    assert check["pages_read_back"] > 0


def test_the_chip_tool_reads_back_across_clients_and_checks_the_schedule():
    r = run([os.path.join(ROOT, "benchmark", "tools", "replicas_check.py"),
             "--workload", CELL, "--seed", str(2 ** 31 + 27),
             "--seconds", "5", "--rehearsal"])
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["ok"] is True and res["correct"] is True
    # every replica's first acknowledged batch through the other three
    assert res["pairs_read_back"] == 12 and res["read_back_equal"] is True
    pairs = line(r.stdout, "cross read-back: ")
    assert {(p["writer"], p["reader"]) for p in pairs} == {
        (a, b) for a in range(4) for b in range(4) if a != b}
    assert res["admissions_in_window"] > 0
    assert res["admissions_off_schedule"] == 0
    assert res["foreign_hit_pages"] == res["prefix_hit_pages"] > 0
    assert res["restore_misses"] == 0 and res["store_errors"] == 0
    assert res["ring_reaches_back"] is True and res["spans_per_s"] > 0
    assert sorted(res["engine_device"].values()) == [0, 1, 2, 3]
