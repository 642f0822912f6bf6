"""BENCHMARK.json and the files it names. The harness finds everything
that belongs to one configuration, one traffic mix or one metric by the
name in BENCHMARK.json; check() is the manifest check the tests run."""

import importlib
import json
import os
import re

from . import ROOT

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bench, workload):
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_of(bench, name):
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_path(traffic):
    return f"benchmark/traffic/{traffic}.json"


def metrics_for(bench, workload, kind):
    """Entries of `kind` ("end_to_end" | "per_layer") that this cell
    reports: those without a "workloads" key and those that list it."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def reader(name):
    """The metric's own small reader, benchmark/metrics/<name>.py."""
    return importlib.import_module(
        "benchmark.metrics." + name.replace("-", "_").replace(".", "_"))


def check(bench, root=ROOT):
    """Returns a list of complaints (empty: the manifest is sound)."""
    bad = []
    names = set()

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME_RE.match(n):
            bad.append(f"{what} name {n!r} outside the allowed characters")

    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in bench[group]:
            name_ok(e["name"], group)
            if e["name"] in seen:
                bad.append(f"{group}: duplicate name {e['name']}")
            seen.add(e["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in names:
            bad.append(f"metric {m['name']} named twice")
        names.add(m["name"])
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"metric {m['name']}: source {m['source']!r}")
    for m in bench["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"end-to-end {m['name']}: bound {m['bound']}")
    if "setup_s" not in {m["name"] for m in bench["end_to_end"]}:
        bad.append("no setup_s among the end-to-end metrics")
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    pairs = set()
    for w in bench["workloads"]:
        name_ok(w["traffic"], "traffic")
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad.append(f"cell {w['name']}: why is not one line of 1-200")
        if not os.path.exists(os.path.join(root,
                                           traffic_path(w["traffic"]))):
            bad.append(f"cell {w['name']}: no traffic file "
                       f"{traffic_path(w['traffic'])}")
        e2e = {m["name"] for m in metrics_for(bench, w["name"],
                                              "end_to_end")}
        if "setup_s" not in e2e or len(e2e) < 2:
            bad.append(f"cell {w['name']}: reports {sorted(e2e)}; needs "
                       f"setup_s and one more end-to-end metric")
        per = metrics_for(bench, w["name"], "per_layer")
        if not per:
            bad.append(f"cell {w['name']}: no per-layer metric")
        for m in per:
            if m["moves"] not in e2e:
                bad.append(f"per-layer {m['name']} moves {m['moves']}, "
                           f"which cell {w['name']} does not report")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    if four > max(1, len(bench["workloads"]) // 4):
        bad.append(f"{four} of {len(bench['workloads'])} cells ask for "
                   f"four chips; at most a quarter (and always one) may")
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        if c["name"] not in used:
            bad.append(f"config {c['name']} is used by no cell")
        if c["file"] in files:
            bad.append(f"config file {c['file']} named twice")
        files.add(c["file"])
        path = os.path.join(root, c["file"])
        if not any(c["file"].startswith(p + "/") for p in bench["paths"]):
            bad.append(f"config file {c['file']} is outside paths")
        elif not os.path.exists(path):
            bad.append(f"config {c['name']}: no file {c['file']}")
        else:
            with open(path) as f:
                conf = json.load(f)
            for k in c["reduced"]:
                name_ok(k, "reduced key")
                if k not in conf or k not in conf.get("reduced", {}):
                    bad.append(f"config {c['name']}: reduced key {k} is "
                               f"not explained in {c['file']}")
            if conf.get("source") != c["source"]:
                bad.append(f"config {c['name']}: source differs from "
                           f"{c['file']}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        for wl in m.get("workloads", ()):
            if wl not in cells:
                bad.append(f"metric {m['name']} lists unknown cell {wl}")
        try:
            r = reader(m["name"])
        except ImportError:
            bad.append(f"metric {m['name']}: no reader "
                       f"benchmark/metrics/{m['name']}.py")
            continue
        kind = "end_to_end" if m in bench["end_to_end"] else "per_layer"
        want = {"UNIT": m["unit"], "BETTER": m["better"],
                "SOURCE": m["source"], "KIND": kind,
                "MOVES": m.get("moves"), "LAYER": m.get("layer")}
        for k, v in want.items():
            if getattr(r, k) != v:
                bad.append(f"metric {m['name']}: {k} is {v!r} in "
                           f"BENCHMARK.json and {getattr(r, k)!r} in its "
                           f"reader")
    return bad
