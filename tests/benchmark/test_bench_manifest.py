"""The manifest check: BENCHMARK.json against the contract's limits and
against the files it names; and a later PR's dummy configuration,
traffic mix, cell and metric, added as new files and entries only."""

import copy
import hashlib
import json
import os
import re
import shutil

import pytest

from benchmark.lib import manifest

BENCH = manifest.load()
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_the_manifest_is_sound():
    assert manifest.check(BENCH) == []


def test_exactly_the_contracts_keys_and_sizes():
    assert set(BENCH) == KEYS
    assert os.path.getsize("BENCHMARK.json") <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_the_full_check_fits_the_drivers_day_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("group,keys,optional", [
    ("configs", {"name", "source", "file", "reduced", "why"}, set()),
    ("workloads", {"name", "config", "traffic", "chips", "why"}, set()),
    ("end_to_end", {"name", "unit", "better", "bound", "source"},
     {"workloads"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"},
     {"workloads"}),
])
def test_entries_have_just_the_keys_shown(group, keys, optional):
    for e in BENCH[group]:
        assert keys <= set(e) <= keys | optional, e["name"]


def test_names_units_and_lines():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\t" not in w["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert 1 <= len(c["why"]) <= 200
        assert c["source"].startswith("https://huggingface.co/")
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


def test_paths_hold_the_benchmark_and_the_command_stays_inside():
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(p) and re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
    for root in BENCH["paths"]:
        for d, _, files in os.walk(root):
            if "__pycache__" in d:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (d, f)


def test_reduced_never_names_a_width():
    widths = re.compile(r"(hidden_size|intermediate|latent|state_|proj|_dim$|"
                        r"_rank$|head_dim|expand|experts_per_tok)")
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert not widths.search(k), k
        with open(c["file"]) as f:
            conf = json.load(f)
        assert conf["hidden_size"] == 4096
        assert conf["intermediate_size"] == 14336
        assert conf["num_attention_heads"] == 32
        assert conf["num_key_value_heads"] == 8
        assert set(conf["reduced"]) == set(c["reduced"])
        assert conf["assumed"] and conf["guarantees"] and conf["deployment"]


def test_a_quarter_of_the_cells_at_most_take_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_for(
            BENCH, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = manifest.metrics_for(BENCH, w["name"], "per_layer")
        assert per and all(m["moves"] in e2e for m in per)


def mutate(fn):
    b = copy.deepcopy(BENCH)
    fn(b)
    return manifest.check(b)


@pytest.mark.parametrize("what,fn", [
    ("name with a space",
     lambda b: b["workloads"][0].update(name="bad name")),
    ("name with a slash",
     lambda b: b["per_layer"][0].update(name="a/b")),
    ("unit with a space",
     lambda b: b["end_to_end"][0].update(unit="tokens per second")),
    ("greek unit", lambda b: b["per_layer"][0].update(unit="µs")),
    ("moves a metric the cell does not report",
     lambda b: [m for m in b["per_layer"]
                if m["name"] == "batch_occupancy"][0].update(
                    moves="ttft_p50_ms")),
    ("moves an unknown metric",
     lambda b: b["per_layer"][0].update(moves="nope")),
    ("too many four-chip cells",
     lambda b: [w.update(chips=4) for w in b["workloads"][:2]]),
    ("three chips", lambda b: b["workloads"][0].update(chips=3)),
    ("bound over a tenth",
     lambda b: b["end_to_end"][0].update(bound=0.2)),
    ("unknown source",
     lambda b: b["per_layer"][0].update(source="guess")),
    ("end-to-end from a program counter",
     lambda b: b["end_to_end"][0].update(source="program_counter")),
    ("no setup_s", lambda b: b["end_to_end"].pop()),
    ("unknown config", lambda b: b["workloads"][0].update(config="x")),
    ("pair twice", lambda b: b["workloads"].append(
        dict(b["workloads"][0], name="again"))),
    ("no traffic file",
     lambda b: b["workloads"][0].update(traffic="missing-mix")),
    ("unused config", lambda b: b["configs"].append(
        dict(b["configs"][0], name="spare", file="benchmark/x.json"))),
    ("no reader", lambda b: b["per_layer"].append(
        dict(b["per_layer"][0], name="unread_metric"))),
    ("reader disagrees", lambda b: b["per_layer"][0].update(unit="s")),
    ("long why", lambda b: b["workloads"][0].update(why="x" * 201)),
    ("reduced key not explained",
     lambda b: b["configs"][0].update(reduced=["vocab_size"])),
])
def test_the_check_catches(what, fn):
    assert mutate(fn), what


def digest_tree(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_later_pr_adds_files_and_entries_and_edits_none(tmp_path,
                                                          monkeypatch):
    root = str(tmp_path)
    shutil.copytree("benchmark", os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest_tree(os.path.join(root, "benchmark"))
    # a configuration: its file of sizes
    with open("benchmark/configs/mistral7b.json") as f:
        conf = json.load(f)
    conf["source"] = "https://huggingface.co/org/dummy/blob/main/config.json"
    with open(os.path.join(root, "benchmark/configs/dummy.json"), "w") as f:
        json.dump(conf, f)
    # a traffic mix: a data file the general generator reads
    with open("benchmark/traffic/unshared.json") as f:
        mix = json.load(f)
    mix["name"] = "dummy-mix"
    with open(os.path.join(root, "benchmark/traffic/dummy-mix.json"),
              "w") as f:
        json.dump(mix, f)
    # a per-layer metric: a small reader of its own
    with open(os.path.join(root, "benchmark/metrics/dummy_steps.py"),
              "w") as f:
        f.write('KIND = "per_layer"\nLAYER = "Model step"\nUNIT = "1"\n'
                'BETTER = "higher"\nSOURCE = "program_counter"\n'
                'MOVES = "itl_mean_ms"\n\n\ndef read(obs):\n'
                '    return obs.counters.get("decode_steps") or None\n')
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": "dummy", "source": conf["source"],
        "file": "benchmark/configs/dummy.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
        "chips": 1, "why": "a dummy cell of a dummy configuration"})
    bench["per_layer"].append({
        "name": "dummy_steps", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "Model step",
        "moves": "itl_mean_ms", "workloads": ["dummy-cell"]})
    import benchmark.metrics

    monkeypatch.setattr(benchmark.metrics, "__path__", list(
        benchmark.metrics.__path__) + [os.path.join(root,
                                                   "benchmark/metrics")])
    assert manifest.check(bench, root=root) == []
    after = digest_tree(os.path.join(root, "benchmark"))
    assert {k: after[k] for k in before} == before   # nothing edited
    assert len(after) == len(before) + 3
    # the harness finds each by the name in BENCHMARK.json
    cell = manifest.cell_of(bench, "dummy-cell")
    assert manifest.config_of(bench, cell["config"])["file"].endswith(
        "dummy.json")
    names = {m["name"] for m in manifest.metrics_for(
        bench, "dummy-cell", "per_layer")}
    assert "dummy_steps" in names and "decode_step_ms" in names

    class Obs:
        counters = {"decode_steps": 12}

    assert manifest.reader("dummy_steps").read(Obs()) == 12
