"""The one command, end to end at a tiny size on the CPU (--rehearsal),
and its refusals. These start processes; they share one file so that
one worker runs them."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(args, cwd=ROOT, env=None, timeout=600):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("JAX_COMPILATION_CACHE_DIR", None)
    e.update(env or {})
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=timeout)


def last_json(stdout):
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def line(stdout, prefix):
    for ln in stdout.splitlines():
        if ln.startswith(prefix):
            return json.loads(ln[len(prefix):])
    raise AssertionError(f"no line {prefix!r} in:\n{stdout[-2000:]}")


@pytest.mark.parametrize("workload,trace", [
    ("mistral7b-sessions", 0),
    ("mixtral8x7b-sessions", 1),
    ("mistral7b-unshared", 0),
])
def test_rehearsal_end_to_end(workload, trace):
    r = run(["--workload", workload, "--seed", str(2 ** 31 + 77),
             "--seconds", "4", "--trace", str(trace), "--rehearsal"])
    assert r.returncode == 0, r.stderr[-3000:]
    assert "NOT a chip run" in r.stdout
    res = last_json(r.stdout)
    assert RESULT_KEYS <= set(res) and res["rehearsal"] is True
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    window = line(r.stdout, "window: ")
    assert window["compilations_in_window"] == 0
    assert window["store_errors"] == 0 and window["engine_ok"] is True
    check = line(r.stdout, "correct: ")
    assert check["checked"] > 0 and check["failed"] == 0
    assert check["pages_read_back"] > 0
    names = set(res["metrics"])
    if trace:
        # no CPU number under a device metric's name
        assert not names & {"decode_step_ms", "prefill_ms_per_ktok",
                            "decode_roofline_share", "prefill_mfu"}
        assert "busy_s" not in res["device"] and "breakdown" not in res
        assert "offload_gbps" in names and "store_write_p99_us" in names
        if "sessions" in workload:
            assert {"prefix_hit_share", "restore_gbps", "ttft_p95_ms",
                    "ttft_hit_p50_ms", "ttft_miss_p50_ms"} <= names
            assert res["metrics"]["prefix_hit_share"]["value"] > 30
    else:
        assert {"setup_s", "itl_mean_ms"} <= names
        # the p95 gap is end to end only where its runs are steady
        assert ("itl_p95_ms" in names) == (workload != "mistral7b-sessions")
        assert ("tokens_per_s" in names) == ("unshared" in workload)
        # TTFT is an end-to-end metric only where its runs are steady
        assert ("ttft_p50_ms" in names) == workload.startswith("mixtral")
        assert "ttft_p95_ms" not in names  # a per-layer metric
        assert all(v["value"] > 0 for v in res["metrics"].values())
    leftovers = [n for n in os.listdir("/dev/shm")
                 if n.startswith("istpu_") and str(os.getpid()) in n]
    assert not leftovers


def test_the_default_invocation_refuses_the_cpu():
    r = run(["--workload", "mistral7b-sessions", "--seed", "1",
             "--seconds", "2", "--trace", "0"])
    assert r.returncode == 2
    assert "needs a TPU" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
    assert "setup:" not in r.stdout  # nothing was built or started


def test_an_unknown_cell_is_an_error():
    r = run(["--workload", "no-such-cell", "--rehearsal"])
    assert r.returncode != 0 and "no-such-cell" in r.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run(["--workload", "mistral7b-sessions", "--seed", "1",
             "--seconds", "2", "--rehearsal"], cwd=str(tmp_path),
            env={"PYTHONPATH": ""})
    assert r.returncode != 0
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


@pytest.fixture(scope="module")
def later_pr_tree(tmp_path_factory):
    """A copy of the tree to which a later PR added a configuration, a
    traffic mix (four replicas, rotated routing), a four-chip cell and
    a per-layer metric: new files and entries only."""
    import shutil

    tmp_path = tmp_path_factory.mktemp("later_pr")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("infinistore_tpu", "native"):
        os.symlink(os.path.join(ROOT, d), tmp_path / d)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmark/configs/mixtral8x7b.json")) as f:
        conf = json.load(f)
    conf["source"] = "https://huggingface.co/org/dummy/blob/main/config.json"
    conf["rehearsal"]["num_hidden_layers"] = 1
    (tmp_path / "benchmark/configs/dummy.json").write_text(json.dumps(conf))
    with open(os.path.join(ROOT,
                           "benchmark/traffic/sessions-rr4.json")) as f:
        mix = json.load(f)
    mix.update(name="dummy-mix", turns=2, session_rate_per_s=2.0, classes=[
        {"context": 512, "message": 128, "answer": 128, "weight": 1.0}])
    (tmp_path / "benchmark/traffic/dummy-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/metrics/dummy_steps.py").write_text(
        'KIND = "per_layer"\nLAYER = "Model step"\nUNIT = "1"\n'
        'BETTER = "higher"\nSOURCE = "program_counter"\n'
        'MOVES = "itl_mean_ms"\n\n\ndef read(obs):\n'
        '    return obs.counters.get("decode_steps") or None\n')
    bench["configs"].append({
        "name": "dummy", "source": conf["source"],
        "file": "benchmark/configs/dummy.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
        "chips": 4, "why": "a dummy over four replicas and one store"})
    bench["per_layer"].append({
        "name": "dummy_steps", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "Model step",
        "moves": "itl_mean_ms", "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_a_later_prs_four_chip_cell_runs_from_new_files_alone(
        later_pr_tree):
    r = run(["--workload", "dummy-cell", "--seed", "5", "--seconds", "3",
             "--trace", "1", "--rehearsal"], cwd=later_pr_tree)
    assert r.returncode == 0, r.stderr[-3000:]
    res = last_json(r.stdout)
    assert res["correct"] is True and res["device"]["count"] == 4
    assert res["metrics"]["dummy_steps"]["value"] > 0
    assert "offload_gbps" in res["metrics"]
    # turn 2 ran on another replica than turn 1 and still hit
    assert res["metrics"]["prefix_hit_share"]["value"] > 30 \
        if "prefix_hit_share" in res["metrics"] else True
    window = line(r.stdout, "window: ")
    assert window["counters"]["prefix_hit_pages"] > 0
    assert window["compilations_in_window"] == 0


def test_too_few_chips_is_refused(later_pr_tree):
    r = run(["--workload", "dummy-cell", "--seed", "1", "--seconds", "2",
             "--rehearsal"], cwd=later_pr_tree,
            env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert r.returncode == 2 and "needs 4 chips" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
