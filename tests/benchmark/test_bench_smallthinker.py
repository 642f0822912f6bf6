"""The configuration smallthinker21b and its cell
smallthinker21b-sessions12k (PR 35): the file against the catalog row,
its costs module against numbers worked by hand, the share-nothing
float32 reference against the program at tiny widths, the five new
readers on a hand-built window, and the cell's rehearsal end to end on
the CPU.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import smallthinker_by_hand as by_hand
from benchmark.configs import smallthinker21b_costs as costs
from benchmark.lib import correct, manifest, serve, traffic
from benchmark.metrics import _scoped_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "smallthinker21b", "smallthinker21b-sessions12k"
FILE = f"benchmark/configs/{CONFIG}.json"
NEW = {"window_attn_roofline_share": ("%", "Kernels"),
       "full_attn_roofline_share": ("%", "Kernels"),
       "moe_prefill_mfu": ("%", "Kernels"),
       "moe_step_roofline_share": ("%", "Kernels"),
       "window_release_p50_ms": ("ms", "Device and host transfer")}
APPENDED = ("prefix_hit_share", "prefill_ms_per_ktok", "prefill_mfu",
            "restore_gbps", "store_read_p99_us", "admit_hit_p50_ms")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ("num_hidden_layers", "rope_layout", "sliding_window_layout")


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


# -- the manifest ------------------------------------------------------------
def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sessions12k", 1)
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == list(CUT) and entry["file"] == FILE
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    per = {m["name"]: m for m in manifest.metrics_for(bench, CELL,
                                                      "per_layer")}
    for name, (unit, layer) in NEW.items():
        m = per[name]
        assert (m["unit"], m["layer"], m["moves"], m["workloads"]) == (
            unit, layer, "itl_mean_ms", [CELL])
    assert set(APPENDED) | set(LIST_FREE) <= set(per)
    for name in APPENDED:
        assert per[name]["workloads"][-1] == CELL
    e2e = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                   "end_to_end")}
    assert e2e == {"itl_mean_ms", "setup_s"}


def test_the_file_carries_the_catalog_row_but_the_cut(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert conf["source"] == row["source_url"]
    kept = {k: v for k, v in row["config"].items() if k not in CUT}
    assert {k: conf[k] for k in kept} == kept
    assert set(conf["reduced"]) == set(CUT)
    assert conf["num_hidden_layers"] == 8
    assert conf["reduced"]["num_hidden_layers"]["published"] == 52
    for key in CUT[1:]:  # two whole periods of the published pattern
        assert conf[key] == row["config"][key][:8] == [0, 1, 1, 1] * 2
    said = " ".join(conf["assumed"])
    for item in ("early_router", "ReLU", "secondary", "attention bias",
                 "eos"):
        assert item in said, item


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.smallthinker"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.n_experts, cfg.top_k,
            cfg.vocab_size, cfg.rope_theta, cfg.norm_eps, cfg.act,
            cfg.early_router, cfg.dtype) == (
        2560, 8, 28, 4, 128, 768, 64, 6, 151936, 1.5e6, 1e-6, "relu",
        True, "bfloat16")
    assert cfg.layer_windows == (0, 4096, 4096, 4096) * 2
    assert cfg.layer_ropes == (False, True, True, True) * 2
    assert cfg.two_kinds and cfg.window == 0 and cfg.kv_pack == 1
    # the accepted programs' names are substrings of the new ones', so
    # the file names no programs of its own
    assert serve.program_names(conf, "decode") == ["decode_fused"]
    assert "admit_fused" in serve.program_names(conf, "prefill")
    tol = correct.tolerances_for(conf)
    assert 0 < tol["logit_tol"] < tol["token_eps"]
    assert 0 < tol["router_margin"] and tol["min_checked_share"] >= 0.25


def test_the_engine_holds_two_pools_sized_from_the_band(conf):
    """Shapes only (jax.eval_shape): the full layers' pool under
    total_pages, the window layers' under 16 slots x 264 entries."""
    from benchmark.tools.aot_memory import engine_pools

    model, cfg = serve.model_config(conf)
    held = engine_pools(model, cfg, serve.serving_config(conf, CONFIG))
    assert held["k_pages"].shape == (2, 13313, 16, 4, 128)
    assert held["wk_pages"].shape == (6, 16 * 264 + 1, 16, 4, 128)
    pools = sum(v.size * v.dtype.itemsize for v in held.values())
    assert pools == 1_703_149_568      # one uniform pool would be 3.49 GB
    assert 8 * 2 * 13313 * 16 * 4 * 128 * 2 == 3_489_923_072
    longest = traffic.shapes(traffic.load(
        "benchmark/traffic/sessions12k.json"))["pages_longest"]
    assert longest <= conf["serving"]["max_pages_per_seq"] == 832
    assert conf["serving"]["total_pages"] == 16 * 832 + 1


# -- the costs module, by hand ------------------------------------------------
def test_costs_by_hand(conf):
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    expert = 3 * 2560 * 768
    router = 2560 * 64
    layer = attn + 64 * expert + router + 2 * 2560
    assert (attn, expert, layer) == (20_971_520, 5_898_240, 398_627_840)
    total = 2 * 151936 * 2560 + 2560 + 8 * layer
    assert costs.param_count(conf) == total == 3_966_937_600
    assert costs.weight_bytes(conf) == 2 * total + 8 * router * 2 \
        == 7_936_496_640
    assert costs.page_bytes_all_layers(conf, 16, 2) == 8 * 2048 * 16 \
        == 262_144
    assert costs.store_block_bytes(conf, 16, 2) == 16_384
    assert costs.snapshot_bytes(conf, 2) == 0
    touched = 64 * (1 - (58 / 64) ** 16)
    assert costs.expected_experts_touched(conf, 16) == pytest.approx(
        touched) and 50.7 < touched < 50.8
    moe = 8 * (touched * expert * 2 + router * 4)
    assert costs.moe_step_bytes(conf, 16) == pytest.approx(moe)
    # every sequence longer than the band: the banded layers read the
    # band, the full layers everything
    live = 16 * 10_000
    assert costs.window_attn_bytes(conf, 16, live) == 6 * 16 * 4096 * 2048
    assert costs.full_attn_bytes(conf, 16, live) == 2 * live * 2048
    assert costs.window_attn_bytes(conf, 16, 16 * 1800) \
        == 6 * 16 * 1800 * 2048
    assert costs.decode_bytes(conf, 16, live) == pytest.approx(
        8 * (attn + 2 * 2560) * 2 + moe + (151936 * 2560 + 2560) * 2
        + 16 * 2560 * 2 + 2 * live * 2048 + 6 * 16 * 4096 * 2048)
    active = attn + 6 * expert + router
    assert costs.decode_flops(conf, 16, live) == (
        2 * 16 * (8 * active + 2560 * 151936)
        + 28 * (2 * live + 6 * 16 * 4096) * 4 * 128)
    assert costs.moe_prefill_flops(conf, 12528) \
        == 2 * 12528 * 8 * (6 * expert + router) == 7_126_583_869_440
    # a banded layer's query sees at most 4,096 keys
    assert costs.banded_pairs(conf, 3, 0) == 1 + 2 + 3
    assert costs.banded_pairs(conf, 5000, 0) \
        == 4096 * 4097 // 2 + (5000 - 4096) * 4096
    assert costs.banded_pairs(conf, 128, 12496) == 128 * 4096
    assert costs.banded_pairs(conf, 10, 4090) \
        == 4091 + 4092 + 4093 + 4094 + 4095 + 5 * 4096
    full = 12400 * 12401 // 2
    assert costs.prefill_flops(conf, 12400) == (
        2 * 12400 * 8 * active
        + 28 * (2 * full + 6 * costs.banded_pairs(conf, 12400)) * 4 * 128
        + 2 * 2560 * 151936)
    assert costs.prefill_flops(conf, 128, 12496) == (
        2 * 128 * 8 * active
        + 28 * (2 * (128 * 12496 + 128 * 129 // 2) + 6 * 128 * 4096)
        * 4 * 128 + 2 * 2560 * 151936)
    # about 17 TFLOP for the longest cold prompt, a third of it
    # attention pairs
    assert 17.0e12 < costs.prefill_flops(conf, 12528) < 17.6e12


def test_the_store_pool_is_sized_from_what_an_offload_writes(conf):
    spec = traffic.load("benchmark/traffic/sessions12k.json")
    pages = traffic.pages_written_per_session(spec) * 262_144
    gb = spec["session_rate_per_s"] * pages * 40 / 2 ** 30
    assert traffic.store_pool_gb(spec, 262_144, 16, 0) >= gb


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load("benchmark/traffic/sessions12k.json")
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (6144, 112, 48, 0.4), (6144, 240, 112, 0.3),
        (12288, 112, 112, 0.2), (12288, 240, 48, 0.1)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"], spec["loop"],
            spec["arrivals"], spec["schedule_seed"]) == (
        3, "sticky", 1, 10, 10, 40, "open", "poisson", 20260927)
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    shapes = traffic.shapes(spec)
    assert len(shapes["cold"]) == 4 and len(shapes["prefix"]) == 8
    assert shapes["longest_context"] == 13152
    # every sequence is longer than the band (costs.decode_bytes leans
    # on it)
    assert min(shapes["cold"]) > conf["sliding_window_size"]
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/smallthinker_moe.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    assert cfg.two_kinds and cfg.window_band == 64 and cfg.n_layers == 8
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, length)
    pos = list(range(0, length, 7))
    ref, margins = serve.reference_module(tiny).forward(params, tiny, toks,
                                                        pos)
    got = model.prefill(params, cfg, jnp.asarray(toks[None], jnp.int32))[0]
    assert np.asarray(ref).shape == (len(pos), 512)
    assert np.asarray(margins).shape == (len(pos), 8)
    clear = np.asarray(margins).min(axis=1) >= 1e-3
    assert clear.sum() * 2 >= len(pos)
    diff = np.abs(np.asarray(ref) - np.asarray(got[0])[pos]).max(axis=1)
    assert diff[clear].max() < 2e-4
    # padding behind the last position asked for is inert
    padded, _ = serve.reference_module(tiny).forward(
        params, tiny, np.concatenate([toks, np.zeros(24, toks.dtype)]), pos)
    assert np.allclose(ref, padded, atol=1e-5)


# -- the readers -------------------------------------------------------------
def window():
    import test_bench_observations as table

    obs = table.full_window()
    obs.conf = serve.load_config(FILE)
    return obs


def scoped(obs, kind, scopes):
    return by_hand.SCOPED[kind, tuple(scopes)]


@pytest.mark.parametrize("name", list(NEW))
def test_reader_on_the_hand_built_window(name, monkeypatch):
    from infinistore_tpu.utils import profiling

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    r = manifest.reader(name)
    assert r.read(window()) == pytest.approx(by_hand.BY_HAND[name],
                                             rel=1e-9)
    assert (r.UNIT, r.LAYER) == NEW[name] and r.MOVES == "itl_mean_ms"
    if r.UNIT == "%":
        assert 0 < by_hand.BY_HAND[name] < 100


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_nothing_on_a_program_without_the_spans_or_scopes(
        name, monkeypatch):
    """A parent commit, or a model of one kind, measured with this
    benchmark: no window batches in the ring, no attn.kernel.window /
    moe. scopes in the trace, a costs module without these counts.
    None, and nothing raised."""
    from infinistore_tpu.utils import profiling

    ring = [s for s in by_hand.RING if s.name.startswith("istpu.engine")]
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    assert manifest.reader(name).read(window()) is None
    import test_bench_observations as table

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    if name != "window_release_p50_ms":  # mistral7b's costs have no such
        assert manifest.reader(name).read(table.full_window()) is None


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 35), "--seconds", "6",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    want = {"prefix_hit_share", "restore_gbps", "offload_gbps",
            "store_read_p99_us", "store_write_p99_us", "admit_hit_p50_ms",
            "admit_miss_p50_ms", "offload_stall_p50_ms",
            "decode_host_p50_ms"}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    # every hit restored what its length implies, per kind: 2 full and
    # 6 banded layers, a band of 4 pages here
    assert c["prefix_hit_pages"] > 0 and c["restore_misses"] == 0
    assert c["restored_pages"] == 2 * (
        2 * c["prefix_hit_pages"]
        + 6 * (c["prefix_hit_pages"] - c["restore_trimmed_pages"]))
    assert c["restore_trimmed_pages"] > 0
    assert c["subfloor_pages_written"] > 0
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    assert check["logit_rows"]["cold"]["taken"] == 4
    assert check["logit_rows"]["hit"]["taken"] == 4
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
