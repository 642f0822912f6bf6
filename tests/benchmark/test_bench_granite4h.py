"""The configuration granite4h-micro and its cell
granite4h-micro-sessions4k (PR 31): the file against the catalog row,
its costs module against numbers worked by hand, the share-nothing
float32 reference against the program at tiny widths, the four new
readers on a hand-built window and a hand-built trace, and the cell's
rehearsal end to end on the CPU.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import granite4h_by_hand as by_hand
from benchmark.configs import granite4h_micro_costs as costs
from benchmark.lib import correct, manifest, serve, traffic
from benchmark.metrics import _scoped_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "granite4h-micro", "granite4h-micro-sessions4k"
FILE = f"benchmark/configs/{CONFIG}.json"
NEW = {"snapshot_restore_p50_ms": ("ms", "Device and host transfer"),
       "snapshot_offload_p50_ms": ("ms", "Device and host transfer"),
       "ssm_step_roofline_share": ("%", "Kernels"),
       "ssm_scan_mfu": ("%", "Kernels")}
APPENDED = ("prefix_hit_share", "prefill_ms_per_ktok", "prefill_mfu",
            "restore_gbps", "store_read_p99_us", "admit_hit_p50_ms")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


# -- the manifest ------------------------------------------------------------
def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sessions4k", 1)
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == [] and entry["file"] == FILE
    per = {m["name"]: m for m in manifest.metrics_for(bench, CELL,
                                                      "per_layer")}
    for name, (unit, layer) in NEW.items():
        m = per[name]
        assert (m["unit"], m["layer"], m["moves"], m["workloads"]) == (
            unit, layer, "itl_mean_ms", [CELL])
    assert set(APPENDED) | set(LIST_FREE) <= set(per)
    e2e = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                   "end_to_end")}
    assert e2e == {"itl_mean_ms", "setup_s"}


def test_the_file_carries_the_catalog_row_untouched(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert conf["source"] == row["source_url"]
    assert {k: conf[k] for k in row["config"]} == row["config"]
    assert conf["reduced"] == {}
    assert any("float32" in a and "State dtype" in a
               for a in conf["assumed"])


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.hybrid"
    assert (cfg.d_model, cfg.n_layers, cfg.n_kv_layers, cfg.n_state_layers,
            cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_chunk, cfg.dtype, cfg.state_dtype) == (
        2048, 40, 4, 36, 32, 8, 64, 8192, 100352, 64, 64, 128, 4, 256,
        "bfloat16", "float32")
    assert [i for i, k in enumerate(cfg.layer_kinds)
            if k == "attention"] == [5, 15, 25, 35]
    assert (cfg.embed_scale, cfg.attn_scale, cfg.residual_mult,
            cfg.logits_div, cfg.use_rope, cfg.window) == (
        12.0, 1 / 64, 0.22, 8.0, False, 0)
    # two kv heads of 64 lanes a cache row: the pool is lane-aligned
    assert cfg.kv_pack == 2 and cfg.kv_page_shape() == (16, 4, 128)
    assert serve.program_names(conf, "decode") == ["decode_fused_st"]
    assert serve.program_names(conf, "prefill") == [
        "admit_fused_st", "admit_fused_px_st"]
    tol = correct.tolerances_for(conf)
    assert 0 < tol["logit_tol"] < tol["token_eps"]


# -- the costs module, by hand ------------------------------------------------
def test_costs_by_hand(conf):
    mixer = 2048 * 8512 + 4096 * 2048 + 4352 * 5 + 3 * 64 + 4096
    assert costs.mixer_params(conf) == mixer == 25_847_232
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 3 * 2048 * 8192
    total = 100352 * 2048 + 2048 + 40 * (mlp + 2 * 2048) + 4 * attn \
        + 36 * mixer
    assert costs.param_count(conf) == total == 3_191_396_096
    assert costs.weight_bytes(conf) == 2 * total + 36 * 3 * 64 * 2
    assert costs.page_bytes_all_layers(conf, 16, 2) == 131072
    assert costs.store_block_bytes(conf, 16, 2) == 16384
    state = 36 * (64 * 64 * 128 + 3 * 4352) * 4
    assert costs.state_bytes(conf) == state == 77_377_536
    # a row is 537,344 x 4 B = 131.2 blocks of 16 KiB: 132 of them
    assert costs.snapshot_bytes(conf, 2) == 36 * 132 * 16384 == 77_856_768
    assert costs.ssm_step_bytes(conf, 16) == 2 * 16 * state + 36 * mixer * 2
    assert costs.decode_bytes(conf, 16, 40_000) == (
        costs.weight_bytes(conf) + 16 * 2048 * 2 + 2 * 16 * state
        + 40_000 * 8192)
    assert costs.ssm_scan_flops(conf, 160) == 36 * 445_403_136
    assert costs.ssm_scan_flops(conf, 4336) == 36 * 13_801_879_552
    matmuls = 40 * mlp + 4 * attn + 36 * (2048 * 8512 + 4096 * 2048)
    assert costs.prefill_flops(conf, 160, 2160) == (
        2 * 160 * matmuls + 4 * 32 * (160 * 2160 + 160 * 161 // 2) * 4 * 64
        + 36 * 445_403_136 + 2 * 2048 * 100352)
    # about 6 GFLOP a token, the scan under 2 % of it
    per_token = costs.prefill_flops(conf, 4336) / 4336
    assert 6.0e9 < per_token < 6.3e9
    assert costs.ssm_scan_flops(conf, 4336) < 0.02 * costs.prefill_flops(
        conf, 4336)


def test_the_engine_and_the_costs_agree_on_a_snapshot(conf):
    """The store pool is sized from costs.snapshot_bytes; the engine
    writes serving._snapshot_row_elems a state layer."""
    from infinistore_tpu import serving

    _, cfg = serve.model_config(conf)
    row = serving._snapshot_row_elems(cfg)
    assert row * 4 * cfg.n_state_layers == costs.snapshot_bytes(conf, 2)
    spec = traffic.load("benchmark/traffic/sessions4k.json")
    pages = traffic.pages_written_per_session(spec) * 131072
    snaps = traffic.offloads_per_session(spec) * 77_856_768
    assert traffic.offloads_per_session(spec) == 3
    gb = spec["session_rate_per_s"] * (pages + snaps) * 40 / 2 ** 30
    assert traffic.store_pool_gb(
        spec, 131072, 16, costs.snapshot_bytes(conf, 2)) >= gb


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load("benchmark/traffic/sessions4k.json")
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (2048, 112, 48, 0.4), (2048, 240, 112, 0.3), (4096, 112, 112, 0.2),
        (4096, 240, 48, 0.1)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"]) == (
        3, "sticky", 1, 10, 10, 40)
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    assert "schedule_seed" in spec
    shapes = traffic.shapes(spec)
    assert len(shapes["cold"]) == 4 and len(shapes["prefix"]) == 8
    assert shapes["pages_longest"] <= conf["serving"]["max_pages_per_seq"]
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/granite_hybrid.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    assert cfg.layer_kinds.count("attention") == 1 and cfg.n_layers == 10
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, length)
    pos = [0, length // 2, length - 1]
    ref, margins = serve.reference_module(tiny).forward(params, tiny, toks,
                                                        pos)
    got = model.prefill(params, cfg, jnp.asarray(toks[None], jnp.int32))[0]
    assert margins is None and np.asarray(ref).shape == (3, 512)
    assert np.max(np.abs(np.asarray(ref) - np.asarray(got[0])[pos])) < 2e-5
    # padding behind the last position asked for is inert
    padded, _ = serve.reference_module(tiny).forward(
        params, tiny, np.concatenate([toks, np.zeros(24, toks.dtype)]), pos)
    assert np.allclose(ref, padded, atol=1e-6)


# -- the readers -------------------------------------------------------------
def window():
    import test_bench_observations as table

    obs = table.full_window()
    obs.conf = serve.load_config(FILE)
    return obs


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_hand_built_window(name, monkeypatch):
    from infinistore_tpu.utils import profiling

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds",
                        lambda obs, kind, scopes: by_hand.SCOPED[kind])
    r = manifest.reader(name)
    assert r.read(window()) == pytest.approx(by_hand.BY_HAND[name],
                                             rel=1e-9)
    assert (r.UNIT, r.LAYER) == NEW[name] and r.MOVES == "itl_mean_ms"
    if r.UNIT == "%":
        assert 0 < by_hand.BY_HAND[name] < 100


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_a_program_without_the_spans_or_scopes(
        name, monkeypatch):
    """A parent commit measured with this benchmark: no state_in /
    state_out spans, no ssm scopes in the trace, a configuration whose
    costs module has no ssm counts. None, and nothing raised."""
    from infinistore_tpu.utils import profiling

    ring = [s for s in by_hand.RING if s.name.startswith("istpu.engine")]
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    assert manifest.reader(name).read(window()) is None
    import test_bench_observations as table

    assert manifest.reader(name).read(table.full_window()) is None


def test_scoped_seconds_on_a_hand_built_trace():
    """Operations by their scope, inside the named programs' runs,
    clipped to the window. Window [1000, 5000) ns."""
    modules = [("jit__decode_fused_st(1)", 1000, 1000),
               ("jit__decode_fused_st(1)", 3000, 1000),
               ("jit__admit_fused_st(2)", 4200, 1500),  # cut by the edge
               ("jit__decode_fused_st(1)", 6000, 1000)]  # outside
    ops = [("jit(_decode_fused_st)/ssm.step/mul fusion.1", 1100, 200),
           ("jit(_decode_fused_st)/ssm.in/dot fusion.2", 1400, 100),
           ("jit(_decode_fused_st)/mlp/dot fusion.3", 1500, 400),
           ("jit(_decode_fused_st)/ssm.out/dot fusion.4", 3100, 300),
           ("jit(_admit_fused_st)/ssm.scan/dot fusion.9", 4300, 400),
           ("jit(_admit_fused_st)/ssm.scan/exp fusion.8", 4900, 300),
           ("jit(_decode_fused_st)/ssm.step/mul fusion.1", 6100, 200)]
    others = ("attn.", "mlp", "lm_head", "embed", "pool.update")
    secs, runs, whole = _scoped_ops.seconds_in(
        ops, modules, (1000, 5000), ["decode_fused_st"], others)
    # the mlp operation alone; two runs of 1000 ns in the window
    assert (secs, runs, whole) == (pytest.approx(400e-9), 2,
                                   pytest.approx(2000e-9))
    secs, runs, whole = _scoped_ops.seconds_in(
        ops, modules, (1000, 5000), ["admit_fused_st", "admit_fused_px_st"],
        ("ssm.scan",))
    # 400 + 100 of 300; the run is cut by the window's edge at 800 ns
    assert (secs, runs, whole) == (pytest.approx(500e-9), 1,
                                   pytest.approx(800e-9))


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 31), "--seconds", "6",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    # every program-span and counter metric of the cell prints
    # (device_trace ones never do in a rehearsal)
    want = {"snapshot_restore_p50_ms", "snapshot_offload_p50_ms",
            "prefix_hit_share", "restore_gbps", "offload_gbps",
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
    assert c["snapshots_restored"] > 0 and c["snapshots_written"] > 0
    assert c["snapshot_misses"] == 0 and c["restore_misses"] == 0
    # (no boundary copy: at an eighth of the lengths an answer is one
    # page, and a sequence finishes a token short of its next edge)
    assert c["prefix_hit_pages"] > 0
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    # 4 classes x (cold + first hit), each through the program that ran
    assert check["logit_rows"] == {"cold": {"taken": 4, "compared": 4},
                                   "hit": {"taken": 4, "compared": 4}}
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
