"""The configuration command-a-plus and its cell command-a-plus-mixed12k
(PR 42): the file against the catalog row, its costs module against
numbers worked by hand and against the program's own parameters, the
share-nothing float32 reference against the program at tiny widths,
the two new readers on a hand-built window, the traffic's shapes, and
the cell's rehearsal end to end on the CPU.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import command_a_by_hand as by_hand
from benchmark.configs import command_a_plus_costs as costs
from benchmark.lib import correct, manifest, serve, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "command-a-plus", "command-a-plus-mixed12k"
FILE = f"benchmark/configs/{CONFIG}.json"
TRAFFIC = "benchmark/traffic/mixed12k.json"
NEW = {"moe_held_pair_skew": ("%", "Model step"),
       "moe_held_rows_share": ("%", "Model step")}
BETTER = {"moe_held_pair_skew": "lower", "moe_held_rows_share": "higher"}
APPENDED = ("prefix_hit_share", "prefill_ms_per_ktok", "prefill_mfu",
            "restore_gbps", "store_read_p99_us", "admit_hit_p50_ms",
            "idle_no_work_share", "host_held_idle_share",
            "decode_dispatch_lead_p50_ms", "decode_return_lag_p50_ms",
            "store_allocate_us_per_key", "store_write_gbps",
            "window_attn_roofline_share", "full_attn_roofline_share",
            "window_release_p50_ms", "moe_prefill_mfu",
            "moe_step_roofline_share")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ("num_hidden_layers", "layer_types", "num_experts", "vocab_size")


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


# -- the manifest ------------------------------------------------------------
def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed12k", 1)
    assert len(cell["why"]) <= 200 and "B/16" in cell["why"]
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == list(CUT) and entry["file"] == FILE
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("moe_held_rows_share") \
        == names.index("moe_held_pair_skew") + 1
    per = {m["name"]: m for m in manifest.metrics_for(bench, CELL,
                                                      "per_layer")}
    for name, (unit, layer) in NEW.items():
        m = per[name]
        assert (m["unit"], m["layer"], m["moves"], m["workloads"]) == (
            unit, layer, "itl_mean_ms", [CELL])
    assert set(APPENDED) | set(LIST_FREE) <= set(per)
    for name in APPENDED:
        assert CELL in per[name]["workloads"]
    e2e = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                   "end_to_end")}
    assert e2e == {"itl_mean_ms", "setup_s"}


def test_the_file_carries_the_catalog_row_but_the_cut(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CUT:
            cut = conf["reduced"][key]
            assert cut["here"] != value and len(cut["why"]) > 10
        else:
            assert conf[key] == value, key
    assert set(conf["reduced"]) == set(CUT)
    # no width among them: depth, its list, the chip's share
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (4, 16, 32768)
    assert conf["layer_types"] == row["config"]["layer_types"][:4] \
        == ["sliding_attention"] * 3 + ["full_attention"]
    assert conf["reduced"]["num_experts"]["published"] == 128
    assert conf["reduced"]["vocab_size"]["published"] == 262144
    share = conf["expert_share"]
    assert (share["router_width"], share["held"], share["chips_a_layer"]) \
        == (128, 16, 8)
    assert 0 <= share["first_expert"] <= 128 - 16
    for group in ("assumed", "deployment", "guarantees"):
        assert conf[group]
    said = " ".join(conf["assumed"])
    for item in ("AVERAGED", "(routed + shared) / 2", "adjacent pairs",
                 "eos", "random from --seed"):
        assert item in said, item
    for item in ("8 v5e chips", "data-parallel attention", "WITHOUT"):
        assert item in conf["deployment"], item
    # how near the expert load is, said where the cut is
    assert "B / 16" in conf["reduced"]["num_experts"]["why"]
    assert conf["serving"] == {"page_size": 16, "max_slots": 16,
                               "max_pages_per_seq": 864,
                               "total_pages": 16 * 861 + 1}


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.cohere"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.rope_theta,
            cfg.norm_eps, cfg.act, cfg.dtype) == (
        4096, 4, 128, 8, 128, 4096, 32768, 5e4, 1e-5, "silu", "bfloat16")
    assert (cfg.n_routed, cfg.n_experts, cfg.first_expert, cfg.top_k,
            cfg.n_shared, cfg.shared_mean, cfg.router) == (
        128, 16, conf["expert_share"]["first_expert"], 8, 4, True,
        "sigmoid")
    assert cfg.layer_windows == (4096, 4096, 4096, 0)
    assert cfg.layer_ropes == (True, True, True, False)
    assert cfg.two_kinds and cfg.norm_center and cfg.rope_adjacent
    assert cfg.logits_div == 1.0 and cfg.kv_page_bytes() == 32768
    assert serve.program_names(conf, "decode") == ["decode_fused"]
    assert "admit_fused" in serve.program_names(conf, "prefill")
    tol = correct.tolerances_for(conf)
    assert 0 < tol["logit_tol"] < tol["token_eps"]
    assert 0 < tol["router_margin"] and 0 < tol["min_checked_share"] <= 0.25


def test_the_programs_parameters_are_what_the_costs_count(conf):
    model, cfg = serve.model_config(conf)
    tree = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(x.size) for x in leaves) == costs.param_count(conf) \
        == 4_733_292_544
    assert sum(int(x.size) * x.dtype.itemsize for x in leaves) \
        == costs.weight_bytes(conf) == 9_470_779_392
    layer = tree["layers"][0]
    assert layer["router"].shape == (4096, 128)
    assert layer["e_gate"].shape == (16, 4096, 4096)
    assert layer["s_down"].shape == (4 * 4096, 4096)


def test_the_engine_holds_two_pools_sized_from_the_band(conf):
    from benchmark.tools.aot_memory import engine_pools

    model, cfg = serve.model_config(conf)
    held = engine_pools(model, cfg, serve.serving_config(conf, CONFIG))
    assert held["k_pages"].shape == (1, 13777, 16, 8, 128)
    assert held["wk_pages"].shape == (3, 16 * 264 + 1, 16, 8, 128)
    pools = sum(v.size * v.dtype.itemsize for v in held.values())
    assert pools == 1_733_558_272
    shapes = traffic.shapes(traffic.load(TRAFFIC))
    assert shapes["pages_longest"] == 861 \
        <= conf["serving"]["max_pages_per_seq"]


# -- the costs module, by hand ------------------------------------------------
def test_costs_by_hand(conf):
    h = by_hand
    assert (h.ATTN, h.EXPERT, h.SHARED, h.ROUTER, h.LAYER, h.PARAMS) == (
        142_606_336, 50_331_648, 201_326_592, 524_288, 1_149_767_680,
        4_733_292_544)
    assert costs.attn_params(conf) == h.ATTN
    assert costs.expert_params(conf) == h.EXPERT
    assert costs.shared_params(conf) == h.SHARED
    assert costs.router_params(conf) == h.ROUTER
    assert costs.layer_params(conf) == h.LAYER
    assert costs.param_count(conf) == h.PARAMS
    assert costs.weight_bytes(conf) == 2 * h.PARAMS + 4 * h.ROUTER * 2
    # 4 KB a token a layer, a 32 KB K or V page, 256 KB a page over all
    assert costs.kv_bytes_per_token_layer(conf) == 4096
    assert costs.store_block_bytes(conf, 16, 2) == 32_768
    assert costs.page_bytes_all_layers(conf, 16, 2) == 4 * 4096 * 16
    assert costs.snapshot_bytes(conf, 2) == 0
    # one held pair a token in expectation; 16 (1 - (15/16) ** active)
    assert costs.held_pairs_per_token(conf) == 1.0
    assert costs.expected_experts_touched(conf, 1) == pytest.approx(1.0)
    assert costs.expected_experts_touched(conf, 2) == pytest.approx(
        16 * (1 - (15 / 16) ** 2)) == pytest.approx(1.9375)
    touched = 16 * (1 - (15 / 16) ** 16)
    assert 10.3 < touched < 10.4
    assert costs.moe_step_bytes(conf, 16) == pytest.approx(
        4 * ((touched * h.EXPERT + h.SHARED) * 2 + h.ROUTER * 4))
    # a fifth of a step's bytes at 2 active tokens, over half at 16
    for active, lo, hi in ((2, 0.17, 0.23), (16, 0.5, 0.62)):
        experts = 4 * costs.expected_experts_touched(conf, active) \
            * h.EXPERT * 2
        assert lo < experts / costs.decode_bytes(conf, active, 0) < hi
    assert costs.moe_prefill_flops(conf, 12528) == 2 * 12528 * 4 * (
        h.EXPERT + h.SHARED + h.ROUTER) == 25_274_741_686_272
    # the window layers' least: sequences under the window read all
    # they have, at most one window each; what the sum can fill to the
    # longest a slot holds (13,824) reads a window
    assert costs.window_tokens(conf, 3, 3000) == 3000
    assert costs.window_tokens(conf, 3, 9000) == 4096
    assert costs.window_tokens(conf, 3, 13824 + 9000) == 2 * 4096
    assert costs.window_tokens(conf, 2, 2 * 13824) == 2 * 4096
    assert costs.window_tokens(conf, 1, 13824) == 4096
    assert costs.window_attn_bytes(conf, 3, 9000) == 3 * 4096 * 4096
    assert costs.full_attn_bytes(conf, 3, 9000) == 9000 * 4096
    live = 20_000
    assert costs.decode_bytes(conf, 2, live) == pytest.approx(
        4 * (h.ATTN + 4096) * 2 + costs.moe_step_bytes(conf, 2)
        + (32768 * 4096 + 4096) * 2 + live * 4096
        + 3 * (4096 + 4096) * 4096)
    active = h.ATTN + h.EXPERT + h.SHARED + h.ROUTER
    assert costs.decode_flops(conf, 2, live) == (
        2 * 2 * (4 * active + 4096 * 32768)
        + 128 * (live + 3 * 8192) * 4 * 128)
    assert costs.banded_pairs(conf, 3, 0) == 1 + 2 + 3
    assert costs.banded_pairs(conf, 5000, 0) \
        == 4096 * 4097 // 2 + (5000 - 4096) * 4096
    assert costs.banded_pairs(conf, 128, 12512) == 128 * 4096
    full = 12528 * 12529 // 2
    assert costs.prefill_flops(conf, 12528) == (
        2 * 12528 * 4 * active
        + 128 * (full + 3 * costs.banded_pairs(conf, 12528)) * 4 * 128
        + 2 * 4096 * 32768)
    # about 53 TFLOP for the longest cold prompt, 15 of them attention
    assert 52e12 < costs.prefill_flops(conf, 12528) < 54e12


def test_the_store_pool_is_sized_from_what_an_offload_writes(conf):
    spec = traffic.load(TRAFFIC)
    pages = traffic.pages_written_per_session(spec) * 262_144
    gb = spec["session_rate_per_s"] * pages * 40 / 2 ** 30
    assert traffic.store_pool_gb(spec, 262_144, 16, 0) >= gb


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load(TRAFFIC)
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (1024, 112, 256, 0.4), (6144, 240, 128, 0.3),
        (12288, 112, 128, 0.2), (12288, 240, 256, 0.1)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"], spec["loop"],
            spec["arrivals"]) == (3, "sticky", 1, 10, 10, 40, "open",
                                  "poisson")
    assert isinstance(spec["schedule_seed"], int)
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    shapes = traffic.shapes(spec)
    assert shapes["cold"] == [1136, 6384, 12400, 12528]
    assert len(shapes["prefix"]) == 8
    assert shapes["longest_context"] == 13776
    # under the window and past it in one queue
    assert shapes["cold"][0] + 2 * (256 + 112) + 256 \
        < conf["sliding_window"] < shapes["cold"][1]
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)


def test_the_forms_the_traffics_shapes_run(conf):
    """A hit's suffix (128 or 256 tokens) runs the dense form over the
    16 held experts, every cold prompt the sorted form over the held
    pairs, one pass of 1.25 x the expected pairs in whole tiles."""
    from infinistore_tpu.models import moe

    _, cfg = serve.model_config(conf)
    shapes = traffic.shapes(traffic.load(TRAFFIC))
    for suffix, _ in shapes["prefix"]:
        assert moe.GATHERED_EXPERTS_MAX_ROWS < suffix
        assert suffix * cfg.n_routed <= moe.DENSE_EXPERTS_MAX_ROWS
    assert [moe.held_rows(t, cfg) for t in shapes["cold"]] \
        == [1536, 8192, 15872, 15872]
    for t in shapes["cold"]:
        assert t * cfg.n_routed > moe.DENSE_EXPERTS_MAX_ROWS
        assert t <= moe.held_rows(t, cfg) < 1.4 * t + 512


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/cohere_moe.py")
    with open(path) as f:
        text = f.read()
    body = text.split('"""', 2)[2]
    assert "infinistore" not in body and "from ." not in body


@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    assert cfg.two_kinds and cfg.window_band == 512 and cfg.n_layers == 4
    assert (cfg.n_routed, cfg.n_experts, cfg.first_expert) == (8, 2, 2)
    assert cfg.n_heads // cfg.n_kv_heads == 16
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, length)
    pos = list(range(0, length, 7))
    ref, margins = serve.reference_module(tiny).forward(params, tiny, toks,
                                                        pos)
    got = model.prefill(params, cfg, jnp.asarray(toks[None], jnp.int32))[0]
    assert np.asarray(ref).shape == (len(pos), 512)
    assert np.asarray(margins).shape == (len(pos), 4)
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
    obs.counters.update(by_hand.COUNTERS)
    return obs


@pytest.mark.parametrize("name", list(NEW))
def test_reader_on_the_hand_built_window(name, monkeypatch):
    from infinistore_tpu.utils import profiling

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    r = manifest.reader(name)
    assert r.read(window()) == pytest.approx(by_hand.BY_HAND[name],
                                             rel=1e-9)
    assert (r.UNIT, r.LAYER) == NEW[name] and r.MOVES == "itl_mean_ms"
    assert 0 < by_hand.BY_HAND[name] < 100 and r.BETTER == BETTER[name]
    # 13.0 % of the pairs held where even routing holds 12.5
    assert by_hand.BY_HAND["moe_held_pair_skew"] == 0.5
    assert by_hand.BY_HAND["moe_held_rows_share"] == pytest.approx(71.038,
                                                                   abs=1e-3)


@pytest.mark.parametrize("name", list(NEW))
def test_reader_gives_nothing_on_a_program_without_the_counts(
        name, monkeypatch):
    """A parent commit, or a model that holds every expert, measured
    with this benchmark: no such counter moves, no prefill span carries
    the fields. None, and nothing raised."""
    from infinistore_tpu.utils import profiling
    import test_bench_observations as table

    ring = [s._replace(fields={k: v for k, v in s.fields.items()
                               if k not in ("pairs_held", "rows_computed")})
            for s in by_hand.RING]
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    assert manifest.reader(name).read(table.full_window()) is None


def test_no_reader_parses_a_name_the_program_does_not_emit():
    def text(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    program = text("infinistore_tpu/serving.py") \
        + text("infinistore_tpu/models/moe.py")
    assert f'"{manifest.reader("moe_held_rows_share").SPAN}"' in program
    for field in ("pairs_held", "rows_computed"):
        assert f'f["{field}"]' in program
    for counter in ("moe_pairs_routed", "moe_pairs_held",
                    "moe_rows_computed"):
        assert f'"{counter}"' in program
    for scope in ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
                  "moe.shared"):
        assert f'named_scope("{scope}")' in program, scope
    for name in APPENDED:
        r = manifest.reader(name)
        if hasattr(r, "COST"):
            assert hasattr(costs, r.COST), r.COST
    assert hasattr(costs, "moe_prefill_flops")


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    # 10 s: the plan (schedule_seed 155) then opens the window with a long
    # cold session (10.3 s) beside the turns of three begun in the ramp;
    # under the first order a 6 s window held its only long ones 1.4 and
    # 0.6 s before its end, and on a loaded machine their sub-floor
    # writes fell after it.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 42), "--seconds", "10",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    want = {"prefix_hit_share", "restore_gbps", "offload_gbps",
            "store_read_p99_us", "store_write_p99_us", "admit_hit_p50_ms",
            "admit_miss_p50_ms", "offload_stall_p50_ms",
            "decode_host_p50_ms", "moe_held_rows_share"}
    assert want | {"moe_held_pair_skew"} <= set(res["metrics"]), \
        sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    # every hit restored what its length implies, per kind: 1 full and
    # 3 window layers
    assert c["prefix_hit_pages"] > 0 and c["restore_misses"] == 0
    assert c["restored_pages"] == 2 * (
        c["prefix_hit_pages"]
        + 3 * (c["prefix_hit_pages"] - c["restore_trimmed_pages"]))
    assert c["subfloor_pages_written"] > 0
    # 2 of the router's 8 experts are held: a quarter of the pairs
    assert 0 < c["moe_pairs_held"] < c["moe_pairs_routed"]
    assert 0.15 < c["moe_pairs_held"] / c["moe_pairs_routed"] < 0.4
    assert c["moe_rows_computed"] > 0
    # its distance from the even share, 2 of 8
    assert res["metrics"]["moe_held_pair_skew"]["value"] == pytest.approx(
        abs(100.0 * c["moe_pairs_held"] / c["moe_pairs_routed"] - 25.0))
    assert w["store_errors"] == 0 and w["engine_ok"] is True
