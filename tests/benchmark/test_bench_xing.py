"""The configuration xing4-29b and its cell xing4-29b-docs32k (PR 40):
the file against the catalog row, its costs module against numbers
worked by hand and against the program's own parameters, the
share-nothing float32 reference against the program at tiny widths,
the four new readers on a hand-built window, the traffic's shapes, and
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

import xing_by_hand as by_hand
from benchmark.configs import xing4_29b_costs as costs
from benchmark.lib import manifest, serve, traffic
from benchmark.metrics import _scoped_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "xing4-29b", "xing4-29b-docs32k"
FILE = f"benchmark/configs/{CONFIG}.json"
NEW = {"latent_attn_roofline_share": ("%", "Kernels"),
       "latent_prefill_mfu": ("%", "Kernels"),
       "hc_mix_roofline_share": ("%", "Kernels"),
       "admit_piece_p50_ms": ("ms", "Scheduler and cache manager")}
APPENDED = ("prefix_hit_share", "prefill_ms_per_ktok", "prefill_mfu",
            "restore_gbps", "store_read_p99_us", "admit_hit_p50_ms",
            "moe_prefill_mfu", "moe_step_roofline_share",
            "idle_no_work_share", "host_held_idle_share",
            "decode_dispatch_lead_p50_ms", "decode_return_lag_p50_ms",
            "store_allocate_us_per_key", "store_write_gbps")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ("num_hidden_layers", "first_k_dense_replace",
       "num_nextn_predict_layers")


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
        CONFIG, "docs32k", 1)
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == list(CUT) and entry["file"] == FILE
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
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
                   if r["name"] == "Xing4.0-29B-A4B")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CUT:
            cut = conf["reduced"][key]
            assert cut["published"] == value and cut["here"] == conf[key]
            assert cut["here"] != value and len(cut["why"]) > 80
        else:
            assert conf[key] == value, key
    assert set(conf["reduced"]) == set(CUT)
    assert (conf["num_hidden_layers"], conf["first_k_dense_replace"],
            conf["num_nextn_predict_layers"]) == (6, 1, 0)
    for group in ("assumed", "deployment", "guarantees"):
        assert conf[group]
    # (a) .. (e) of the issue, each said
    said = " ".join(conf["assumed"])
    for mark in ("(a)", "(b)", "(c)", "(d)", "(e)"):
        assert mark in said
    assert conf["serving"] == {"page_size": 16, "max_slots": 8,
                               "max_pages_per_seq": 2144,
                               "total_pages": 17153,
                               "admit_piece": conf["serving"]["admit_piece"]}
    assert conf["serving"]["admit_piece"] in (4096, 8192)


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.xing"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.vocab_size) == (
        3584, 6, 32, 131072)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope, cfg.qk_rope,
            cfg.v_dim) == (768, 512, 128, 64, 128)
    assert (cfg.n_dense_lead, cfg.ffn_dense, cfg.n_experts, cfg.d_ff,
            cfg.top_k, cfg.n_shared, cfg.route_scale) == (
        1, 9216, 64, 1024, 4, 1, 2.0)
    assert (cfg.hc_mult, cfg.hc_iters, cfg.hc_eps, cfg.hc_clamp) == (
        4, 20, 1e-6, 30.0)
    assert cfg.yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.latent_width == 640 == costs.stored_row_values(conf)
    assert cfg.kv_page_bytes() == 20480 == 5 * costs.store_block_bytes(conf)
    from infinistore_tpu.models import decoder
    assert decoder.latent_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)


def test_the_programs_parameters_are_what_the_costs_count(conf):
    model, cfg = serve.model_config(conf)
    tree = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(x.size) for x in leaves) == costs.param_count(conf)
    assert sum(int(x.size) * x.dtype.itemsize for x in leaves) \
        == costs.weight_bytes(conf) == 9_596_236_304


def test_costs_by_hand(conf):
    h = by_hand
    assert costs.attn_params(conf) == h.ATTN == 28_411_136
    assert costs.expert_params(conf) == h.EXPERT == 11_010_048
    assert costs.router_params(conf) == h.ROUTER
    assert 2 * costs.hc_params(conf) == 2 * h.HC == 716_854
    assert costs.layer_params(conf, True) == h.SPARSE_LAYER
    assert costs.layer_params(conf, False) == h.DENSE_LAYER
    assert costs.param_count(conf) == h.PARAMS
    assert costs.weight_bytes(conf) == 2 * h.PARAMS + 2 * h.F32_PARAMS
    assert 9.59e9 < costs.weight_bytes(conf) < 9.60e9
    # the cache: 576 values needed, 640 stored
    assert costs.latent_values(conf) == 576
    assert costs.page_bytes_all_layers(conf) == 6 * 16 * 640 * 2 == 122_880
    # a page is 16 x 640 x 2 B = 20,480 B; the store's unit is a power
    # of two of KB: 4 KB, of which a page takes 5
    assert costs.store_block_bytes(conf) == 4096
    assert costs.store_block_bytes(conf, 16, 4) == 8192
    assert costs.snapshot_bytes(conf) == 0
    assert costs.latent_attn_bytes(conf, 16, 28_800) == 199_065_600
    # 3 tokens over 16 cached: pairs 3 x 16 + 6 = 54
    assert costs.latent_prefill_flops(conf, 3, 16) == 6 * (
        32 * 54 * 2 * 320 + 19 * 2 * 512 * 32 * 256)
    assert costs.latent_prefill_flops(conf, 8192, 0) == 4_535_988_781_056
    assert costs.hc_prefill_bytes(conf, 10) == 10 * 12 * 12 * 3584 * 2
    # 8 tokens touch 64 (1 - (60 / 64) ** 8) = 25.8... of 64 experts
    touched = 64 * (1 - (60 / 64) ** 8)
    assert costs.expected_experts_touched(conf, 8) == pytest.approx(touched)
    assert costs.moe_step_bytes(conf, 8) == pytest.approx(
        5 * ((touched + 1) * h.EXPERT * 2 + h.ROUTER * 4))
    assert costs.moe_prefill_flops(conf, 100) == 2 * 100 * 5 * (
        5 * h.EXPERT + h.ROUTER)
    token = 6 * (h.ATTN + 2 * h.HC_F32) + h.DENSE_MLP \
        + 5 * (5 * h.EXPERT + h.ROUTER)
    assert costs.decode_bytes(conf, 8, 1000) == pytest.approx(
        6 * ((h.ATTN + 2 * 3584 + 2 * 14_336) * 2 + 2 * h.HC_F32 * 4)
        + h.DENSE_MLP * 2 + costs.moe_step_bytes(conf, 8)
        + (131_072 * 3584 + 3584) * 2 + 8 * 3584 * 2
        + 6 * 1000 * 1152)
    assert costs.decode_flops(conf, 8, 1000) == 2 * 8 * (
        token + 3584 * 131_072) + 6 * 32 * 1000 * 2 * (576 + 512)
    assert costs.prefill_flops(conf, 3, 16) == 2 * 3 * (
        token - 6 * 512 * 32 * 256) + costs.latent_prefill_flops(
        conf, 3, 16) + 2 * 3584 * 131_072


def test_the_store_pool_is_sized_from_what_an_offload_writes(conf):
    from benchmark.lib import cell
    _, cfg = serve.model_config(conf)
    spec = traffic.load("benchmark/traffic/docs32k.json")
    pool_gb, block_kb = cell.store_sizes(conf, cfg, spec)
    assert block_kb == 4
    per_s = spec["session_rate_per_s"] * traffic.pages_written_per_session(
        spec) * 122_880
    assert pool_gb >= per_s * 40 / 2 ** 30 > pool_gb - 0.25


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load("benchmark/traffic/docs32k.json")
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (16384, 112, 48, 0.4), (16384, 240, 112, 0.2),
        (32768, 112, 48, 0.3), (32768, 240, 112, 0.1)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"], spec["loop"],
            spec["arrivals"]) == (4, "sticky", 1, 10, 10, 40, "open",
                                  "poisson")
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    shapes = traffic.shapes(spec)
    assert len(shapes["cold"]) == 4 and len(shapes["prefix"]) == 12
    assert shapes["longest_context"] == 34176
    assert shapes["pages_longest"] <= conf["serving"]["max_pages_per_seq"]
    # the hits' (suffix, prefix) pairs: the new message and the last
    # answer's unstored tail page, over everything stored before
    assert min(p for _, p in shapes["prefix"]) == 16528
    assert max(p for _, p in shapes["prefix"]) == 33808
    assert {s for s, _ in shapes["prefix"]} == {128, 256}
    # the cold prompts' pieces: programs per (tokens, prefix pages)
    piece = conf["serving"]["admit_piece"]
    programs = set()
    for n in shapes["cold"]:
        for done in range(0, n, piece):
            programs.add((min(piece, n - done), done // 16))
    whole = {p for p in programs if p[0] == piece}
    assert len(programs - whole) == 4                  # the tails
    assert len(whole) == 32768 // piece                # over 0, 1, 2 .. pieces
    # one token a page is left to prefill, so every cold prompt is
    # admitted in pieces and every hit in one program
    assert min(shapes["cold"]) > piece
    assert max(s for s, _ in shapes["prefix"]) <= piece
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/xing_latent.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    assert cfg.hc_mult == 4 and cfg.n_layers == 6 and cfg.latent_width == 128
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(0, cfg.vocab_size, length)
    pos = list(range(0, length, 7))
    ref, margins = serve.reference_module(tiny).forward(params, tiny, toks,
                                                        pos)
    got = model.prefill(params, cfg, jnp.asarray(toks[None], jnp.int32))[0]
    assert np.asarray(ref).shape == (len(pos), 512)
    assert np.asarray(margins).shape == (len(pos), 6)
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
    """A parent commit, or another family, measured with this
    benchmark: no pieces in the ring, no hc. / attn.expand scopes in
    the trace, a costs module without these counts. None, and nothing
    raised."""
    from infinistore_tpu.utils import profiling

    ring = [s for s in by_hand.RING if s.name.startswith("istpu.engine")]
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    assert manifest.reader(name).read(window()) is None
    import test_bench_observations as table

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    if name != "admit_piece_p50_ms":  # mistral7b's costs have no such
        assert manifest.reader(name).read(table.full_window()) is None


def test_no_reader_parses_a_name_the_program_does_not_emit():
    """The spans, counters and scopes the four readers and the cell's
    lines read are the ones the program writes."""
    def text(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    program = text("infinistore_tpu/serving.py") \
        + text("infinistore_tpu/models/decoder.py") \
        + text("infinistore_tpu/models/moe.py")
    for name in NEW:
        r = manifest.reader(name)
        for scope in getattr(r, "SCOPES", ()):
            assert f'named_scope("{scope}' in program, scope
        if hasattr(r, "SPAN"):
            assert f'"{r.SPAN}"' in program
        if hasattr(r, "COST"):
            assert hasattr(costs, r.COST)
    for scope in ("attn.absorb", "attn.expand", "attn.kernel", "hc.coef",
                  "hc.mix", "moe.shared"):
        assert f'named_scope("{scope}")' in program, scope
    for counter in ("admit_pieces", "latent_pages_written",
                    "latent_pages_restored"):
        assert f'"{counter}"' in program


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds", "6",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    want = {"prefix_hit_share", "restore_gbps", "offload_gbps",
            "store_read_p99_us", "store_write_p99_us", "admit_hit_p50_ms",
            "offload_stall_p50_ms", "decode_host_p50_ms",
            "admit_piece_p50_ms"}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    # every hit restored one page a layer of what its length implies
    assert c["prefix_hit_pages"] > 0 and c["restore_misses"] == 0
    assert c["restored_pages"] == 6 * c["prefix_hit_pages"] \
        == c["latent_pages_restored"]
    assert c["latent_pages_written"] == 6 * c["offloaded_pages"] > 0
    assert c["admit_pieces"] > 0
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    assert check["logit_rows"]["cold"]["taken"] == 4
    assert check["logit_rows"]["hit"]["taken"] == 4
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
