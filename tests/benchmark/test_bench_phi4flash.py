"""The cell phi4-mini-flash-traces12k (PR 53): its configuration file
against the catalog row, the costs module against the program's own
parameter tree and a table worked by hand (phi_by_hand.py), the traffic
file's lengths and program count, the two new readers on a hand-built
window, and the traced rehearsal end to end."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import phi_by_hand as by_hand
from benchmark.configs import phi4_mini_flash_costs as costs
from benchmark.lib import manifest, serve, traffic
from benchmark.metrics import _scoped_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "phi4-mini-flash", "phi4-mini-flash-traces12k"
FILE = f"benchmark/configs/{CONFIG}.json"
TRAFFIC = "benchmark/traffic/traces12k.json"
NEW = {"shared_kv_attn_roofline_share": ("%", "Kernels", "higher"),
       "admit_rows_run_share": ("%", "Model step", "lower")}
APPENDED = ("ssm_step_roofline_share", "ssm_scan_mfu",
            "window_attn_roofline_share", "full_attn_roofline_share",
            "prefill_ms_per_ktok", "prefill_mfu", "snapshot_restore_p50_ms",
            "snapshot_offload_p50_ms", "window_release_p50_ms",
            "state_active_share", "prefix_hit_share", "restore_gbps",
            "store_read_p99_us", "admit_hit_p50_ms",
            "store_allocate_us_per_key", "store_write_gbps",
            "decode_ahead_share", "gap_engine_mean_ms", "gap_step_ms",
            "gap_admit_miss_ms", "gap_admit_hit_ms", "gap_offload_ms",
            "gap_other_ms", "gap_stalled_share", "gap_stalled_p50_ms",
            # what an admission's stall moves (asked for in review)
            "itl_tail_p95_ms", "idle_no_work_share", "host_held_idle_share")
# ... and not on these: the two decode_ readers wait for their own
# issue (ROADMAP R0); an admission is one program, never pieces; the
# idle shares' lists ended before PR 46; the rest read other families'
# scopes and counts.
NOT_LISTED = ("decode_dispatch_lead_p50_ms", "decode_return_lag_p50_ms",
              "gap_admit_piece_ms", "admit_piece_p50_ms",
              "moe_prefill_mfu", "moe_step_roofline_share",
              "latent_attn_roofline_share", "sparse_attn_roofline_share",
              "select_active_share")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


def test_the_cell_its_configuration_and_its_two_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert len(bench["workloads"]) == 11 and len(bench["configs"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "traces12k", 1)
    assert len(cell["why"]) <= 200
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == [] and entry["file"] == FILE
    assert entry["source"] == SOURCE
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)
    per = {m["name"]: m for m in manifest.metrics_for(bench, CELL,
                                                      "per_layer")}
    for name, (unit, layer, better) in NEW.items():
        m = per[name]
        assert (m["unit"], m["layer"], m["better"], m["moves"],
                m["workloads"]) == (unit, layer, better, "itl_mean_ms",
                                    [CELL])
    assert set(APPENDED) | set(LIST_FREE) <= set(per)
    for name in APPENDED:
        assert per[name]["workloads"][-1] == CELL
    assert not set(NOT_LISTED) & set(per)
    e2e = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                   "end_to_end")}
    assert e2e == {"itl_mean_ms", "setup_s"}


def test_the_file_carries_the_catalog_row_whole(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert conf["source"] == row["source_url"] == SOURCE
    for key, value in row["config"].items():
        assert conf[key] == value, key
    assert conf["reduced"] == {}
    for group in ("assumed", "deployment", "guarantees"):
        assert conf[group]
    assert (conf["mamba_d_state"], conf["mamba_d_conv"], conf["mamba_expand"],
            conf["mamba_dt_rank"]) == (16, 4, 2, 160)
    assert "BY HALVES" in " ".join(conf["assumed"])
    assert len(conf["guarantees"]) == 5
    assert "all three of its kinds" in conf["guarantees"][1]
    assert conf["serving"] == {"page_size": 16, "max_slots": 16,
                               "max_pages_per_seq": 992,
                               "total_pages": 16384}
    tiny = serve.load_config(FILE, rehearsal=True)
    assert tiny["num_hidden_layers"] == 8 and tiny["sliding_window"] == 64


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.phi_flash"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        2560, 32, 40, 20, 64, 10240, 200064)
    assert (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.dt_rank) == (
        5120, 16, 4, 160)
    assert cfg.layer_kinds == tuple(k for k, _ in costs.layout(conf))
    assert cfg.layer_windows == tuple(b for _, b in costs.layout(conf))
    assert cfg.two_kinds and cfg.n_kv_layers == 9 and cfg.n_state_layers == 9
    assert cfg.norm_eps == 1e-5 and not cfg.use_rope and cfg.norm_center
    assert cfg.kv_pack == 2 and cfg.pair_rows and cfg.page_rows == 10
    assert cfg.kv_page_shape() == (160, 128)
    assert cfg.kv_page_bytes() == by_hand.K_PAGE
    assert cfg.state_dtype == "float32"
    assert sum(int(np.prod(s)) for s in cfg.state_shapes().values()) * 4 \
        * 9 == by_hand.STATE == costs.state_bytes(conf)


def test_the_programs_parameters_are_what_the_costs_count(conf):
    model, cfg = serve.model_config(conf)
    tree = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(x.size) for x in leaves) == costs.param_count(conf) \
        == by_hand.PARAMS == 3_852_562_944
    assert sum(int(x.size) * x.dtype.itemsize for x in leaves) \
        == costs.weight_bytes(conf) == by_hand.WEIGHT_BYTES \
        == 7_706_792_960
    # the engine's pools are what the file's deployment says
    from benchmark.tools.aot_memory import engine_pools
    held = engine_pools(model, cfg, serve.serving_config(conf, "t"))
    assert held["k_pages"].shape == held["v_pages"].shape == (
        1, 16384, 160, 128)
    assert held["wk_pages"].shape == held["wv_pages"].shape == (
        8, 16 * 40 + 1, 160, 128)
    pools = 2 * (16384 + 8 * 641) * by_hand.K_PAGE
    states = 2 * 16 * by_hand.STATE
    assert pools == 1_762_263_040 and states == 112_066_560
    # 9.58 GB held: 57 % of the chip before any temporary
    assert 9.57e9 < pools + states + costs.weight_bytes(conf) < 9.59e9


def test_costs_by_hand(conf):
    h = by_hand
    assert costs.mlp_params(conf) == h.MLP == 78_643_200
    assert costs.mamba_params(conf) == h.MAMBA == 41_241_600
    assert costs.attn_params(conf) == h.ATTN == 19_668_864
    assert costs.cross_params(conf) == h.CROSS == 13_112_704
    assert costs.gmu_params(conf) == h.GMU == 26_214_400
    assert [h.MAMBA + h.MLP + h.NORMS, h.ATTN + h.MLP + h.NORMS,
            h.GMU + h.MLP + h.NORMS, h.CROSS + h.MLP + h.NORMS] == [
        119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert costs.param_count(conf) == h.PARAMS
    assert costs.weight_bytes(conf) == h.WEIGHT_BYTES
    assert costs.kv_bytes_per_token_layer(conf) == h.KV_TOKEN_LAYER == 5120
    assert costs.page_bytes_all_layers(conf) == h.PAGE_ALL_LAYERS
    assert costs.store_block_bytes(conf) == 8192      # 40 KB = 5 units
    assert h.K_PAGE % costs.store_block_bytes(conf) == 0
    assert costs.snapshot_bytes(conf) == h.SNAPSHOT == 3_686_400
    # a decode step of 13 sequences of 11,000 live tokens
    live = 13 * 11_000
    assert costs.full_attn_bytes(conf, 13, live) == live * 5120
    assert costs.shared_kv_attn_bytes(conf, 13, live) == 7 * live * 5120
    assert costs.window_attn_bytes(conf, 13, live) == 8 * 13 * 512 * 5120
    assert costs.window_attn_bytes(conf, 13, 1000) == 8 * 1000 * 5120
    mixers = 9 * (h.MAMBA - (5120 * 18)) * 2 + 9 * 5120 * 18 * 4 \
        + 7 * h.GMU * 2
    assert costs.ssm_step_bytes(conf, 13) == 2 * 13 * h.STATE + mixers
    assert costs.decode_bytes(conf, 13, live) == (
        h.WEIGHT_BYTES + 13 * 2560 * 2 + 2 * 13 * h.STATE
        + 8 * live * 5120 + 8 * 13 * 512 * 5120)
    assert 13.9e9 < costs.decode_bytes(conf, 13, live) < 14.0e9
    # an admission of 12,400 tokens: 17 layers and layer 17's K and V on
    # every row, the rest on one
    mamba_mm = h.MAMBA - (5120 * 4 + 5120 + 5120 * 18)
    every = 17 * h.MLP + 9 * mamba_mm + 8 * (2 * 2560 * 2560
                                             + 2 * 2560 * 1280) \
        + 2 * 2560 * 1280
    one = 15 * h.MLP + 8 * 2 * 2560 * 2560 + 7 * h.GMU
    s = 12_400
    banded = sum(min(i + 1, 512) for i in range(s))
    assert costs.banded_pairs(conf, s) == banded
    assert costs.ssm_scan_flops(conf, s) == s * 9 * 6 * 16 * 5120
    assert costs.prefill_flops(conf, s) == (
        2 * s * every + 2 * one + 8 * 40 * banded * 256
        + 8 * 40 * s * 256 + s * 9 * 6 * 16 * 5120 + 2 * 2560 * 200_064)
    assert 46e12 < costs.prefill_flops(conf, s) < 48e12
    # ... of the 83 TFLOP every row through every layer would take
    whole = 2 * s * (every + one - 2 * 2560 * 1280 + 2 * 2560 * 1280)
    assert 1.7 < whole / (2 * s * every) < 1.8
    # a hit: 128 tokens over 13,408
    assert costs.banded_pairs(conf, 128, 13_408) == 128 * 512
    assert costs.decode_flops(conf, 13, live) == (
        2 * 13 * (every + one + 2560 * 200_064) + 8 * 40 * live * 256
        + 8 * 40 * 13 * 512 * 256 + 13 * 9 * 6 * 16 * 5120)


def test_the_store_pool_is_sized_from_what_an_offload_writes(conf):
    from benchmark.lib import cell
    _, cfg = serve.model_config(conf)
    spec = traffic.load(TRAFFIC)
    pool_gb, block_kb = cell.store_sizes(conf, cfg, spec)
    assert block_kb == 8
    per_s = spec["session_rate_per_s"] * (
        traffic.pages_written_per_session(spec) * 737_280
        + traffic.offloads_per_session(spec) * 3_686_400)
    assert pool_gb >= per_s * 40 / 2 ** 30 > pool_gb - 0.5


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load(TRAFFIC)
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (8192, 112, 512, 0.25), (8192, 240, 1024, 0.25),
        (12288, 112, 1024, 0.25), (12288, 240, 512, 0.25)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"], spec["loop"],
            spec["arrivals"]) == (3, "sticky", 1, 10, 10, 40, "open",
                                  "poisson")
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    assert isinstance(spec["schedule_seed"], int)
    shapes = traffic.shapes(spec)
    assert shapes["cold"] == [8304, 8432, 12400, 12528]
    assert len(shapes["prefix"]) == 8
    assert shapes["longest_context"] == 15696
    assert shapes["pages_longest"] == 981 \
        <= conf["serving"]["max_pages_per_seq"]
    # turns 2-3 restore 550-909 whole pages of the full layer, the last
    # 32 of the eight banded layers and a snapshot
    hits = sorted(p // 16 for _, p in shapes["prefix"])
    assert (hits[0], hits[-1]) == (550, 909)
    assert hits[0] * 2 * by_hand.K_PAGE == 45_056_000
    assert 32 * 8 * 2 * by_hand.K_PAGE == 20_971_520
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)
    assert spec["knee"]["config"] == CONFIG


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/phi4_flash.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    """The file's rehearsal preset through the harness's own loaders:
    under the band (64) and over three of it."""
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length).astype(np.int32)
    pos = list(range(length))
    ref, margins = serve.reference_module(tiny).forward(params, tiny, toks,
                                                        pos)
    assert margins is None
    logits = model.forward_dense(params, cfg, jnp.asarray(toks[None]))[0]
    assert np.abs(np.asarray(logits[0]) - np.asarray(ref)).max() < 2e-4
    # padding behind the last position asked for is inert
    padded, _ = serve.reference_module(tiny).forward(
        params, tiny, np.concatenate([toks, np.zeros(24, toks.dtype)]), pos)
    # (float32 sums in another order: at the file's `cross_out_gain`
    # rows of |logit| 1-3 differ by up to 1e-5)
    assert np.allclose(ref, padded, atol=5e-5)


def test_the_tolerances_lie_between_their_readings(conf):
    """Each limit between ITS two readings: first-token rows under
    `logit_tol` (the admission programs: a cross layer that reads
    nothing of the shared cache is a second reading now), answered
    tokens under `token_eps` (the decode program: a borrower that reads
    nothing, or through another slot's table)."""
    from benchmark.lib import correct
    tol = correct.tolerances_for(conf)
    r = tol["readings"]
    assert max(r["first"].values()) < tol["logit_tol"] < min(
        r["second"].values())
    for fault in ("band_minus_a_page", "no_lambda", "memory_after_gate",
                  "one_cross_blind", "all_cross_blind", "fp8_reference"):
        assert fault in r["second"]
    assert max(r["token_first"].values()) < tol["token_eps"] < min(
        r["token_second"].values())
    for fault in ("one_borrower_blind", "all_borrowers_blind",
                  "borrowers_next_table"):
        assert fault in r["token_second"]
    # room on both sides: at least 1.5 times each way
    assert 1.5 * max(r["first"].values()) < tol["logit_tol"]
    assert 1.5 * tol["logit_tol"] < min(r["second"].values())
    assert 1.5 * max(r["token_first"].values()) < tol["token_eps"]
    assert 1.5 * tol["token_eps"] < min(r["token_second"].values())
    # ... and what NO limit tells is said, with its reading under them
    unseen = r["not_told_apart"]
    assert set(unseen) == {"bf16_state"}
    assert max(unseen.values()) < tol["logit_tol"]
    # the fixture the readings were taken under is the file's
    assert conf["random_init"]["cross_out_gain"] == 8.0


# -- the readers -------------------------------------------------------------
def window():
    import test_bench_observations as table

    obs = table.full_window()
    obs.counters.update(by_hand.COUNTERS)
    obs.conf = serve.load_config(FILE)
    return obs


def scoped(obs, kind, scopes):
    return by_hand.SCOPED[kind, tuple(scopes)]


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_hand_built_window(name, monkeypatch):
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    r = manifest.reader(name)
    want = by_hand.BY_HAND[name]
    assert r.read(window()) == pytest.approx(want, rel=1e-9)
    assert (r.UNIT, r.LAYER, r.BETTER) == NEW[name]
    assert r.MOVES == "itl_mean_ms" and 0 < want < 100


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_a_program_without_its_scope_or_counters(
        name, monkeypatch):
    """A parent commit, or another family, measured with this
    benchmark: no scoped operation in the trace, no such counter, a
    costs module without the count. None, and nothing raised."""
    import test_bench_observations as table

    r = manifest.reader(name)
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    bare = table.full_window()
    bare.conf = serve.load_config(FILE)
    assert r.read(bare) is None      # the parent under this file
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    assert r.read(table.full_window()) is None       # mistral7b's costs


def test_no_reader_parses_a_name_the_program_does_not_emit():
    """The scopes and counts the readers on this cell's lists read are
    the ones the program writes and this costs module has."""
    def text(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    program = text("infinistore_tpu/serving.py") \
        + text("infinistore_tpu/models/decoder.py") \
        + text("infinistore_tpu/ops/ssm.py")
    for name in list(NEW) + list(APPENDED):
        r = manifest.reader(name)
        for scope in getattr(r, "SCOPES", ()):
            if scope.startswith("attn.kernel."):  # composed by pool
                assert scope.rsplit(".", 1)[1] in ("full", "window",
                                                   "cross")
                assert '"attn.kernel.cross"' in program \
                    and 'f"attn.kernel.{pool}"' in program
            elif not scope.endswith("."):
                assert f'named_scope("{scope}")' in program, scope
        if hasattr(r, "COST"):
            assert hasattr(costs, r.COST), r.COST
    for count in ("ssm_step_bytes", "ssm_scan_flops"):
        assert hasattr(costs, count)
    for counter in ("stack_rows_run", "stack_rows_all",
                    "shared_kv_rows_read"):
        assert f'"{counter}"' in program
    for scope in ("ssm.gmu", "attn.diff", "attn.kernel.cross"):
        assert f'named_scope("{scope}")' in program


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds", "8",
         "--trace", "1", "--rehearsal", "--rate", "0.5"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    want = {"offload_gbps", "store_write_p99_us", "offload_stall_p50_ms",
            "decode_host_p50_ms", "state_active_share",
            "admit_rows_run_share", "decode_ahead_share"}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)
    # the rehearsal's 8 layers cut at layer 5: (5 s + 3) / 8 s
    assert 62 < res["metrics"]["admit_rows_run_share"]["value"] < 63.5
    assert res["metrics"]["state_active_share"]["value"] == 100.0

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    assert 0 < c["stack_rows_run"] < c["stack_rows_all"]
    assert c["shared_kv_rows_read"] > 0       # one cross layer
    assert c["state_rows_run"] == c["state_rows_active"] \
        == c["decoded_tokens"] > 0
    assert c["snapshots_written"] > 0 and c["boundary_copies"] > 0
    assert c["window_pages_released"] > 0
    assert c["subfloor_pages_written"] == 0     # no snapshot below a band
    assert c["restore_misses"] == 0 and c["snapshot_misses"] == 0
    assert c["admit_pieces"] == 0 and c["latent_pages_written"] == 0
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    assert check["logit_rows"]["cold"]["taken"] == 4
    assert check["logit_rows"]["hit"]["taken"] == 4
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
