"""The cell glm-5.2-docs32k-answers (PR 46): its configuration file
against the catalog row, the costs module against the program's own
parameter tree and a table worked by hand (glm_by_hand.py), the traffic
file's lengths and program count, the five new readers on a recorded
span set, and the traced rehearsal end to end."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_by_hand as by_hand
from benchmark.configs import glm_5_2_costs as costs
from benchmark.lib import manifest, serve, traffic
from benchmark.metrics import _scoped_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "glm-5.2", "glm-5.2-docs32k-answers"
FILE = f"benchmark/configs/{CONFIG}.json"
TRAFFIC = "benchmark/traffic/docs32k-answers.json"
NEW = {"sparse_attn_roofline_share": ("%", "Kernels"),
       "index_score_roofline_share": ("%", "Kernels"),
       "index_topk_ms": ("ms", "Kernels"),
       "index_prefill_mfu": ("%", "Kernels"),
       "sparse_attn_rows_share": ("%", "Model step")}
APPENDED = ("prefill_ms_per_ktok", "moe_prefill_mfu",
            "moe_held_rows_share", "admit_piece_p50_ms",
            "latent_prefill_mfu", "prefix_hit_share", "restore_gbps",
            "admit_hit_p50_ms", "store_read_p99_us",
            "store_allocate_us_per_key", "store_write_gbps",
            "decode_ahead_share")
# ... and not on these: the dense latent kernel does not run in this
# cell's decode, the two decode_ readers wait for their own issue
# (ROADMAP R0), the skew's reader reads another family's key, and
# prefill_mfu takes a probe's keys for the prompt's pages, of which
# this family's probe carries two a page (PERF.md section 7); the two
# idle shares find no idle to split in this cell's traced seconds (the
# order of arrivals puts a cold admission there: busy 3.96 of 4 s).
NOT_LISTED = ("latent_attn_roofline_share", "moe_step_roofline_share",
              "prefill_mfu", "idle_no_work_share", "host_held_idle_share",
              "decode_dispatch_lead_p50_ms",
              "decode_return_lag_p50_ms", "moe_held_pair_skew")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ("num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
       "indexer_types", "n_routed_experts", "vocab_size",
       "num_nextn_predict_layers")


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


def test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "docs32k-answers", 1)
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == list(CUT) and entry["file"] == FILE
    assert entry["source"] == \
        "https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json"
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
    assert not set(NOT_LISTED) & set(per)
    e2e = {m["name"] for m in manifest.metrics_for(bench, CELL,
                                                   "end_to_end")}
    assert e2e == {"itl_mean_ms", "setup_s"}


def test_the_file_carries_the_catalog_row_but_the_cut(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CUT:
            cut = conf["reduced"][key]
            assert cut["here"] != value and len(cut["why"]) > 10
            if not isinstance(value, list):
                assert cut["published"] == value
                assert cut["here"] == conf[key]
        else:
            assert conf[key] == value, key
    assert set(conf["reduced"]) == set(CUT)
    # published layers 2-6: the indexer pattern's period in its 3:1
    assert conf["indexer_types"] == row["config"]["indexer_types"][2:7] \
        == ["full", "shared", "shared", "shared", "full"]
    assert conf["mlp_layer_types"] == row["config"]["mlp_layer_types"][2:7] \
        == ["dense", "sparse", "sparse", "sparse", "sparse"]
    assert (conf["num_hidden_layers"], conf["first_k_dense_replace"],
            conf["n_routed_experts"], conf["vocab_size"],
            conf["num_nextn_predict_layers"]) == (5, 1, 16, 19360, 0)
    assert conf["vocab_size"] * 8 == row["config"]["vocab_size"]
    share = conf["expert_share"]
    assert (share["router_width"], share["first_expert"], share["held"],
            share["chips_a_layer"]) == (256, 0, 16, 16)
    for group in ("assumed", "deployment", "guarantees"):
        assert conf[group]
    said = " ".join(conf["assumed"])
    for mark in ("(a)", "(b)", "(c)", "(d)", "(e)", "(f)"):
        assert mark in said
    assert "B / 32" in conf["deployment"] and "B / 2" in conf["deployment"]
    assert conf["serving"] == {"page_size": 16, "max_slots": 8,
                               "max_pages_per_seq": 2192,
                               "total_pages": 17537,
                               "admit_piece": conf["serving"]["admit_piece"]}
    assert conf["serving"]["admit_piece"] in (2048, 4096, 8192)
    # the rehearsal keeps the selection: a prompt is many times its k
    tiny = serve.load_config(FILE, rehearsal=True)
    assert tiny["index_topk"] == 32
    assert tiny["indexer_types"] == conf["indexer_types"]


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.glm"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.vocab_size) == (
        6144, 5, 64, 19360)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope, cfg.qk_rope,
            cfg.v_dim) == (2048, 512, 192, 64, 256)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (
        32, 128, 2048)
    assert cfg.indexer_kinds == ("full", "shared", "shared", "shared",
                                 "full")
    assert cfg.dense_layers == (True, False, False, False, False)
    assert (cfg.ffn_dense, cfg.n_experts, cfg.n_routed, cfg.first_expert,
            cfg.d_ff, cfg.top_k, cfg.n_shared, cfg.route_scale) == (
        12288, 16, 256, 0, 2048, 8, 1, 2.5)
    assert cfg.rope_theta == 8e6 and cfg.rope_adjacent and not cfg.yarn
    assert cfg.q_init_gain == conf["random_init"]["query_gain"] == 4.0
    assert cfg.o_init_gain == conf["random_init"]["attn_out_gain"] == 1 / 32
    assert cfg.down_init_gain == conf["random_init"]["ffn_out_gain"] \
        == 1 / 512
    assert cfg.latent_width == 640 == costs.stored_row_values(conf)
    assert cfg.page_kinds == "ci"
    assert int(np.prod(cfg.page_shape("c"))) * 2 == by_hand.LATENT_PAGE
    assert int(np.prod(cfg.page_shape("i"))) * 2 == by_hand.INDEX_PAGE \
        == costs.store_block_bytes(conf)
    assert cfg.page_layers("i") == (0, 4)
    from infinistore_tpu.models import decoder
    assert decoder.latent_scale(cfg) == pytest.approx(256 ** -0.5)


def test_the_programs_parameters_are_what_the_costs_count(conf):
    model, cfg = serve.model_config(conf)
    tree = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(x.size) for x in leaves) == costs.param_count(conf) \
        == 3_881_517_056
    assert sum(int(x.size) * x.dtype.itemsize for x in leaves) \
        == costs.weight_bytes(conf) == 7_775_619_072
    # the engine's pools are what the file's deployment says
    from benchmark.tools.aot_memory import engine_pools
    held = engine_pools(model, cfg, serve.serving_config(conf, "t"))
    assert held["k_pages"].shape == (5, 17537, 16, 640)
    assert held["v_pages"].shape == (2, 17537, 16, 128)


def test_costs_by_hand(conf):
    h = by_hand
    assert costs.attn_params(conf) == h.ATTN == 165_022_208
    assert costs.indexer_params(conf) == h.INDEXER == 9_371_904
    assert costs.expert_params(conf) == h.EXPERT
    assert costs.router_params(conf) == h.ROUTER
    assert costs.dense_mlp_params(conf) == h.DENSE_MLP
    assert costs.layer_params(conf, True, False) == h.EXPERT_LAYER \
        == 808_336_128
    assert costs.layer_params(conf, True, True) == 817_708_032
    assert costs.layer_params(conf, False, True) == h.DENSE_LAYER \
        == 400_898_816
    assert costs.param_count(conf) == h.PARAMS
    assert costs.weight_bytes(conf) == 2 * h.PARAMS + 2 * h.F32_PARAMS
    assert costs.page_bytes_all_layers(conf) == 110_592 \
        == 5 * h.LATENT_PAGE + 2 * h.INDEX_PAGE
    assert costs.store_block_bytes(conf) == 4096
    assert costs.snapshot_bytes(conf) == 0
    # what the selection leaves of a decode step of 8 sequences of
    # 30,000 tokens: 2,048 rows each in 5 layers, every key in 2
    assert costs.selected_rows(conf, 8, 240_000) == 16_384
    assert costs.selected_rows(conf, 8, 9_000) == 9_000
    assert costs.sparse_attn_bytes(conf, 8, 240_000) \
        == 5 * 16_384 * 1_152
    assert costs.index_score_bytes(conf, 8, 240_000) \
        == 2 * (240_000 * 256 + 2 * h.INDEXER)
    touched = 16 * (1 - (1 - 8 / 256) ** 8)          # 3.59 held experts
    assert costs.expected_experts_touched(conf, 8) == pytest.approx(touched)
    moe = 4 * ((touched + 1) * h.EXPERT * 2 + h.ROUTER * 4)
    assert costs.moe_step_bytes(conf, 8) == pytest.approx(moe)
    weights = (5 * (h.ATTN + h.NORMS) * 2 + h.DENSE_MLP * 2 + moe
               + (19_360 * 6144 + 6144) * 2 + 8 * 6144 * 2)
    assert costs.decode_bytes(conf, 8, 240_000) == pytest.approx(
        weights + 5 * 16_384 * 1_152 + 2 * (240_000 * 256 + 2 * h.INDEXER))
    # an admission: pairs the selection leaves, the scores' every pair
    assert costs.selected_pairs(conf, 4096, 28_672) == 4096 * 2048
    assert costs.selected_pairs(conf, 4096, 0) \
        == 2048 * 2049 // 2 + 2048 * 2048
    assert costs.selected_pairs(conf, 128, 1024) == 128 * 1024 + 8256
    assert costs.index_prefill_flops(conf, 128, 1024) == 0   # all selected
    assert costs.index_prefill_flops(conf, 4096, 4096) \
        == 2 * 25_167_872 * 8192
    wkvb = 2 * 512 * 64 * 448
    pairs = 4096 * 2048
    assert costs.latent_prefill_flops(conf, 4096, 28_672) == 5 * min(
        64 * pairs * 2 * 512 + 32_768 * wkvb,
        64 * pairs * 2 * 1088 + 4096 * wkvb)
    held = 8 * 16 / 256                                # 0.5 pairs a token
    token = (5 * h.ATTN + 2 * h.INDEXER + h.DENSE_MLP
             + 4 * ((held + 1) * h.EXPERT + h.ROUTER))
    assert costs.moe_prefill_flops(conf, 1000) == pytest.approx(
        2 * 1000 * 4 * ((held + 1) * h.EXPERT + h.ROUTER))
    assert costs.prefill_flops(conf, 4096, 28_672) == pytest.approx(
        2 * 4096 * (token - 5 * 512 * 64 * 448)
        + costs.latent_prefill_flops(conf, 4096, 28_672)
        + 2 * 2 * (4096 * 28_672 + 4096 * 4097 // 2) * 4096
        + 2 * 6144 * 19_360)


def test_the_store_pool_is_sized_from_what_an_offload_writes(conf):
    from benchmark.lib import cell
    _, cfg = serve.model_config(conf)
    spec = traffic.load(TRAFFIC)
    pool_gb, block_kb = cell.store_sizes(conf, cfg, spec)
    assert block_kb == 4
    per_s = spec["session_rate_per_s"] * traffic.pages_written_per_session(
        spec) * 110_592
    assert pool_gb >= per_s * 40 / 2 ** 30 > pool_gb - 0.5


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load(TRAFFIC)
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (16384, 112, 256, 0.3), (32768, 112, 256, 0.4),
        (32768, 240, 512, 0.3)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"], spec["loop"],
            spec["arrivals"]) == (3, "sticky", 1, 10, 10, 40, "open",
                                  "poisson")
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    assert isinstance(spec["schedule_seed"], int)
    shapes = traffic.shapes(spec)
    assert len(shapes["cold"]) == 3 and len(shapes["prefix"]) == 6
    assert shapes["longest_context"] == 35024
    assert shapes["pages_longest"] <= conf["serving"]["max_pages_per_seq"]
    # turns 2-3 restore 1,046-2,141 whole pages of both kinds
    hits = sorted(p // 16 for _, p in shapes["prefix"])
    assert (hits[0], hits[-1]) == (1046, 2141)
    assert {s for s, _ in shapes["prefix"]} == {128, 256}
    # every context is 8-17 x index_topk: every program selects
    assert min(shapes["cold"]) > 8 * conf["index_topk"]
    assert shapes["longest_context"] < 18 * conf["index_topk"]
    # the cold prompts' pieces: programs per (tokens, prefix pages)
    piece = conf["serving"]["admit_piece"]
    programs = set()
    for n in shapes["cold"]:
        for done in range(0, n, piece):
            programs.add((min(piece, n - done), done // 16))
    whole = {p for p in programs if p[0] == piece}
    assert len(programs - whole) == 3                  # the tails
    assert len(whole) == 32768 // piece                # over 0, 1, 2 .. pieces
    assert min(shapes["cold"]) > piece
    assert max(s for s, _ in shapes["prefix"]) <= piece
    assert len(programs) + len(shapes["prefix"]) + 1 <= 32   # + decode
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/glm_dsa.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 48), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    """The file's rehearsal preset through the harness's own loaders:
    under index_topk (48 tokens: every row selected) and over it."""
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length).astype(np.int32)
    pos = list(range(length))
    ref, margins, chosen = serve.reference_module(
        tiny).forward_with_selection(params, tiny, toks, pos)
    logits = model.prefill(params, cfg, jnp.asarray(toks[None]))[0]
    clear = np.asarray(margins).min(axis=1) >= 1e-3
    for parts in chosen.values():
        clear &= parts[3] >= 1e-5
    assert clear.sum() > length // 3
    diff = np.abs(np.asarray(logits[0]) - np.asarray(ref)).max(axis=1)
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
    benchmark: no selection's fields in the ring, no attn.index /
    attn.topk / attn.gather scopes in the trace, a costs module
    without these counts. None, and nothing raised."""
    from infinistore_tpu.utils import profiling

    ring = [s for s in by_hand.RING if s.name.startswith("istpu.engine")]
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    assert manifest.reader(name).read(window()) is None
    import test_bench_observations as table

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    if name not in ("sparse_attn_rows_share", "index_topk_ms"):
        # mistral7b's costs have no such count
        assert manifest.reader(name).read(table.full_window()) is None


def test_no_reader_parses_a_name_the_program_does_not_emit():
    """The spans, counters and scopes the five readers and the cell's
    lines read are the ones the program writes."""
    def text(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    program = text("infinistore_tpu/serving.py") \
        + text("infinistore_tpu/models/decoder.py") \
        + text("infinistore_tpu/ops/sparse_select.py")
    for name in NEW:
        r = manifest.reader(name)
        for scope in getattr(r, "SCOPES", ()):
            assert f'named_scope("{scope}")' in program, scope
        if hasattr(r, "SPAN"):
            assert f'"{r.SPAN}"' in program
        if hasattr(r, "COST"):
            assert hasattr(costs, r.COST)
    for field in ("rows_selected", "rows_live"):
        assert f'df["{field}"]' in program
    for counter in ("index_keys_scored", "attn_rows_selected",
                    "attn_rows_live", "index_pages_offloaded",
                    "index_pages_restored"):
        assert f'"{counter}"' in program


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds", "6",
         "--trace", "1", "--rehearsal", "--rate", "0.5"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    want = {"offload_gbps", "store_write_p99_us", "offload_stall_p50_ms",
            "decode_host_p50_ms", "admit_piece_p50_ms",
            "sparse_attn_rows_share"}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)
    # lengths cut by 8 against index_topk 32: 1.0-1.6 % of the rows
    assert 0.5 < res["metrics"]["sparse_attn_rows_share"]["value"] < 3

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    assert c["latent_pages_written"] == 5 * c["offloaded_pages"] > 0
    assert c["index_pages_offloaded"] == 2 * c["offloaded_pages"]
    assert 5 * c["index_pages_restored"] == 2 * c["latent_pages_restored"]
    assert c["restored_pages"] == 7 * c["prefix_hit_pages"]
    assert c["restore_misses"] == 0 and c["admit_pieces"] > 0
    # 32 rows a layer a decoded token; a step is counted where it is
    # dispatched and its tokens where they land, so the window's edges
    # may hold a step of 4 slots each
    assert abs(c["attn_rows_selected"] - 5 * 32 * c["decoded_tokens"]) \
        <= 5 * 32 * 8 and c["attn_rows_selected"] > 0
    # every entry of every slot's table is scored in the 2 owner layers
    tiny = serve.load_config(FILE, rehearsal=True)["serving"]
    assert c["index_keys_scored"] % (
        2 * tiny["max_slots"] * tiny["max_pages_per_seq"]
        * tiny["page_size"]) == 0 and c["index_keys_scored"] > 0
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    assert check["logit_rows"]["cold"]["taken"] == 3
    assert check["logit_rows"]["hit"]["taken"] == 3
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
