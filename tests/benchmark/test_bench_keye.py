"""The cell keye-vl2-30b-a3b-docs32k-answers (PR 49): its configuration
file against the catalog row, the costs module against the program's
own parameter tree and a table worked by hand (keye_by_hand.py), the
traffic file's lengths and program count, the new reader on a recorded
span set, and the traced rehearsal end to end."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import keye_by_hand as by_hand
from benchmark.configs import keye_vl2_30b_a3b_costs as costs
from benchmark.lib import manifest, serve, traffic
from benchmark.metrics import _scoped_ops

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "keye-vl2-30b-a3b", "keye-vl2-30b-a3b-docs32k-answers"
FILE = f"benchmark/configs/{CONFIG}.json"
TRAFFIC = "benchmark/traffic/docs32k-answers-kvi.json"
NEW = {"sparse_prefill_mfu": ("%", "Kernels")}
APPENDED = ("prefix_hit_share", "prefill_ms_per_ktok", "restore_gbps",
            "store_read_p99_us", "admit_hit_p50_ms",
            "store_allocate_us_per_key", "store_write_gbps",
            "admit_piece_p50_ms", "decode_ahead_share", "moe_prefill_mfu",
            "sparse_attn_roofline_share", "index_score_roofline_share",
            "index_topk_ms", "index_prefill_mfu", "sparse_attn_rows_share",
            "select_active_share", "moe_step_roofline_share")
# ... and not on these: prefill_mfu takes a probe's keys for the
# prompt's pages, of which this family's probe carries three a page;
# the two decode_ readers wait for their own issue (ROADMAP R0); the
# latent and held-share readers read other families' keys; the two idle
# shares find no idle to split where a cold admission lies across the
# traced seconds (PERF.md section 7). moe_step_roofline_share IS listed:
# every expert is held and the decode kernel fetches the touched ones,
# so every traced run of the builder read it under 100 %.
NOT_LISTED = ("prefill_mfu", "decode_dispatch_lead_p50_ms",
              "decode_return_lag_p50_ms", "latent_prefill_mfu",
              "latent_attn_roofline_share", "moe_held_rows_share",
              "moe_held_pair_skew", "idle_no_work_share",
              "host_held_idle_share")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ("num_hidden_layers",)


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


def test_the_cell_its_configuration_and_its_metric_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert len(bench["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "docs32k-answers-kvi", 1)
    assert len(cell["why"]) <= 200
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == list(CUT) and entry["file"] == FILE
    assert entry["source"] == ("https://huggingface.co/Kwai-Keye/"
                               "Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert [m["name"] for m in bench["per_layer"][-1:]] == list(NEW)
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


def test_the_file_carries_the_catalog_row_but_the_depth(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert conf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CUT:
            cut = conf["reduced"][key]
            assert (cut["published"], cut["here"]) == (value, conf[key])
            assert len(cut["why"]) > 10
        else:
            assert conf[key] == value, key
    assert set(conf["reduced"]) == set(CUT)
    assert (row["config"]["num_hidden_layers"],
            conf["num_hidden_layers"]) == (48, 5)
    for group in ("assumed", "deployment", "guarantees"):
        assert conf[group]
    said = " ".join(conf["assumed"])
    for mark in ("(a)", "(b)", "(c)", "(d)", "(e)", "(f)", "(g)"):
        assert mark in said
    assert "far larger than in a deployment" in \
        conf["reduced"]["num_hidden_layers"]["why"]
    assert len(conf["guarantees"]) == 6
    assert "ALL THREE kinds" in conf["guarantees"][1]
    assert conf["serving"] == {"page_size": 16, "max_slots": 6,
                               "max_pages_per_seq": 2192,
                               "total_pages": 13153, "admit_piece": 4096}
    # the rehearsal keeps the selection: a prompt is many times its k
    tiny = serve.load_config(FILE, rehearsal=True)
    assert tiny["sa_config"]["topk"] == 32
    # ... in two layers: a piece a layer is a second of the CPU's
    assert tiny["num_hidden_layers"] == 2


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.keye"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (2048, 5, 32, 4, 128, 151936)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.index_width) == (16, 64, 2048, 128)
    assert (cfg.n_experts, cfg.d_ff, cfg.top_k, cfg.n_shared, cfg.router,
            cfg.holds_share) == (128, 768, 8, 0, "softmax", False)
    assert cfg.rope_theta == 1e7 and not cfg.rope_adjacent
    assert cfg.norm_eps == 1e-6 and cfg.max_seq == 262144
    init = conf["random_init"]
    assert (cfg.q_init_gain, cfg.o_init_gain, cfg.down_init_gain) == (
        init["query_gain"], init["attn_out_gain"], init["ffn_out_gain"]) \
        == (2.0, 1 / 128, 1 / 128)
    assert cfg.page_kinds == "kvi"
    assert int(np.prod(cfg.page_shape("k"))) * 2 == by_hand.KV_PAGE \
        == int(np.prod(cfg.page_shape("v"))) * 2
    assert int(np.prod(cfg.page_shape("i"))) * 2 == by_hand.INDEX_PAGE \
        == costs.store_block_bytes(conf)
    assert all(cfg.page_layers(k) == (0, 1, 2, 3, 4) for k in "kvi")


def test_the_programs_parameters_are_what_the_costs_count(conf):
    model, cfg = serve.model_config(conf)
    tree = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(x.size) for x in leaves) == costs.param_count(conf) \
        == 3_749_240_704
    assert sum(int(x.size) * x.dtype.itemsize for x in leaves) \
        == costs.weight_bytes(conf) == 7_501_102_848
    # the engine's pools are what the file's deployment says
    from benchmark.tools.aot_memory_kvi import engine_pools
    held = engine_pools(model, cfg, serve.serving_config(conf, "t"))
    assert held["k_pages"].shape == (5, 13153, 16, 4, 128)
    v_pool, i_pool = held["v_pages"]
    assert v_pool.shape == (5, 13153, 16, 4, 128)
    assert i_pool.shape == (5, 13153, 16, 128)
    pools = 2 * 5 * 13153 * by_hand.KV_PAGE + 5 * 13153 * by_hand.INDEX_PAGE
    assert pools == 2_424_360_960
    assert 9.92e9 < pools + costs.weight_bytes(conf) < 9.94e9   # held


def test_costs_by_hand(conf):
    h = by_hand
    assert costs.attn_params(conf) == h.ATTN == 18_874_624
    assert costs.indexer_params(conf) == h.INDEXER == 2_261_120
    assert costs.expert_params(conf) == h.EXPERT == 4_718_592
    assert costs.router_params(conf) == h.ROUTER == 262_144
    assert costs.layer_params(conf) == h.LAYER == 625_381_760
    assert costs.param_count(conf) == h.PARAMS
    assert costs.weight_bytes(conf) == 2 * h.PARAMS + 2 * 5 * h.ROUTER
    six = dict(conf, num_hidden_layers=6)
    assert round(costs.weight_bytes(six) / 1e9, 2) == 8.75
    assert round(costs.weight_bytes(dict(conf, num_hidden_layers=48))
                 / 1e9, 1) == 61.3
    assert costs.page_bytes_all_layers(conf) == 184_320 \
        == 5 * (2 * h.KV_PAGE + h.INDEX_PAGE)
    assert costs.store_block_bytes(conf) == 4096
    assert costs.snapshot_bytes(conf) == 0
    # what the selection leaves of a decode step of 6 sequences of
    # 30,000 tokens: 2,048 K and V rows each in 5 layers, every key
    assert costs.selected_rows(conf, 6, 180_000) == 12_288
    assert costs.selected_rows(conf, 6, 9_000) == 9_000
    assert costs.sparse_attn_bytes(conf, 6, 180_000) == 5 * 12_288 * 2_048
    assert costs.index_score_bytes(conf, 6, 180_000) \
        == 5 * (180_000 * 128 + 2 * h.INDEXER)
    touched = 128 * (1 - (1 - 8 / 128) ** 6)           # 41.1 experts
    assert costs.expected_experts_touched(conf, 6) == pytest.approx(touched)
    moe = 5 * (touched * h.EXPERT * 2 + h.ROUTER * 4)
    assert costs.moe_step_bytes(conf, 6) == pytest.approx(moe)
    weights = (5 * (h.ATTN + h.NORMS) * 2 + moe
               + (151_936 * 2048 + 2048) * 2 + 6 * 2048 * 2)
    assert costs.decode_bytes(conf, 6, 180_000) == pytest.approx(
        weights + 5 * 12_288 * 2_048 + 5 * (180_000 * 128 + 2 * h.INDEXER))
    # an admission: pairs the selection leaves, the scores' every pair
    assert costs.selected_pairs(conf, 4096, 28_672) == 4096 * 2048
    assert costs.selected_pairs(conf, 4096, 0) \
        == 2048 * 2049 // 2 + 2048 * 2048
    assert costs.selected_pairs(conf, 128, 1024) == 128 * 1024 + 8256
    assert costs.index_prefill_flops(conf, 128, 1024) == 0   # all selected
    assert costs.index_prefill_flops(conf, 4096, 4096) \
        == 5 * 25_167_872 * 2048
    assert costs.sparse_prefill_flops(conf, 4096, 28_672) \
        == 5 * 4096 * 2048 * 32 * 512
    token = 5 * (h.ATTN + h.INDEXER + 8 * h.EXPERT + h.ROUTER)
    assert costs.moe_prefill_flops(conf, 1000) \
        == 2 * 1000 * 5 * (8 * h.EXPERT + h.ROUTER)
    assert costs.prefill_flops(conf, 4096, 28_672) == (
        2 * 4096 * token + 5 * 4096 * 2048 * 32 * 512
        + 5 * (4096 * 28_672 + 4096 * 4097 // 2) * 2048
        + 2 * 2048 * 151_936)
    assert costs.decode_flops(conf, 6, 180_000) == (
        2 * 6 * (token + 2048 * 151_936) + 5 * 180_000 * 2048
        + 5 * 32 * 12_288 * 512)


def test_the_store_pool_is_sized_from_what_an_offload_writes(conf):
    from benchmark.lib import cell
    _, cfg = serve.model_config(conf)
    spec = traffic.load(TRAFFIC)
    pool_gb, block_kb = cell.store_sizes(conf, cfg, spec)
    assert block_kb == 4
    per_s = spec["session_rate_per_s"] * traffic.pages_written_per_session(
        spec) * 184_320
    assert pool_gb >= per_s * 40 / 2 ** 30 > pool_gb - 0.5


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load(TRAFFIC)
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (16384, 112, 256, 0.3), (32768, 112, 256, 0.4),
        (32768, 240, 512, 0.3)]
    # the classes of docs32k-answers: the two selections side by side
    assert spec["classes"] == traffic.load(
        "benchmark/traffic/docs32k-answers.json")["classes"]
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
    # turns 2-3 restore 1,046-2,141 whole pages of three kinds
    hits = sorted(p // 16 for _, p in shapes["prefix"])
    assert (hits[0], hits[-1]) == (1046, 2141)
    assert 192e6 < hits[0] * 184_320 and hits[-1] * 184_320 < 395e6
    # every context is 8-17 x topk: every program selects
    topk = conf["sa_config"]["topk"]
    assert min(shapes["cold"]) > 8 * topk
    assert shapes["longest_context"] < 18 * topk
    piece = conf["serving"]["admit_piece"]
    programs = set()
    for n in shapes["cold"]:
        for done in range(0, n, piece):
            programs.add((min(piece, n - done), done // 16))
    assert len(programs) + len(shapes["prefix"]) + 1 == 18   # + decode
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/keye_dsa.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 32), (2 ** 31 + 5, 200)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    """The file's rehearsal preset through the harness's own loaders:
    at topk (32 tokens: every row selected, the dense path) and over
    it."""
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


# -- the reader --------------------------------------------------------------
def window():
    import test_bench_observations as table

    obs = table.full_window()
    obs.conf = serve.load_config(FILE)
    return obs


def scoped(obs, kind, scopes):
    return by_hand.SCOPED[kind, tuple(scopes)]


def test_reader_on_the_hand_built_window(monkeypatch):
    from infinistore_tpu.utils import profiling

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    r = manifest.reader("sparse_prefill_mfu")
    want = by_hand.BY_HAND["sparse_prefill_mfu"]
    assert r.read(window()) == pytest.approx(want, rel=1e-9)
    assert (r.UNIT, r.LAYER) == NEW["sparse_prefill_mfu"]
    assert r.MOVES == "itl_mean_ms" and 0 < want < 100


def test_reader_gives_nothing_on_a_program_without_the_spans_or_scopes(
        monkeypatch):
    """A parent commit, or another family, measured with this
    benchmark: no admission in the ring, no scoped operations in the
    trace, a costs module without the count. None, and nothing
    raised."""
    from infinistore_tpu.utils import profiling

    r = manifest.reader("sparse_prefill_mfu")
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING[:1])
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    assert r.read(window()) is None
    import test_bench_observations as table

    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    assert r.read(table.full_window()) is None       # mistral7b's costs
    glm = table.full_window()
    glm.conf = serve.load_config("benchmark/configs/glm-5.2.json")
    assert r.read(glm) is None                       # glm-5.2's costs


def test_no_reader_parses_a_name_the_program_does_not_emit():
    """The scopes and counts the readers on this cell's lists read are
    the ones the program writes and this costs module has."""
    def text(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    program = text("infinistore_tpu/serving.py") \
        + text("infinistore_tpu/models/decoder.py") \
        + text("infinistore_tpu/ops/sparse_select.py")
    for name in list(NEW) + list(APPENDED):
        r = manifest.reader(name)
        for scope in getattr(r, "SCOPES", ()):
            if not scope.endswith("."):
                assert f'named_scope("{scope}")' in program, scope
        if hasattr(r, "COST"):
            assert hasattr(costs, r.COST), r.COST
    assert program.count("**self._kinds_field") == 2


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
            "sparse_attn_rows_share", "select_active_share"}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)
    # lengths cut by 8 against topk 32: 1.0-1.6 % of the rows
    assert 0.5 < res["metrics"]["sparse_attn_rows_share"]["value"] < 3

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    assert c["latent_pages_written"] == 0 == c["latent_pages_restored"]
    # the rehearsal's two layers: 6 keys a page of tokens, a third of
    # them the index keys'
    assert c["index_pages_offloaded"] == 2 * c["offloaded_pages"] > 0
    assert c["restored_pages"] == 6 * c["prefix_hit_pages"] > 0
    assert 3 * c["index_pages_restored"] == c["restored_pages"]
    assert c["restore_misses"] == 0 and c["admit_pieces"] > 0
    assert abs(c["attn_rows_selected"] - 2 * 32 * c["decoded_tokens"]) \
        <= 2 * 32 * 8 and c["attn_rows_selected"] > 0
    tiny = serve.load_config(FILE, rehearsal=True)["serving"]
    assert c["index_keys_scored"] % (
        2 * tiny["max_slots"] * tiny["max_pages_per_seq"]
        * tiny["page_size"]) == 0 and c["index_keys_scored"] > 0
    assert 0 < c["moe_experts_fetched"] <= c["moe_experts_held"]
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    assert check["logit_rows"]["cold"]["taken"] == 3
    assert check["logit_rows"]["hit"]["taken"] == 3
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
