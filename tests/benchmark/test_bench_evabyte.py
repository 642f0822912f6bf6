"""The cell evabyte-docs24k-bytes (PR 55): its configuration file
against the catalog row, the costs module against the program's own
parameter tree and a table worked by hand (evabyte_by_hand.py), the
traffic file's lengths in positions and in cache rows, the four new
readers on a hand-built window, and the traced rehearsal end to end."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import evabyte_by_hand as by_hand
from benchmark.configs import evabyte_costs as costs
from benchmark.lib import manifest, serve, traffic
from benchmark.metrics import _scoped_ops, fold_roofline_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG, CELL = "evabyte", "evabyte-docs24k-bytes"
FILE = f"benchmark/configs/{CONFIG}.json"
TRAFFIC = "benchmark/traffic/docs24k-bytes.json"
NEW = {"folded_attn_roofline_share": ("%", "Kernels", "higher"),
       "fold_roofline_share": ("%", "Kernels", "higher"),
       "cache_rows_share": ("%", "Model step", "lower"),
       "fold_p50_ms": ("ms", "Scheduler and cache manager", "lower")}
APPENDED = ("prefix_hit_share", "prefill_ms_per_ktok", "restore_gbps",
            "store_read_p99_us", "admit_hit_p50_ms",
            "store_allocate_us_per_key", "store_write_gbps",
            "admit_piece_p50_ms", "decode_ahead_share",
            "gap_engine_mean_ms", "gap_step_ms", "gap_admit_miss_ms",
            "gap_admit_hit_ms", "gap_admit_piece_ms", "gap_offload_ms",
            "gap_other_ms", "gap_stalled_share", "gap_stalled_p50_ms")
# ... and not on these: `prefill_mfu` counts a prompt's pages from the
# probe's keys (one a page but the last) and the hit from its answer,
# and this family's probe carries summary pages' keys and exact pages'
# (PERF.md section 7, as PRs 46 and 49 left it off); the two decode_
# readers wait for their own issue (ROADMAP R0); the idle shares' lists
# ended before PR 46; the rest read other families' scopes and counts.
NOT_LISTED = ("prefill_mfu", "decode_dispatch_lead_p50_ms",
              "decode_return_lag_p50_ms", "idle_no_work_share",
              "host_held_idle_share", "moe_prefill_mfu",
              "latent_attn_roofline_share", "sparse_attn_roofline_share",
              "window_attn_roofline_share", "state_active_share",
              "select_active_share", "itl_tail_p95_ms")
LIST_FREE = ("decode_step_ms", "decode_roofline_share", "offload_gbps",
             "store_write_p99_us", "admit_miss_p50_ms",
             "offload_stall_p50_ms", "decode_host_p50_ms")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"


@pytest.fixture(scope="module")
def conf():
    return serve.load_config(FILE)


def test_the_cell_its_configuration_and_its_four_metrics_are_in_the_manifest():
    bench = manifest.load()
    assert manifest.check(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    assert len(bench["workloads"]) == 12 and len(bench["configs"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = manifest.cell_of(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "docs24k-bytes", 1)
    assert len(cell["why"]) <= 200
    entry = manifest.config_of(bench, CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == FILE and entry["source"] == SOURCE
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
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


def test_the_file_carries_the_catalog_row_but_its_depth(conf):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert conf["source"] == row["source_url"] == SOURCE
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert conf[key] == value, key
    assert row["config"]["num_hidden_layers"] == 32
    assert set(conf["reduced"]) == {"num_hidden_layers"}
    cut = conf["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"], conf["num_hidden_layers"]) == (
        32, 12, 12)
    for group in ("assumed", "deployment", "guarantees", "random_init"):
        assert conf[group]
    assumed = " ".join(conf["assumed"])
    for word in ("fold_phi", "ROTATED", "8 x 320", "eos is never matched"):
        assert word in assumed
    assert len(conf["guarantees"]) == 5
    assert conf["serving"] == {"page_size": 16, "max_slots": 8,
                               "max_pages_per_seq": 256,
                               "total_pages": 2049, "admit_piece": 2048}
    tiny = serve.load_config(FILE, rehearsal=True)
    assert tiny["num_hidden_layers"] == 2 and tiny["window_size"] == 256
    assert tiny["chunk_size"] == 16 and tiny["torch_dtype"] == "float32"


def test_the_bridge_reads_the_published_widths(conf):
    model, cfg = serve.model_config(conf)
    assert model.__name__ == "infinistore_tpu.models.evabyte"
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.head_width) == (
        4096, 12, 32, 32, 128, 11008, 320, 2560)
    assert (cfg.fold_window, cfg.fold_chunk, cfg.n_pred_heads) == (
        2048, 16, 8)
    assert cfg.rope_theta == 100000.0 and cfg.norm_eps == 1e-5
    assert cfg.norm_plus_one and cfg.fp32_stream and not cfg.window_band
    assert (cfg.phi_gain, cfg.mu_gain) == (
        conf["random_init"]["phi_gain"], conf["random_init"]["mu_gain"])
    assert cfg.kv_page_shape() == (16, 32, 128)
    assert cfg.kv_page_bytes() == by_hand.K_PAGE == 131_072


def test_the_programs_parameters_are_what_the_costs_count(conf):
    model, cfg = serve.model_config(conf)
    tree = jax.eval_shape(lambda k: model.init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(int(x.size) for x in leaves) == costs.param_count(conf) \
        == by_hand.PARAMS == 2_440_499_200
    assert sum(int(x.size) * x.dtype.itemsize for x in leaves) \
        == costs.weight_bytes(conf) == 4_880_998_400
    whole = dict(conf, num_hidden_layers=32)
    assert costs.param_count(whole) == by_hand.PARAMS_WHOLE \
        == 6_488_330_240
    # the engine's pools are what the file's deployment says
    from benchmark.tools.aot_memory import engine_pools
    held = engine_pools(model, cfg, serve.serving_config(conf, "t"))
    assert set(held) == {"k_pages", "v_pages"}
    assert held["k_pages"].shape == held["v_pages"].shape == (
        12, 2049, 16, 32, 128)
    pools = sum(int(v.size) * v.dtype.itemsize for v in held.values())
    assert pools == by_hand.POOLS == 6_445_596_672
    # 11.3 GB held: 71 % of a chip of 16 GB before any temporary
    assert 11.32e9 < pools + costs.weight_bytes(conf) < 11.33e9


def test_costs_by_hand(conf):
    h = by_hand
    assert costs.attn_params(conf) == h.ATTN == 67_117_056
    assert costs.mlp_params(conf) == h.MLP == 135_266_304
    assert costs.layer_params(conf) == h.LAYER == 202_391_552
    assert costs.param_count(conf) - 12 * h.LAYER == h.OUTSIDE == 11_800_576
    assert costs.row_bytes(conf) == h.ROW == 16_384
    assert costs.full_page_bytes(conf) == h.POOL_PAGE == 3_145_728
    assert costs.page_bytes_all_layers(conf) == h.POOL_PAGE // 4 == 786_432
    assert costs.store_block_bytes(conf) == h.K_PAGE == 131_072
    assert costs.snapshot_bytes(conf) == 0
    assert costs.fold_bytes(conf) == h.FOLD_BYTES == 427_819_008
    # 0.52 ms at the chip's 819 GB/s
    assert 0.52e-3 < costs.fold_bytes(conf) / 819e9 < 0.53e-3
    # rows against positions: a window's end, its start, the deepest
    assert [costs.cache_rows(conf, p) for p in
            (0, 2047, 2048, 14_336, 28_671, 28_672, 29_391)] == [
        0, 2047, 128, 896, 3_711, 1_792, 2_511]
    assert costs.folded_attn_bytes(conf, 15_000) == 12 * 15_000 * h.ROW
    # a decode step of 5 sequences at 110,000 positions between them:
    # the least it can read is 6,875 + 5 rows
    assert costs.least_rows(conf, 5, 110_000) == 6_880
    weights = 2 * (12 * h.LAYER + 4096 * 2560 + 4096) + 5 * 4096 * 2
    assert costs.decode_bytes(conf, 5, 110_000) == (
        weights + 12 * 6_880 * h.ROW)
    assert 6.2e9 < costs.decode_bytes(conf, 5, 110_000) < 6.3e9
    token = 12 * (4 * 4096 * 4096 + h.MLP)
    assert costs.decode_flops(conf, 5, 110_000) == (
        2 * 5 * (token + 4096 * 2560) + 12 * 32 * 6_880 * 512)
    # a piece of a whole window over 96 pages of summary rows
    s, rows = 2048, 96 * 16
    assert costs.prefill_flops(conf, s, rows) == (
        2 * s * token + 12 * 32 * (s * rows + s * (s + 1) // 2) * 512
        + 2 * 4096 * 2560)
    assert 10.9e12 < costs.prefill_flops(conf, s, rows) < 11.0e12


def test_the_store_pool_is_sized_from_what_a_session_writes(conf):
    """By hand over the four classes: the pages of positions
    lib/traffic.py counts a session, the pool pages this family really
    writes (summary + exact), and the quarter page a counted page that
    covers them in the mean."""
    from benchmark.lib import cell
    _, cfg = serve.model_config(conf)
    spec = traffic.load(TRAFFIC)
    counted, written = [], []
    for c in spec["classes"]:
        turns = traffic.turn_lengths(c, 3)
        counted.append(sum(t["offload_pages"] for t in turns))
        stored_sum, n = 0, 0
        for t in turns:
            held = t["prompt"] + t["answer"] - 1     # positions in pages
            w = held // 2048
            lo = max(t["hit"] // 16, w * 128)
            n += (8 * w - stored_sum) + max(0, held // 16 - lo)
            stored_sum = 8 * w
        written.append(n)
    assert counted == [972, 1116, 1836, 1788]
    assert written == [242, 281, 249, 354]
    assert traffic.pages_written_per_session(spec) == 1428
    mean_mb = sum(written) / 4 * by_hand.POOL_PAGE / 1e6
    assert 885 < mean_mb < 886
    assert 1428 * costs.page_bytes_all_layers(conf) / 1e6 > 1.2 * mean_mb
    pool_gb, block_kb = cell.store_sizes(conf, cfg, spec)
    assert block_kb == 128
    per_s = spec["session_rate_per_s"] * 1428 * 786_432
    assert pool_gb >= per_s * 40 / 2 ** 30 > pool_gb - 0.5


def test_the_traffic_is_the_issues(conf):
    spec = traffic.load(TRAFFIC)
    assert [(c["context"], c["message"], c["answer"], c["weight"])
            for c in spec["classes"]] == [
        (13312, 240, 512, 0.25), (13312, 496, 1024, 0.25),
        (25600, 240, 1024, 0.25), (25600, 496, 512, 0.25)]
    assert (spec["turns"], spec["route"], spec["replicas"], spec["ramp_s"],
            spec["drain_s"], spec["store_pool_seconds"], spec["loop"],
            spec["arrivals"]) == (3, "sticky", 1, 10, 10, 40, "open",
                                  "poisson")
    assert spec["think_s"] == {"floor": 1.0, "mean_exp": 1.0}
    assert isinstance(spec["schedule_seed"], int)
    shapes = traffic.shapes(spec)
    assert shapes["cold"] == [13552, 13808, 25840, 26096]
    assert shapes["longest_context"] == 29392 <= 32768
    # in cache ROWS no sequence passes the table: 8 windows' summaries
    # fewer than positions / 16 would need
    from infinistore_tpu.serving import ServingEngine
    model, cfg = serve.model_config(conf)
    eng = ServingEngine.__new__(ServingEngine)
    eng.cfg, eng._fold, eng._fold_in, eng._fold_out = cfg, 2048, 128, 8
    assert eng._pages_on_the_way(29392) == 8 * 13 + 128 == 232
    assert eng._pages_on_the_way(32768) == 248 \
        <= conf["serving"]["max_pages_per_seq"]
    assert shapes["pages_longest"] == 1837     # ... of positions
    # the hits' first pieces and the cold tails, as the programs see them
    from benchmark.tools.aot_memory_fold import hit_shapes
    hits, tails, deepest = hit_shapes(spec, 2048, 16)
    assert hits == [(32, 222), (48, 181), (256, 85), (256, 118),
                    (256, 158), (256, 197), (512, 86), (512, 165)]
    assert tails == [(1264, 48), (1264, 96), (1520, 48), (1520, 96)]
    assert deepest == 13
    # turn 2's hits by class: summary + exact pages of 3 MiB
    turn2 = [traffic.turn_lengths(c, 3)[1]["hit"] // 16
             for c in spec["classes"]]
    assert [(8 * (h // 128), h % 128) for h in turn2] == [
        (48, 110), (56, 30), (104, 14), (96, 126)]
    knee = spec["knee"]["knee_session_rate_per_s"]
    assert spec["session_rate_per_s"] == pytest.approx(0.8 * knee)
    assert spec["knee"]["config"] == CONFIG


# -- the reference, sharing nothing with the program -------------------------
def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark/reference/evabyte_eva.py")
    with open(path) as f:
        text = f.read()
    assert "infinistore" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("seed,length", [(1, 200), (2 ** 31 + 5, 3 * 256 + 70)])
def test_reference_agrees_with_the_program_at_tiny_widths(seed, length):
    """The file's rehearsal preset through the harness's own loaders:
    inside one window and over three; the next byte's head, which is
    what the harness is handed, and all heads."""
    tiny = serve.load_config(FILE, rehearsal=True)
    model, cfg = serve.model_config(tiny)
    params = serve.init_weights(model, cfg, seed)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, length).astype(np.int32)
    pos = list(range(length))
    reference = serve.reference_module(tiny)
    ref, margins = reference.forward(params, tiny, toks, pos)
    assert margins is None and ref.shape == (length, cfg.vocab_size)
    every = reference.forward(params, tiny, toks, pos, all_heads=True)[0]
    assert every.shape == (length, cfg.head_width)
    logits = model.forward_dense(params, cfg, jnp.asarray(toks[None]))[0]
    assert np.abs(np.asarray(logits[0]) - np.asarray(every)).max() < 2e-4
    assert np.array_equal(np.asarray(every)[:, :cfg.vocab_size],
                          np.asarray(ref))
    # padding behind the last position asked for is inert
    padded, _ = reference.forward(
        params, tiny, np.concatenate([toks, np.zeros(24, toks.dtype)]), pos)
    assert np.allclose(ref, padded, atol=5e-5)


def test_the_tolerances_lie_between_their_readings(conf):
    """Each limit between ITS two readings: first-token rows under
    `logit_tol`, answered tokens under `token_eps`; every planted fault
    of the issue is a second reading of at least one of them."""
    from benchmark.lib import correct
    from benchmark.reference import evabyte_eva
    tol = correct.tolerances_for(conf)
    r = tol["readings"]
    faults = (*evabyte_eva.FAULTS, "fp8_reference")
    assert set(faults) == set(r["second"]) == set(r["second_least_row"])
    # what `token_eps` does not tell, `logit_tol` does
    assert set(r["token_second"]) | set(r["token_not_told_apart"]) \
        == set(faults)
    assert max(r["token_not_told_apart"].values()) < tol["token_eps"]
    # ISSUE 55's stricter reading, the least ROW of the least fault, is
    # still over the limit (by less than the 1.5 times held below)
    assert tol["logit_tol"] < min(r["second_least_row"].values())
    assert max(r["first"].values()) < tol["logit_tol"] < min(
        r["second"].values())
    assert max(r["token_first"].values()) < tol["token_eps"] < min(
        r["token_second"].values())
    # room on both sides: at least 1.5 times each way
    assert 1.5 * max(r["first"].values()) < tol["logit_tol"]
    assert 1.5 * tol["logit_tol"] < min(r["second"].values())
    assert 1.5 * max(r["token_first"].values()) < tol["token_eps"]
    assert 1.5 * tol["token_eps"] < min(r["token_second"].values())
    # the fixture the readings were taken under is the file's
    assert tol["readings"]["under"] == conf["random_init"]["phi_gain"] \
        == conf["random_init"]["mu_gain"]


# -- the readers -------------------------------------------------------------
def window():
    import test_bench_observations as table

    obs = table.full_window()
    obs.counters.update(by_hand.COUNTERS)
    obs.conf = serve.load_config(FILE)
    return obs


def scoped(obs, kind, scopes):
    return by_hand.SCOPED[kind, tuple(scopes)]


@pytest.fixture
def hand_built(monkeypatch):
    from infinistore_tpu.utils import profiling

    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    monkeypatch.setattr(fold_roofline_share, "fold_seconds",
                        lambda obs: by_hand.FOLDS)
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_the_hand_built_window(name, hand_built):
    r = manifest.reader(name)
    want = by_hand.BY_HAND[name]
    assert r.read(window()) == pytest.approx(want, rel=1e-9)
    assert (r.UNIT, r.LAYER, r.BETTER) == NEW[name]
    assert r.MOVES == "itl_mean_ms" and 0 < want < 100


def test_the_median_traced_step_is_taken_from_the_traced_seconds(hand_built):
    """Of the ring's five steps with rows three started in the traced
    seconds: 14,000, 15,000 and 16,000 rows."""
    from benchmark.lib import program_spans
    from benchmark.metrics import folded_attn_roofline_share as r

    obs = window()
    assert r.traced_rows(obs, program_spans.ring(obs)) == [
        14_000, 15_000, 16_000]


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_nothing_on_a_program_without_its_scope_or_counters(
        name, monkeypatch):
    """A parent commit, or another family, measured with this
    benchmark: no scoped operation in the trace, no such span, field or
    counter, a costs module without the count. None, and nothing
    raised."""
    import test_bench_observations as table
    from infinistore_tpu.utils import profiling

    r = manifest.reader(name)
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: None)
    monkeypatch.setattr(profiling, "spans", lambda: table.RING)
    bare = table.full_window()
    bare.conf = serve.load_config(FILE)
    assert r.read(bare) is None      # the parent under this file
    monkeypatch.setattr(_scoped_ops, "seconds", scoped)
    monkeypatch.setattr(fold_roofline_share, "fold_seconds",
                        lambda obs: by_hand.FOLDS)
    assert r.read(table.full_window()) is None       # mistral7b's costs


def test_the_fold_programs_seconds_are_read_by_their_own_name(monkeypatch):
    """`fold_seconds` looks for the programs the file names under
    `program.programs.fold` (a kind lib/serve.py does not know) and
    for the `attn.fold` scope inside them."""
    obs = window()
    obs.trace = {"busy_s": 1.0}
    assert obs.conf["program"]["programs"]["fold"] == ["fold_window"]
    seen = {}
    ops = [("jit(_fold_window)/attn.fold/gather fusion.1", 100, 400),
           ("jit(_fold_window)/attn.fold/scatter fusion.2", 600, 300),
           ("jit(_decode_fused)/attn.kernel custom-call.3", 2000, 500)]
    modules = [("jit__fold_window(5)", 50, 900),
               ("jit__decode_fused(7)", 1900, 700)]
    monkeypatch.setattr(_scoped_ops, "_xplane", lambda: "x")
    monkeypatch.setitem(_scoped_ops._cache, "x", (ops, modules, (0, 10_000)))

    def seconds_in(o, m, w, needles, scopes):
        seen.update(needles=needles, scopes=scopes)
        return _seconds_in(o, m, w, needles, scopes)

    _seconds_in = _scoped_ops.seconds_in
    monkeypatch.setattr(_scoped_ops, "seconds_in", seconds_in)
    found = fold_roofline_share.fold_seconds(obs)
    assert seen == {"needles": ["fold_window"], "scopes": ("attn.fold",)}
    assert found == (700e-9, 1, 900e-9)
    obs.conf = serve.load_config("benchmark/configs/mistral7b.json")
    assert fold_roofline_share.fold_seconds(obs) is None


def test_no_reader_parses_a_name_the_program_does_not_emit():
    """The scopes, spans, fields and counters the readers on this
    cell's lists read are the ones the program writes and this costs
    module has."""
    def text(rel):
        with open(os.path.join(ROOT, rel)) as f:
            return f.read()

    program = text("infinistore_tpu/serving.py") \
        + text("infinistore_tpu/models/decoder.py") \
        + text("infinistore_tpu/models/evabyte.py")
    for name in list(NEW) + list(APPENDED):
        r = manifest.reader(name)
        for scope in getattr(r, "SCOPES", ()):
            if scope == "attn.kernel":      # composed: _kernel_scope
                assert '"attn.kernel"' in program
            else:
                assert f'named_scope("{scope}")' in program, scope
        if hasattr(r, "COST"):
            assert hasattr(costs, r.COST), r.COST
    for counter in ("attn_rows_read", "attn_positions_live",
                    "windows_folded", "fold_pages_freed",
                    "summary_pages_written", "summary_pages_offloaded",
                    "summary_pages_restored", "exact_pages_restored",
                    "hits_cut_to_window_edge"):
        assert f'"{counter}"' in program
    assert '"istpu.cache.fold"' in program
    for field in ("cache_rows", "positions", "cut_to_window_edge",
                  "summary_pages", "exact_pages", "pages_in", "pages_out",
                  "during"):
        assert field in program
    assert "_fold_window" in program


# -- the rehearsal -----------------------------------------------------------
def test_the_traced_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 40), "--seconds", "10",
         "--trace", "1", "--rehearsal", "--rate", "0.5"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["rehearsal"] is True
    assert res["failed"] == 0
    want = {"offload_gbps", "store_write_p99_us", "offload_stall_p50_ms",
            "decode_host_p50_ms", "decode_ahead_share", "cache_rows_share",
            "fold_p50_ms", "admit_piece_p50_ms", "prefix_hit_share"}
    assert want <= set(res["metrics"]), sorted(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)
    # lengths cut by 8 and a window of 256: the same 6.5-14 windows
    assert 5 < res["metrics"]["cache_rows_share"]["value"] < 25

    def line(prefix):
        ln = next(ln for ln in r.stdout.splitlines()
                  if ln.startswith(prefix))
        return json.loads(ln[len(prefix):])

    w = line("window: ")
    c = w["counters"]
    assert c["windows_folded"] > 0
    assert c["fold_pages_freed"] == 15 * c["windows_folded"]
    assert c["summary_pages_written"] == c["windows_folded"]
    assert 0 < c["attn_rows_read"] < c["attn_positions_live"] / 4
    assert c["admit_pieces"] > 0
    # a finished session's exact pages below its last window were never
    # written: far fewer pages of either kind reach the store than the
    # finishes held positions for
    assert c["summary_pages_offloaded"] > 0
    assert c["summary_pages_restored"] > 0 and c["exact_pages_restored"] > 0
    assert c["offloaded_pages"] + c["summary_pages_offloaded"] \
        < c["prefill_tokens"] / 16 / 3
    # (a hit may end at its window's edge with nothing evicted: a turn
    # that finished under a page into a window left no exact page)
    assert c["restore_misses"] == 0
    assert c["latent_pages_written"] == 0 and c["snapshots_written"] == 0
    assert w["store_errors"] == 0 and w["engine_ok"] is True
    assert w["compilations_in_window"] == 0
    check = line("correct: ")
    assert check["logit_rows"]["cold"]["taken"] == 4
    assert check["logit_rows"]["hit"]["taken"] == 4
    assert check["failed"] == 0 and check["hit_expected_ran_cold"] == 0
    assert check["pages_read_back"] > 0
