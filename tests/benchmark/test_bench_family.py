"""A made-up family driven through the harness by FILES alone: a
configuration in a temporary directory with a `layer_types` list and no
`num_key_value_heads`, its own costs module, tolerances file and
program names, and a stub engine that offers only `first_token_logits`,
`stats`, `slots` and `step`. Nothing under benchmark/lib or
benchmark/metrics knows it: what passes here, the next configuration
can be added with (PERF.md, section 4, lists the contract)."""

import json
import textwrap
import types

import numpy as np
import pytest

from benchmark.lib import cell, correct, manifest, serve, traffic
from benchmark.lib.store import Span

VOCAB, PAGE = 64, 16
MODULE = "madeup_family_files"

# The family's own module, as a configuration's files would bring it:
# bridge, costs (lib/costs.py's questions, answered for a cache of a
# state snapshot plus K/V pages on the attention layers alone) and the
# plain reference (a token's successor depends on the token alone).
SOURCE = textwrap.dedent('''
    import types

    import numpy as np

    SEEN = []


    def bridge(hf, page_size=16, dtype="float32"):
        SEEN.append(hf)
        return types.SimpleNamespace(
            page_size=page_size, vocab_size=hf.vocab_size,
            jdtype=np.dtype("float16"), layer_types=tuple(hf.layer_types))


    def _attention_layers(conf):
        return sum(1 for t in conf["layer_types"] if t == "attention")


    def weight_bytes(conf, itemsize=2):
        return conf["hidden_size"] ** 2 * len(conf["layer_types"]) * itemsize


    def page_bytes_all_layers(conf, page=16, itemsize=2):
        return 2 * _attention_layers(conf) * conf["kv_width"] * page * itemsize


    def store_block_bytes(conf, page=16, itemsize=2):
        return conf["kv_width"] * page * itemsize


    def snapshot_bytes(conf, itemsize=2):
        states = len(conf["layer_types"]) - _attention_layers(conf)
        return states * conf["state_size"] * itemsize


    def decode_bytes(conf, active, live_tokens, page=16, itemsize=2):
        return weight_bytes(conf, itemsize) + active * snapshot_bytes(
            conf, itemsize) + live_tokens * page_bytes_all_layers(
                conf, 1, itemsize)


    def decode_flops(conf, active, live_tokens):
        return 2 * active * weight_bytes(conf, 1)


    def prefill_flops(conf, suffix, prefix=0):
        return 2 * suffix * weight_bytes(conf, 1)


    def row(token, vocab):
        """Reference logits after `token`: a peak of 4 at its successor."""
        out = np.zeros(vocab, np.float32)
        out[(5 * int(token) + 3) % vocab] = 4.0
        return out


    def forward(params, conf, toks, positions):
        return np.stack([row(toks[p], conf["vocab_size"])
                         for p in positions]), None
''')


@pytest.fixture
def family(tmp_path, monkeypatch):
    """(conf, module, config path): the files of the made-up family."""
    (tmp_path / f"{MODULE}.py").write_text(SOURCE)
    monkeypatch.syspath_prepend(str(tmp_path))
    tol = tmp_path / "tolerances.json"
    tol.write_text(json.dumps({"madeup": {
        "token_eps": 0.5, "logit_tol": 1.0, "min_checked_share": 0.25,
        "why": "made up: wider than the dense family's 0.35"}}))
    conf = {
        "hidden_size": 256, "vocab_size": VOCAB, "kv_width": 128,
        "state_size": 4096, "torch_dtype": "float16",
        "layer_types": ["mamba", "mamba", "attention", "mamba"],
        "rope_scaling": {"rope_type": "none"},
        "source": "made up", "reduced": {}, "assumed": [],
        "deployment": "none", "guarantees": [],
        "program": {
            "model": MODULE, "bridge": f"{MODULE}:bridge",
            "reference": MODULE, "costs": MODULE,
            "tolerances": {"file": str(tol), "family": "madeup"},
            "programs": {"decode": ["ssm_decode"],
                         "prefill": ["ssm_admit", "ssm_admit_hit"]},
        },
        "serving": {"page_size": PAGE, "max_slots": 2,
                    "max_pages_per_seq": 8, "total_pages": 32,
                    "host_steps": 2},
        "rehearsal": {},
    }
    path = tmp_path / "madeup.json"
    path.write_text(json.dumps(conf))
    loaded = serve.load_config(str(path))
    module = serve.costs_module(loaded)
    module.SEEN.clear()
    return loaded, module, str(path)


SPEC = {"loop": "open", "arrivals": "poisson", "session_rate_per_s": 2.0,
        "turns": 2, "classes": [{"context": 32, "message": 16,
                                 "answer": 16, "weight": 1.0}],
        "think_s": {"floor": 0.0, "mean_exp": 0.0}, "ramp_s": 1,
        "drain_s": 1, "replicas": 1, "route": "sticky",
        "store_pool_seconds": 4000}


# -- the bridge and the engine's configuration ---------------------------
@pytest.mark.parametrize("key,reaches", [
    ("layer_types", True), ("rope_scaling", True), ("kv_width", True),
    ("torch_dtype", True), ("program", False), ("serving", False),
    ("reduced", False), ("assumed", False), ("guarantees", False),
    ("source", False), ("deployment", False), ("rehearsal", False),
])
def test_every_published_key_reaches_the_bridge(family, key, reaches):
    conf, module, _ = family
    model, cfg = serve.model_config(conf)
    hf = module.SEEN[-1]
    assert hasattr(hf, key) is reaches
    if reaches:
        assert getattr(hf, key) == conf[key]
    assert model is module and cfg.page_size == PAGE
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    assert not hasattr(hf, "num_key_value_heads")


def test_every_key_of_the_serving_group_reaches_serving_config(family):
    conf, _, _ = family
    sc = serve.serving_config(conf, "madeup-s1")
    assert (sc.max_slots, sc.total_pages, sc.max_pages_per_seq,
            sc.host_steps, sc.model_id) == (2, 32, 8, 2, "madeup-s1")
    conf["serving"]["no_such_field"] = 1
    with pytest.raises(TypeError):
        serve.serving_config(conf, "madeup-s1")


# -- sizing --------------------------------------------------------------
def test_pool_and_block_come_from_the_family_costs_module(family):
    conf, module, _ = family
    _, cfg = serve.model_config(conf)
    pool_gb, block_kb = cell.store_sizes(conf, cfg, SPEC)
    # one attention layer of four: a page is 2 x 128 x 16 float16
    assert module.page_bytes_all_layers(conf, PAGE, 2) == 8192
    assert block_kb == 128 * PAGE * 2 >> 10 == 4
    pages = traffic.pages_written_per_session(SPEC, PAGE)
    by_hand = 2.0 * 4000 * (pages * 8192 + 2 * 3 * 4096 * 2) / 2 ** 30
    assert traffic.offloads_per_session(SPEC, PAGE) == 2
    assert pool_gb == max(0.5, np.ceil(by_hand * 4) / 4) == 0.75


@pytest.mark.parametrize("state_size,pool_gb", [
    (0, 0.5), (4096, 0.75), (65536, 6.25)])
def test_a_snapshot_changes_the_pool(family, state_size, pool_gb):
    conf, _, _ = family
    _, cfg = serve.model_config(conf)
    conf["state_size"] = state_size
    assert cell.store_sizes(conf, cfg, SPEC)[0] == pool_gb
    assert cell.store_sizes(conf, cfg, SPEC, rehearsal=True)[0] == 0.125


# -- what decides `correct`, with a stub engine ---------------------------
class StubEngine:
    """Offers first_token_logits, stats, slots and step, and nothing
    else. `stored` is the stub's store: prompts (as tuples) whose pages
    it holds; a prompt hits on the longest stored prefix."""

    def __init__(self, module, off=None, stored_from_start=False):
        self.module = module
        self.off = off or {}
        self.stored = [()] if stored_from_start else []
        self.asked = []
        self.stats = {"prefill_tokens": 0, "prefix_hit_pages": 0,
                      "decoded_tokens": 0, "decode_steps": 0,
                      "offloaded_pages": 0}
        self.slots = [None, None]

    def first_token_logits(self, prompt):
        hit = 0
        for s in self.stored:
            if tuple(prompt[:len(s)]) == s:
                hit = max(hit, (len(prompt) - 1) // PAGE)
        path = "hit" if hit else "cold"
        self.asked.append(path)
        row = self.module.row(prompt[-1], VOCAB) + self.off.get(path, 0.0)
        return row, hit

    def step(self):
        self.stats["decode_steps"] += 1
        self.stats["decoded_tokens"] += 2
        return 2

    def play(self, spec, sess):
        """What the HTTP path would do: answers every turn greedily by
        the reference, and holds the session's pages afterwards."""
        ctx, msgs = traffic.session_tokens(spec, sess, VOCAB)
        history, records = list(ctx), []
        for turn in range(1, spec["turns"] + 1):
            prompt = history + msgs[turn - 1]
            answer, last = [], prompt[-1]
            for _ in range(spec["classes"][sess.cls]["answer"]):
                last = int(np.argmax(self.module.row(last, VOCAB)))
                answer.append(last)
            records.append({"turn": turn, "tokens": answer})
            history = prompt + answer
            self.stored.append(tuple(prompt))
        return records


class StubStore:
    def __init__(self, pages=None):
        self.pages = np.arange(3 * 8, dtype=np.float16).reshape(3, 2, 4) \
            if pages is None else pages
        self.tapped = (["a", "b", "c"], self.pages)
        self.asked = None

    def get_kv_pages_host(self, keys, page_shape, dtype):
        self.asked = (tuple(page_shape), np.dtype(dtype))
        return self.pages.copy()


def run_check(family, **engine):
    conf, module, _ = family
    model, cfg = serve.model_config(conf)
    eng = StubEngine(module, **engine)
    store = StubStore()
    replica = types.SimpleNamespace(engine=eng, store=store,
                                    inner_store=store, index=0)
    samples = correct.sample_sessions(SPEC, 7)
    cold = correct.cold_first_logits(SPEC, samples, [replica], model, cfg,
                                     VOCAB)
    records = {s.index: eng.play(SPEC, s) for s in samples}
    said = []
    ok, details = correct.check(
        conf, SPEC, model, cfg, None, serve.reference_module(conf),
        [replica], samples, records, VOCAB, correct.tolerances_for(conf),
        cold_rows=cold, log=said.append)
    return ok, details, eng, store, said


@pytest.mark.parametrize("engine,ok,rows,worst", [
    # one cold and one hit row, each through the path that ran
    ({}, True, {"cold": 1, "hit": 1}, {"cold": 0.0, "hit": 0.0}),
    # inside the family's own logit_tol of 1.0, outside the dense 0.35
    ({"off": {"hit": 0.6}}, True, {"cold": 1, "hit": 1},
     {"cold": 0.0, "hit": 0.6}),
    # a row off by more than the family's own tolerance
    ({"off": {"hit": 1.5}}, False, {"cold": 1, "hit": 1},
     {"cold": 0.0, "hit": 1.5}),
    ({"off": {"cold": -1.5}}, False, {"cold": 1, "hit": 1},
     {"cold": 1.5, "hit": 0.0}),
    # the store held the first prompt already: the cold program never ran
    ({"stored_from_start": True}, False, {"cold": 0, "hit": 2},
     {"cold": 0.0, "hit": 0.0}),
], ids=["both", "inside_own_tol", "hit_off", "cold_off", "no_cold_row"])
def test_check_compares_a_cold_and_a_hit_row(family, engine, ok, rows,
                                             worst):
    got, details, eng, store, said = run_check(family, **engine)
    assert got is ok
    assert {k: v["taken"] for k, v in details["logit_rows"].items()} == rows
    assert details["logit_checked"] == 2 and details["failed"] == 0
    assert details["worst_first_logit_diff"] == pytest.approx(worst)
    assert details["tolerances"]["logit_tol"] == 1.0
    assert [e["path"] for e in details["per_turn"]] == eng.asked
    # read-back: shape and dtype of a page are the tapped array's
    assert store.asked == ((2, 4), np.dtype("float16"))
    assert details["pages_read_back"] == 3
    if rows["cold"] == 0:
        assert any("no first-token row" in m and "cold" in m for m in said)


def test_a_hit_expected_that_ran_cold_is_counted_and_fails(family,
                                                           capsys):
    conf, module, _ = family

    class Forgetful(StubEngine):
        def play(self, spec, sess):
            records = super().play(spec, sess)
            self.stored.clear()  # the store lost every page
            return records

    model, cfg = serve.model_config(conf)
    eng, store = Forgetful(module), StubStore()
    replica = types.SimpleNamespace(engine=eng, store=store,
                                    inner_store=store, index=0)
    samples = correct.sample_sessions(SPEC, 9)
    cold = correct.cold_first_logits(SPEC, samples, [replica], model, cfg,
                                     VOCAB)
    records = {s.index: eng.play(SPEC, s) for s in samples}
    said = []
    ok, details = correct.check(
        conf, SPEC, model, cfg, None, serve.reference_module(conf),
        [replica], samples, records, VOCAB, correct.tolerances_for(conf),
        cold_rows=cold, log=said.append)
    assert ok is False and details["hit_expected_ran_cold"] == 1
    assert details["logit_rows"] == {"cold": {"taken": 2, "compared": 2},
                                     "hit": {"taken": 0, "compared": 0}}
    assert any("no first-token row" in m and "hit" in m for m in said)
    assert "expected as a hit ran the cold program" in capsys.readouterr().out


def test_a_page_read_back_different_fails(family):
    conf, module, _ = family
    store = StubStore()
    store.get_kv_pages_host = lambda keys, shape, dtype: store.pages + 1
    replica = types.SimpleNamespace(store=store, inner_store=store)
    assert correct.read_back(replica) == (3, False)
    store.tapped = None
    assert correct.read_back(replica) == (0, None)


def test_step_spans_need_only_step_stats_and_slots(family):
    _, module, _ = family
    eng = StubEngine(module)
    eng.slots = [types.SimpleNamespace(seq_len=40), None]
    spans = serve.StepSpans(eng, name="bench.step")
    assert eng.step() == 2
    (s,) = spans.records
    assert (s.active, s.live_tokens) == (2, 40)
    assert s.moved["decode_steps"] == 1 and s.moved["decoded_tokens"] == 2


# -- the readers, on a synthetic trace with the family's program names ----
def traced(conf, programs):
    obs = cell.Observations()
    obs.conf = conf
    obs.window, obs.seconds = (100.0, 110.0), 10.0
    obs.trace_window = (103.5, 107.5)
    obs.steps = [serve.Step(104.0 + i * 0.02, 0.015, 2, 2 * 100, {
        "prefill_tokens": 64 if i == 0 else 0, "decoded_tokens": 2,
        "decode_steps": 1}) for i in range(20)]
    obs.spans = [Span("probe", 104.0, 0.001, 0, 3, 2)]
    obs.trace = {"busy_s": 1.0, "window_s": 4.0, "programs": programs}
    obs.peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    return obs


OWN = {"jit_ssm_decode(3)": [0.004] * 20, "jit_ssm_admit(5)": [0.010],
       "jit_ssm_admit_hit(6)": [0.006]}
THEIRS = {"jit__decode_fused(3)": [0.004] * 20,
          "jit__admit_fused(5)": [0.010], "jit__admit_fused_px(6)": [0.006]}


def by_hand(conf, module):
    return {
        "decode_step_ms": 4.0,
        "decode_roofline_share": 100.0 * module.decode_bytes(
            conf, 2, 200, PAGE) / 1e9 / 0.004,
        "prefill_ms_per_ktok": 16.0 / 0.064,
        "prefill_mfu": 100.0 * module.prefill_flops(
            conf, 2 * PAGE, 2 * PAGE) / 1e9 / 0.016,
    }


@pytest.mark.parametrize("name", ["decode_step_ms", "decode_roofline_share",
                                  "prefill_ms_per_ktok", "prefill_mfu"])
def test_readers_find_the_family_programs_and_costs(family, name):
    conf, module, _ = family
    got = manifest.reader(name).read(traced(conf, OWN))
    assert got is not None
    assert got == pytest.approx(by_hand(conf, module)[name], rel=1e-9)
    # ... and nothing under the accepted families' names
    assert manifest.reader(name).read(traced(conf, THEIRS)) is None
