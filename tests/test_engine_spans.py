"""The program's own spans (utils/profiling.py): the tree an admission,
a hit and a finish leave in the ring, the counters beside them, the
ring's bound, and the ring's clock against the jax profiler's.

CPU, tiny widths, the in-process loop-back store of conftest.py.
"""

import glob
import gzip
import json
import os
import sys
import threading
import time
import types
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu import serving
from infinistore_tpu.models import llama, moe
from infinistore_tpu.serving import (
    Request, ServingConfig, ServingEngine, _Work,
)
from infinistore_tpu.serving_http import ServingHTTPServer
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 8
# time_ns() stamps a start and perf_counter_ns() measures a duration:
# two clocks, read a few hundred ns apart. Containment holds to this.
SLACK_NS = 1_000_000


@pytest.fixture(scope="module")
def cfg():
    return llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=128, page_size=PAGE, dtype="float32",
    )


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.PRNGKey(0), cfg)


def _prompt(seed, n, vocab=128):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, vocab, n)]


def _engine(params, cfg, conn, model_id, **sc):
    sc.setdefault("max_slots", 2)
    sc.setdefault("total_pages", 64)
    return ServingEngine(
        params, cfg, ServingConfig(model_id=model_id, **sc),
        store=None if conn is None else TpuKVStore(conn),
    )


def _run(eng, *reqs):
    """Drive `reqs` to completion; returns the spans recorded since
    the first of them arrived."""
    t0 = min(r.arrived_ns for r in reqs)
    eng.run(list(reqs))
    return profiling.spans(since_ns=t0)


def _named(spans, name, **fields):
    return [s for s in spans if s.name == name
            and all(s.fields.get(k) == v for k, v in fields.items())]


def _children(spans, parent):
    return sorted((s for s in spans if s.parent == parent.id),
                  key=lambda s: s.t0_ns)


def _stage(spans, rid):
    """The one istpu.cache.stage span of request `rid`: its probe and
    store read on the engine's restore thread (since PR 56)."""
    (stage,) = [s for s in _named(spans, "istpu.cache.stage")
                if s.request == rid]
    return stage


def _less_dispatch(span):
    """A model span's fields without `dispatch_ns`, which is a time:
    held to lie inside the span."""
    fields = dict(span.fields)
    assert 0 < fields.pop("dispatch_ns") <= span.dur_ns
    return fields


def _inside(child, parent):
    """A child never outlasts its parent, nor starts before it — but
    for a queue wait, which began long before the step that ended it."""
    return ((child.t0_ns >= parent.t0_ns - SLACK_NS
             or child.name == "istpu.sched.queue_wait")
            and child.t0_ns + child.dur_ns
            <= parent.t0_ns + parent.dur_ns + SLACK_NS)


def test_miss_span_tree(params, cfg, shm_conn):
    eng = _engine(params, cfg, shm_conn, "spans-miss")
    spans = _run(eng, Request("m1", _prompt(1, 3 * PAGE + 2),
                              max_new_tokens=3))
    (admit,) = _named(spans, "istpu.sched.admit")
    assert admit.request == "m1" and admit.engine == eng.engine_id
    assert admit.fields["outcome"] == "admitted"
    assert admit.fields["hit_pages"] == 0
    assert admit.fields["prompt_tokens"] == 3 * PAGE + 2
    step = next(s for s in spans if s.id == admit.parent)
    assert step.name == "istpu.engine.step"
    kids = _children(spans, admit)
    # ... and, behind the program of an admission out of idle, the
    # trivial programs of `_settle` as a span of their own.
    assert [k.name for k in kids] == ["istpu.model.prefill",
                                      "istpu.engine.settle"]
    assert all(k.request == "m1" for k in kids)
    assert _less_dispatch(kids[0]) == {
        "program": "cold", "tokens": 3 * PAGE + 2,
        "padded_tokens": 4 * PAGE}
    assert kids[1].fields == {"programs": serving.SETTLE_PROGRAMS}
    # The probe ran on the restore thread, from `submit` on: a span of
    # the request and the engine under no step, its child the probe.
    stage = _stage(spans, "m1")
    assert (stage.parent, stage.engine) == (0, eng.engine_id)
    assert stage.tid != admit.tid
    queued = stage.fields.pop("queued_ns")
    assert 0 <= queued < 10 ** 9
    assert stage.fields == {"hit_pages": 0, "pages": 0, "bytes": 0}
    (probe,) = _children(spans, stage)
    assert (probe.name, probe.request) == ("istpu.cache.probe", "m1")
    assert probe.fields == {"pages": 3, "hit_pages": 0}
    assert admit.fields["staged_ns"] == 0
    # The cold program writes the pool itself: no separate pool write.
    # Queue wait: recorded after the fact, from the request's arrival
    # to the start of the admission, under the step that admitted it.
    (wait,) = _named(spans, "istpu.sched.queue_wait")
    assert wait.request == "m1" and wait.parent == step.id
    assert wait.fields == {"slot": 0, "queue_len": 0}
    assert abs(wait.t0_ns + wait.dur_ns - admit.t0_ns) < SLACK_NS


def test_hit_span_tree(params, cfg, shm_conn):
    eng = _engine(params, cfg, shm_conn, "spans-hit")
    first = _prompt(2, 4 * PAGE)
    out = eng.run([Request("h0", first, max_new_tokens=PAGE)])["h0"]
    follow = first + out + _prompt(3, 5)
    spans = _run(eng, Request("h1", follow, max_new_tokens=2))
    (admit,) = _named(spans, "istpu.sched.admit")
    hit = admit.fields["hit_pages"]
    # 4 prompt pages + the full pages the first answer completed.
    assert hit == (len(first) + len(out) - 1) // PAGE == 4
    kids = _children(spans, admit)
    # One program from the store call's return to the row pull: the
    # restored pages' way into the pool, the prefix form and the
    # suffix's page-out are inside it, so none of them is a span.
    assert [k.name for k in kids] == [
        "istpu.cache.restore", "istpu.model.prefill",
        "istpu.engine.settle"]
    assert all(k.request == "h1" for k in kids)
    restore, prefill, _ = kids
    # The store's part ran on the restore thread (since PR 56): the
    # probe and the store call under the request's stage span; what
    # the engine thread still pays is the restore span, here the wait
    # for that read, and it has no child.
    stage = _stage(spans, "h1")
    assert stage.tid != admit.tid and stage.engine == eng.engine_id
    assert not _children(spans, restore)
    probe, *read = _children(spans, stage)
    assert probe.name == "istpu.cache.probe"
    assert probe.fields["hit_pages"] == hit
    assert admit.fields["staged_ns"] == stage.dur_ns > 0
    assert 0 <= admit.fields["staged_wait_ns"] <= stage.dur_ns \
        + stage.fields["queued_ns"] + SLACK_NS
    assert eng.stats["restores_staged"] == 1
    # none of the gap's causes is ever the restore thread's
    assert not [s for s in spans if s.tid == stage.tid
                and s.name in serving._CAUSE_OF_SPAN]
    # restore: bytes are pages x the bytes of one page over every layer
    # and both kinds, and its transfer is one h2d of as many bytes.
    page_bytes = 2 * cfg.n_layers * cfg.kv_page_bytes()
    # the engine offloaded these pages itself: none is foreign. How
    # they lay in the store's pool is the store's to say: one run is
    # transferred from the pool itself, more are copied once.
    runs = restore.fields["runs"]
    assert runs >= 1
    assert restore.fields == {
        "pages": hit, "bytes": hit * page_bytes, "foreign_pages": 0,
        "runs": runs, "copied_bytes": hit * page_bytes * (runs > 1)}
    assert eng.stats["restore_runs"] == runs
    assert eng.stats["restore_copied_bytes"] \
        == restore.fields["copied_bytes"]
    assert admit.fields["foreign_pages"] == 0
    assert eng.stats["foreign_hit_pages"] == 0
    # The store call as three numbers: the PIN (a key a layer and
    # kind of every page), the host's view of the pinned blocks (what
    # `last_read` says), the transfer.
    pin, view, h2d = read
    assert (pin.name, view.name, h2d.name) == (
        "istpu.store.pin", "istpu.store.view", "istpu.xfer.h2d")
    assert {k: stage.fields[k] for k in ("hit_pages", "pages", "bytes")} \
        == {"hit_pages": hit, "pages": hit, "bytes": hit * page_bytes}
    assert pin.fields == {"keys": hit * 2 * cfg.n_layers}
    assert view.fields == {"runs": runs,
                           "copied_bytes": restore.fields["copied_bytes"]}
    assert h2d.fields["bytes"] == hit * page_bytes
    n_sfx = len(follow) - hit * PAGE
    # No window here, so first_live is 0 and every hit page is restored.
    assert _less_dispatch(prefill) == {
        "program": "prefix", "tokens": n_sfx,
        "padded_tokens": -(-n_sfx // PAGE) * PAGE, "restored_pages": hit}
    assert not _children(spans, prefill)
    assert not _named(spans, "istpu.cache.to_kv")


def test_a_hit_over_two_offloads_is_two_runs_and_one_copy(params, cfg,
                                                          shm_conn):
    """Turn 3 restores what turn 1's finish and turn 2's finish wrote,
    with another session's offload between them in the pool: at least
    two runs, every byte copied once on the host, the same span tree;
    the counters add the restores up."""
    eng = _engine(params, cfg, shm_conn, "spans-hit-two-offloads")
    t1 = _prompt(40, 4 * PAGE)
    out1 = eng.run([Request("a1", t1, max_new_tokens=PAGE)])["a1"]
    eng.run([Request("b1", _prompt(41, 4 * PAGE), max_new_tokens=PAGE)])
    t2 = t1 + out1 + _prompt(42, PAGE)
    spans2 = _run(eng, Request("a2", t2, max_new_tokens=PAGE))
    out2 = eng.outputs["a2"]
    spans3 = _run(eng, Request("a3", t2 + out2 + _prompt(43, 3),
                               max_new_tokens=2))
    page_bytes = 2 * cfg.n_layers * cfg.kv_page_bytes()
    total = {"runs": 0, "copied_bytes": 0}
    for spans, want_hit, min_runs in ((spans2, 4, 1), (spans3, 6, 2)):
        (admit,) = _named(spans, "istpu.sched.admit")
        assert [k.name for k in _children(spans, admit)] == [
            "istpu.cache.restore", "istpu.model.prefill",
            "istpu.engine.settle"]
        (restore,) = _named(spans, "istpu.cache.restore")
        assert restore.parent == admit.id
        f = restore.fields
        assert f["pages"] == admit.fields["hit_pages"] == want_hit
        assert f["runs"] >= min_runs
        assert f["copied_bytes"] == (f["bytes"] if f["runs"] > 1 else 0)
        assert f["bytes"] == want_hit * page_bytes
        assert [k.name for k in _children(spans, _stage(
            spans, admit.request))] == [
            "istpu.cache.probe", "istpu.store.pin", "istpu.store.view",
            "istpu.xfer.h2d"]
        for k in total:
            total[k] += f[k]
    assert total["copied_bytes"] > 0
    assert eng.stats["restore_runs"] == total["runs"]
    assert eng.stats["restore_copied_bytes"] == total["copied_bytes"]


def test_a_store_that_does_not_say_leaves_the_fields_out(params, cfg,
                                                         shm_conn):
    """`runs` and `copied_bytes` are what the store's `last_read` says;
    a store without it (a double, a wrapper of the engine's surface
    alone) restores as before."""
    inner = TpuKVStore(shm_conn)

    class Plain:
        conn = inner.conn
        cached_prefix_len = staticmethod(inner.cached_prefix_len)
        get_kv_pages = staticmethod(inner.get_kv_pages)
        put_kv_pages = staticmethod(inner.put_kv_pages)
        prefetch = staticmethod(inner.prefetch)

    eng = ServingEngine(params, cfg, ServingConfig(
        model_id="spans-plain-store", max_slots=2, total_pages=64),
        store=Plain())
    first = _prompt(44, 4 * PAGE)
    out = eng.run([Request("p0", first, max_new_tokens=PAGE)])["p0"]
    spans = _run(eng, Request("p1", first + out + _prompt(45, 5),
                              max_new_tokens=2))
    (restore,) = _named(spans, "istpu.cache.restore")
    assert set(restore.fields) == {"pages", "bytes", "foreign_pages"}
    assert restore.fields["pages"] == 4
    assert eng.stats["restore_runs"] == 0
    assert eng.stats["store_errors"] == 0


def test_windowed_hit_restores_from_first_live(cfg, shm_conn):
    """With a window the restore starts at the first page the suffix
    can attend: `restored_pages` is hit - first_live, the restore's
    `pages` likewise, and the tree has the same three children."""
    import dataclasses

    wcfg = dataclasses.replace(cfg, window=2 * PAGE)
    wparams = llama.init_params(jax.random.PRNGKey(0), wcfg)
    eng = _engine(wparams, wcfg, shm_conn, "spans-hit-window")
    first = _prompt(4, 4 * PAGE)
    out = eng.run([Request("w0", first, max_new_tokens=PAGE)])["w0"]
    spans = _run(eng, Request("w1", first + out + _prompt(5, 5),
                              max_new_tokens=2))
    (admit,) = _named(spans, "istpu.sched.admit")
    hit = admit.fields["hit_pages"]
    first_live = (hit * PAGE - 2 * PAGE + 1) // PAGE
    assert (hit, first_live) == (4, 2)
    kids = _children(spans, admit)
    assert [k.name for k in kids] == [
        "istpu.cache.restore", "istpu.model.prefill",
        "istpu.engine.settle"]
    assert kids[0].fields["pages"] == hit - first_live
    assert _stage(spans, "w1").fields["pages"] == hit - first_live
    assert kids[1].fields["restored_pages"] == hit - first_live
    assert kids[1].fields["program"] == "prefix"


def test_a_hit_in_pieces_places_its_pages_in_the_first_piece(params, cfg,
                                                             shm_conn):
    """A hit admitted in pieces (admit_piece > 0): the admission holds
    the probe, the store call and the FIRST piece, whose prefix
    program places the restored pages; every later piece reads the
    pool (`pool_read`) and places nothing."""
    eng = _engine(params, cfg, shm_conn, "spans-hit-pieces",
                  admit_piece=PAGE)
    first = _prompt(6, 4 * PAGE)
    out = eng.run([Request("c0", first, max_new_tokens=PAGE)])["c0"]
    spans = _run(eng, Request("c1", first + out + _prompt(7, 2 * PAGE + 5),
                              max_new_tokens=2))
    (admit,) = _named(spans, "istpu.sched.admit", outcome="admitted")
    hit = admit.fields["hit_pages"]
    assert hit == 4
    pieces = [s for s in _named(spans, "istpu.sched.admit_piece")
              if s.request == "c1"]
    assert [(p.fields["piece"], p.fields["of"], p.fields["tokens"],
             p.fields["prefix_pages"]) for p in pieces] == [
        (1, 4, PAGE, hit), (2, 4, PAGE, hit + 1), (3, 4, PAGE, hit + 2),
        (4, 4, 5, hit + 3)]
    assert [k.name for k in _children(spans, admit)] == [
        "istpu.cache.restore", "istpu.sched.admit_piece"]
    assert pieces[0].parent == admit.id
    (placed,) = _children(spans, pieces[0])
    assert placed.name == "istpu.model.prefill"
    assert placed.fields["program"] == "prefix"
    assert placed.fields["restored_pages"] == hit
    for i, piece in enumerate(pieces[1:], 1):
        read, ran = _children(spans, piece)
        assert (read.name, read.fields) == (
            "istpu.cache.pool_read", {"pages": hit + i})
        assert ran.name == "istpu.model.prefill"
        # the drop sentinel for every page read: they are where they lie
        assert ran.fields["restored_pages"] == hit + i


def _host_calls(trace_dir):
    """Every call of a jitted function (an eager jnp operation is one
    too: each dispatches a program) the profiler saw on the host, as
    (name, start_ns, end_ns), outermost events only: jax's dispatch
    nests two `PjitFunction(<name>)` events a call."""
    calls = sorted((s, s + d, n) for n, s, d, _ in
                   _xplane_events(trace_dir, prefix="PjitFunction("))
    out = []
    for s, e, n in calls:
        if out and s < out[-1][2]:
            continue  # nested in the call before
        out.append((n[len("PjitFunction("):-1], s, e))
    return out


def test_a_warm_hit_admission_is_one_program(params, cfg, shm_conn,
                                             tmp_path):
    """The counter that says the mechanism engages: between the store
    call's return and the pull of the logits row a warmed hit admission
    dispatches ONE jitted program, `_admit_fused_px`, and no eager
    operation (each would be a `PjitFunction(...)` host event of its
    own: a pad, a slice, a stack), and compiles nothing."""
    eng = _engine(params, cfg, shm_conn, "spans-hit-one-program")

    def session(seed, rid):
        first = _prompt(seed, 4 * PAGE)
        out = eng.run([Request(rid + "a", first,
                               max_new_tokens=PAGE)])[rid + "a"]
        return Request(rid + "b", first + out + _prompt(seed + 1, 5),
                       max_new_tokens=2)

    eng.run([session(20, "warm")])  # every shape of a hit admission
    follow = session(22, "hit")
    built = eng.stats["compilations"]
    with profiling.profile_window(trace_dir=tmp_path) as w:
        eng.run([follow])
    assert eng.stats["compilations"] == built
    (admit,) = [s for s in w.engine_spans if s.name == "istpu.sched.admit"]
    assert admit.fields["hit_pages"] == 4
    # The profiler's events are on one clock of their own: take the
    # admission's spans from the same plane.
    events = _xplane_events(str(tmp_path))
    (restore,) = [e for e in events if e[0] == "istpu.cache.restore"]
    (prefill,) = [e for e in events if e[0] == "istpu.model.prefill"]
    (whole,) = [e for e in events if e[0] == "istpu.sched.admit"]
    calls = _host_calls(str(tmp_path))
    # The counter counts: the decode steps after the admission are there.
    assert "_decode_fused" in {n for n, *_ in calls}
    between = [n for n, s, e in calls
               if restore[1] + restore[2] <= s <= prefill[1] + prefill[2]]
    assert between == ["_admit_fused_px"]
    # ... and in the whole admission nothing else but, after the row
    # pull, the trivial programs that follow every admission.
    assert [n for n, s, e in calls
            if whole[1] <= s <= whole[1] + whole[2]] == (
        ["_admit_fused_px"] + ["_tick"] * serving.SETTLE_PROGRAMS)


def test_admissions_settle_for_a_while_after_one_out_of_idle(
        params, cfg, monkeypatch):
    """`_settle` runs behind the program of every one-shot admission,
    cold or hit, into an idle engine or beside running sequences, for
    SETTLE_S after an admission that found no sequence running, and
    behind none later: an engine that is never idle stops paying."""
    eng = _engine(params, cfg, None, "spans-settle")
    now = [100.0]
    monkeypatch.setattr(serving.time, "monotonic", lambda: now[0])
    settled = []
    real = eng._settle
    monkeypatch.setattr(eng, "_settle",
                        lambda: (settled.append(len(eng.queue)), real()))
    # Two slots, three requests: the first finds the engine idle, the
    # second a running sequence, the third waits for a slot and is
    # admitted beside the longer of the two.
    eng.run([Request("s0", _prompt(30, PAGE + 2), max_new_tokens=12),
             Request("s1", _prompt(31, PAGE + 2), max_new_tokens=3),
             Request("s2", _prompt(32, PAGE + 2), max_new_tokens=3)])
    assert len(settled) == 3
    # Long after: the one out of idle settles and restarts the clock ...
    now[0] += 10 * serving.SETTLE_S
    eng.submit(Request("s3", _prompt(33, PAGE + 2), max_new_tokens=30))
    eng.step()
    assert len(settled) == 4
    # ... one beside it, SETTLE_S later, does not.
    now[0] += serving.SETTLE_S
    eng.submit(Request("s4", _prompt(34, PAGE + 2), max_new_tokens=2))
    eng.run()
    assert len(settled) == 4


def test_an_idle_engine_ticks_every_idle_tick_s(params, cfg, monkeypatch):
    """`idle()`, which the HTTP loop calls on every pass that finds no
    work, sends the device one trivial program an IDLE_TICK_S and
    nothing between."""
    eng = _engine(params, cfg, None, "spans-idle")
    # Times a float holds exactly: a sum of 0.002s may fall a rounding
    # short of the period on the pass that should reach it.
    now = [1024.0]
    eng._ticked = now[0]
    monkeypatch.setattr(serving.time, "monotonic", lambda: now[0])
    ticks = []
    real = serving._tick
    monkeypatch.setattr(serving, "_tick",
                        lambda x: (ticks.append(now[0]), real(x))[1])
    for _ in range(52):  # a pass every 2 ms for 102 ms: over the period once
        now[0] += 2.0 ** -9
        eng.idle()
        if now[0] - eng._ticked < serving.IDLE_TICK_S - 0.003:
            assert len(ticks) <= 1
    assert len(ticks) == 1
    now[0] += 10 * serving.IDLE_TICK_S  # however long the pause: one
    eng.idle()
    eng.idle()
    assert len(ticks) == 2


def _post(port, prompt, n):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"prompt": prompt, "max_new_tokens": n,
                         "stream": False}).encode(), method="POST")
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def test_an_engine_left_without_work_records_one_no_work_a_spell(
        params, cfg, monkeypatch):
    """Behind ServingHTTPServer an engine with nothing to step is ONE
    `istpu.engine.no_work` a spell, however many 2 ms passes it lasts:
    opened by the first pass that finds no work, closed by the next
    submission (or by shutdown), `ticks` the programs `idle()` sent in
    it; while the engine steps there is none."""
    eng = _engine(params, cfg, None, "spans-no-work")
    sent = []
    real = eng.idle
    monkeypatch.setattr(eng, "idle",
                        lambda: bool(real() and sent.append(time.time_ns())
                                     is None))
    t0 = time.time_ns()
    srv = ServingHTTPServer(eng, port=0)
    port = srv.start()
    try:
        deadline = time.time() + 30
        while len(sent) < 2 and time.time() < deadline:
            time.sleep(0.01)  # two tick periods and many passes
        res = _post(port, _prompt(50, PAGE + 2), 6)
        assert len(res["tokens"]) == 6
        while len(sent) < 4 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        srv.shutdown()
    spans = profiling.spans(since_ns=t0)
    mine = [s for s in spans if s.engine == eng.engine_id]
    first, second = _named(mine, "istpu.engine.no_work")
    assert (first.parent, first.request) == (0, None)
    (submit,) = _named(mine, "istpu.sched.submit")
    assert submit.request == res["request_id"]
    assert submit.fields == {"prompt_tokens": PAGE + 2, "queue_len": 0}
    steps = _named(mine, "istpu.engine.step")
    assert len(steps) >= 6 and submit.parent == 0
    # The first spell ends where the submission is taken, the second
    # starts behind the last step and is closed by shutdown; no step
    # and no submission lies in either.
    assert first.t0_ns + first.dur_ns <= submit.t0_ns + SLACK_NS
    assert second.t0_ns >= steps[-1].t0_ns + steps[-1].dur_ns - SLACK_NS
    for spell in (first, second):
        end = spell.t0_ns + spell.dur_ns
        assert not [s for s in steps + [submit]
                    if spell.t0_ns + SLACK_NS < s.t0_ns < end - SLACK_NS]
        assert spell.fields == {"ticks": sum(
            1 for t in sent if spell.t0_ns <= t <= end + SLACK_NS)}
    assert first.fields["ticks"] >= 2
    assert first.fields["ticks"] + second.fields["ticks"] == len(sent)
    # One span a spell: the first alone held some tens of passes.
    assert first.dur_ns > 1.5 * serving.IDLE_TICK_S * 1e9


def test_steps_say_whether_their_inputs_were_on_the_device(params, cfg):
    """`rows_uploaded` on a plain step that dispatched: true where
    inputs were rebuilt and uploaded (the first step after an
    admission; a finish changes the active set too; behind a step in
    flight the tables alone, where a slot took a new page), false where
    the device still held them; a call that only lands the step a run
    ends with dispatched nothing and says nothing; no step carries the
    `steady` that said the same thing (gone since PR 51); and a decode
    span's `dispatch_ns`, the start of the span to the return of the
    dispatch, lies inside it."""
    eng = _engine(params, cfg, None, "spans-steady")
    spans = _run(eng, Request("a", _prompt(51, PAGE + 2), max_new_tokens=6))
    t0 = time.time_ns()
    eng.submit(Request("b", _prompt(52, PAGE + 2), max_new_tokens=9))
    eng.step()
    eng.submit(Request("c", _prompt(53, PAGE + 2), max_new_tokens=3))
    eng.run()
    spans += profiling.spans(since_ns=t0)
    by_id = {s.id: s for s in spans}
    steps = [s for s in _named(spans, "istpu.engine.step")
             if s.fields["kind"] == "decode"]
    # "a" alone: its first step began a run, the last landed it
    assert [s.fields.get("rows_uploaded") for s in steps[:5]] \
        == [True] + [False] * 3 + [None]
    for st in _named(spans, "istpu.engine.step"):
        admitted = bool([k for k in _children(spans, st)
                         if k.name == "istpu.sched.admit"])
        assert not (admitted and st.fields.get("rows_uploaded") is False)
        assert "steady" not in st.fields
    # "b" was admitted; "c" was, beside the step in flight, which then
    # landed; a step of both began a run; "c"'s budget ended it; it
    # left; "b" took a second page at its 17th token (the
    # tables alone went up, behind a step in flight); one step held
    # its inputs; "b"'s budget ended the run.
    later = [s.fields.get("rows_uploaded") for s in steps[5:]]
    assert later == [True, None, True, None, True, True, False, None]
    assert "rows_uploaded" not in _named(spans, "istpu.engine.step",
                                         kind="idle")[0].fields
    decodes = _named(spans, "istpu.model.decode", program="decode_fused")
    assert len(decodes) == len(steps) == eng.stats["decode_steps"]
    for d in decodes:
        assert _less_dispatch(d).keys() == {"program", "live_pages"}
        assert by_id[d.parent].fields["kind"] == "decode"


def test_finish_span_tree(params, cfg, shm_conn):
    """A finish on two threads: `istpu.cache.offload` on the engine
    thread, a child of its step, is the gathers' dispatch alone;
    `istpu.cache.upload` on the engine's upload thread, under the same
    engine and request, holds the wait for the transfer, the store
    batch and the sync."""
    eng = _engine(params, cfg, shm_conn, "spans-finish")
    spans = _run(eng, Request("f1", _prompt(4, 2 * PAGE + 1),
                              max_new_tokens=PAGE))
    (off,) = _named(spans, "istpu.cache.offload")
    # 2 * PAGE + 1 prompt tokens + PAGE - 1 decoded ones have KV.
    assert off.request == "f1" and off.fields["reason"] == "finish"
    assert off.fields["pages"] == 3
    assert off.fields["bytes"] == 3 * 2 * cfg.n_layers * cfg.kv_page_bytes()
    assert eng.stats["offloaded_pages"] == 3
    # One gather program over the bucket's rows (3 pages are a bucket
    # of their own), one store batch to come: nothing on this thread
    # waits for a transfer or calls the store.
    assert off.fields["padded_pages"] == 3 and off.fields["puts"] == 1
    assert _children(spans, off) == []
    step = next(s for s in spans if s.id == off.parent)
    assert step.name == "istpu.engine.step"
    # ... on the other thread one transfer, the store batch as allocate
    # (a key a layer and kind of every page) and the copy into the
    # store's pool with the commit, and one sync.
    (upl,) = _named(spans, "istpu.cache.upload")
    assert (upl.request, upl.engine, upl.parent) == ("f1", eng.engine_id, 0)
    assert upl.tid != off.tid == step.tid
    assert upl.fields.pop("queued_ns") >= 0
    assert upl.fields == {"reason": "finish", "pages": 3,
                          "bytes": off.fields["bytes"], "puts": 1}
    assert upl.t0_ns >= off.t0_ns - SLACK_NS
    d2h, allocate, write, sync = _children(spans, upl)
    assert (d2h.name, allocate.name, write.name, sync.name) == (
        "istpu.xfer.d2h", "istpu.store.allocate", "istpu.store.write",
        "istpu.cache.offload_sync")
    assert d2h.fields == write.fields == {"bytes": off.fields["bytes"]}
    assert allocate.fields == {"keys": 3 * 2 * cfg.n_layers,
                               "bytes": off.fields["bytes"]}
    assert {s.request for s in (d2h, allocate, write, sync)} == {"f1"}
    assert {s.engine for s in (d2h, allocate, write, sync)} == {
        eng.engine_id}
    assert {s.tid for s in (d2h, allocate, write, sync)} == {upl.tid}
    assert all(_inside(s, upl) for s in (d2h, allocate, write, sync))
    assert (eng.stats["uploads"], eng.stats["upload_backpressure_waits"]
            ) == (1, 0)
    assert eng.stats["done_held_ms"] > 0


def test_done_waits_for_the_sync_behind_the_http_loop(params, cfg, shm_conn,
                                                      gated_sync):
    """Behind ServingHTTPServer: while a finished request's sync is
    held, its client has every token and no `done`, another request
    runs to ITS finish meanwhile, and the loop, with nothing left to
    step, is in ONE `istpu.engine.upload_wait` and not in
    `istpu.engine.no_work`. Both are delivered, in order, once the sync
    returns; shutdown leaves no upload and no upload thread."""
    eng = _engine(params, cfg, shm_conn, "spans-http-held")
    srv = ServingHTTPServer(eng, port=0)
    port = srv.start()
    t0 = time.time_ns()
    try:
        first = srv.submit_request(_prompt(60, 2 * PAGE), max_new_tokens=4)
        second = srv.submit_request(_prompt(61, PAGE + 3),
                                    max_new_tokens=3 * PAGE)
        deadline = time.time() + 60
        while eng.finished < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.finished == 2 and eng.uploads_pending == 2
        time.sleep(0.05)  # some passes of the loop with nothing to step
        for (rid, st), n in ((first, 4), (second, 3 * PAGE)):
            assert st.n_tokens == n and st.tokens is None
            assert rid not in eng.outputs
        assert eng.stats["offloaded_pages"] == 0
        gated_sync.set()
        while (first[1].tokens is None or second[1].tokens is None) \
                and time.time() < deadline:
            time.sleep(0.005)
        assert len(first[1].tokens) == 4 and len(second[1].tokens) == 3 * PAGE
        assert first[1].done_t <= second[1].done_t
    finally:
        gated_sync.set()
        srv.shutdown()
    assert eng.uploads_pending == 0 and eng._upload_thread is None
    assert eng.stats["offloaded_pages"] == 2 + 4 and eng.stats["uploads"] == 2
    mine = [s for s in profiling.spans(since_ns=t0)
            if s.engine == eng.engine_id]
    (wait,) = _named(mine, "istpu.engine.upload_wait")
    assert wait.parent == 0 and wait.dur_ns > 40e6
    last = _named(mine, "istpu.engine.step")[-1]
    assert wait.t0_ns >= last.t0_ns + last.dur_ns - SLACK_NS
    for spell in _named(mine, "istpu.engine.no_work"):
        assert (spell.t0_ns + spell.dur_ns <= wait.t0_ns + SLACK_NS
                or spell.t0_ns >= wait.t0_ns + wait.dur_ns - SLACK_NS)
    # done_held_ms: what the two `done`s waited, from each finish on.
    assert eng.stats["done_held_ms"] >= 2 * 50


def test_shutdown_returns_with_the_uploads_in_the_store(params, cfg,
                                                        shm_conn,
                                                        monkeypatch):
    """`shutdown()` while a finished request's store write is still
    running: it returns only when the write is synced, the upload
    thread joined; the caller may close the connection then."""
    real = shm_conn.sync
    synced = []

    def slow():
        time.sleep(0.3)
        real()
        synced.append(time.perf_counter())
    monkeypatch.setattr(shm_conn, "sync", slow)
    eng = _engine(params, cfg, shm_conn, "spans-shutdown")
    srv = ServingHTTPServer(eng, port=0)
    srv.start()
    prompt = _prompt(62, 2 * PAGE)
    try:
        rid, st = srv.submit_request(prompt, max_new_tokens=2)
        deadline = time.time() + 60
        while eng.finished < 1 and time.time() < deadline:
            time.sleep(0.005)
        assert eng.uploads_pending == 1 and not synced
    finally:
        srv.shutdown()
    returned = time.perf_counter()
    assert synced and synced[0] <= returned
    assert eng.uploads_pending == 0 and eng._upload_thread is None
    assert st.tokens is not None and len(st.tokens) == 2
    other = _engine(params, cfg, shm_conn, "spans-shutdown")
    assert other._probe_hit(_Work(
        req=Request("probe", prompt + [1]), prompt=prompt + [1]))[0] == 2


def test_children_inside_parents_and_steps_hold_their_sum(
        params, cfg, shm_conn):
    eng = _engine(params, cfg, shm_conn, "spans-sum", max_slots=3)
    base = _prompt(5, 3 * PAGE)
    spans = _run(eng, *[
        Request(f"s{i}", base + _prompt(10 + i, 3 + i), max_new_tokens=6)
        for i in range(5)])
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.parent:
            assert _inside(s, by_id[s.parent]), (s, by_id[s.parent])
    steps = _named(spans, "istpu.engine.step")
    assert len(steps) > 6
    for st in steps:
        kids = _children(spans, st)
        # queue_wait is a wait that ended inside the step, not work
        # done in it.
        work = sum(k.dur_ns for k in kids
                   if k.name != "istpu.sched.queue_wait")
        assert work <= st.dur_ns + SLACK_NS
    # A step that only finishes what is left runs no program.
    assert {st.fields["kind"] for st in steps} == {"decode", "idle"}
    assert steps[-1].fields["kind"] == "idle"
    decodes = _named(spans, "istpu.model.decode", program="decode_fused")
    assert len(decodes) == eng.stats["decode_steps"]
    assert {by_id[d.parent].name for d in decodes} == {"istpu.engine.step"}
    assert max(st.fields["active"] for st in steps) == 3


@pytest.mark.parametrize("window", [0, 2 * PAGE])
def test_decode_spans_carry_live_pages_and_counters_sum_them(params, cfg,
                                                             window):
    """What the paged-decode kernel walks, counted on the host from the
    lengths the engine holds: a decode span's `live_pages` are the
    table entries (over every attention layer) that hold a key of an
    active row's band in that step, `attn_pages_live` their sum and
    `attn_pages_table` the whole grid a step (slots x table entries x
    layers), so the two give the live share of the grid."""
    import dataclasses

    cfg = dataclasses.replace(cfg, window=window)
    eng = _engine(params, cfg, None, f"spans-live-{window}", max_slots=3,
                  max_pages_per_seq=12)
    n_prompt, n_new = 3 * PAGE + 2, 9
    spans = _run(eng, Request("l1", _prompt(7, n_prompt),
                              max_new_tokens=n_new))
    decodes = _named(spans, "istpu.model.decode", program="decode_fused")
    assert len(decodes) == eng.stats["decode_steps"] == n_new - 1

    def live(n):  # keys 0..n-1, the band's floor at n - window
        first = max(n - window, 0) // PAGE if window else 0
        return cfg.n_layers * ((n - 1) // PAGE - first + 1)

    # step i attends the prompt, the tokens before it and its own
    want = [live(n_prompt + i + 1) for i in range(n_new - 1)]
    assert [d.fields["live_pages"] for d in decodes] == want
    assert eng.stats["attn_pages_live"] == sum(want)
    assert eng.stats["attn_pages_table"] == (
        (n_new - 1) * cfg.n_layers * 3 * 12)


def test_step_kinds_burst_spec_decode(params, cfg):
    def kinds(**sc):
        eng = _engine(params, cfg, None, "spans-kinds", **sc)
        eng.proposer = lambda ctx, k: [1] * k
        prompt = _prompt(6, 2 * PAGE + 3)
        spans = _run(eng, Request("k", prompt + prompt, max_new_tokens=10))
        return ({s.fields["kind"] for s in _named(spans, "istpu.engine.step")}
                - {"idle"},
                {s.fields["program"]
                 for s in _named(spans, "istpu.model.decode")},
                len(_named(spans, "istpu.sched.admit_piece")))

    assert kinds(host_steps=4) == ({"burst", "decode"},
                                   {"decode_scan", "decode_fused"}, 0)
    # pieces are no kind of step: each runs in the step that decodes the
    # others; behind them the plain steps run ahead: a dispatch, a wait
    assert kinds(admit_piece=PAGE) == ({"decode"},
                                       {"decode_fused", "land"}, 5)
    k, p, _ = kinds(spec_k=3)
    assert "spec" in k and "verify" in p


def test_one_request_id_from_http_to_offload(params, cfg, shm_conn):
    eng = _engine(params, cfg, shm_conn, "spans-http")
    srv = ServingHTTPServer(eng, port=0)
    port = srv.start()
    t0 = time.time_ns()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps({"prompt": _prompt(7, 2 * PAGE + 2),
                             "max_new_tokens": PAGE,
                             "stream": False}).encode(), method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            res = json.loads(r.read())
        deadline = time.time() + 30
        while time.time() < deadline and not _named(
                profiling.spans(since_ns=t0), "istpu.http.request"):
            time.sleep(0.01)  # recorded after the response is written
    finally:
        srv.shutdown()
    rid = res["request_id"]
    mine = [s for s in profiling.spans(since_ns=t0) if s.request == rid]
    names = {s.name for s in mine}
    assert {"istpu.http.request", "istpu.sched.queue_wait",
            "istpu.sched.admit", "istpu.cache.probe",
            "istpu.model.prefill", "istpu.cache.offload",
            "istpu.cache.upload", "istpu.xfer.d2h",
            "istpu.cache.offload_sync"} <= names
    (http,) = _named(mine, "istpu.http.request")
    (wait,) = _named(mine, "istpu.sched.queue_wait")
    (admit,) = _named(mine, "istpu.sched.admit")
    (off,) = _named(mine, "istpu.cache.offload")
    # One origin: the queue wait starts at the stamp the handler took
    # before it read the body, and so does the server's TTFT.
    assert wait.t0_ns == http.t0_ns
    assert http.fields["prompt_tokens"] == 2 * PAGE + 2
    assert http.fields["tokens_out"] == PAGE
    first_ns = http.fields["first_token_ns"]
    assert abs(first_ns / 1e6 - res["ttft_ms"]) < 0.01
    assert wait.dur_ns + admit.dur_ns <= first_ns + SLACK_NS
    assert _inside(admit, http) and _inside(off, http)
    # `done` follows the acknowledgement: the upload thread's sync has
    # returned before the response is written.
    (upl,) = _named(mine, "istpu.cache.upload")
    assert upl.tid != off.tid and _inside(upl, http)
    assert {s.engine for s in mine} == {eng.engine_id}


def test_admit_retries_and_the_second_queue_wait(params, cfg, shm_conn):
    # 10 usable pages: the second request (4 + growth) cannot be
    # admitted beside the first until that one is done; the first
    # outgrows the pool's rest beside the second and is preempted.
    eng = _engine(params, cfg, shm_conn, "spans-retry", total_pages=11,
                  max_pages_per_seq=10)
    t0 = time.time_ns()
    eng.submit(Request("a", _prompt(8, 5 * PAGE), max_new_tokens=3 * PAGE))
    eng.step()
    eng.submit(Request("b", _prompt(9, 4 * PAGE), max_new_tokens=PAGE))
    eng.run()
    spans = profiling.spans(since_ns=t0)
    refused = _named(spans, "istpu.sched.admit", outcome="no_pages")
    assert refused and eng.stats["admit_retries"] == len(refused)
    assert {s.request for s in refused} <= {"a", "b"}
    assert all([k.name for k in _children(spans, s)]
               in ([], ["istpu.cache.probe"]) for s in refused)
    assert eng.stats["preemptions"] >= 1
    swapped = _named(spans, "istpu.cache.offload", reason="preempt")
    assert len(swapped) == eng.stats["preemptions"]
    victim = swapped[0].request
    waits = _named(spans, "istpu.sched.queue_wait")
    # One wait an admission: the victim's second one starts at its
    # swap-out, not at its arrival.
    assert len(waits) == len(_named(spans, "istpu.sched.admit",
                                    outcome="admitted"))
    mine = sorted((w for w in waits if w.request == victim),
                  key=lambda w: w.t0_ns)
    assert len(mine) == 1 + sum(1 for s in swapped if s.request == victim)
    assert mine[1].t0_ns >= swapped[0].t0_ns


def test_compilations_are_counted_in_the_step_that_paid(cfg):
    # A width no other test of this process uses: its programs are new.
    import dataclasses

    cfg = dataclasses.replace(cfg, vocab_size=136)
    params = llama.init_params(jax.random.PRNGKey(1), cfg)
    eng = _engine(params, cfg, None, "spans-compile")
    spans = _run(eng, Request("c", _prompt(11, PAGE + 1, 136),
                              max_new_tokens=4))
    steps = _named(spans, "istpu.engine.step")
    built = [s.fields["compiled"] for s in steps]
    assert built[0] >= 1                      # the cold program
    assert sum(built) == eng.stats["compilations"] >= 2
    assert built[-1] == 0                     # a warm decode step
    before = eng.stats["compilations"]
    _run(eng, Request("c2", _prompt(12, PAGE + 1, 136), max_new_tokens=4))
    assert eng.stats["compilations"] == before


def test_the_ring_holds_at_most_its_bound():
    keep = profiling.spans()
    try:
        for i in range(profiling.RING_SPANS + 10):
            profiling.record("istpu.test.fill", i, 1)
        ring = profiling.spans()
        assert len(ring) == profiling.RING_SPANS
        assert ring[0].t0_ns == 10 and ring[-1].name == "istpu.test.fill"
        assert profiling.spans(since_ns=profiling.RING_SPANS) == ring[-10:]
    finally:
        profiling._ring.clear()
        profiling._ring.extend(keep)


def test_spans_nest_per_thread_and_inherit_request_and_engine():
    t0 = time.time_ns()
    seen = {}

    def other():
        with profiling.span("istpu.test.other") as f:
            f["x"] = 1
        seen["done"] = True

    with profiling.span("istpu.test.outer", "req-9", 42, a=1):
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
        with profiling.span("istpu.test.inner"):
            profiling.record("istpu.test.after", 5, 6, n=2)
    assert seen["done"]
    got = {s.name: s for s in profiling.spans(since_ns=0)
           if s.name.startswith("istpu.test.") and (s.t0_ns >= t0
                                                    or s.t0_ns == 5)}
    outer, inner = got["istpu.test.outer"], got["istpu.test.inner"]
    after, oth = got["istpu.test.after"], got["istpu.test.other"]
    assert (outer.parent, outer.request, outer.engine) == (0, "req-9", 42)
    assert (inner.parent, inner.request, inner.engine) == (
        outer.id, "req-9", 42)
    assert (after.parent, after.request, after.fields) == (
        inner.id, "req-9", {"n": 2})
    # Another thread's span is nobody's child here.
    assert (oth.parent, oth.request, oth.fields) == (0, None, {"x": 1})
    assert oth.tid != outer.tid


def test_chrome_trace_is_the_trace_event_form():
    t0 = time.time_ns()
    with profiling.span("istpu.test.chrome", "r1", 7, pages=3):
        pass
    out = profiling.chrome_trace()
    json.dumps(out)
    (ev,) = [e for e in out["traceEvents"]
             if e["name"] == "istpu.test.chrome"]
    assert ev["ph"] == "X" and ev["pid"] == os.getpid()
    assert abs(ev["ts"] * 1e3 - t0) < 1e9 and ev["dur"] >= 0
    assert ev["args"]["request_id"] == "r1" and ev["args"]["engine"] == 7
    assert ev["args"]["pages"] == 3 and ev["args"]["parent"] == 0
    meta = out["metadata"]
    assert abs(meta["clock_realtime_ns"] - time.time_ns()) < 5e9
    assert abs(meta["clock_monotonic_ns"] - time.monotonic_ns()) < 5e9


def _xplane_events(trace_dir, prefix="istpu."):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(prefix):
                    out.append((ev.name, ev.start_ns, ev.duration_ns,
                                plane.name))
    return out


def test_ring_and_profiler_record_the_same_spans(params, cfg, shm_conn,
                                                 tmp_path):
    """Under a jax.profiler session every istpu.* span is also a host
    event of the xplane. The profiler's clock counts from the start of
    its session (neither unix nor monotonic time), so the ring's starts
    match the trace's after ONE offset, measured from the matched
    pairs: within 1 ms at the quartiles here (tens of microseconds on
    an idle host; PERF.md has the chip's figure)."""
    eng = _engine(params, cfg, shm_conn, "spans-xplane")
    first = _prompt(13, 2 * PAGE)
    eng.run([Request("warm", first, max_new_tokens=3)])
    with profiling.profile_window(trace_dir=tmp_path) as w:
        out = eng.run([Request("x", _prompt(14, 2 * PAGE),
                               max_new_tokens=PAGE)])["x"]
        eng.run([Request("y", _prompt(14, 2 * PAGE) + out + [1, 2],
                         max_new_tokens=2)])
    events = _xplane_events(str(tmp_path))
    assert {p for *_, p in events} == {"/host:CPU"}
    names = {n for n, *_ in events}
    # a miss, its finish, a hit: every span of the engine thread
    assert {profiling.WINDOW_SPAN, "istpu.engine.step",
            "istpu.sched.admit", "istpu.model.prefill",
            "istpu.model.decode", "istpu.engine.settle",
            "istpu.cache.offload", "istpu.store.allocate",
            "istpu.store.write", "istpu.cache.restore", "istpu.store.pin",
            "istpu.store.view", "istpu.xfer.h2d"} <= names
    # A wait recorded after the fact was never an annotation.
    ring = [s for s in w.engine_spans if s.name != "istpu.sched.queue_wait"]
    assert len(ring) == len(w.engine_spans) - 2
    assert sorted(n for n, *_ in events) == sorted(s.name for s in ring)
    offset, spread, pairs = profiling.clock_offset_ns(
        ring, [(n, s) for n, s, *_ in events])
    assert pairs == len(ring) >= 10
    # Not unix time: the session's first events start near zero.
    assert min(s for _, s, *_ in events) < 60e9 < 1e18 < offset
    assert spread < 1e6
    # Durations are the same interval on either clock.
    ring_dur = sorted(s.dur_ns for s in ring if s.name == "istpu.engine.step")
    trace_dur = sorted(d for n, _, d, _ in events
                       if n == "istpu.engine.step")
    assert abs(np.median(ring_dur) - np.median(trace_dur)) < 1e6


def test_merge_lands_store_and_engine_spans_on_the_jax_axis(tmp_path):
    # A jax trace whose session began at unix second 1000: its events
    # count microseconds from there.
    session_ns = 1000 * 10 ** 9
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    jax_events = [
        {"ph": "X", "pid": 701, "tid": 1, "name": "istpu.engine.step",
         "ts": 50.0, "dur": 30.0},
        {"ph": "X", "pid": 701, "tid": 1, "name": "istpu.engine.step",
         "ts": 150.0, "dur": 30.0},
        {"ph": "X", "pid": 3, "tid": 0, "name": "fusion.1", "ts": 60.0,
         "dur": 5.0},
    ]
    with gzip.open(prof / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": jax_events}, f)
    ring = [
        profiling.Span(1, 0, "istpu.engine.step", session_ns + 50_000,
                       30_000, 9, None, 1, {"kind": "decode"}),
        profiling.Span(2, 1, "istpu.model.decode", session_ns + 55_000,
                       20_000, 9, None, 1, {}),
        profiling.Span(3, 0, "istpu.engine.step", session_ns + 150_400,
                       30_000, 9, "r", 1, {"kind": "decode"}),
    ]
    # CLOCK_MONOTONIC read 7 s when CLOCK_REALTIME read 1000 s; a store
    # span at monotonic 7 s + 70 us is jax time 70 us.
    clocks = (session_ns, 7 * 10 ** 9)
    store = [{"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
              "args": {"name": "worker0"}},
             {"ph": "X", "pid": 1, "tid": 0, "name": "GET",
              "ts": 7e6 + 70.0, "dur": 4.0}]
    path, offset = profiling._merge_perfetto(
        str(tmp_path), store, ring, clocks)
    assert offset == (session_ns + 200, 600, 2)
    with gzip.open(path, "rt") as f:
        merged = json.load(f)["traceEvents"]
    assert jax_events == merged[:3]
    get = next(e for e in merged if e.get("name") == "GET")
    assert get["ts"] == pytest.approx(70.0 - 0.2) and get["dur"] == 4.0
    mine = [e for e in merged if e.get("pid") == profiling._RING_PID
            and e["ph"] == "X"]
    assert [e["ts"] for e in mine] == pytest.approx([49.8, 54.8, 150.2])
    assert mine[2]["args"] == {"id": 3, "parent": 0, "request_id": "r",
                               "engine": 1, "kind": "decode"}
    # Without a jax timeline the axis is the store's own.
    alone = tmp_path / "alone"
    alone.mkdir()
    path, offset = profiling._merge_perfetto(str(alone), store, ring, clocks)
    assert offset is None
    with gzip.open(path, "rt") as f:
        merged = json.load(f)["traceEvents"]
    assert next(e for e in merged if e.get("name") == "GET")["ts"] == \
        7e6 + 70.0
    assert next(e for e in merged if e.get("name") ==
                "istpu.model.decode")["ts"] == pytest.approx(7e6 + 55.0)


@pytest.mark.parametrize("hit", [False, True], ids=["miss", "hit"])
def test_first_token_logits_equals_the_private_pieces(params, cfg,
                                                      shm_conn, hit):
    """ServingEngine.first_token_logits against what the benchmark's
    correct.py builds from _admit_fused / _probe_hit /
    restore_prefix_pages / pages_to_kv / _prefill_px_jit today."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.lib import correct

    store = TpuKVStore(shm_conn)
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, total_pages=64, model_id=f"spans-ftl-{hit}"),
        store=store)
    prompt = _prompt(15, 5 * PAGE + 3)
    if hit:
        eng.run([Request("seed", prompt[:4 * PAGE + 1], max_new_tokens=1)])
    free, pool = list(eng.free_pages), np.asarray(eng.k_pages)
    replica = types.SimpleNamespace(engine=eng, inner_store=store)
    want, want_hit = correct.program_first_logits(
        replica, llama, cfg, prompt, hit)
    row, hit_pages = eng.first_token_logits(prompt)
    assert hit_pages == want_hit == (4 if hit else 0)
    assert row.dtype == np.float32 and row.shape == (cfg.vocab_size,)
    np.testing.assert_array_equal(row, want)
    # ... and against the dense forward, whichever program ran.
    ref, _ = llama.forward_dense(params, cfg, jnp.asarray([prompt]))
    np.testing.assert_allclose(row, np.asarray(ref[0, -1]), atol=2e-4)
    # Nothing was admitted and no pool page written.
    assert eng.free_pages == free and not eng.queue
    np.testing.assert_array_equal(np.asarray(eng.k_pages), pool)
    # An engine with work in it refuses.
    eng.submit(Request("busy", prompt, max_new_tokens=2))
    with pytest.raises(RuntimeError, match="idle"):
        eng.first_token_logits(prompt)
    eng.run()
    assert isinstance(_Work(req=Request("w", [1]), prompt=[1]).queued_ns, int)


STAGES = {
    llama: {"embed", "attn.qkv", "attn.rope", "attn.kernel", "attn.out",
            "mlp", "pool.update", "lm_head"},
    moe: {"embed", "attn.qkv", "attn.rope", "attn.kernel", "attn.out",
          "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "pool.update", "lm_head"},
}


@pytest.mark.parametrize("model", [llama, moe], ids=["llama", "moe"])
def test_every_stage_is_named_in_the_step_programs(model):
    """jax.named_scope: the decode and the prefill program's operations
    carry their stage, and no layer index, in their metadata."""
    import re

    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
              n_kv_heads=1, d_ff=64, page_size=PAGE, dtype="float32")
    cfg = (moe.MoEConfig(n_experts=4, top_k=2, **kw) if model is moe
           else llama.LlamaConfig(**kw))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    pool = jnp.zeros((cfg.n_layers, 8, PAGE, cfg.n_kv_heads, cfg.head_dim))
    decode = model.decode_step.lower(
        params, cfg, jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32),
        pool, pool, jnp.zeros((2, 4), jnp.int32)).as_text(debug_info=True)
    prefill = jax.jit(model.prefill, static_argnums=1).lower(
        params, cfg, jnp.zeros((1, 3 * PAGE), jnp.int32)  # > a decode batch
    ).as_text(debug_info=True)
    found = set(re.findall(r"/((?:attn|moe|pool)\.\w+|embed|mlp|lm_head)/",
                           decode))
    # a decode step's experts are one kernel under `moe.experts`, the
    # gates' sum inside it
    assert found == STAGES[model] - {"moe.combine"}
    found = set(re.findall(r"/((?:attn|moe|pool)\.\w+|embed|mlp|lm_head)/",
                           prefill))
    assert found == STAGES[model] - {"pool.update"}


def test_threads_record_while_the_ring_is_read():
    """More recording threads than cores, a short switch interval and a
    reader snapshotting throughout: no snapshot fails, ids stay unique
    and every thread's spans arrive whole and in order."""
    n_threads, n_each = 2 * (os.cpu_count() or 4), 400
    t0 = time.time_ns()
    errors, stop = [], threading.Event()

    def writer(k):
        try:
            for i in range(n_each):
                with profiling.span("istpu.test.stress", f"w{k}", k, i=i):
                    profiling.record("istpu.test.stress.after", t0, 1, i=i)
        except Exception as e:  # surfaced below
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                profiling.spans(since_ns=t0)
                profiling.chrome_trace()
        except Exception as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        rd = threading.Thread(target=reader)
        rd.start()
        ws = [threading.Thread(target=writer, args=(k,))
              for k in range(n_threads)]
        for w in ws:
            w.start()
        for w in ws:
            w.join(timeout=120)
        stop.set()
        rd.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not rd.is_alive()
    assert not any(w.is_alive() for w in ws)
    mine = [s for s in profiling.spans(since_ns=t0)
            if s.name.startswith("istpu.test.stress")]
    assert len(mine) == 2 * n_threads * n_each
    assert len({s.id for s in mine}) == len(mine)
    for k in range(n_threads):
        outer = [s for s in mine if s.engine == k
                 and s.name == "istpu.test.stress"]
        inner = {s.parent: s for s in mine if s.engine == k
                 and s.name == "istpu.test.stress.after"}
        assert [s.fields["i"] for s in outer] == list(range(n_each))
        assert all(inner[s.id].fields["i"] == s.fields["i"]
                   and inner[s.id].request == f"w{k}" for s in outer)


# ---- a plain decode step runs one step ahead (PR 44) ----


def test_a_run_ahead_call_is_a_dispatch_then_a_wait(params, cfg):
    """A call that ran ahead is a kind-`decode` step with `ahead=True`
    whose last two `istpu.model.decode` children are the dispatch of
    the step behind alone (`dispatch_ns`, `program`, `live_pages`),
    ending before the second begins, and the wait for the step in
    flight (`program="land"`). The call that BEGINS a run dispatched
    its own step first, under a span of the same form; the call that
    ENDS one only waits. The two counters are what the spans count."""
    eng = _engine(params, cfg, None, "spans-ahead", max_slots=3)
    spans = _run(eng, Request("a", _prompt(61, PAGE + 3), max_new_tokens=12),
                 Request("b", _prompt(62, PAGE + 5), max_new_tokens=7))
    steps = [s for s in _named(spans, "istpu.engine.step")
             if s.fields["kind"] == "decode"]
    ahead = [s for s in steps if s.fields.get("ahead")]
    assert eng.stats["decode_steps"] == len(steps) == 11
    # two runs (one ends with b's budget), each but its last step
    assert eng.stats["decode_steps_ahead"] == len(ahead) == 9
    assert eng.stats["decode_rows_dropped"] == 0
    shapes = []
    for st in steps:
        kids = [k for k in _children(spans, st)
                if k.name == "istpu.model.decode"]
        shapes.append("".join("w" if k.fields["program"] == "land" else "d"
                              for k in kids))
        for k in kids:
            if k.fields["program"] == "land":
                # ... and how many slots waited for it (PR 51)
                assert k.fields == {"program": "land", "dispatch_ns": 0,
                                    "waiting": k.fields["waiting"]}
            else:
                assert _less_dispatch(k).keys() == {"program", "live_pages"}
                assert k.fields["program"] == "decode_fused"
                # the dispatch alone: it returned as the span ended
                assert k.dur_ns - k.fields["dispatch_ns"] < SLACK_NS
        for a, b in zip(kids, kids[1:]):
            assert a.t0_ns + a.dur_ns <= b.t0_ns + SLACK_NS
        # the step's own host work is what no child covers
        assert sum(k.dur_ns for k in kids) < st.dur_ns + SLACK_NS
        assert bool(st.fields.get("ahead")) == shapes[-1].endswith("dw")
        # a call that only waits dispatched nothing, and says nothing
        assert ("rows_uploaded" not in st.fields) == (shapes[-1] == "w")
    # d: a dispatch alone, w: a wait alone
    assert shapes == ["ddw"] + ["dw"] * 4 + ["w"] + ["ddw"] + ["dw"] * 3 + ["w"]
    # b crossed a page edge at its 4th token, a at its 6th: the tables
    # went up anew behind a step in flight, tokens and lengths did not
    assert [s.fields["rows_uploaded"] for s in ahead].count(True) >= 3


def test_the_span_readers_over_synchronous_and_run_ahead_steps():
    """`benchmark/metrics/_idle_by_span.lead_and_lag` and
    `decode_host_p50_ms.value`, as they stand, over a hand-made ring of
    one synchronous plain step and three run-ahead ones with the
    device's programs beside them: lead and lag come from the
    synchronous step alone (a run-ahead call's program starts inside
    its WAIT, not inside its first decode span), the host time from
    all four."""
    from benchmark.metrics import _idle_by_span, decode_host_p50_ms

    ms = 1_000_000
    ids = iter(range(1, 100))

    def step(t0, dur, kids, **fields):
        """One step and its decode children [(offset, dur, fields)],
        as the ring holds them: a span enters as it ends."""
        sid = next(ids)
        out = [profiling.Span(next(ids), sid, "istpu.model.decode",
                              t0 + at, d, 1, None, 1, f)
               for at, d, f in kids]
        return out + [profiling.Span(sid, 0, "istpu.engine.step", t0, dur,
                                     1, None, 1,
                                     dict(kind="decode", active=2, k=1,
                                          **fields))]

    sent = dict(program="decode_fused", live_pages=4)
    land = dict(program="land", dispatch_ns=0)
    ring = (
        # synchronous: dispatch to tokens in ONE span; its program runs
        # [1.0, 4.0) ms, the tokens are there at 4.7
        step(0, 5 * ms, [(300_000, 4_400_000,
                          dict(sent, dispatch_ns=200_000))],
             rows_uploaded=True)
        # begins a run: its own step's dispatch (the program runs [6.0,
        # 9.0)), the dispatch of the one behind it, then the wait
        + step(5 * ms, 5 * ms, [(300_000, 200_000,
                                 dict(sent, dispatch_ns=200_000)),
                                (700_000, 200_000,
                                 dict(sent, dispatch_ns=200_000)),
                                (1 * ms, 3_700_000, land)],
               ahead=True, rows_uploaded=True)
        # two in the middle of it: programs [9, 12) and [12, 15) start
        # as the one before ends, inside the call's wait
        + step(10 * ms, 3 * ms, [(400_000, 200_000,
                                  dict(sent, dispatch_ns=200_000)),
                                 (700_000, 2 * ms, land)],
               ahead=True, rows_uploaded=False)
        + step(13 * ms, 3 * ms, [(400_000, 200_000,
                                  dict(sent, dispatch_ns=200_000)),
                                 (700_000, 2 * ms, land)],
               ahead=True, rows_uploaded=False))
    modules = [("jit__decode_fused", 1 * ms, 3 * ms),
               ("jit__decode_fused", 6 * ms, 3 * ms),
               ("jit__decode_fused", 9 * ms, 3 * ms),
               ("jit__decode_fused", 12 * ms, 3 * ms),
               ("jit__decode_fused", 15 * ms, 3 * ms)]
    lead, lag = _idle_by_span.lead_and_lag(ring, modules, 0, 20 * ms,
                                           ("decode_fused",))
    assert lead == [1 * ms] and lag == [700_000]

    class Obs:
        window = (0.0, 0.02)

    # step less ALL its decode children: 0.6, 0.9, 0.8, 0.8 ms
    assert decode_host_p50_ms.value(Obs(), ring) == pytest.approx(0.8)


# ---- the gap between tokens, by what the engine thread did in it (PR 51) ----

CAUSES = ["step", "admit_miss", "admit_hit", "admit_piece", "offload"]


def _gap_stats(eng):
    """The eight counters `_emit` keeps, by their short names, and
    what the five causes leave of the gaps' length: `other`."""
    g = {c: eng.stats[f"gap_ns_{c}"] for c in CAUSES}
    g.update(tokens=eng.stats["gap_tokens"], ns=eng.stats["gap_ns"],
             stalled=eng.stats["gaps_stalled"])
    g["other"] = g["ns"] - sum(g[c] for c in CAUSES)
    return g


def _heard(*reqs):
    """`reqs` with a callback that notes when each token was emitted;
    returns {request id: [perf_counter_ns a token]}."""
    heard = {r.request_id: [] for r in reqs}
    for r in reqs:
        r.on_token = lambda rid, _t: heard[rid].append(
            time.perf_counter_ns())
    return heard


def _decoding(eng, req, steps=3):
    """`req` admitted into an idle engine and `steps` steps on; the
    spans from here on are what happens while it decodes."""
    eng.submit(req)
    for _ in range(steps):
        eng.step()
    return time.time_ns()


def _of(spans, name, rid):
    return [s for s in _named(spans, name) if s.request == rid]


def _lands(spans):
    """The decode spans under which a plain step landed."""
    return [s for s in _named(spans, "istpu.model.decode")
            if "waiting" in s.fields]


def test_one_request_alone_has_no_stalled_gap(params, cfg):
    """Alone, a request's gaps are steps and the loop around them: no
    other cause runs, the first token is no gap, and their length is
    what a client's callback sees between tokens."""
    eng = _engine(params, cfg, None, "gaps-alone")
    req = Request("a", _prompt(71, PAGE + 2), max_new_tokens=7)
    heard = _heard(req)
    spans = _run(eng, req)
    g = _gap_stats(eng)
    assert g["tokens"] == 6 and g["stalled"] == 0
    assert [g[c] for c in CAUSES[1:]] == [0, 0, 0, 0]
    assert 0 < g["step"] <= g["ns"] and g["other"] >= 0
    assert abs(g["ns"] - (heard["a"][-1] - heard["a"][0])) < SLACK_NS
    # the step's part is the decode spans between the first token and
    # the last: all of them but the tail of the one the last landed in
    decodes = sum(s.dur_ns for s in _named(spans, "istpu.model.decode"))
    assert decodes - SLACK_NS < g["step"] <= decodes
    assert [s.fields["waiting"] for s in _lands(spans)] == [0] + [1] * 5
    assert not any("stall_ns" in s.fields for s in _lands(spans))


def _a_miss(eng, conn):
    return Request("b", _prompt(73, 2 * PAGE + 3), max_new_tokens=14)


def _a_hit(eng, conn):
    first = _prompt(74, 3 * PAGE)
    out = eng.run([Request("b0", first, max_new_tokens=PAGE)])["b0"]
    return Request("b", first + out + _prompt(75, 3), max_new_tokens=14)


@pytest.mark.parametrize("cause, second", [
    ("admit_miss", _a_miss), ("admit_hit", _a_hit)], ids=["miss", "hit"])
def test_an_admission_is_in_the_gap_of_the_slot_that_waited_and_in_none_of_its_own(
        params, cfg, shm_conn, cause, second):
    """`b` is admitted while `a` decodes: the admission's span, whole
    and once, is in the ONE gap of `a` that held it, under the cause
    its `hit_pages` name, and in none of `b`'s own; the land span
    behind it says so on the ring."""
    eng = _engine(params, cfg, shm_conn, f"gaps-{cause}")
    b = second(eng, shm_conn)
    before = _gap_stats(eng)
    t0 = _decoding(eng, Request("a", _prompt(72, PAGE + 1),
                                max_new_tokens=10, cache=False))
    eng.submit(b)
    out = eng.run()
    spans = profiling.spans(since_ns=t0)
    (admit,) = _of(spans, "istpu.sched.admit", "b")
    assert (admit.fields["hit_pages"] > 0) == (cause == "admit_hit")
    g = _gap_stats(eng)
    assert g[cause] - before[cause] == admit.dur_ns
    assert g["stalled"] - before["stalled"] == 1
    # nothing else stalled anyone: `a` writes nothing, `b` finishes last
    assert [g[c] - before[c] for c in CAUSES[1:] if c != cause] == [0, 0, 0]
    assert g["tokens"] - before["tokens"] == len(out["a"]) + len(out["b"]) - 2
    assert g["other"] >= 0
    (stalled,) = [s for s in _lands(spans) if "stall_ns" in s.fields]
    assert stalled.fields["waiting"] == 1
    assert stalled.fields["stall_ns"] == admit.dur_ns
    assert stalled.fields["stall_cause"] == cause
    assert stalled.t0_ns >= admit.t0_ns + admit.dur_ns - SLACK_NS
    # `b` emitted at its admission, not at the land before
    after = [s for s in _lands(spans) if s.t0_ns > stalled.t0_ns]
    assert after[0].fields["waiting"] == 1
    assert after[1].fields["waiting"] == 2


def test_pieces_are_in_the_gaps_of_the_slot_that_waited(params, cfg):
    """An admission in pieces while `a` decodes: every piece is in the
    gap of `a` it ran in, and `b`, whose pieces they are, has none of
    them in its own."""
    eng = _engine(params, cfg, None, "gaps-piece", admit_piece=PAGE)
    t0 = _decoding(eng, Request("a", _prompt(76, PAGE),
                                max_new_tokens=30))
    eng.submit(Request("b", _prompt(77, 3 * PAGE + 2), max_new_tokens=5))
    out = eng.run()
    spans = profiling.spans(since_ns=t0)
    pieces = _named(spans, "istpu.sched.admit_piece")
    assert len(pieces) == 4 == eng.stats["admit_pieces"]
    (admit,) = _of(spans, "istpu.sched.admit", "b")
    g = _gap_stats(eng)
    assert g["admit_piece"] == sum(p.dur_ns for p in pieces)
    assert g["admit_miss"] == admit.dur_ns and g["admit_hit"] == 0
    # the admission held no piece (a cold prompt's first runs with the
    # next step), so the five gaps that stalled are four pieces' and its
    assert g["stalled"] == 4 + 1
    assert g["tokens"] == len(out["a"]) + len(out["b"]) - 2
    stalled = [s.fields for s in _lands(spans) if "stall_ns" in s.fields]
    assert [f["stall_cause"] for f in stalled].count("admit_piece") == 4
    assert sum(f["stall_ns"] for f in stalled) \
        == g["admit_piece"] + g["admit_miss"]


def test_a_finishs_offload_is_in_the_gap_of_the_slot_that_goes_on(
        params, cfg, shm_conn):
    """`a` finishes while `b` decodes: what the ENGINE thread does of
    its offload (the span `istpu.cache.offload`; the upload thread's
    part counts nowhere) is in the one gap of `b` that held it."""
    eng = _engine(params, cfg, shm_conn, "gaps-offload")
    t0 = time.time_ns()
    out = eng.run([Request("a", _prompt(78, 2 * PAGE), max_new_tokens=4),
                   Request("b", _prompt(79, PAGE + 1), max_new_tokens=12,
                           cache=False)])
    spans = profiling.spans(since_ns=t0)
    (offload,) = _named(spans, "istpu.cache.offload", reason="finish")
    assert offload.request == "a"
    (upload,) = _named(spans, "istpu.cache.upload")
    assert upload.tid != offload.tid
    g = _gap_stats(eng)
    assert g["offload"] == offload.dur_ns
    # `b` was admitted behind `a`'s first token: that gap of `a` too
    (admit_b,) = _of(spans, "istpu.sched.admit", "b")
    assert g["admit_miss"] == admit_b.dur_ns
    assert g["stalled"] == 2
    assert g["tokens"] == len(out["a"]) + len(out["b"]) - 2 == 14
    (stalled,) = [s for s in _lands(spans)
                  if s.fields.get("stall_cause") == "offload"]
    assert stalled.fields["stall_ns"] == offload.dur_ns
    assert stalled.fields["waiting"] == 1


def test_k_tokens_at_once_are_k_gaps_and_one_interval(params, cfg):
    """A burst emits k tokens in one call: k gaps, the first the
    interval since the burst before and the rest 0, as the callback
    hears them."""
    eng = _engine(params, cfg, None, "gaps-burst", host_steps=4)
    req = Request("a", _prompt(80, PAGE + 2), max_new_tokens=9)
    heard = _heard(req)
    spans = _run(eng, req)
    assert {s.fields["kind"] for s in _named(spans, "istpu.engine.step")} \
        >= {"burst"}
    g = _gap_stats(eng)
    assert g["tokens"] == 8 and g["stalled"] == 0
    assert abs(g["ns"] - (heard["a"][-1] - heard["a"][0])) < SLACK_NS
    assert 0 < g["step"] <= g["ns"]


def test_a_row_dropped_behind_an_eos_is_no_gap(params, cfg):
    """Under run-ahead the step behind an EOS holds a row nobody sees:
    it is counted as dropped, and as no gap."""
    prompts = {"x": _prompt(81, PAGE + 2), "y": _prompt(82, PAGE + 5)}
    free = _engine(params, cfg, None, "gaps-free", max_slots=3).run(
        [Request(r, p, max_new_tokens=12) for r, p in prompts.items()])
    at = next(i for i in range(3, 11) if free["x"][i] not in free["x"][:i]
              and free["x"][i] not in free["y"])
    eng = _engine(params, cfg, None, "gaps-eos", max_slots=3,
                  eos_id=free["x"][at])
    out = eng.run([Request(r, p, max_new_tokens=12)
                   for r, p in prompts.items()])
    assert out["x"] == free["x"][:at + 1] and len(out["y"]) == 12
    assert eng.stats["decode_rows_dropped"] >= 1
    g = _gap_stats(eng)
    assert g["tokens"] == len(out["x"]) + len(out["y"]) - 2
    assert g["other"] >= 0


@pytest.mark.parametrize("ahead", [True, False], ids=["ahead", "sync"])
def test_the_five_causes_never_exceed_the_gaps(params, cfg, shm_conn,
                                               ahead):
    """Arrivals, hits, finishes and their offloads among decoding
    slots, one step ahead or held synchronous: only the outermost
    listed span counts, so the five causes sum to no more than the
    gaps' length, gap by gap and so in the sums."""
    eng = _engine(params, cfg, shm_conn, f"gaps-sum-{ahead}", max_slots=3)
    if not ahead:
        eng._proven = lambda active: False
    first = _prompt(83, 2 * PAGE)
    done = eng.run([Request("r0", first, max_new_tokens=PAGE)])["r0"]
    reqs = [Request("r1", first + done + _prompt(84, 3), max_new_tokens=9),
            Request("r2", _prompt(85, 3 * PAGE + 1), max_new_tokens=14),
            Request("r3", _prompt(86, PAGE), max_new_tokens=20),
            Request("r4", _prompt(87, 2 * PAGE + 5), max_new_tokens=6)]
    heard = _heard(*reqs)
    t0 = time.time_ns()
    eng.submit(reqs[1])
    eng.step()
    eng.submit(reqs[0])
    eng.step()
    eng.step()
    for r in reqs[2:]:
        eng.submit(r)
    out = eng.run()
    assert (eng.stats["decode_steps_ahead"] > 0) == ahead
    g = _gap_stats(eng)
    assert g["tokens"] == PAGE - 1 + sum(len(out[r.request_id]) - 1
                                        for r in reqs)
    assert all(g[c] > 0 for c in CAUSES if c != "admit_piece"), g
    assert g["other"] >= 0 and g["stalled"] >= 4
    # the gaps' length is the callbacks' (r0 ran before they listened)
    spans = profiling.spans(since_ns=t0)
    mine = sum(t[-1] - t[0] for t in heard.values())
    assert mine <= g["ns"] and g["ns"] - mine < mine
    # on the ring: a land's `stall_ns` is what its waiting slots' gaps
    # hold of the four causes, and never more than the lands are apart
    lands = _lands(spans)
    for a, b in zip(lands, lands[1:]):
        apart = b.t0_ns + b.dur_ns - a.t0_ns - a.dur_ns
        assert b.fields.get("stall_ns", 0) <= apart + SLACK_NS
        assert b.fields["waiting"] <= a.fields["waiting"] + 3
    assert sum(s.fields["waiting"] for s in lands
               if "stall_ns" in s.fields) <= eng.stats["gaps_stalled"]


def test_a_swapped_out_sequence_carries_its_gap_across_the_swap(
        params, cfg, shm_conn):
    """A sequence preempted through the store and resumed: the gap
    between its last token before and its first after is ONE gap,
    which holds the hit that resumed it."""
    eng = _engine(params, cfg, shm_conn, "gaps-swap", total_pages=11,
                  max_pages_per_seq=10)
    reqs = [Request("a", _prompt(8, 5 * PAGE), max_new_tokens=3 * PAGE),
            Request("b", _prompt(9, 4 * PAGE), max_new_tokens=PAGE)]
    heard = _heard(*reqs)
    t0 = time.time_ns()
    eng.submit(reqs[0])
    eng.step()
    eng.submit(reqs[1])
    out = eng.run()
    assert eng.stats["preemptions"] >= 1
    spans = profiling.spans(since_ns=t0)
    resumed = [s for s in _named(spans, "istpu.sched.admit",
                                 outcome="admitted")
               if s.fields["hit_pages"] > 0]
    assert resumed
    g = _gap_stats(eng)
    assert g["tokens"] == len(out["a"]) + len(out["b"]) - 2
    assert abs(g["ns"] - sum(t[-1] - t[0] for t in heard.values())) \
        < SLACK_NS
    assert g["admit_hit"] > 0 and g["other"] >= 0
