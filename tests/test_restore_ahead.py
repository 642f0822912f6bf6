"""A hit's store read on the engine's restore thread (PR 56): `submit`
hands a caching request's probe, pin, copy and host-to-device transfer
to that thread, decode steps go on beside it, and the admission takes
pages that are already in HBM. Same keys, same bytes, same programs:
every test holds the tokens to those of the synchronous store call (an
engine that stages nothing), on the CPU, with a store whose page reads
can be held by an event."""

import json
import threading
import time
import types
import urllib.request

import jax
import numpy as np
import pytest

from infinistore_tpu import InfiniStoreKeyNotFound, serving
from infinistore_tpu.models import llama
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.serving_http import ServingHTTPServer
from infinistore_tpu.tpu import TpuKVStore

HIT_PAGES = 5  # of the plain family's second turn (`_plain`)


class Held(TpuKVStore):
    """A store whose page reads wait while `gate` is clear, fail with
    `fail` where one is set, and say what they did: how many there
    were, on which threads, onto which devices, and the most bytes the
    engine `watch`ed had staged when one began."""

    def __init__(self, conn):
        super().__init__(conn)
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()
        self.fail = None
        self.reads, self.threads, self.devices = 0, set(), set()
        self.watch, self.most_staged = None, 0

    def get_kv_pages(self, *a, **kw):
        self.reads += 1
        self.threads.add(threading.get_ident())
        if self.watch is not None:
            self.most_staged = max(self.most_staged,
                                   self.watch._staged_bytes)
        self.entered.set()
        assert self.gate.wait(60)
        if self.fail is not None:
            raise self.fail
        out = super().get_kv_pages(*a, **kw)
        self.devices |= set(out.devices())
        return out


@pytest.fixture(scope="module")
def families():
    """name -> (model module, config, params, ServingConfig fields,
    tokens of a first turn), one family of each shape of hit, built on
    first use at the presets of the families' own tests."""
    from test_evabyte import CONF as FOLDED
    from test_glm import CONF as INDEXED
    from test_hybrid_state import CONF as STATE
    from test_window_full import CONF as BANDED

    from infinistore_tpu.models import (evabyte, glm, hf, hybrid,
                                        smallthinker)

    def plain():
        c = llama.LlamaConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq=128, page_size=8, dtype="float32")
        return llama, c, llama.init_params(jax.random.PRNGKey(0), c), {}, 37

    def bridged(model, bridge, conf, turn, page=8, **sc):
        c = bridge(types.SimpleNamespace(**conf), page_size=page,
                   dtype="float32")
        p = jax.jit(model.init_params, static_argnums=1)(
            jax.random.PRNGKey(0), c)
        return model, c, p, sc, turn

    makers = {
        "one_kind": plain,
        # a band of 4 pages: the banded layers' read begins above 0
        "full_and_window_kinds": lambda: bridged(
            smallthinker, hf.smallthinker_config_from_hf, BANDED, 61,
            total_pages=96, max_pages_per_seq=32),
        "pages_and_snapshot": lambda: bridged(
            hybrid, hf.hybrid_config_from_hf, STATE, 37),
        "index_kinds_as_a_tuple": lambda: bridged(
            glm, hf.glm_dsa_config_from_hf, INDEXED, 45,
            total_pages=160, max_pages_per_seq=48),
        # a window of 256 positions: one folded, 4 exact pages behind it
        "a_folded_prefix": lambda: bridged(
            evabyte, hf.evabyte_config_from_hf, FOLDED, 300, page=16,
            total_pages=96, max_pages_per_seq=24, admit_piece=256),
    }
    made = {}

    def family(name):
        if name not in made:
            made[name] = makers[name]()
        return made[name]
    return family


def _tokens(seed, vocab, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, vocab, n)]


def _engine(family, store, model_id, device=None, staging=True, **sc):
    model, cfg, params, fields, _ = family
    fields = {"max_slots": 2, "total_pages": 64, "max_pages_per_seq": 16,
              **fields, **sc}
    if device is not None:
        params = jax.device_put(params, device)
    eng = ServingEngine(params, cfg, ServingConfig(model_id=model_id,
                                                   **fields),
                        store=store, model=model)
    if not staging:  # the store call of a hit where it always was
        eng._stage = lambda work: None
    return eng


def _stored_turn(family, conn, model_id, seed=5, new=11):
    """A first turn served and offloaded through `conn`; returns the
    next turn's prompt: the first, its answer and a new message."""
    _, cfg, _, _, n = family
    first = _tokens(seed, cfg.vocab_size, n)
    eng = _engine(family, TpuKVStore(conn), model_id)
    out = eng.run([Request("turn1", first, max_new_tokens=new)])["turn1"]
    assert eng.stats["offloaded_pages"] > 0
    eng.close()
    return first + out + _tokens(seed + 1, cfg.vocab_size, 5)


def _synchronous(family, conn, model_id):
    """The next turn by an engine that stages nothing, over a first
    turn stored under a model id of its own (its finish writes the
    turn's pages, which the engine under test must not find): (the
    engine, its tokens)."""
    turn2 = _stored_turn(family, conn, model_id + "-sync")
    sync = _engine(family, TpuKVStore(conn), model_id + "-sync",
                   staging=False)
    want = sync.run([Request("t2", turn2, max_new_tokens=6)])["t2"]
    sync.close()
    assert sync.stats["restores_staged"] == 0
    return sync, want


def _plain(families, shm_conn, model_id):
    """(family, the next turn's prompt over a stored first turn, a
    held store, the tokens the synchronous store call gives it)."""
    family = families("one_kind")
    sync, want = _synchronous(family, shm_conn, model_id)
    assert sync.stats["prefix_hit_pages"] == HIT_PAGES
    turn2 = _stored_turn(family, shm_conn, model_id)
    return family, turn2, Held(shm_conn), want


def _restore_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("istpu-restore-")]


@pytest.mark.parametrize("name", [
    "one_kind", "full_and_window_kinds", "pages_and_snapshot",
    "index_kinds_as_a_tuple", "a_folded_prefix"])
def test_a_staged_hit_gives_the_tokens_of_the_synchronous_call(
        families, shm_conn, name):
    """... on the engine's own device, read on a thread that is not
    the one that steps the engine, and counted as the synchronous call
    counts it."""
    family = families(name)
    model_id = f"ahead-{name}"
    sync, want = _synchronous(family, shm_conn, model_id)
    assert sync.stats["prefix_hit_pages"] > 0
    turn2 = _stored_turn(family, shm_conn, model_id)
    store = Held(shm_conn)
    chip = jax.devices()[1]
    eng = _engine(family, store, model_id, device=chip)
    assert eng.run([Request("t2", turn2, max_new_tokens=6)])["t2"] == want
    assert eng.stats["restores_staged"] == 1
    assert eng.stats["restores_staged_late"] == 0
    assert store.devices == {chip} and threading.get_ident() not in store.threads
    for key in ("prefix_hit_pages", "restored_pages", "restore_runs",
                "snapshots_restored", "restore_misses", "store_errors",
                "summary_pages_restored", "index_pages_restored",
                "restore_trimmed_pages", "prefill_tokens"):
        assert eng.stats[key] == sync.stats[key], key
    # nothing staged is left, and close() leaves no thread
    assert eng._staged_bytes == 0
    eng.close()
    assert eng._restore_thread is None and not _restore_threads()


def test_decode_steps_land_beside_a_read_that_is_held(families, shm_conn):
    """With a sequence decoding and the head's read held, the steps go
    on (ahead, too: a head without its pages is no admission a call
    could make), the head stays queued under no admission span, and
    once the pages are there the next call admits it."""
    family, turn2, store, want = _plain(families, shm_conn, "beside")
    _, cfg, *_ = family
    eng = _engine(family, store, "beside")
    eng.submit(Request("long", _tokens(9, cfg.vocab_size, 9),
                       max_new_tokens=40, cache=False))
    while eng.stats["decode_steps"] < 2:
        eng.step()
    store.gate.clear()
    eng.submit(Request("t2", turn2, max_new_tokens=6))
    assert store.entered.wait(60)
    steps, ahead = (eng.stats[k] for k in ("decode_steps",
                                           "decode_steps_ahead"))
    for _ in range(6):
        eng.step()
    assert eng.stats["decode_steps"] >= steps + 5
    assert eng.stats["decode_steps_ahead"] > ahead
    assert [w.req.request_id for w in eng.queue] == ["t2"]
    assert eng.slots[1] is None and eng.stats["prefix_hit_pages"] == 0
    store.gate.set()
    assert eng.queue[0].staged.done.wait(60)
    eng.step()
    assert not eng.queue and eng.slots[1] is not None
    out = eng.run()
    assert out["t2"] == want and len(out["long"]) == 40
    assert eng.stats["restores_staged"] == 1
    assert eng.stats["restores_staged_late"] == 0
    assert eng.stats["restore_stage_wait_ms"] == 0
    eng.close()


def _open_later(gate, after_s=0.3):
    timer = threading.Timer(after_s, gate.set)
    timer.start()
    return timer


@pytest.mark.parametrize("driver", ["step", "run", "http"])
def test_an_empty_engine_waits_for_the_heads_pages(families, shm_conn,
                                                   driver):
    """With nothing decoding the head is admitted in the `step()` call
    that finds it, however long its read takes, so neither `run()` nor
    serving_http's rule for a head that can never admit drops it."""
    family, turn2, store, want = _plain(families, shm_conn,
                                        f"empty-{driver}")
    eng = _engine(family, store, f"empty-{driver}")
    store.gate.clear()
    if driver == "http":
        srv = ServingHTTPServer(eng, port=0)
        base = f"http://127.0.0.1:{srv.start()}"
        timer = _open_later(store.gate)
        req = urllib.request.Request(
            f"{base}/generate", method="POST",
            data=json.dumps({"prompt": turn2, "max_new_tokens": 6,
                             "stream": False}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            got = json.loads(r.read())["tokens"]
        srv.shutdown()
    else:
        if driver == "step":
            eng.submit(Request("t2", turn2, max_new_tokens=6))
            timer = _open_later(store.gate)
            eng.step()
            assert not eng.queue and eng.slots[0] is not None
            got = eng.run()["t2"]
        else:
            timer = _open_later(store.gate)
            got = eng.run([Request("t2", turn2, max_new_tokens=6)])["t2"]
        eng.close()
    timer.join()
    assert got == want
    assert eng.stats["restores_staged"] == 1
    assert eng.stats["restores_staged_late"] == 0
    assert eng.stats["restore_stage_wait_ms"] > 100
    assert not _restore_threads()


@pytest.mark.parametrize("error, counted", [
    (InfiniStoreKeyNotFound("evicted"), "restore_misses"),
    (OSError("connection lost"), "store_errors")])
def test_what_goes_wrong_on_the_restore_thread_is_counted_on_the_engines(
        families, shm_conn, error, counted):
    """A key evicted between probe and read admits cold and is a miss;
    any other failure reaches `_store_failed` on the engine thread and
    the engine serves on without the store. The request is served
    either way, with the tokens of a cold admission."""
    family, turn2, store, want = _plain(families, shm_conn,
                                        f"wrong-{counted}")
    eng = _engine(family, store, f"wrong-{counted}")
    store.fail = error
    out = eng.run([Request("t2", turn2, max_new_tokens=6)])
    assert out["t2"] == want  # float32 on the CPU: a cold run's tokens
    assert eng.stats[counted] == 1 and store.reads == 1
    assert eng.stats["prefix_hit_pages"] == 0
    assert eng.stats["restores_staged"] == 0
    assert eng._store_ok == (counted == "restore_misses")
    assert eng._staged_bytes == 0
    eng.close()


def test_a_retried_admission_makes_one_store_call(families, shm_conn):
    """Under pool pressure the head's admission comes back for want of
    pages, call after call: what it staged stays on it."""
    family, turn2, store, want = _plain(families, shm_conn, "retry")
    _, cfg, *_ = family
    # 11 usable pages: 7 for the sequence that runs, 4 < the head's 7
    eng = _engine(family, store, "retry", total_pages=12)
    eng.submit(Request("long", _tokens(9, cfg.vocab_size, 33),
                       max_new_tokens=20, cache=False))
    eng.step()
    eng.submit(Request("t2", turn2, max_new_tokens=6))
    out = eng.run()
    assert out["t2"] == want and len(out["long"]) == 20
    assert eng.stats["admit_retries"] > 3 and eng.stats["preemptions"] == 0
    assert store.reads == 1 and eng.stats["restores_staged"] == 1
    assert eng._staged_bytes == 0
    eng.close()


@pytest.mark.parametrize("bound_in_hits", [2.5, 0])
def test_the_staged_bytes_never_pass_the_bound(families, shm_conn,
                                               monkeypatch, bound_in_hits):
    """Four hits queued behind an engine whose one slot is taken: the
    restore thread reads as many as the bound holds and the next as
    their admissions take them; a hit larger than the bound alone is
    read when nothing else is staged."""
    family, turn2, store, want = _plain(families, shm_conn,
                                        f"bound-{bound_in_hits}")
    _, cfg, *_ = family
    eng = _engine(family, store, f"bound-{bound_in_hits}", max_slots=1)
    hit = HIT_PAGES * eng._page_bytes
    bound = int(bound_in_hits * hit)
    monkeypatch.setattr(serving, "RESTORE_STAGED_BYTES", bound)
    store.watch = eng
    eng.submit(Request("long", _tokens(9, cfg.vocab_size, 9),
                       max_new_tokens=12, cache=False))
    eng.step()
    for i in range(4):
        eng.submit(Request(f"t2-{i}", turn2, max_new_tokens=6))
    # the restore thread fills the bound and waits there
    deadline = time.monotonic() + 60
    while store.reads < max(1, int(bound_in_hits)) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)
    assert store.reads == max(1, int(bound_in_hits))
    assert eng._staged_bytes == store.reads * hit
    out = eng.run()
    assert all(out[f"t2-{i}"] == want for i in range(4))
    assert store.reads == 4 and eng.stats["restores_staged"] == 4
    # the most that was staged as a read began, that read counted (a
    # hit probed behind the first one's finish is a page deeper)
    assert max(1, int(bound_in_hits)) * hit <= store.most_staged \
        <= max(bound, hit + eng._page_bytes)
    assert eng._staged_bytes == 0
    eng.close()


def test_close_leaves_no_thread_and_no_staged_array(families, shm_conn):
    """... with hits queued and staged, one of them mid-read; the
    engine stays usable, and serves them by the synchronous call."""
    family, turn2, store, want = _plain(families, shm_conn, "close")
    _, cfg, *_ = family
    eng = _engine(family, store, "close", max_slots=1)
    eng.submit(Request("long", _tokens(9, cfg.vocab_size, 9),
                       max_new_tokens=12, cache=False))
    eng.step()
    eng.submit(Request("a", turn2, max_new_tokens=6))
    assert eng.queue[0].staged.done.wait(60)
    assert eng._staged_bytes > 0
    store.entered.clear()
    store.gate.clear()
    eng.submit(Request("b", turn2, max_new_tokens=6))
    assert store.entered.wait(60)
    held = [w.staged for w in eng.queue]
    closer = threading.Thread(target=eng.close)
    closer.start()
    time.sleep(0.1)
    store.gate.set()  # a read under way ends; what it read is dropped
    closer.join(60)
    assert not closer.is_alive()
    assert eng._restore_thread is None and not _restore_threads()
    assert eng._staged_bytes == 0
    assert all(w.staged is None for w in eng.queue)
    assert all(st.restored is None and st.snap is None for st in held)
    out = eng.run()
    assert out["a"] == want and out["b"] == want
    assert eng.stats["restores_staged"] == 0
    assert eng.stats["prefix_hit_pages"] > 0
    eng.close()
