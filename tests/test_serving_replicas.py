"""Four engines on four devices over ONE native store: the deployment
of the cell mistral7b-replicas4-sessions, at the tiny widths of its
configuration file's rehearsal block, on the CPU's forced host devices.

Turn k of session s runs on engine (s + k) mod 4, so every hit restores
pages another engine wrote. Held here: the answers against the plain
float32 reference (benchmark/reference/dense_decoder.py), the store's
visibility across connections, the bit-exact read-back through the
other connections, the counters and span fields that tell a foreign hit
from an own one, and a miss (never a wrong page) after eviction.
"""

import os
import sys
import types

import jax
import numpy as np
import pytest

from infinistore_tpu import (
    ClientConfig, InfiniStoreServer, InfinityConnection, ServerConfig,
)
from infinistore_tpu import serving
from infinistore_tpu.serving import (
    Request, ServingConfig, ServingEngine, content_page_keys,
)
from infinistore_tpu.tpu import TpuKVStore
from infinistore_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import correct, serve  # noqa: E402
from benchmark.lib.store import SpanStore  # noqa: E402

R = 4
CONTEXT, MESSAGE, ANSWER, TURNS = 64, 16, 32, 3
CHECK = 8
# float32 engine against the float32 reference at hidden 128, 2 layers:
# test_bench_reference.py sees under 2e-4 on a plain prefill; the hit
# path adds the restore and the page -> contiguous form, which move no
# bit. A wrong page, position or layer moves logits by O(1).
LOGIT_TOL = 1e-3
TOKEN_EPS = 2 * LOGIT_TOL


@pytest.fixture(scope="module")
def tiny():
    conf = serve.load_config("benchmark/configs/mistral7b-replicas4.json",
                             rehearsal=True)
    model, cfg = serve.model_config(conf)
    params = serve.init_weights(model, cfg, 2 ** 31 + 26, jax.devices()[0])
    return types.SimpleNamespace(
        conf=conf, model=model, cfg=cfg, params=params,
        reference=serve.reference_module(conf))


def _connect(port):
    conn = InfinityConnection(ClientConfig(host_addr="127.0.0.1",
                                           service_port=port))
    conn.connect()
    assert conn.shm_connected
    return conn


def _replicas(tiny, port, model_id, n=R, total_pages=96):
    """n engines on n devices, a connection each, one store."""
    out = []
    for i, dev in enumerate(jax.devices()[:n]):
        conn = _connect(port)
        inner = TpuKVStore(conn)
        store = SpanStore(inner)
        eng = ServingEngine(
            jax.device_put(tiny.params, dev), tiny.cfg,
            ServingConfig(max_slots=2, total_pages=total_pages,
                          max_pages_per_seq=24, model_id=model_id),
            store=store, model=tiny.model)
        out.append(types.SimpleNamespace(
            index=i, device=dev, conn=conn, inner_store=inner,
            store=store, engine=eng))
    return out


def _close(reps):
    for r in reps:
        r.conn.close()


@pytest.fixture(scope="module")
def reps(tiny, server):
    out = _replicas(tiny, server.service_port, "replicas4-test")
    yield out
    _close(out)


def _tokens(seed, n, vocab):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


def _play(reps, tiny, s, turns=TURNS, answer=ANSWER, route=None):
    """Session s, turn k on replica (s + k) mod R (or route(k)).
    Returns [(prompt, answered tokens, replica)] per turn."""
    vocab = tiny.cfg.vocab_size
    history = _tokens(1000 + s, CONTEXT, vocab)
    out = []
    for k in range(1, turns + 1):
        rep = reps[route(k) if route else (s + k) % len(reps)]
        prompt = history + _tokens(2000 + 10 * s + k, MESSAGE, vocab)
        rid = f"s{s}t{k}"
        got = rep.engine.run([Request(rid, prompt,
                                      max_new_tokens=answer)])[rid]
        assert len(got) == answer
        out.append((prompt, got, rep))
        history = prompt + got
    return out


def _reference_rows(tiny, turns):
    """The float32 reference's logits at the CHECK first answered
    positions of every turn: one pass over the whole history."""
    last_prompt, last_gen = turns[-1][0], turns[-1][1]
    seq = list(last_prompt) + list(last_gen[:CHECK])
    toks = np.zeros(-(-len(seq) // 64) * 64, np.int32)
    toks[:len(seq)] = seq
    positions = []
    for prompt, _, _ in turns:
        positions += [len(prompt) - 1 + i for i in range(CHECK)]
    rows, _ = tiny.reference.forward(tiny.params, tiny.conf, toks,
                                     positions)
    return np.asarray(rows, np.float32).reshape(len(turns), CHECK, -1)


def _expected_hits(turns, page):
    """Hit pages the schedule implies per turn: every full page of the
    previous turn's sequence (its last token's KV is never appended)."""
    out, stored = [], 0
    for prompt, got, _ in turns:
        out.append(min(stored, (len(prompt) - 1) // page))
        stored = (len(prompt) + len(got) - 1) // page
    return out


@pytest.mark.parametrize("s", range(R))
def test_a_rotated_session_answers_as_the_float32_reference(reps, tiny, s):
    before = [dict(r.engine.stats) for r in reps]
    t0 = profiling.clock_pair()[0]
    turns = _play(reps, tiny, s)
    rows = _reference_rows(tiny, turns)
    page = tiny.cfg.page_size
    hits = _expected_hits(turns, page)
    assert hits[0] == 0 and all(h > 0 for h in hits[1:])
    admits = {a.request: a for a in profiling.spans(since_ns=t0)
              if a.name == "istpu.sched.admit"}
    for ti, (prompt, got, rep) in enumerate(turns):
        assert rep.index == (s + ti + 1) % R
        # prefill, then decode through the restored foreign pages
        deficits = correct.token_deficits(rows[ti], got[:CHECK])
        assert max(deficits) <= TOKEN_EPS, (ti, deficits)
        a = admits[f"s{s}t{ti + 1}"]
        assert a.engine == rep.engine.engine_id
        assert a.fields["hit_pages"] == hits[ti]
        assert a.fields["foreign_pages"] == hits[ti]
        # The hit program on the replica the route names, engine idle.
        # The session is over, so turn 1's prompt hits too, on pages
        # this replica wrote itself: the same program, the same row.
        row, hit = rep.engine.first_token_logits(prompt)
        assert hit == (len(prompt) - 1) // page >= hits[ti]
        assert np.max(np.abs(row - rows[ti][0])) <= LOGIT_TOL
    moved = [{k: r.engine.stats[k] - b[k] for k in b}
             for r, b in zip(reps, before)]
    assert sum(m["prefix_hit_pages"] for m in moved) == sum(hits)
    for m in moved:
        assert m["foreign_hit_pages"] == m["prefix_hit_pages"]
        assert m["restore_misses"] == 0 and m["store_errors"] == 0


def test_read_back_through_every_other_connection_is_bit_exact(reps, tiny):
    a = reps[0]
    a.store.arm_tap()
    _play(reps, tiny, 40, turns=1, route=lambda k: 0)
    keys, dev_pages = a.store.tapped  # A's first acknowledged put batch
    want = np.ascontiguousarray(np.asarray(dev_pages)).view(np.uint8)
    assert len(keys) > 0 and want.any()
    for other in reps[1:]:
        back = other.inner_store.get_kv_pages_host(
            keys, tiny.cfg.kv_page_shape(), tiny.cfg.jdtype)
        assert np.array_equal(
            np.ascontiguousarray(back).view(np.uint8), want), other.index
    # and what correct.py does, on the writer's own connection
    assert correct.read_back(a, tiny.cfg) == (len(keys), True)


def test_a_probe_right_after_the_writers_sync_finds_the_whole_chain(
        reps, tiny):
    """The writer's offload ends in conn.sync(), on its engine's upload
    thread. The moment that returns - before the engine counts the
    offload, let alone puts the request's tokens where a done event is
    sent from - every other connection's probe finds all of the chain,
    in every layer and kind. Nothing is slept on: the probes run from a
    hook on the acknowledgement itself; what the finished slot held is
    noted at its finish, the slot being free by then."""
    page = tiny.cfg.page_size
    writer = reps[1]
    others = [r for r in reps if r is not writer]
    inner_sync = writer.conn.sync
    inner_finish = writer.engine._finish
    found, finished = [], []

    def finish(i, slot):
        finished.append((list(slot.work.prompt) + list(slot.generated),
                         slot.seq_len // page, slot.work.req.request_id))
        inner_finish(i, slot)

    def sync_then_probe(*a, **kw):
        out = inner_sync(*a, **kw)
        seq, n_full, rid = finished[-1]
        assert writer.engine.stats["offloaded_pages"] == counted
        assert rid not in writer.engine.outputs
        for other in others:
            for li in range(tiny.cfg.n_layers):
                for kind in ("k", "v"):
                    keys = content_page_keys(seq, page, n_full, li, kind,
                                             namespace=other.engine._ns)
                    found.append((
                        other.inner_store.cached_prefix_len(keys), n_full))
        return out

    writer.conn.sync = sync_then_probe
    writer.engine._finish = finish
    try:
        for rnd in range(3):
            counted = writer.engine.stats["offloaded_pages"]
            prompt = _tokens(7000 + rnd, CONTEXT + MESSAGE + page * rnd,
                             tiny.cfg.vocab_size)
            writer.engine.run([Request(f"vis{rnd}", prompt,
                                       max_new_tokens=ANSWER)])
    finally:
        writer.conn.sync = inner_sync
        del writer.engine._finish
    assert writer.engine.stats["store_errors"] == 0
    assert len(found) == 3 * (R - 1) * tiny.cfg.n_layers * 2
    assert all(got == want and want >= 6 for got, want in found), found


def test_counters_and_span_fields_tell_foreign_pages_from_own(reps, tiny):
    """Turn 2 on another engine: every hit page is foreign. Turn 3 back
    on the engine that ran turn 2: the pages it offloaded itself are
    not, the first turn's still are."""
    page = tiny.cfg.page_size
    t0 = profiling.clock_pair()[0]
    before = [dict(r.engine.stats) for r in reps]
    turns = _play(reps, tiny, 50, route=lambda k: {1: 2, 2: 3, 3: 3}[k])
    hits = _expected_hits(turns, page)
    spans = profiling.spans(since_ns=t0)
    admits = {a.request: a for a in spans if a.name == "istpu.sched.admit"}
    restores = {s.parent: s for s in spans
                if s.name == "istpu.cache.restore"}
    own3 = hits[2] - hits[1]  # what engine 3 itself wrote after turn 2
    assert own3 > 0
    want = {"s50t1": (0, 0), "s50t2": (hits[1], hits[1]),
            "s50t3": (hits[2], hits[2] - own3)}
    for rid, (hit, foreign) in want.items():
        a = admits[rid]
        assert (a.fields["hit_pages"], a.fields["foreign_pages"]) \
            == (hit, foreign), rid
        if hit:
            r = restores[a.id]
            assert r.fields["pages"] == hit
            assert r.fields["foreign_pages"] == foreign
        else:
            assert a.id not in restores
    moved = [{k: r.engine.stats[k] - b[k] for k in b}
             for r, b in zip(reps, before)]
    assert [m["prefix_hit_pages"] for m in moved] == [
        0, 0, 0, hits[1] + hits[2]]
    assert [m["foreign_hit_pages"] for m in moved] == [
        0, 0, 0, hits[1] + hits[2] - own3]
    # a span's engine maps to its replica's device through the steps
    device_of = {}
    for st in spans:
        if st.name == "istpu.engine.step":
            device_of.setdefault(st.engine, set()).add(st.fields["device"])
    assert device_of == {reps[2].engine.engine_id: {reps[2].device.id},
                         reps[3].engine.engine_id: {reps[3].device.id}}
    assert reps[2].device.id != reps[3].device.id


def test_the_own_digest_set_is_bounded_oldest_first(tiny, server,
                                                    monkeypatch):
    monkeypatch.setattr(serving, "OWN_DIGESTS", 4)
    (rep,) = _replicas(tiny, server.service_port, "replicas4-own", n=1)
    try:
        turns = _play([rep], tiny, 60, turns=2, route=lambda k: 0)
        page = tiny.cfg.page_size
        own = list(rep.engine._own_digests)
        assert len(own) == 4
        seq = turns[-1][0] + turns[-1][1]
        n_full = (len(seq) - 1) // page
        assert own == serving.content_page_digests(
            seq, page, n_full, rep.engine._ns)[-4:]
        # turn 2 hit the engine's own pages; all but the 4 it still
        # remembered count as foreign, and none is counted twice
        hit = _expected_hits(turns, page)[1]
        assert rep.engine.stats["prefix_hit_pages"] == hit
        assert 0 < rep.engine.stats["foreign_hit_pages"] <= hit
    finally:
        _close([rep])


@pytest.fixture
def small_store():
    """Room for about one and a half sessions' pages, LRU eviction
    inline only (deterministic): blocks of one 4 KB page."""
    srv = InfiniStoreServer(ServerConfig(
        service_port=0, prealloc_size=(96 * 4 << 10) / (1 << 30),
        minimal_allocate_size=4, enable_eviction=True, reclaim_high=1.0))
    srv.start()
    yield srv
    srv.stop()


def test_an_evicted_chain_is_a_miss_and_a_recompute_never_a_wrong_page(
        tiny, small_store):
    reps = _replicas(tiny, small_store.service_port, "replicas4-evict")
    page = tiny.cfg.page_size
    vocab = tiny.cfg.vocab_size
    try:
        a, b, c = reps[0], reps[1], reps[2]
        first = _tokens(9001, CONTEXT + MESSAGE, vocab)
        out1 = a.engine.run([Request("e1", first,
                                     max_new_tokens=ANSWER)])["e1"]
        n1 = (len(first) + ANSWER - 1) // page
        chain = content_page_keys(first + out1, page, n1, 0, "k",
                                  namespace=b.engine._ns)
        assert b.inner_store.cached_prefix_len(chain) == n1
        # Other sessions through a third client until the pool has
        # evicted the first chain's head (a condition, not a count).
        for i in range(40):
            if b.inner_store.cached_prefix_len(chain[:1]) == 0:
                break
            c.engine.run([Request(f"fill{i}", _tokens(
                9100 + i, CONTEXT + MESSAGE, vocab), max_new_tokens=page)])
        assert b.inner_store.cached_prefix_len(chain[:1]) == 0
        assert small_store.stats()["evictions"] > 0
        follow = first + out1 + _tokens(9002, MESSAGE, vocab)
        out2 = b.engine.run([Request("e2", follow,
                                     max_new_tokens=ANSWER)])["e2"]
        turns = [(first, out1, a), (follow, out2, b)]
        rows = _reference_rows(tiny, turns)
        for ti, (_, got, _) in enumerate(turns):
            assert max(correct.token_deficits(rows[ti], got[:CHECK])) \
                <= TOKEN_EPS
        # a miss (or a chain the restore found broken), and a recompute
        st = b.engine.stats
        assert st["prefix_hit_pages"] == 0 and st["store_errors"] == 0
        assert st["prefill_tokens"] == len(follow)
        assert all(r.engine.stats["store_errors"] == 0 for r in reps)
    finally:
        _close(reps)
