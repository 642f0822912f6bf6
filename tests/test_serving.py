"""Continuous-batching serving engine tests: batching must be a pure
scheduling concern (same tokens as isolated runs), the store must carry
prefixes across requests (multi-turn hit), and pool pressure must
degrade gracefully."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from infinistore_tpu.models import llama
from infinistore_tpu.serving import (
    Request,
    ServingConfig,
    ServingEngine,
    content_page_keys,
    prompt_lookup_propose,
)
from infinistore_tpu.utils import profiling


@pytest.fixture(scope="module")
def cfg():
    return llama.LlamaConfig(
        vocab_size=128,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq=128,
        page_size=8,
        dtype="float32",
    )


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.PRNGKey(0), cfg)


def _prompt(rng, cfg, n):
    return [int(t) for t in rng.integers(0, cfg.vocab_size, n)]


def _steps_of_kind(eng, kind):
    """How many of the engine's steps were of `kind`, by the spans they
    left (utils/profiling.py)."""
    return sum(1 for s in profiling.spans()
               if s.name == "istpu.engine.step" and s.engine == eng.engine_id
               and s.fields["kind"] == kind)


def _dense_greedy_reference(params, cfg, prompt, n_new):
    """Greedy generation by re-running the dense forward each step —
    a paged-cache-free oracle for the engine's token stream."""
    toks = list(prompt)
    out = []
    for _ in range(n_new):
        logits, _ = llama.forward_dense(
            params, cfg, jnp.asarray([toks], dtype=jnp.int32)
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def test_single_request_matches_dense_reference(params, cfg):
    rng = np.random.default_rng(0)
    prompt = _prompt(rng, cfg, 13)  # non-page-aligned on purpose
    eng = ServingEngine(params, cfg, ServingConfig(max_slots=2))
    out = eng.run([Request("r0", prompt, max_new_tokens=6)])
    ref = _dense_greedy_reference(params, cfg, prompt, 6)
    assert out["r0"] == ref


def test_continuous_batching_equals_isolated_runs(params, cfg):
    """5 requests of mixed lengths through 2 slots: tokens must equal
    each request's isolated single-slot run — batching is scheduling,
    not math."""
    rng = np.random.default_rng(1)
    reqs = [
        Request(f"r{i}", _prompt(rng, cfg, n), max_new_tokens=m)
        for i, (n, m) in enumerate(
            [(5, 4), (16, 7), (9, 1), (24, 5), (12, 3)]
        )
    ]
    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=2, total_pages=32)
    )
    out = eng.run(reqs)
    assert set(out) == {f"r{i}" for i in range(5)}
    for r in reqs:
        solo = ServingEngine(params, cfg, ServingConfig(max_slots=1))
        ref = solo.run(
            [Request("x", r.prompt, max_new_tokens=r.max_new_tokens)]
        )
        assert out[r.request_id] == ref["x"], r.request_id
    # All pages returned; no slot left behind.
    assert sorted(eng.free_pages) == list(range(1, 32))
    assert eng.slots == [None, None]
    assert eng.stats["decoded_tokens"] > 0


def test_multiturn_prefix_hit_through_store(params, cfg, shm_conn):
    """Turn 2 of a conversation must HIT the pages turn 1 offloaded:
    restored prefix + suffix-only prefill lands on the same tokens as a
    store-less engine given the full prompt."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(2)
    turn1 = _prompt(rng, cfg, 16)  # two full pages
    store = TpuKVStore(shm_conn)

    eng1 = ServingEngine(params, cfg, store=store)
    out1 = eng1.run([Request("t1", turn1, max_new_tokens=8)])
    assert eng1.stats["offloaded_pages"] > 0
    assert eng1.stats["prefix_hit_pages"] == 0  # cold store

    # Turn 2 prompt extends turn 1's prompt + reply (the cached tokens).
    convo = turn1 + out1["t1"]
    turn2 = convo[: (len(convo) // cfg.page_size) * cfg.page_size]
    turn2 = turn2 + _prompt(rng, cfg, 5)
    eng2 = ServingEngine(params, cfg, store=store)
    out2 = eng2.run([Request("t2", turn2, max_new_tokens=6)])
    assert eng2.stats["prefix_hit_pages"] > 0

    cold = ServingEngine(params, cfg)  # no store: full prefill oracle
    ref = cold.run([Request("x", turn2, max_new_tokens=6)])
    assert out2["t2"] == ref["x"]


def test_engine_lives_where_its_weights_live(params, cfg, shm_conn):
    """A replica whose weights sit on device 1 keeps its pool, its
    restores and its steps there — also when another thread drives it
    (the HTTP engine thread; jax.default_device is thread-local) — and
    hits the prefix an engine on device 0 offloaded."""
    import threading

    from infinistore_tpu.tpu import TpuKVStore

    class Recording(TpuKVStore):
        def get_kv_pages(self, *a, **kw):
            out = super().get_kv_pages(*a, **kw)
            self.restored_on = set(out.devices())
            return out

    dev0, dev1 = jax.devices()[:2]
    rng = np.random.default_rng(21)
    turn1 = _prompt(rng, cfg, 16)
    sc = ServingConfig(model_id="replicas")
    eng0 = ServingEngine(params, cfg, sc, store=TpuKVStore(shm_conn))
    out1 = eng0.run([Request("t1", turn1, max_new_tokens=8)])
    assert eng0.device == dev0

    store1 = Recording(shm_conn)
    eng1 = ServingEngine(jax.device_put(params, dev1), cfg, sc, store=store1)
    turn2 = turn1 + out1["t1"] + _prompt(rng, cfg, 5)  # 3 full pages + 5
    out2 = {}
    t = threading.Thread(
        target=lambda: out2.update(
            eng1.run([Request("t2", turn2, max_new_tokens=6)])
        )
    )
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    assert eng1.stats["prefix_hit_pages"] > 0
    assert store1.restored_on == {dev1}
    assert set(eng1.k_pages.devices()) == {dev1}
    assert set(eng0.k_pages.devices()) == {dev0}
    ref = ServingEngine(params, cfg).run(
        [Request("x", turn2, max_new_tokens=6)]
    )
    assert out2["t2"] == ref["x"]


def test_identical_prompts_share_pages(params, cfg, shm_conn):
    """Two requests with the same prompt: the second admission hits the
    first's offloaded pages (content addressing needs no seq ids)."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(3)
    prompt = _prompt(rng, cfg, 24)
    store = TpuKVStore(shm_conn)
    eng = ServingEngine(params, cfg, store=store)
    out_a = eng.run([Request("a", prompt, max_new_tokens=4)])
    out_b = eng.run([Request("b", prompt, max_new_tokens=4)])
    assert out_a["a"] == out_b["b"]
    # 24 tokens = 3 pages; hit is capped at 2 so >=1 token prefills.
    assert eng.stats["prefix_hit_pages"] == 2


def test_cache_opt_out(params, cfg, shm_conn):
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(4)
    prompt = _prompt(rng, cfg, 16)
    store = TpuKVStore(shm_conn)
    eng = ServingEngine(params, cfg, store=store)
    eng.run([Request("a", prompt, max_new_tokens=2, cache=False)])
    assert eng.stats["offloaded_pages"] == 0
    eng.run([Request("b", prompt, max_new_tokens=2)])
    assert eng.stats["prefix_hit_pages"] == 0  # nothing was offloaded


def test_eos_stops_generation(params, cfg):
    """Whatever token the model emits first, making IT the EOS id must
    stop the sequence at length 1."""
    rng = np.random.default_rng(5)
    prompt = _prompt(rng, cfg, 9)
    probe = ServingEngine(params, cfg)
    first = probe.run([Request("p", prompt, max_new_tokens=1)])["p"][0]
    eng = ServingEngine(
        params, cfg, ServingConfig(eos_id=first)
    )
    out = eng.run([Request("r", prompt, max_new_tokens=50)])
    assert out["r"] == [first]


def test_preemption_through_store_resumes_exactly(params, cfg, shm_conn):
    """Two growing sequences in a pool too small for both: one must be
    swapped out THROUGH the store and resume via the prefix-hit path,
    finishing with exactly the tokens of an uncontended run."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(7)
    reqs = [
        Request(f"r{i}", _prompt(rng, cfg, 16), max_new_tokens=24)
        for i in range(2)
    ]
    store = TpuKVStore(shm_conn)
    sc = ServingConfig(max_slots=2, total_pages=8, max_pages_per_seq=8)
    eng = ServingEngine(params, cfg, sc, store=store)
    out = eng.run(
        [Request(r.request_id, r.prompt, r.max_new_tokens) for r in reqs]
    )
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["prefix_hit_pages"] > 0  # resume restored pages
    for r in reqs:
        big = ServingEngine(
            params, cfg, ServingConfig(max_slots=1, total_pages=16)
        )
        ref = big.run([Request("x", r.prompt, r.max_new_tokens)])
        assert out[r.request_id] == ref["x"], r.request_id
        assert len(out[r.request_id]) == 24, r.request_id
    assert sorted(eng.free_pages) == list(range(1, 8))


def test_a_preempted_request_readmits_as_a_full_hit(params, cfg, shm_conn):
    """A preemption alone waits for its upload (its re-admission needs
    the pages in the store): when `_preempt` returns nothing is in
    flight, and the swapped-out sequence comes back as a hit over every
    full page it had, not a shorter one."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(17)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=2, total_pages=8, max_pages_per_seq=8),
        store=TpuKVStore(shm_conn))
    for i in range(2):
        eng.submit(Request(f"r{i}", _prompt(rng, cfg, 16),
                           max_new_tokens=24))
    real, swapped = eng._preempt, []

    def preempt(i, slot):
        real(i, slot)
        assert eng.uploads_pending == 0
        swapped.append((slot.work.req.request_id,
                        slot.seq_len // cfg.page_size))
    eng._preempt = preempt
    t0 = time.time_ns()
    out = eng.run()
    assert swapped and {len(v) for v in out.values()} == {24}
    hits = [s for s in profiling.spans(since_ns=t0)
            if s.name == "istpu.sched.admit" and s.engine == eng.engine_id
            and s.fields["outcome"] == "admitted"
            and s.fields["hit_pages"]]
    # A re-admission keeps one token to prefill, so a victim swapped
    # out on a page edge hits one page less than it had.
    assert [(s.request, s.fields["hit_pages"]) for s in hits] in (
        swapped, [(rid, n - 1) for rid, n in swapped])
    assert eng.stats["restore_misses"] == 0 == eng.stats["store_errors"]


def test_done_follows_the_sync_while_other_slots_decode(params, cfg,
                                                        shm_conn,
                                                        gated_sync):
    """With the store's sync held: the finished request's slot and
    pages are free at once and the other slot decodes on, step after
    step, while the finished request is NOT in `outputs`; it is there
    within one pass after the acknowledgement came back."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(18)
    eng = ServingEngine(params, cfg, ServingConfig(max_slots=2,
                                                   model_id="held-sync"),
                        store=TpuKVStore(shm_conn))
    eng.submit(Request("short", _prompt(rng, cfg, 16), max_new_tokens=3))
    eng.submit(Request("long", _prompt(rng, cfg, 11), max_new_tokens=40))
    while eng.finished == 0:
        eng.step()
    assert eng.uploads_pending == 1 and eng.stats["uploads"] == 1
    assert eng.slots[0] is None and len(eng.free_pages) >= 63 - 3
    before = eng.stats["decoded_tokens"]
    for n in range(1, 13):
        assert eng.step() == 1
        assert eng.stats["decoded_tokens"] == before + n
        assert "short" not in eng.outputs
    assert eng.stats["offloaded_pages"] == 0
    assert eng.stats["done_held_ms"] == 0
    gated_sync.set()
    deadline = time.time() + 60
    while eng._acked.empty() and time.time() < deadline:
        time.sleep(0.001)
    eng.step()
    assert len(eng.outputs["short"]) == 3
    assert eng.stats["offloaded_pages"] == 2 and eng.uploads_pending == 0
    assert eng.stats["done_held_ms"] > 0
    ref = ServingEngine(params, cfg).run(
        [Request("x", eng.slots[1].work.prompt, max_new_tokens=40)])
    assert eng.run()["long"] == ref["x"]


def test_run_returns_only_with_every_upload_acknowledged(params, cfg,
                                                         shm_conn,
                                                         monkeypatch):
    """`run()` ends with the queue of uploads empty: what it offloaded
    is in the store and counted, however slow the store was."""
    from infinistore_tpu.tpu import TpuKVStore

    real = shm_conn.sync

    def slow():
        time.sleep(0.2)
        return real()
    monkeypatch.setattr(shm_conn, "sync", slow)
    rng = np.random.default_rng(19)
    prompts = [_prompt(rng, cfg, 16 + i) for i in range(3)]
    store = TpuKVStore(shm_conn)
    eng = ServingEngine(params, cfg, ServingConfig(max_slots=3,
                                                   model_id="run-drains"),
                        store=store)
    out = eng.run([Request(f"r{i}", p, max_new_tokens=2 + i)
                   for i, p in enumerate(prompts)])
    assert sorted(out) == ["r0", "r1", "r2"]
    assert eng.uploads_pending == 0 and eng.stats["uploads"] == 3
    assert eng.stats["offloaded_pages"] == 6
    assert eng.stats["done_held_ms"] >= 3 * 200
    for p in prompts:
        keys = content_page_keys(p, cfg.page_size, 2, 0, "k",
                                 namespace=eng._ns)
        assert store.cached_prefix_len(keys) == 2
    eng.close()
    assert eng._upload_thread is None
    eng.close()  # twice is once


def test_preemption_without_store_recomputes(params, cfg):
    """Preemption must work store-less: the prefix is recomputed on
    resume instead of restored, with identical tokens."""
    rng = np.random.default_rng(8)
    reqs = [
        Request(f"r{i}", _prompt(rng, cfg, 16), max_new_tokens=24)
        for i in range(2)
    ]
    sc = ServingConfig(max_slots=2, total_pages=8, max_pages_per_seq=8)
    eng = ServingEngine(params, cfg, sc)
    out = eng.run(
        [Request(r.request_id, r.prompt, r.max_new_tokens) for r in reqs]
    )
    assert eng.stats["preemptions"] >= 1
    for r in reqs:
        big = ServingEngine(
            params, cfg, ServingConfig(max_slots=1, total_pages=16)
        )
        ref = big.run([Request("x", r.prompt, r.max_new_tokens)])
        assert out[r.request_id] == ref["x"], r.request_id


def test_pool_exhaustion_finishes_early_not_deadlocks(params, cfg):
    """A pool too small for the requested generation length must end the
    sequence early with the tokens produced so far — never hang."""
    sc = ServingConfig(max_slots=1, total_pages=4, max_pages_per_seq=8)
    eng = ServingEngine(params, cfg, sc)
    prompt = list(range(1, 17))  # 2 pages; pool has 3 usable
    out = eng.run([Request("r", prompt, max_new_tokens=40)])
    assert 1 <= len(out["r"]) < 40
    assert sorted(eng.free_pages) == [1, 2, 3]


def test_impossible_request_raises(params, cfg):
    sc = ServingConfig(max_slots=1, total_pages=3, max_pages_per_seq=8)
    eng = ServingEngine(params, cfg, sc)
    with pytest.raises(RuntimeError, match="more pool pages than exist"):
        eng.run([Request("r", list(range(1, 33)), max_new_tokens=4)])


def test_oversized_request_rejected_at_submit(params, cfg):
    eng = ServingEngine(params, cfg, ServingConfig(max_pages_per_seq=2))
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        eng.submit(Request("r", list(range(1, 17)), max_new_tokens=16))


def test_quantized_store_wire(params, cfg, shm_conn):
    """quantized_store=True: turn 2 hits turn 1's int8 pages, restores
    through dequantization, and completes; quantized and raw pages never
    cross-hit (disjoint namespaces)."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(9)
    turn1 = _prompt(rng, cfg, 16)
    store = TpuKVStore(shm_conn)
    qcfg = ServingConfig(quantized_store=True)

    eng1 = ServingEngine(params, cfg, qcfg, store=store)
    out1 = eng1.run([Request("t1", turn1, max_new_tokens=8)])
    assert eng1.stats["offloaded_pages"] > 0

    convo = turn1 + out1["t1"]
    turn2 = convo[: (len(convo) // cfg.page_size) * cfg.page_size]
    turn2 = turn2 + _prompt(rng, cfg, 5)
    eng2 = ServingEngine(params, cfg, qcfg, store=store)
    out2 = eng2.run([Request("t2", turn2, max_new_tokens=6)])
    assert eng2.stats["prefix_hit_pages"] > 0
    assert len(out2["t2"]) == 6

    # int8 is a different wire format: a raw-dtype engine must NOT hit
    # the quantized pages (and vice versa) even for the same tokens.
    raw = ServingEngine(params, cfg, store=store)
    raw.run([Request("r", turn2, max_new_tokens=2)])
    assert raw.stats["prefix_hit_pages"] == 0
    # Vice versa: fresh-token raw pages must be invisible to q8 probes.
    fresh = _prompt(rng, cfg, 24)
    raw2 = ServingEngine(params, cfg, store=store)
    raw2.run([Request("r2", fresh, max_new_tokens=2)])
    assert raw2.stats["offloaded_pages"] > 0
    q8 = ServingEngine(params, cfg, qcfg, store=store)
    q8.run([Request("q", fresh, max_new_tokens=2)])
    assert q8.stats["prefix_hit_pages"] == 0


def test_model_namespace_prevents_cross_hits(params, cfg, shm_conn):
    """Engines with different model_ids (different checkpoints) sharing
    one store must never restore each other's KV."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(6)
    prompt = _prompt(rng, cfg, 24)
    store = TpuKVStore(shm_conn)
    eng_a = ServingEngine(
        params, cfg, ServingConfig(model_id="ckpt-a"), store=store
    )
    eng_a.run([Request("a", prompt, max_new_tokens=2)])
    assert eng_a.stats["offloaded_pages"] > 0
    eng_b = ServingEngine(
        params, cfg, ServingConfig(model_id="ckpt-b"), store=store
    )
    eng_b.run([Request("b", prompt, max_new_tokens=2)])
    assert eng_b.stats["prefix_hit_pages"] == 0


def test_prompt_lookup_proposer():
    # ...A B C x y z ... A B C -> propose x y z (latest match wins).
    ctx = [1, 2, 3, 7, 8, 9, 4, 1, 2, 3, 5, 6, 0, 1, 2, 3]
    assert prompt_lookup_propose(ctx, 3, ngram=3) == [5, 6, 0]
    assert prompt_lookup_propose(ctx, 2, ngram=3) == [5, 6]
    assert prompt_lookup_propose([1, 2, 3, 4], 3, ngram=2) == []
    assert prompt_lookup_propose([5], 3) == []


class _OracleProposer:
    """Proposes the exact greedy continuation (precomputed) — every
    draft accepted; the strongest stress on the verify/accept path."""

    def __init__(self, lookup):
        self.lookup = lookup  # {context tuple -> next tokens}

    def __call__(self, context, k):
        return self.lookup.get(tuple(context), [])[:k]


@pytest.mark.parametrize("proposer_kind", ["oracle", "adversarial",
                                           "lookup"])
def test_speculative_decoding_token_parity(params, cfg, proposer_kind):
    """Speculative decoding must emit EXACTLY the plain-decode tokens
    whatever the proposer does — a perfect oracle (all accepted), an
    adversarial one (all rejected), or real prompt-lookup."""
    rng = np.random.default_rng(11)
    base = _prompt(rng, cfg, 11)
    n_new = 12
    plain = ServingEngine(params, cfg, ServingConfig(max_slots=2))
    ref = plain.run([Request("x", base, max_new_tokens=n_new)])["x"]

    if proposer_kind == "oracle":
        # Precompute greedy continuations at every context length.
        lookup = {}
        toks = list(base) + ref
        for i in range(len(base), len(toks)):
            lookup[tuple(toks[:i])] = toks[i:]
        proposer = _OracleProposer(lookup)
    elif proposer_kind == "adversarial":
        def proposer(context, k):
            return [(context[-1] + 13) % cfg.vocab_size] * k
    else:
        proposer = prompt_lookup_propose

    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=2, spec_k=3),
        proposer=proposer,
    )
    out = eng.run([Request("r", base, max_new_tokens=n_new)])
    assert out["r"] == ref, proposer_kind
    if proposer_kind == "oracle":
        assert eng.stats["spec_accepted"] > 0
        # Every proposal accepted -> far fewer steps than tokens.
        assert eng.stats["decode_steps"] < n_new - 1
    if proposer_kind == "adversarial":
        assert eng.stats["spec_accepted"] == 0
        assert eng.stats["decode_steps"] == n_new - 1


def test_speculative_batched_mixed_slots(params, cfg):
    """Slots with and without accepted drafts share verify batches;
    every request's tokens must still match its plain run."""
    rng = np.random.default_rng(12)
    reqs = [
        Request(f"r{i}", _prompt(rng, cfg, n), max_new_tokens=mx)
        for i, (n, mx) in enumerate([(9, 8), (17, 10), (5, 6)])
    ]
    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=2, spec_k=2)
    )
    out = eng.run(
        [Request(r.request_id, r.prompt, r.max_new_tokens) for r in reqs]
    )
    for r in reqs:
        plain = ServingEngine(params, cfg, ServingConfig(max_slots=1))
        ref = plain.run([Request("x", r.prompt, r.max_new_tokens)])
        assert out[r.request_id] == ref["x"], r.request_id
    assert eng.slots == [None, None]


def test_speculative_eos_truncation(params, cfg):
    """An EOS accepted mid-draft must end the output AT the EOS."""
    rng = np.random.default_rng(13)
    base = _prompt(rng, cfg, 9)
    plain = ServingEngine(params, cfg)
    ref = plain.run([Request("x", base, max_new_tokens=8)])["x"]
    eos = ref[3]  # make the 4th generated token the EOS
    want = ref[: 4]
    lookup = {}
    toks = list(base) + ref
    for i in range(len(base), len(toks)):
        lookup[tuple(toks[:i])] = toks[i:]
    eng = ServingEngine(
        params, cfg, ServingConfig(spec_k=3, eos_id=eos),
        proposer=_OracleProposer(lookup),
    )
    out = eng.run([Request("r", base, max_new_tokens=8)])
    assert out["r"] == want


@pytest.mark.parametrize("pages", [1, 2, 8])
def test_pieces_token_parity(params, cfg, pages):
    """An admission in pieces must emit exactly the one-program
    admission's tokens, for pieces of a page, of two, and longer than
    the whole prompt (no piece runs: the one program)."""
    rng = np.random.default_rng(14)
    prompt = _prompt(rng, cfg, 21)
    ref = ServingEngine(params, cfg).run(
        [Request("x", prompt, max_new_tokens=7)]
    )
    eng = ServingEngine(
        params, cfg, ServingConfig(admit_piece=pages * cfg.page_size)
    )
    out = eng.run([Request("r", prompt, max_new_tokens=7)])
    assert out["r"] == ref["x"]
    assert eng.stats["admit_pieces"] == {1: 3, 2: 2, 8: 0}[pages]
    assert eng.stats["prefill_tokens"] == 21


def test_pieces_interleave_with_decode(params, cfg):
    """While a long prompt is admitted a piece an engine step, a
    sequence that already runs lands a token between every two pieces,
    and both outputs match their isolated runs."""
    rng = np.random.default_rng(15)
    short = _prompt(rng, cfg, 5)
    long_p = _prompt(rng, cfg, 40)
    eng = ServingEngine(
        params, cfg,
        ServingConfig(max_slots=2, total_pages=32,
                      admit_piece=cfg.page_size),
    )
    # Admit the short request, let it produce a couple of tokens, then
    # submit the long one: its 5 pieces overlap short's decode.
    eng.submit(Request("short", short, max_new_tokens=16))
    eng.step()
    eng.step()
    eng.submit(Request("long", long_p, max_new_tokens=4))
    seen = []  # (pieces run, short's tokens) after every step
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        if eng.slots[0] is not None:
            seen.append((eng.stats["admit_pieces"],
                         len(eng.slots[0].generated)))
    # Every step that ran a piece also decoded: one token of short's
    # between every two pieces.
    seen = [at for at in seen if at[0]]
    n0 = seen[0][1]
    assert seen[:5] == [(k + 1, n0 + k) for k in range(5)]
    for rid, prompt, mx in [("short", short, 16), ("long", long_p, 4)]:
        ref = ServingEngine(params, cfg).run(
            [Request("x", prompt, max_new_tokens=mx)]
        )
        assert eng.outputs[rid] == ref["x"], rid


def test_pieces_with_store_hit(params, cfg, shm_conn):
    """Admission in pieces over a cached prefix: the restored pages go
    into the pool with the first piece, and the later pieces attend
    them there, with token parity."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(16)
    turn1 = _prompt(rng, cfg, 16)
    store = TpuKVStore(shm_conn)
    eng1 = ServingEngine(params, cfg, store=store)
    out1 = eng1.run([Request("t1", turn1, max_new_tokens=8)])

    convo = turn1 + out1["t1"]
    turn2 = convo[: (len(convo) // cfg.page_size) * cfg.page_size]
    turn2 = turn2 + _prompt(rng, cfg, 13)
    eng2 = ServingEngine(
        params, cfg, ServingConfig(admit_piece=cfg.page_size), store=store
    )
    out2 = eng2.run([Request("t2", turn2, max_new_tokens=6)])
    assert eng2.stats["prefix_hit_pages"] == 2
    assert eng2.stats["admit_pieces"] == 3
    ref = ServingEngine(params, cfg).run(
        [Request("x", turn2, max_new_tokens=6)]
    )
    assert out2["t2"] == ref["x"]


def test_sampling_seeded_deterministic(params, cfg):
    """temperature>0 with a seed must reproduce exactly across engines;
    different seeds must diverge; temperature=0 stays pure greedy."""
    rng = np.random.default_rng(17)
    prompt = _prompt(rng, cfg, 10)

    def gen(seed, temp=0.8):
        eng = ServingEngine(params, cfg)
        return eng.run(
            [Request("r", prompt, max_new_tokens=12, temperature=temp,
                     top_k=8, seed=seed)]
        )["r"]

    assert gen(1) == gen(1)
    outs = {tuple(gen(s)) for s in range(5)}
    assert len(outs) > 1  # 5 seeds all colliding would be a broken RNG
    greedy = ServingEngine(params, cfg).run(
        [Request("g", prompt, max_new_tokens=12)]
    )["g"]
    assert gen(2, temp=0.0) == greedy


def test_sampling_survives_preemption(params, cfg, shm_conn):
    """The RNG stream travels with the request: a sampled sequence that
    is preempted and resumed must emit exactly the uncontended run's
    tokens (one draw per token, no replays, no skips)."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(18)
    reqs = [
        Request(f"r{i}", _prompt(rng, cfg, 16), max_new_tokens=24,
                temperature=0.7, seed=100 + i)
        for i in range(2)
    ]
    store = TpuKVStore(shm_conn)
    sc = ServingConfig(max_slots=2, total_pages=8, max_pages_per_seq=8)
    eng = ServingEngine(params, cfg, sc, store=store)
    out = eng.run(
        [Request(r.request_id, r.prompt, r.max_new_tokens,
                 temperature=r.temperature, seed=r.seed) for r in reqs]
    )
    assert eng.stats["preemptions"] >= 1
    for r in reqs:
        big = ServingEngine(
            params, cfg, ServingConfig(max_slots=1, total_pages=16)
        )
        ref = big.run(
            [Request("x", r.prompt, r.max_new_tokens,
                     temperature=r.temperature, seed=r.seed)]
        )
        assert out[r.request_id] == ref["x"], r.request_id


def test_sampling_rides_pieces(params, cfg):
    """A sampling request admitted in pieces must produce its
    plain-engine sampled stream (the last piece's logits row feeds the
    sampler, one RNG draw per token). The spec path no longer
    guarantees STREAM equality for samplers — rejection sampling
    consumes extra draws — only DISTRIBUTION equality
    (test_spec_sampling_*)."""
    rng = np.random.default_rng(19)
    prompt = _prompt(rng, cfg, 18)
    req = dict(max_new_tokens=10, temperature=0.9, top_k=4, seed=7)
    ref = ServingEngine(params, cfg).run(
        [Request("x", prompt, **req)]
    )["x"]
    eng = ServingEngine(params, cfg,
                        ServingConfig(admit_piece=cfg.page_size))
    out = eng.run([Request("r", prompt, **req)])
    assert out["r"] == ref
    assert eng.stats["admit_pieces"] == 3


def test_spec_sampling_accepts_drafts(params, cfg):
    """Rejection-sampling acceptance: a sampled request whose drafts
    track the target distribution must accept draft tokens (>1 token
    per decode step on average), completing in fewer steps than
    draft-less decoding — the VERDICT-6 property that speculation and
    sampling compose. Acceptance probability is p_target[draft], so the
    proposer drafts the model's own greedy continuation and a low
    temperature concentrates p on it."""

    def model_proposer(context, k):
        toks = list(context)
        out = []
        for _ in range(k):
            logits, _ = llama.forward_dense(
                params, cfg, jnp.asarray([toks], dtype=jnp.int32)
            )
            t = int(jnp.argmax(logits[0, -1]))
            out.append(t)
            toks.append(t)
        return out

    rng = np.random.default_rng(21)
    prompt = _prompt(rng, cfg, 9)
    n_new = 16
    eng = ServingEngine(
        params, cfg, ServingConfig(spec_k=2), proposer=model_proposer
    )
    out = eng.run(
        [Request("r", prompt, max_new_tokens=n_new, temperature=0.25,
                 seed=3)]
    )["r"]
    assert len(out) == n_new
    assert eng.stats["spec_proposed"] > 0
    assert eng.stats["spec_accepted"] > 0
    # Accepted drafts mean strictly fewer verify steps than tokens.
    assert eng.stats["decode_steps"] < n_new - 1


def test_spec_sampling_distribution_parity(params, cfg):
    """The rejection sampler must leave every emitted position exactly
    target-distributed: with FIXED logits rows, the empirical marginal
    of the first emitted token over many trials must match the direct
    sampling distribution (the mathematical property that makes
    speculation output-distribution-invariant), and positions reached
    after an accepted draft must match their target conditionals."""
    from infinistore_tpu.serving import ServingEngine as SE

    vocab = 16
    rng = np.random.default_rng(42)
    rows = rng.standard_normal((3, vocab)) * 2.0
    req = Request("r", [1], temperature=0.8, top_k=0, seed=0)
    p0 = SE._probs(req, rows[0])
    p1 = SE._probs(req, rows[1])
    draft = [int(np.argsort(p0)[-2]), int(np.argsort(p1)[-3])]

    class W:  # minimal _Work stand-in for _sample_over_draft
        pass

    n_trials = 20000
    first = np.zeros(vocab)
    second = np.zeros(vocab)
    n_second = 0
    for t in range(n_trials):
        w = W()
        w.req = req
        w.rng = np.random.default_rng(1000 + t)
        emitted, _ = SE._sample_over_draft(SE, w, draft, rows)
        first[emitted[0]] += 1
        if len(emitted) > 1:  # position 1 reached (draft[0] accepted)
            second[emitted[1]] += 1
            n_second += 1
    tv0 = 0.5 * np.abs(first / n_trials - p0).sum()
    assert tv0 < 0.02, tv0
    # Conditioned on accepting draft[0], position 1 is p1-distributed.
    tv1 = 0.5 * np.abs(second / n_second - p1).sum()
    assert tv1 < 0.03, tv1
    # Sanity: acceptance of draft[0] happened at its target rate.
    assert abs(n_second / n_trials - p0[draft[0]]) < 0.02


@pytest.mark.parametrize("hs", [2, 4, 8])
def test_multi_step_scheduling_token_parity(params, cfg, hs):
    """host_steps>1 fuses k decode steps into one device program; the
    token stream must be bit-identical to single-step decoding (the
    scan body IS decode_step), across mixed prompt lengths and
    finish-at-different-times batches."""
    rng = np.random.default_rng(31)
    reqs = [(_prompt(rng, cfg, n), mx)
            for n, mx in [(9, 13), (17, 7), (5, 16)]]
    ref_eng = ServingEngine(params, cfg, ServingConfig(max_slots=2))
    refs = ref_eng.run(
        [Request(f"x{i}", p, max_new_tokens=m)
         for i, (p, m) in enumerate(reqs)]
    )
    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=2, host_steps=hs)
    )
    out = eng.run(
        [Request(f"x{i}", p, max_new_tokens=m)
         for i, (p, m) in enumerate(reqs)]
    )
    assert out == refs
    assert _steps_of_kind(eng, "burst") > 0
    assert eng.stats["decoded_tokens"] == ref_eng.stats["decoded_tokens"]


def test_multi_step_eos_trims_burst(params, cfg):
    """An EOS produced mid-burst must end the output AT the EOS even
    though the device computed the full burst."""
    rng = np.random.default_rng(32)
    base = _prompt(rng, cfg, 9)
    plain = ServingEngine(params, cfg)
    ref = plain.run([Request("x", base, max_new_tokens=12)])["x"]
    eos = ref[4]
    want_ref = ServingEngine(
        params, cfg, ServingConfig(eos_id=eos)
    ).run([Request("x", base, max_new_tokens=12)])["x"]
    eng = ServingEngine(
        params, cfg, ServingConfig(eos_id=eos, host_steps=8)
    )
    out = eng.run([Request("r", base, max_new_tokens=12)])["r"]
    assert out == want_ref
    assert out[-1] == eos


def test_multi_step_streams_in_order(params, cfg):
    """on_token still fires once per token, in order, under bursts."""
    rng = np.random.default_rng(33)
    base = _prompt(rng, cfg, 7)
    got = []
    eng = ServingEngine(
        params, cfg, ServingConfig(host_steps=4)
    )
    out = eng.run(
        [Request("r", base, max_new_tokens=10,
                 on_token=lambda rid, t: got.append(t))]
    )
    assert got == out["r"]


def test_zero_token_budget_rejected_at_submit(params, cfg):
    """max_new_tokens=0 would still emit the admission token; reject it
    up front (ADVICE r3)."""
    eng = ServingEngine(params, cfg)
    with pytest.raises(ValueError):
        eng.submit(Request("r", [1, 2], max_new_tokens=0))


def test_preempted_overgrown_request_finishes_partial(params, cfg):
    """A preempted request whose grown prompt outgrew the pool finishes
    with its accumulated output instead of raising away every other
    request's results (ADVICE r3)."""
    from infinistore_tpu.serving import _Work

    eng = ServingEngine(
        params, cfg,
        ServingConfig(total_pages=4, max_pages_per_seq=16),
    )
    w = _Work(
        req=Request("big", [1] * 8, max_new_tokens=4),
        prompt=[1] * (cfg.page_size * 8),  # 8 pages > 3 usable
        done=[7, 8, 9],
    )
    eng.queue.append(w)
    eng.stats["requests"] += 1
    out = eng.run([Request("ok", [2] * 8, max_new_tokens=3)])
    assert out["big"] == [7, 8, 9]
    assert len(out["ok"]) == 3


def test_fresh_impossible_request_still_raises(params, cfg):
    """A NEVER-run request that cannot fit the pool is a caller error:
    it has no partial output to salvage, so it must still raise."""
    eng = ServingEngine(
        params, cfg,
        ServingConfig(total_pages=4, max_pages_per_seq=16),
    )
    with pytest.raises(RuntimeError):
        eng.run([Request("big", [1] * (cfg.page_size * 8),
                         max_new_tokens=2)])


def test_default_model_id_fingerprints_weights(params, cfg, shm_conn):
    """With model_id left at its default and a store attached, the key
    namespace derives from a weights fingerprint: different checkpoints
    never cross-hit, identical ones still share (ADVICE r3)."""
    from infinistore_tpu.tpu import TpuKVStore

    params2 = llama.init_params(jax.random.PRNGKey(1), cfg)
    store = TpuKVStore(shm_conn)
    e1 = ServingEngine(params, cfg, store=store)
    e2 = ServingEngine(params2, cfg, store=store)
    assert e1._ns != e2._ns
    e3 = ServingEngine(params, cfg, store=store)
    assert e1._ns == e3._ns


def test_streaming_on_token_exactly_once_in_order(params, cfg, shm_conn):
    """on_token must deliver every output token exactly once, in order,
    across plain decode, speculation (multi-token appends), admission
    in pieces, and preemption/resume."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(24)
    streamed = {}

    def cb(rid, tok):
        streamed.setdefault(rid, []).append(tok)

    # Preemption-inducing config with spec enabled.
    reqs = [
        Request(f"r{i}", _prompt(rng, cfg, 16), max_new_tokens=24,
                on_token=cb)
        for i in range(2)
    ]
    sc = ServingConfig(max_slots=2, total_pages=8, max_pages_per_seq=8,
                       spec_k=2)
    eng = ServingEngine(params, cfg, sc, store=TpuKVStore(shm_conn))
    out = eng.run(reqs)
    assert eng.stats["preemptions"] >= 1
    for rid, toks in out.items():
        assert streamed[rid] == toks, rid

    # Admission in pieces.
    streamed.clear()
    prompt = _prompt(rng, cfg, 21)
    eng2 = ServingEngine(
        params, cfg, ServingConfig(admit_piece=cfg.page_size)
    )
    out2 = eng2.run(
        [Request("c", prompt, max_new_tokens=7, on_token=cb)]
    )
    assert streamed["c"] == out2["c"] and eng2.stats["admit_pieces"] == 3

    # EOS-truncating speculation: an oracle proposer drives a draft
    # containing the EOS; post-EOS tokens must never reach the stream.
    streamed.clear()
    base = _prompt(rng, cfg, 9)
    ref = ServingEngine(params, cfg).run(
        [Request("x", base, max_new_tokens=8)]
    )["x"]
    eos = ref[3]
    lookup = {}
    toks = list(base) + ref
    for i in range(len(base), len(toks)):
        lookup[tuple(toks[:i])] = toks[i:]
    eng3 = ServingEngine(
        params, cfg, ServingConfig(spec_k=3, eos_id=eos),
        proposer=_OracleProposer(lookup),
    )
    out3 = eng3.run([Request("e", base, max_new_tokens=8, on_token=cb)])
    assert out3["e"] == ref[:4]  # truncated AT the EOS
    assert streamed["e"] == out3["e"]  # and streamed identically


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_engine_config_fuzz_token_parity(params, cfg, seed, shm_conn):
    """Property test: ANY engine configuration (slots, pieces,
    speculation, store, pool pressure) must emit each request's
    plain-engine token stream. Catches scheduler interactions no
    single-feature test covers."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(2, 5))
    reqs = [
        Request(
            f"r{i}",
            _prompt(rng, cfg, int(rng.integers(3, 30))),
            max_new_tokens=int(rng.integers(1, 14)),
        )
        for i in range(n_req)
    ]
    sc = ServingConfig(
        max_slots=int(rng.integers(1, 4)),
        total_pages=int(rng.integers(16, 48)),
        admit_piece=int(rng.choice([0, 1, 2])) * cfg.page_size,
        spec_k=int(rng.choice([0, 2])),
    )
    store = TpuKVStore(shm_conn) if rng.random() < 0.5 else None
    eng = ServingEngine(params, cfg, sc, store=store)
    out = eng.run(
        [Request(r.request_id, r.prompt, r.max_new_tokens) for r in reqs]
    )
    for r in reqs:
        ref = ServingEngine(params, cfg).run(
            [Request("x", r.prompt, r.max_new_tokens)]
        )
        assert out[r.request_id] == ref["x"], (seed, sc, r.request_id)
    # No leaked pages whatever path was taken.
    assert sorted(eng.free_pages) == list(range(1, sc.total_pages))


class _FlakyStore:
    """Store stub that fails on the chosen operation — the engine must
    degrade to store-less serving, never fail a request."""

    def __init__(self, fail_on):
        self.fail_on = fail_on
        self.calls = []

    def cached_prefix_len(self, keys):
        self.calls.append("probe")
        if self.fail_on == "probe":
            raise ConnectionError("store down")
        # Claim a hit only for the restore-failure case; the offload
        # case must reach put_kv_pages, which a hit's get would shadow.
        return 1 if self.fail_on == "get" else 0

    def get_kv_pages(self, keys, page_shape, dtype, device=None):
        self.calls.append("get")
        if self.fail_on == "get":
            raise ConnectionError("evicted mid-restore")
        raise AssertionError("unexpected get")

    def put_kv_pages(self, keys, pages, sync=False):
        self.calls.append("put")
        if self.fail_on == "put":
            raise ConnectionError("store down")


@pytest.mark.parametrize("fail_on", ["probe", "get", "put"])
def test_store_failure_degrades_to_storeless(params, cfg, fail_on):
    """A store failure at any point (probe, restore, offload) must cost
    only cache hits — the request completes with exactly the tokens of
    a store-less run, and the engine stops touching the broken store."""
    rng = np.random.default_rng(10)
    prompt = _prompt(rng, cfg, 16)
    eng = ServingEngine(params, cfg, store=_FlakyStore(fail_on))
    out = eng.run([Request("r", prompt, max_new_tokens=5)])
    ref = ServingEngine(params, cfg).run(
        [Request("x", prompt, max_new_tokens=5)]
    )
    assert out["r"] == ref["x"]
    assert eng.stats["store_errors"] == 1
    # Downgrade is sticky: a second request makes no store calls.
    store = eng.store
    n_calls = len(store.calls)
    eng.run([Request("r2", prompt, max_new_tokens=3)])
    assert len(store.calls) == n_calls
    assert eng.stats["store_errors"] == 1


def test_content_keys_diverge_with_any_token():
    a = content_page_keys([1, 2, 3, 4, 5, 6, 7, 8], 4, 2, 0, "k")
    b = content_page_keys([1, 2, 3, 4, 5, 6, 7, 9], 4, 2, 0, "k")
    assert a[0] == b[0]          # first page identical
    assert a[1] != b[1]          # second diverges
    c = content_page_keys([9, 2, 3, 4, 5, 6, 7, 8], 4, 2, 0, "k")
    assert a[0] != c[0] and a[1] != c[1]  # chain: early change poisons all


def test_steady_cache_keeps_inactive_rows_zero(params, cfg):
    """Round-4 advisor regression: the steady-state device cache stored
    lens that advanced EVERY row, so after the first reuse inactive
    slots carried seq_lens > 0 — defeating the MoE validity mask
    (models/moe.py: valid = seq_lens > 0) that keeps garbage rows out
    of expert capacity. Live rows advance, idle rows must stay 0."""
    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=4, total_pages=64)
    )
    rng = np.random.default_rng(17)
    for i in range(2):  # 2 of 4 slots active
        eng.submit(Request(
            f"zi{i}",
            [int(t) for t in rng.integers(0, cfg.vocab_size, 9)],
            max_new_tokens=12,
        ))
    eng.step()  # admission
    for _ in range(5):  # steady decode with cache reuse
        eng.step()
    assert eng._steady is not None, "steady cache should be engaged"
    lens = np.asarray(eng._steady[2])
    active = {i for i, s in enumerate(eng.slots) if s is not None}
    assert active and len(active) < 4
    for i in range(4):
        if i in active:
            assert lens[i] > 0
        else:
            assert lens[i] == 0, (i, lens)


# ---- sliding-window KV bound (rolling-buffer property) ----


@pytest.fixture(scope="module")
def wcfg(cfg):
    import dataclasses

    return dataclasses.replace(cfg, window=16)


@pytest.fixture(scope="module")
def wparams(wcfg):
    return llama.init_params(jax.random.PRNGKey(0), wcfg)


def test_windowed_release_bounds_live_pages(wparams, wcfg):
    """A windowed model's live KV stays O(window) per slot however long
    the generation runs: pages below the band floor return to the pool
    mid-generation."""
    rng = np.random.default_rng(51)
    sc = ServingConfig(max_slots=1, total_pages=32, max_pages_per_seq=16)
    eng = ServingEngine(wparams, wcfg, sc)
    eng.submit(Request("w", _prompt(rng, wcfg, 8), max_new_tokens=64))
    eng.step()  # admission
    max_used = 0
    while eng.queue or any(s is not None for s in eng.slots):
        used = (sc.total_pages - 1) - len(eng.free_pages)
        max_used = max(max_used, used)
        eng.step()
    # 72 tokens at page 8 = 9 pages without release; the window (16
    # tokens = 2 pages) plus the partial tail and one in-flight page
    # bound the live set far below that.
    assert max_used <= 4, max_used
    slot_out = eng.outputs["w"]
    assert len(slot_out) == 64


def test_windowed_release_stream_identical_to_no_release(wparams, wcfg):
    """Freeing sub-floor pages (and letting the pool reuse them while
    stale table entries still point there) must never change a single
    token: the band mask makes freed positions unobservable."""
    rng = np.random.default_rng(53)
    prompt_a = _prompt(rng, wcfg, 8)
    prompt_b = _prompt(rng, wcfg, 12)
    sc = ServingConfig(max_slots=2, total_pages=64, max_pages_per_seq=16)

    eng = ServingEngine(wparams, wcfg, sc)
    out = eng.run([
        Request("a", prompt_a, max_new_tokens=48),
        Request("b", prompt_b, max_new_tokens=48),
    ])

    ref_eng = ServingEngine(wparams, wcfg, sc)
    ref_eng._release_windowed = lambda slot: None  # release disabled
    ref = ref_eng.run([
        Request("a", prompt_a, max_new_tokens=48),
        Request("b", prompt_b, max_new_tokens=48),
    ])
    assert out["a"] == ref["a"]
    assert out["b"] == ref["b"]


def test_windowed_release_keeps_store_chain(wparams, wcfg, shm_conn):
    """Pages are offloaded to the store BEFORE leaving the pool, so the
    content-key chain stays intact and a repeat of the same prompt
    still prefix-hits."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(55)
    prompt = _prompt(rng, wcfg, 24)
    store = TpuKVStore(shm_conn)
    sc = ServingConfig(max_slots=1, total_pages=32, max_pages_per_seq=16,
                       model_id="winchain")
    eng = ServingEngine(wparams, wcfg, sc, store=store)
    out1 = eng.run([Request("c1", prompt, max_new_tokens=40)])
    assert eng.stats["offloaded_pages"] > 0

    eng2 = ServingEngine(wparams, wcfg, sc, store=store)
    out2 = eng2.run([Request("c2", prompt, max_new_tokens=40)])
    assert eng2.stats["prefix_hit_pages"] > 0  # chain intact
    assert out1["c1"] == out2["c2"]


def test_windowed_release_stream_identical_spec_and_chunked(wparams, wcfg):
    """The speculative-verify and admission-in-pieces release sites
    must be as unobservable as the plain-decode one: stream parity vs a
    release-disabled engine under spec_k>0 and admit_piece>0."""
    rng = np.random.default_rng(57)
    prompt = _prompt(rng, wcfg, 20)
    for sc in (
        ServingConfig(max_slots=2, total_pages=64, max_pages_per_seq=16,
                      spec_k=3),
        ServingConfig(max_slots=2, total_pages=64, max_pages_per_seq=16,
                      admit_piece=8),
    ):
        eng = ServingEngine(wparams, wcfg, sc)
        out = eng.run([Request("s", prompt, max_new_tokens=40)])
        ref_eng = ServingEngine(wparams, wcfg, sc)
        ref_eng._release_windowed = lambda slot: None
        ref = ref_eng.run([Request("s", prompt, max_new_tokens=40)])
        assert out["s"] == ref["s"], sc
        assert len(out["s"]) == 40


def test_windowed_preemption_readmits_beyond_pool(wparams, wcfg, shm_conn):
    """The capability windowed admission exists for: a sequence whose
    GROWN length exceeds the whole pool must still re-admit after
    preemption — sub-floor pages are already in the store, so
    re-admission allocates only O(window) pool pages — and finish its
    FULL requested length (no silent truncation)."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(61)
    store = TpuKVStore(shm_conn)
    # 7 usable pages; each request grows to 8+72=80 tokens = 10 pages.
    sc = ServingConfig(max_slots=2, total_pages=8, max_pages_per_seq=16,
                       model_id="winpool")
    eng = ServingEngine(wparams, wcfg, sc, store=store)
    reqs = [Request(f"g{i}", _prompt(rng, wcfg, 8), max_new_tokens=72)
            for i in range(2)]
    out = eng.run([Request(r.request_id, r.prompt, r.max_new_tokens)
                   for r in reqs])
    for r in reqs:
        assert len(out[r.request_id]) == 72, (
            r.request_id, len(out[r.request_id])
        )
        big = ServingEngine(wparams, wcfg, ServingConfig(
            max_slots=1, total_pages=32, max_pages_per_seq=16))
        ref = big.run([Request("x", r.prompt, max_new_tokens=72)])
        assert out[r.request_id] == ref["x"], r.request_id


def test_windowed_release_poisoned_reuse_parity(wparams, wcfg):
    """The reuse-safety claim, made falsifiable: freed pages are
    POISONED with a huge finite value while stale page-table entries
    still point at them — if any attention path attended one sub-floor
    position, the poisoned logits would dominate the softmax and the
    stream would diverge. (Finite, not NaN: masked positions contribute
    probability-zero times the value, and 0 * NaN = NaN would trip the
    test on the mask itself — production reuse writes finite floats.)"""
    rng = np.random.default_rng(63)
    prompt = _prompt(rng, wcfg, 8)
    sc = ServingConfig(max_slots=1, total_pages=32, max_pages_per_seq=16)

    ref_eng = ServingEngine(wparams, wcfg, sc)
    ref_eng._release_windowed = lambda slot: None
    ref = ref_eng.run([Request("p", prompt, max_new_tokens=48)])

    eng = ServingEngine(wparams, wcfg, sc)
    eng.submit(Request("p", prompt, max_new_tokens=48))
    eng.step()  # admission
    while eng.queue or any(s is not None for s in eng.slots):
        freed = [p for p in eng.free_pages if p != 0]
        if freed:
            sel = jnp.asarray(np.asarray(freed, np.int32))
            eng.k_pages = eng.k_pages.at[:, sel].set(1e4)
            eng.v_pages = eng.v_pages.at[:, sel].set(1e4)
        eng.step()
    assert eng.outputs["p"] == ref["p"]


@pytest.mark.parametrize("pages", [1, 2])
def test_windowed_pieces_free_pages_between_pieces(wparams, wcfg, pages):
    """Store-less, one sliding window, a prompt of several windows in
    pieces: the one-program admission's tokens, and after every piece
    the slot holds no page that lies wholly below the band's floor."""
    rng = np.random.default_rng(63)
    prompt = _prompt(rng, wcfg, 43)  # 6 pages, window 16 = 2 pages
    page, n_pages = wcfg.page_size, 6
    sc = dict(max_slots=2, total_pages=64, max_pages_per_seq=16)
    eng = ServingEngine(wparams, wcfg, ServingConfig(
        admit_piece=pages * page, **sc))
    eng.submit(Request("w", prompt, max_new_tokens=12))
    n_pieces = -(-len(prompt) // (pages * page))
    for k in range(1, n_pieces):  # the last piece's step decodes too
        eng.step()
        assert eng.stats["admit_pieces"] == k
        seq = k * pages * page
        assert eng.slots[0].seq_len == seq
        assert eng.slots[0].released == max(0, seq - wcfg.window) // page
        assert len(eng.free_pages) == 63 - n_pages + eng.slots[0].released
    out = eng.run()
    ref = ServingEngine(wparams, wcfg, ServingConfig(**sc)).run(
        [Request("w", prompt, max_new_tokens=12)])
    assert out["w"] == ref["w"]
    assert sorted(eng.free_pages) == list(range(1, 64))


@pytest.mark.parametrize("hit", [False, True])
def test_first_token_logits_in_pieces_under_a_window(wparams, wcfg, shm_conn,
                                                     hit):
    """`first_token_logits` runs what an admission in pieces runs, on
    pages it takes and gives back: under a window (cold, and over a
    hit's trimmed restore) the one-program row, nothing released, the
    free list as it was."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(67)
    prompt = _prompt(rng, wcfg, 45)
    sc = dict(max_slots=2, total_pages=64, max_pages_per_seq=16,
              model_id="winftl")
    store = TpuKVStore(shm_conn) if hit else None
    if hit:  # pages [0, 3) of the prompt, from another engine
        ServingEngine(wparams, wcfg, ServingConfig(**sc), store=store).run(
            [Request("seed", prompt[:25], max_new_tokens=1)])
    one = ServingEngine(wparams, wcfg, ServingConfig(**sc), store=store)
    eng = ServingEngine(wparams, wcfg, ServingConfig(admit_piece=8, **sc),
                        store=store)
    want, hit_one = one.first_token_logits(prompt)
    got, hit_pieces = eng.first_token_logits(prompt)
    assert hit_one == hit_pieces == (3 if hit else 0)
    assert eng.stats["admit_pieces"] == (3 if hit else 6)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert sorted(eng.free_pages) == list(range(1, 64))
    assert eng.stats["offloaded_pages"] == 0 and eng.uploads_pending == 0


def test_windowed_release_in_pieces_with_store(wparams, wcfg, shm_conn):
    """The release sites of an admission in pieces under a store: a
    prompt of 5 pages against a window of 2 frees pages BETWEEN pieces
    (each offloaded first: the store's chain stays gap-free) and on
    the repeat, a hit whose first piece takes the trimmed restore, with
    stream parity against a release-disabled engine."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(65)
    prompt = _prompt(rng, wcfg, 43)  # 5 pages + 3, window 16 = 2 pages
    store = TpuKVStore(shm_conn)
    sc = ServingConfig(max_slots=2, total_pages=64, max_pages_per_seq=16,
                       admit_piece=8, model_id="winpiece")
    eng = ServingEngine(wparams, wcfg, sc, store=store)
    eng.submit(Request("k1", prompt, max_new_tokens=24))
    held = []  # pool pages the slot holds after every piece
    while eng.stats["admit_pieces"] < 6:
        eng.step()
        held.append(sc.total_pages - 1 - len(eng.free_pages))
    # 6 pages at admission; a piece leaves what its successor attends.
    assert held == [6, 6, 5, 4, 3, 3], held
    out1 = eng.run()
    assert eng.stats["offloaded_pages"] >= 5

    ref_eng = ServingEngine(wparams, wcfg, ServingConfig(
        max_slots=2, total_pages=64, max_pages_per_seq=16, admit_piece=8))
    ref_eng._release_windowed = lambda slot: None
    ref = ref_eng.run([Request("k1", prompt, max_new_tokens=24)])
    assert out1["k1"] == ref["k1"]
    one = ServingEngine(wparams, wcfg, ServingConfig(
        max_slots=2, total_pages=64, max_pages_per_seq=16))
    assert out1["k1"] == one.run(
        [Request("k1", prompt, max_new_tokens=24)])["k1"]

    # The chain is gap-free: every full page of prompt + answer is in
    # the store, though the pool never held more than 6 of them.
    told = prompt + out1["k1"]
    n_full = (len(told) - 1) // wcfg.page_size
    for layer in range(wcfg.n_layers):
        for kind in "kv":
            assert store.cached_prefix_len(content_page_keys(
                told, wcfg.page_size, n_full, layer, kind,
                namespace=eng._ns)) == n_full

    # The same document, another question: a hit of 5 pages (of which
    # the restore brings the 2 its suffix can attend) whose 23 further
    # tokens go in 3 pieces.
    eng2 = ServingEngine(wparams, wcfg, sc, store=store)
    again = prompt + _prompt(rng, wcfg, 20)
    out2 = eng2.run([Request("k2", again, max_new_tokens=8)])
    assert eng2.stats["prefix_hit_pages"] == 5
    assert eng2.stats["restored_pages"] == 2 * wcfg.n_layers * 2
    assert eng2.stats["admit_pieces"] == 3
    assert out2["k2"] == one.run(
        [Request("k2", again, max_new_tokens=8)])["k2"]
    assert sorted(eng2.free_pages) == list(range(1, sc.total_pages))


@pytest.mark.parametrize("seed", [71, 72, 73, 74])
def test_engine_config_fuzz_window_and_quantized(cfg, seed, shm_conn):
    """Cross-feature fuzz over the round-5 additions: sliding window x
    int8 weight quantization x pieces x speculation x store x pool
    pressure. Every configuration must emit each request's token
    stream from a plain engine with the SAME model variant (windowed
    masks and quantized weights change the math, so the oracle shares
    them — the property is that scheduling features stay pure)."""
    import dataclasses

    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(seed)
    window = int(rng.choice([0, 16]))
    vcfg = dataclasses.replace(cfg, window=window)
    params = llama.init_params(jax.random.PRNGKey(0), vcfg)
    if rng.random() < 0.5:
        params = llama.quantize_params(params, vcfg)

    n_req = int(rng.integers(2, 4))
    reqs = [
        Request(
            f"r{i}",
            _prompt(rng, vcfg, int(rng.integers(3, 30))),
            max_new_tokens=int(rng.integers(1, 40)),
        )
        for i in range(n_req)
    ]
    sc = ServingConfig(
        max_slots=int(rng.integers(1, 4)),
        total_pages=int(rng.integers(12, 48)),
        admit_piece=int(rng.choice([0, 1, 2])) * cfg.page_size,
        spec_k=int(rng.choice([0, 2])),
        host_steps=int(rng.choice([1, 4])),
    )
    store = TpuKVStore(shm_conn) if rng.random() < 0.5 else None
    eng = ServingEngine(params, vcfg, sc, store=store)
    out = eng.run(
        [Request(r.request_id, r.prompt, r.max_new_tokens) for r in reqs]
    )
    for r in reqs:
        ref = ServingEngine(params, vcfg).run(
            [Request("x", r.prompt, r.max_new_tokens)]
        )
        assert out[r.request_id] == ref["x"], (seed, window, sc,
                                               r.request_id)
    # No leaked pages whatever combination ran (windowed release must
    # hand everything back too).
    assert sorted(eng.free_pages) == list(range(1, sc.total_pages)), seed


def test_admission_survives_store_death_after_cached_probe(
    params, cfg, shm_conn
):
    """ADVICE r5 regression: the probe is cached on _Work while a
    request waits under pool pressure, so it can outlive the store —
    another slot's failure latches _store_ok=False between the probe
    and (re)admission. The windowed one-shot path then computes
    skip = p0 while the cached hit still points at the restore, which
    used to trip `assert skip == first_live` (and under -O, silently
    misplace suffix pages). A dead store chain must read as a MISS."""
    import dataclasses

    from infinistore_tpu.tpu import TpuKVStore

    # Geometry chosen so the store-less floor and the hit floor differ
    # (p0 = (53-20)//8 = 4, first_live = (6*8-19)//8 = 3): the old code
    # then asserted 4 == 3.
    wcfg = dataclasses.replace(cfg, window=20)
    rng = np.random.default_rng(17)
    prompt = _prompt(rng, wcfg, 53)
    store = TpuKVStore(shm_conn)
    eng1 = ServingEngine(params, wcfg, store=store)
    eng1.run([Request("warm", prompt, max_new_tokens=1)])
    assert eng1.stats["offloaded_pages"] > 0

    eng2 = ServingEngine(params, wcfg, store=store)
    eng2.submit(Request("r", prompt, max_new_tokens=3))
    work = eng2.queue[0]
    work.probe = eng2._probe_hit(work)
    assert work.probe[0] > 0  # a real cached hit
    eng2._store_ok = False  # another slot's store op failed meanwhile
    out = eng2.run()  # must not assert / attempt the restore
    assert eng2.stats["restored_pages"] == 0
    cold = ServingEngine(params, wcfg)
    ref = cold.run([Request("x", prompt, max_new_tokens=3)])
    assert out["r"] == ref["x"]


def test_admission_prefetch_restores_from_pool(params, cfg, tmp_path):
    """Async read pipeline (PR 5): when the cached prefix chain has
    been spilled to the store's disk tier, the admission probe's
    prefetch promotes it BEFORE the restore asks — the restore then
    pins pool-resident pages and the server pays ZERO inline disk
    reads on the restore path (disk_reads_inline flat across turn 2),
    while the promotion worker's counters move."""
    from infinistore_tpu import (
        ClientConfig,
        InfiniStoreServer,
        InfinityConnection,
        ServerConfig,
        TYPE_SHM,
    )
    from infinistore_tpu.tpu import TpuKVStore

    import time

    # Tiny pool + disk tier; wide watermark band so promotion admission
    # has headroom for the whole prefix chain (hit*2L*2 pages of 4 KB
    # blocks) while filler keeps the engine pages spilled.
    srv = InfiniStoreServer(
        ServerConfig(
            service_port=0,
            prealloc_size=(256 << 10) / (1 << 30),  # 64 x 4 KB blocks
            minimal_allocate_size=4,
            ssd_path=str(tmp_path),
            ssd_size=(2 << 20) / (1 << 30),
            reclaim_high=0.9,
            reclaim_low=0.5,
        )
    )
    srv.start()
    conn = InfinityConnection(
        ClientConfig(
            host_addr="127.0.0.1",
            service_port=srv.service_port,
            connection_type=TYPE_SHM,
        )
    )
    conn.connect()
    try:
        store = TpuKVStore(conn)
        rng = np.random.default_rng(21)
        turn1 = _prompt(rng, cfg, 16)  # two full pages
        eng1 = ServingEngine(params, cfg, store=store)
        out1 = eng1.run([Request("t1", turn1, max_new_tokens=8)])
        assert eng1.stats["offloaded_pages"] > 0

        # Push the engine's pages to DISK: filler twice the pool.
        blk = 4096
        filler = np.zeros(blk, dtype=np.uint8)
        for i in range(128):
            conn.put_cache(filler, [(f"filler{i}", 0)], blk)
        conn.sync()
        deadline = time.time() + 10
        while time.time() < deadline and srv.stats()["spills"] == 0:
            time.sleep(0.02)
        assert srv.stats()["spills"] > 0

        convo = turn1 + out1["t1"]
        turn2 = convo[: (len(convo) // cfg.page_size) * cfg.page_size]
        turn2 = turn2 + _prompt(rng, cfg, 5)
        before = srv.stats()
        eng2 = ServingEngine(params, cfg, store=store)
        out2 = eng2.run([Request("t2", turn2, max_new_tokens=6)])
        after = srv.stats()
        assert eng2.stats["prefix_hit_pages"] > 0
        assert eng2.stats["prefetched_pages"] > 0
        assert eng2.stats["restore_misses"] == 0
        # THE acceptance property: the restore path paid no inline
        # disk reads — pages were pool-resident (promoted by the
        # worker off the prefetch) or pinned through the BUSY-retry
        # that waits for the promotion, never read inline.
        assert after["disk_reads_inline"] == before["disk_reads_inline"], (
            before["disk_reads_inline"], after["disk_reads_inline"],
        )
        cold = ServingEngine(params, cfg)
        ref = cold.run([Request("x", turn2, max_new_tokens=6)])
        assert out2["t2"] == ref["x"]
    finally:
        conn.close()
        srv.stop()


def test_eviction_race_during_prefetch_degrades_to_miss(
    params, cfg, shm_conn
):
    """A chain evicted between the probe's prefetch and the restore is
    a routine CACHE MISS — restore_misses counts it, the engine prefills
    cold, tokens stay correct, and the store is NOT downgraded."""
    from infinistore_tpu.tpu import TpuKVStore

    rng = np.random.default_rng(22)
    turn1 = _prompt(rng, cfg, 16)
    base = TpuKVStore(shm_conn)
    eng1 = ServingEngine(params, cfg, store=base)
    out1 = eng1.run([Request("t1", turn1, max_new_tokens=8)])
    assert eng1.stats["offloaded_pages"] > 0

    class RacyStore(TpuKVStore):
        """Evicts the very chain it was asked to prefetch — the
        worst-case LRU race between probe and restore."""

        def prefetch(self, keys):
            ok = super().prefetch(keys)
            self.conn.delete_keys(list(dict.fromkeys(keys)))
            return ok

    racy = RacyStore(shm_conn)
    convo = turn1 + out1["t1"]
    turn2 = convo[: (len(convo) // cfg.page_size) * cfg.page_size]
    turn2 = turn2 + _prompt(rng, cfg, 5)
    eng2 = ServingEngine(params, cfg, store=racy)
    out2 = eng2.run([Request("t2", turn2, max_new_tokens=6)])
    assert eng2.stats["prefetched_pages"] > 0  # the hint fired
    assert eng2.stats["restore_misses"] >= 1   # ...and lost the race
    assert eng2.stats["store_errors"] == 0     # a miss, never an error
    assert eng2._store_ok                      # no downgrade
    cold = ServingEngine(params, cfg)
    ref = cold.run([Request("x", turn2, max_new_tokens=6)])
    assert out2["t2"] == ref["x"]


# ---- a plain decode step runs one step ahead (PR 44) ----
# Token for token, the engine as it is against the same engine held
# synchronous (the one predicate, `_proven`, patched to False), on the
# five families at tiny widths.


@pytest.fixture(scope="module")
def families(cfg, params):
    """name -> (model module, config, params, prompt length that makes
    a sequence pass what the family sheds), built on first use."""
    import types

    from test_hybrid_state import CONF as STATE
    from test_latent import CONF as LATENT
    from test_window_full import CONF as BANDED

    from infinistore_tpu.models import hf, hybrid, moe, smallthinker, xing

    def sparse():
        c = moe.MoEConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=64, n_experts=4, top_k=2, max_seq=128, page_size=8,
            dtype="float32", capacity_factor=4.0)
        return moe, c, moe.init_params(jax.random.PRNGKey(3), c), 8

    def bridged(model, bridge, conf, base):
        c = bridge(types.SimpleNamespace(**conf), page_size=8,
                   dtype="float32")
        return model, c, model.init_params(jax.random.PRNGKey(0), c), base

    makers = {
        "llama": lambda: (llama, cfg, params, 8),
        "moe": sparse,
        "state": lambda: bridged(hybrid, hf.hybrid_config_from_hf, STATE, 8),
        # a band of 4 pages: 40 tokens and more shed banded pages
        "banded": lambda: bridged(smallthinker,
                                  hf.smallthinker_config_from_hf, BANDED, 40),
        "latent": lambda: bridged(xing, hf.xing_config_from_hf, LATENT, 8),
    }
    made = {}

    def family(name):
        if name not in made:
            made[name] = makers[name]()
        return made[name]
    return family


class _Pair:
    """The engine as it is and the same engine held synchronous, each
    with the on_token streams it fired."""

    def __init__(self, family, store=None, **sc):
        self.model, self.cfg, self.params, self.base = family
        sc.setdefault("max_slots", 3)
        sc.setdefault("total_pages", 64)
        sc.setdefault("max_pages_per_seq", 16)
        self.engines, self.fired = {}, {}
        for name in ("ahead", "sync"):
            self.engines[name] = ServingEngine(
                self.params, self.cfg,
                ServingConfig(model_id=f"{name}-{id(self)}", **sc),
                store=store, model=self.model)
            self.fired[name] = []
        self.engines["sync"]._proven = lambda active: False

    def prompt(self, seed, more):
        rng = np.random.default_rng(seed)
        return _prompt(rng, self.cfg, self.base + more)

    def play(self, name, script, between=None):
        """Step engine `name` through `script`: [(the count of landed
        decode steps at which the request is due, request fields)]."""
        eng, fired = self.engines[name], self.fired[name]
        due = sorted(script, key=lambda e: e[0])
        while due or eng.queue or any(s is not None for s in eng.slots):
            idle = not eng.queue and all(s is None for s in eng.slots)
            while due and (idle or eng.stats["decode_steps"] >= due[0][0]):
                kw = dict(due.pop(0)[1])
                eng.submit(Request(
                    kw.pop("rid"), kw.pop("prompt"),
                    on_token=lambda rid, t: fired.append((rid, t)), **kw))
                idle = False
            eng.step()
            if between is not None:
                between(eng)
        eng.drain_uploads()
        return dict(eng.outputs)

    def same(self, script, between=None):
        """Both engines through `script`: the same tokens, each
        request's stream its output, in order and number."""
        ahead = self.play("ahead", script, between)
        sync = self.play("sync", script)
        assert ahead == sync and all(sync.values())
        for name, out in (("ahead", ahead), ("sync", sync)):
            fired, stats = self.fired[name], self.engines[name].stats
            for rid, tokens in out.items():
                assert [t for r, t in fired if r == rid] == tokens
            assert len(fired) == sum(map(len, out.values()))
            assert stats["decode_steps_ahead"] <= stats["decode_steps"]
        a, s = self.engines["ahead"].stats, self.engines["sync"].stats
        assert s["decode_steps_ahead"] == 0 and s["decode_rows_dropped"] == 0
        assert a["decoded_tokens"] == s["decoded_tokens"]
        assert a["preemptions"] == s["preemptions"]
        return ahead


def _ahead_steps(eng):
    return [s for s in profiling.spans()
            if s.name == "istpu.engine.step" and s.engine == eng.engine_id
            and s.fields.get("ahead")]


def _ahead_admitted_while_others_decode(pair, _):
    """... into a free slot, and (the latent family) in pieces."""
    script = [(0, dict(rid="a", prompt=pair.prompt(1, 1), max_new_tokens=14)),
              (0, dict(rid="b", prompt=pair.prompt(2, 3), max_new_tokens=14)),
              (4, dict(rid="c", prompt=pair.prompt(3, 30),
                       max_new_tokens=9))]
    pair.same(script)
    eng = pair.engines["ahead"]
    # ahead before the admission and after it, not across it
    assert 0 < eng.stats["decode_steps_ahead"] < eng.stats["decode_steps"]
    if eng.sc.admit_piece:
        assert eng.stats["admit_pieces"] >= 2


def _ahead_finish_while_others_go_on(pair, _):
    pair.same([(0, dict(rid=f"f{n}", prompt=pair.prompt(10 + n, 2),
                        max_new_tokens=n)) for n in (4, 9, 15)])
    eng = pair.engines["ahead"]
    assert 0 < eng.stats["decode_steps_ahead"] < eng.stats["decode_steps"]


def _ahead_page_edges_on_different_steps(pair, _):
    """A step whose tables changed still runs ahead: only they go up."""
    pair.same([(0, dict(rid=f"e{more}", prompt=pair.prompt(20 + more, more),
                        max_new_tokens=22)) for more in (1, 3, 6)])
    eng = pair.engines["ahead"]
    steps = _ahead_steps(eng)
    assert any(s.fields["rows_uploaded"] for s in steps)
    assert not all(s.fields["rows_uploaded"] for s in steps)
    # all but the first step; the last ends every budget
    assert eng.stats["decode_steps_ahead"] == eng.stats["decode_steps"] - 1
    if eng._win_layers:
        assert eng.stats["window_pages_released"] > 0
    if eng.state is not None:
        assert eng.stats["boundary_copies"] >= 3


def _ahead_pool_runs_out(pair, _):
    out = pair.same([(0, dict(rid=f"p{n}", prompt=pair.prompt(30 + n, n),
                              max_new_tokens=30)) for n in (1, 2, 3)])
    assert pair.engines["ahead"].stats["preemptions"] > 0
    assert all(len(t) == 30 for t in out.values())


def _ahead_sampler_joins_greedy(pair, _):
    script = [(0, dict(rid="g0", prompt=pair.prompt(40, 1),
                       max_new_tokens=20)),
              (0, dict(rid="g1", prompt=pair.prompt(41, 4),
                       max_new_tokens=20)),
              (3, dict(rid="s", prompt=pair.prompt(42, 2), max_new_tokens=6,
                       temperature=0.9, top_k=8, seed=7))]
    pair.same(script)
    eng = pair.engines["ahead"]
    # synchronous while the sampler is there, ahead before and after
    assert 0 < eng.stats["decode_steps_ahead"] <= eng.stats["decode_steps"] - 5


def _ahead_eos_in_mid_answer(pair, store):
    """The row behind an EOS is dropped unseen; what the store holds is
    what the synchronous engine wrote. A family with state stays
    synchronous under an EOS."""
    prompts = {"x": pair.prompt(50, 2), "y": pair.prompt(51, 5)}
    plain = ServingEngine(pair.params, pair.cfg, ServingConfig(
        max_slots=3, total_pages=64, max_pages_per_seq=16), model=pair.model)
    free = plain.run([Request(r, p, max_new_tokens=12)
                      for r, p in prompts.items()])
    # an EOS that first shows in mid-answer of x; the tiny family with
    # state repeats one token a prompt, so there y's, which x never says
    ends, at = "x", next((i for i in range(3, 11)
                          if free["x"][i] not in free["x"][:i]), None)
    if at is None:
        ends, at = "y", 0
    eos = free[ends][at]
    ended = _Pair((pair.model, pair.cfg, pair.params, pair.base),
                  store=store, eos_id=eos)
    out = ended.same([(0, dict(rid=r, prompt=p, max_new_tokens=12))
                      for r, p in prompts.items()])
    assert out[ends] == free[ends][:at + 1]
    assert len(out["x"]) > 8 or ends == "x"
    a = ended.engines["ahead"].stats
    if ended.engines["ahead"].state is not None:
        assert a["decode_steps_ahead"] == 0 and a["decode_rows_dropped"] == 0
    else:
        assert a["decode_steps_ahead"] > 0 and a["decode_rows_dropped"] >= 1
    # read back through a fresh engine of each namespace
    rows = {}
    for name, eng in ended.engines.items():
        assert eng.stats["offloaded_pages"] > 0
        reader = ServingEngine(pair.params, pair.cfg, eng.sc, store=store,
                               model=pair.model)
        rows[name] = reader.first_token_logits(prompts["x"] + out["x"])
    assert rows["ahead"][1] == rows["sync"][1] > 0
    assert np.array_equal(rows["ahead"][0], rows["sync"][0])


def _ahead_close_and_drain_with_a_step_in_flight(pair, _):
    """Whoever stops stepping lands the step in flight first."""
    stops = iter(("drain_uploads", "close", "idle"))
    landed = []

    def between(eng):
        if eng._flight is not None and eng.stats["decode_steps"] % 4 == 2:
            stop = next(stops, None)
            if stop is not None:
                before = eng.stats["decode_steps"]
                getattr(eng, stop)()
                assert eng._flight is None
                assert eng.stats["decode_steps"] == before + 1
                landed.append(stop)
    pair.same([(0, dict(rid=f"d{n}", prompt=pair.prompt(60 + n, n),
                        max_new_tokens=18)) for n in (1, 2)], between)
    assert landed == ["drain_uploads", "close", "idle"]


def _ahead_on_token_order_and_count(pair, _):
    """Every request's stream in order and none twice (`same`), with
    arrivals at every third step; submitted together, the two engines
    fire ONE stream (an arrival joins the step BEHIND the one in
    flight, so its later tokens interleave one step on)."""
    out = pair.same([(3 * n, dict(rid=f"o{n}", prompt=pair.prompt(70 + n, n),
                                  max_new_tokens=8 + n)) for n in range(5)])
    fired = pair.fired["ahead"]
    assert len(fired) == sum(8 + n for n in range(5)) and len(out) == 5
    again = _Pair((pair.model, pair.cfg, pair.params, pair.base))
    again.same([(0, dict(rid=f"t{n}", prompt=pair.prompt(80 + n, n),
                         max_new_tokens=6 + n)) for n in range(3)])
    assert again.fired["ahead"] == again.fired["sync"]


@pytest.mark.parametrize("case", [
    _ahead_admitted_while_others_decode, _ahead_finish_while_others_go_on,
    _ahead_page_edges_on_different_steps, _ahead_pool_runs_out,
    _ahead_sampler_joins_greedy, _ahead_eos_in_mid_answer,
    _ahead_close_and_drain_with_a_step_in_flight,
    _ahead_on_token_order_and_count,
], ids=lambda f: f.__name__[len("_ahead_"):])
@pytest.mark.parametrize("name", ["llama", "moe", "state", "banded", "latent"])
def test_a_step_ahead_gives_the_synchronous_engines_tokens(families, name,
                                                           case, shm_conn):
    from infinistore_tpu.tpu import TpuKVStore

    family = families(name)
    sc = {}
    if case is _ahead_pool_runs_out:
        # room for two of the three sequences' 30 tokens
        sc["total_pages"] = 2 * (-(-(family[3] + 33) // 8)) + 1
    if name == "latent" and case is _ahead_admitted_while_others_decode:
        sc["admit_piece"] = 16
    case(_Pair(family, **sc), TpuKVStore(shm_conn))
