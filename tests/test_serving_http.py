"""HTTP serving front end: real requests over a real socket, streamed
tokens, continuous batching across concurrent clients, per-request
TTFT/tok_s in /stats (VERDICT r3 item 7)."""

import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from infinistore_tpu.models import llama
from infinistore_tpu.serving import Request, ServingConfig, ServingEngine
from infinistore_tpu.serving_http import ServingHTTPServer


@pytest.fixture(scope="module")
def cfg():
    return llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq=128, page_size=8, dtype="float32",
    )


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def server(params, cfg):
    eng = ServingEngine(
        params, cfg, ServingConfig(max_slots=4, total_pages=64)
    )
    srv = ServingHTTPServer(eng, port=0)
    port = srv.start()
    yield f"http://127.0.0.1:{port}"
    srv.shutdown()


def _post(base, body, stream):
    req = urllib.request.Request(
        f"{base}/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        if not stream:
            return json.loads(r.read())
        events = []
        for line in r:
            line = line.strip()
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
        return events


def _ref(params, cfg, prompt, n_new):
    return ServingEngine(params, cfg).run(
        [Request("x", prompt, max_new_tokens=n_new)]
    )["x"]


def test_nonstreaming_roundtrip(server, params, cfg):
    rng = np.random.default_rng(1)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 9)]
    res = _post(server, {"prompt": prompt, "max_new_tokens": 6,
                         "stream": False}, stream=False)
    assert res["tokens"] == _ref(params, cfg, prompt, 6)
    assert res["ttft_ms"] is not None and res["ttft_ms"] >= 0
    assert res["tok_s"] > 0


def test_eight_concurrent_streaming_requests(server, params, cfg):
    """8 clients stream simultaneously; every stream's per-token events
    must concatenate to exactly that prompt's isolated greedy output
    (continuous batching is a pure scheduling concern), and /stats must
    report the serving metrics."""
    rng = np.random.default_rng(2)
    prompts = [
        [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
        for n in (5, 8, 11, 7, 9, 6, 13, 10)
    ]
    n_new = 8
    results = [None] * len(prompts)
    errors = []

    def client(i):
        try:
            events = _post(
                server,
                {"prompt": prompts[i], "max_new_tokens": n_new},
                stream=True,
            )
            toks = [e["token"] for e in events if "token" in e]
            final = [e for e in events if e.get("done")]
            assert len(final) == 1
            assert final[0]["tokens"] == toks
            results[i] = toks
        except Exception as e:  # surface in the main thread
            errors.append((i, e))

    threads = [
        threading.Thread(target=client, args=(i,))
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    for i, p in enumerate(prompts):
        assert results[i] == _ref(params, cfg, p, n_new), i

    stats = json.loads(
        urllib.request.urlopen(f"{server}/stats", timeout=30).read()
    )
    assert stats["requests_done"] >= 8
    assert stats["ttft_ms_mean"] > 0
    assert stats["tok_s_mean"] > 0
    # Each request's FIRST token comes from admission prefill logits,
    # not a decode step.
    assert stats["engine"]["decoded_tokens"] >= 8 * (n_new - 1)


def test_sampled_stream_and_bad_requests(server, cfg):
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 6)]
    events = _post(
        server,
        {"prompt": prompt, "max_new_tokens": 5, "temperature": 0.8,
         "top_k": 8, "seed": 11},
        stream=True,
    )
    toks = [e["token"] for e in events if "token" in e]
    assert len(toks) == 5
    # Bad requests answer 400, not a hung stream.
    for body in ({"prompt": []}, {"prompt": [1], "max_new_tokens": 0},
                 {"nope": 1}):
        req = urllib.request.Request(
            f"{server}/generate", data=json.dumps(body).encode(),
            method="POST",
        )
        try:
            urllib.request.urlopen(req, timeout=30)
            assert False, "expected HTTPError"
        except urllib.error.HTTPError as e:
            assert e.code == 400


def test_health(server):
    assert json.loads(
        urllib.request.urlopen(f"{server}/health", timeout=10).read()
    )["status"] == "ok"


def test_trace_answers_the_span_ring_and_ttft_counts_from_arrival(
        server, cfg):
    """GET /trace: the program's span ring as Chrome trace-event JSON
    (the form the store's /trace answers in), holding this request's
    spans from the HTTP edge down; the server's ttft_ms counts from the
    arrival stamp the engine's queue wait starts at."""
    rng = np.random.default_rng(4)
    prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 10)]
    res = _post(server, {"prompt": prompt, "max_new_tokens": 5,
                         "stream": False}, stream=False)
    rid = res["request_id"]
    mine = []
    for _ in range(200):  # http.request lands after the response
        trace = json.loads(
            urllib.request.urlopen(f"{server}/trace", timeout=30).read())
        mine = [e for e in trace["traceEvents"]
                if e.get("args", {}).get("request_id") == rid]
        if any(e["name"] == "istpu.http.request" for e in mine):
            break
    by_name = {e["name"]: e for e in mine}
    assert {"istpu.http.request", "istpu.sched.queue_wait",
            "istpu.sched.admit", "istpu.model.prefill"} <= set(by_name)
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in mine)
    assert {"clock_realtime_ns", "clock_monotonic_ns"} <= set(
        trace["metadata"])
    http, wait = by_name["istpu.http.request"], by_name[
        "istpu.sched.queue_wait"]
    assert http["ts"] == wait["ts"]  # one origin
    assert http["args"]["tokens_out"] == 5
    assert http["args"]["first_token_ns"] / 1e6 == pytest.approx(
        res["ttft_ms"], abs=0.01)
    # TTFT holds the wait and the admission it follows.
    assert res["ttft_ms"] * 1e3 >= wait["dur"] + by_name[
        "istpu.sched.admit"]["dur"] - 1e3


@pytest.mark.parametrize("fails", [False, True],
                         ids=["ticks", "a-failing-tick-is-not-fatal"])
def test_the_loop_ticks_an_idle_engine_and_no_stepping_one(
        params, cfg, monkeypatch, fails):
    """The engine loop calls `engine.idle()` on the passes that find
    nothing to step and on no other, and an `idle()` that raises leaves
    the server serving (a broken device is the next step's to report)."""
    import time

    eng = ServingEngine(params, cfg,
                        ServingConfig(max_slots=4, total_pages=64))
    calls = []
    real_idle, real_step = eng.idle, eng.step

    def idle():
        calls.append("idle")
        if fails:
            raise RuntimeError("tick failed")
        real_idle()

    def step():
        calls.append("step")
        return real_step()

    monkeypatch.setattr(eng, "idle", idle)
    monkeypatch.setattr(eng, "step", step)
    srv = ServingHTTPServer(eng, port=0)
    base = f"http://127.0.0.1:{srv.start()}"
    try:
        deadline = time.time() + 30
        while calls.count("idle") < 3 and time.time() < deadline:
            time.sleep(0.01)
        assert calls.count("idle") >= 3 and "step" not in calls
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        res = _post(base, {"prompt": prompt, "max_new_tokens": 5,
                           "stream": False}, stream=False)
        assert res["tokens"] == _ref(params, cfg, prompt, 5)
        first, last = calls.index("step"), \
            len(calls) - 1 - calls[::-1].index("step")
        assert "idle" not in calls[first:last]
    finally:
        srv.shutdown()
