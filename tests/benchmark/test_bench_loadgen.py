"""Due-time accounting and lateness on a fake clock, and the streaming
client against a stub server."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmark.lib import loadgen, traffic


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def time(self):
        return self.now

    def sleep(self, s):
        self.now += s


def small_spec(turns=3):
    return {
        "loop": "open", "arrivals": "poisson", "session_rate_per_s": 1.0,
        "turns": turns,
        "classes": [{"context": 64, "message": 16, "answer": 16,
                     "weight": 1.0}],
        "think_s": {"floor": 1.0, "mean_exp": 1.0}, "ramp_s": 2,
        "drain_s": 5, "replicas": 2, "route": "rotate",
        "store_pool_seconds": 20,
    }


def fake_request(clock, send_delay=0.25, service=0.5, fail_on=()):
    calls = []

    def request(url, prompt, n, toks, times):
        clock.now += send_delay        # the generator ran late
        sent = clock.now
        calls.append((url, list(prompt), n))
        if len(calls) in fail_on:
            return sent, None, "ConnectionRefusedError: refused"
        for i in range(n):
            clock.now += service / n
            toks.append(100 + i)
            times.append(clock.now)
        return sent, clock.now, None

    request.calls = calls
    return request


def player(spec, clock, request):
    return loadgen.Player(spec, 1, 10, 1000.0, ["http://a", "http://b"],
                          512, 16, clock=clock.time, sleep=clock.sleep,
                          request=request)


def test_requests_are_timed_from_due_and_lateness_is_kept():
    clock = FakeClock()
    req = fake_request(clock)
    p = player(small_spec(), clock, req)
    sess = traffic.Session(index=0, cls=0, arrival_s=0.5,
                           thinks_s=[1.5, 2.0])
    recs = p.run_session(sess)
    assert [r["turn"] for r in recs] == [1, 2, 3]
    assert recs[0]["due"] == pytest.approx(1000.5)
    for r in recs:
        assert r["sent"] - r["due"] == pytest.approx(0.25, abs=0.06)
        assert r["token_times"][0] > r["sent"] > r["due"] - 1e-9
        assert len(r["token_times"]) == 16 and r["ended"]
    # the next turn is due a think time after the previous DONE
    assert recs[1]["due"] == pytest.approx(recs[0]["done"] + 1.5)
    assert recs[2]["due"] == pytest.approx(recs[1]["done"] + 2.0)


def test_turn_prompts_grow_by_answer_and_message():
    clock = FakeClock()
    req = fake_request(clock)
    p = player(small_spec(), clock, req)
    sess = traffic.Session(index=3, cls=0, arrival_s=0.0,
                           thinks_s=[1.0, 1.0], token_seed=9)
    recs = p.run_session(sess)
    lens = [len(c[1]) for c in req.calls]
    assert lens == [80, 112, 144] == [r["prompt_tokens"] for r in recs]
    assert req.calls[1][1][:80] == req.calls[0][1]
    assert req.calls[1][1][80:96] == [100 + i for i in range(16)]
    # rotate: turn k of session 3 goes to replica (3 + k) mod 2
    assert [c[0] for c in req.calls] == ["http://a", "http://b", "http://a"]
    assert [r["expected_hit_tokens"] for r in recs] == [0, 80, 112]


def test_a_failed_turn_ends_its_session_and_is_kept_as_an_error():
    clock = FakeClock()
    p = player(small_spec(), clock, fake_request(clock, fail_on=(2,)))
    sess = traffic.Session(index=0, cls=0, arrival_s=0.0,
                           thinks_s=[1.0, 1.0])
    recs = p.run_session(sess)
    assert len(recs) == 2 and recs[0]["error"] is None
    assert "refused" in recs[1]["error"] and not recs[1]["token_times"]


def test_no_turn_starts_after_the_window():
    clock = FakeClock()
    p = player(small_spec(), clock, fake_request(clock, service=6.0))
    sess = traffic.Session(index=0, cls=0, arrival_s=0.0,
                           thinks_s=[3.0, 3.0])
    recs = p.run_session(sess)  # window ends at 1012; turn 3 due later
    assert [r["turn"] for r in recs] == [1, 2]
    assert p.in_window(1002.0) and p.in_window(1011.9)
    assert not p.in_window(1001.9) and not p.in_window(1012.0)


def test_warm_up_runs_without_think_time():
    clock = FakeClock()
    p = player(small_spec(), clock, fake_request(clock, send_delay=0.0))
    sess = traffic.Session(index=0, cls=0, arrival_s=99.0,
                           thinks_s=[5.0, 5.0])
    recs = p.run_session(sess, due=clock.now, think=False)
    assert len(recs) == 3
    assert recs[1]["due"] == pytest.approx(recs[0]["done"])


class Stub(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    mode = "ok"

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(n))
        if Stub.mode == "400":
            self.send_response(400)
            self.send_header("Content-Length", "0")
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        count = 0 if Stub.mode == "empty" else body["max_new_tokens"]
        for i in range(count):
            self._chunk({"token": len(body["prompt"]) + i})
        self._chunk({"done": True, "tokens": []})
        self.wfile.write(b"0\r\n\r\n")

    def _chunk(self, obj):
        data = f"data: {json.dumps(obj)}\n\n".encode()
        self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()


@pytest.fixture()
def stub_url():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()
    srv.server_close()


@pytest.mark.parametrize("mode,want_err,want_n", [
    ("ok", None, 5), ("empty", "empty", 0), ("400", "http 400", 0),
])
def test_stream_request_against_a_stub(stub_url, mode, want_err, want_n):
    Stub.mode = mode
    toks, times = [], []
    sent, done, err = loadgen.stream_request(stub_url, [1, 2, 3], 5, toks,
                                             times)
    assert err == want_err and len(toks) == want_n == len(times)
    if mode == "ok":
        assert toks == [3, 4, 5, 6, 7] and done >= times[-1] >= sent


def test_a_refused_connection_is_an_error_not_an_exception():
    toks, times = [], []
    _, done, err = loadgen.stream_request("http://127.0.0.1:9", [1], 2,
                                          toks, times, timeout=2)
    assert err is not None and not toks and done is None
