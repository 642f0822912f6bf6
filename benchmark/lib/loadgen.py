#!/usr/bin/env python3
"""Open-loop load generator, a JAX-free process of its own (it must not
share the GIL with the engine thread). Reads a job file, plays the
plan that lib/traffic.py makes from (traffic file, seed), and writes
one record per request. Every time is the generator's own clock, and a
request is timed from when it was DUE, not from when it was sent.

    python3 benchmark/lib/loadgen.py --job job.json

job.json: {"traffic": {...spec...}, "seed", "seconds", "t0" (unix time
at which the ramp starts), "urls": [one per replica], "vocab",
"page", "out": path}
"""

import argparse
import http.client
import json
import os
import sys
import threading
import time
import urllib.parse

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
from benchmark.lib import traffic  # noqa: E402


def stream_request(url, prompt, max_new_tokens, toks, times,
                   clock=time.time, timeout=600):
    """POST /generate with stream=true, appending token ids to `toks`
    and their arrival times to `times` as they stream (so a request cut
    at the end of a run keeps what it got). Returns (sent_at, done_at,
    error or None). A response with no token is an error ("empty")."""
    u = urllib.parse.urlparse(url)
    body = json.dumps({"prompt": prompt, "max_new_tokens": max_new_tokens,
                       "stream": True}).encode()
    done_at, err = None, None
    sent = clock()
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json",
                              "Connection": "close"})
        resp = conn.getresponse()
        if resp.status != 200:
            return sent, clock(), f"http {resp.status}"
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data:"):
                continue
            now = clock()
            ev = json.loads(line[5:])
            if "token" in ev:
                toks.append(ev["token"])
                times.append(now)
            elif ev.get("done"):
                done_at = now
                break
    except Exception as e:  # refused, reset, timed out
        err = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    if err is None and not toks:
        err = "empty"
    return sent, done_at, err


class Player:
    """Plays sessions against the replicas; thread per live session."""

    def __init__(self, spec, seed, seconds, t0, urls, vocab, page=16,
                 clock=time.time, sleep=time.sleep, request=stream_request):
        self.spec, self.seed, self.seconds = spec, seed, seconds
        self.t0, self.urls, self.vocab, self.page = t0, urls, vocab, page
        self.clock, self.sleep, self.request = clock, sleep, request
        self.ramp = spec["ramp_s"]
        self.end = t0 + self.ramp + seconds
        self.records = []
        self.lock = threading.Lock()
        self.stop = threading.Event()

    def in_window(self, t):
        return self.t0 + self.ramp <= t < self.end

    def _wait(self, until):
        while not self.stop.is_set():
            left = until - self.clock()
            if left <= 0:
                return True
            self.sleep(min(left, 0.05))
        return False

    def run_session(self, sess, due=None, think=True):
        """All turns of one session. `due` overrides the arrival time
        (warm-up: now); think=False drops the think time (warm-up)."""
        spec = self.spec
        cls = spec["classes"][sess.cls]
        lens = traffic.turn_lengths(cls, spec["turns"], self.page)
        ctx, msgs = traffic.session_tokens(spec, sess, self.vocab)
        history = list(ctx)
        due = self.t0 + sess.arrival_s if due is None else due
        out = []
        for turn in range(1, spec["turns"] + 1):
            if not self._wait(due) or (think and due >= self.end):
                break
            prompt = history + msgs[turn - 1]
            replica = traffic.replica_of(spec, sess.index, turn)
            toks, times = [], []
            rec = {
                "session": sess.index, "turn": turn, "cls": sess.cls,
                "replica": replica, "due": due, "sent": None,
                "token_times": times, "done": None,
                "want_tokens": cls["answer"],
                "prompt_tokens": len(prompt),
                "expected_hit_tokens": lens[turn - 1]["hit"],
                "error": None, "tokens": toks, "ended": False,
            }
            out.append(rec)
            with self.lock:
                self.records.append(rec)
            sent, done_at, err = self.request(
                self.urls[replica], prompt, cls["answer"], toks, times)
            rec.update(sent=sent, done=done_at, error=err, ended=True)
            if err is not None or done_at is None \
                    or len(toks) != cls["answer"]:
                break  # a broken turn ends its session
            history = prompt + toks
            if turn < spec["turns"]:
                due = done_at + (sess.thinks_s[turn - 1] if think else 0.0)
        return out

    def play(self):
        """The ramp and the window; returns when every request due in
        the window has ended or drain_s has passed after it."""
        sessions = traffic.plan(self.spec, self.seed,
                                self.ramp + self.seconds)
        threads = []
        for sess in sessions:
            if not self._wait(self.t0 + sess.arrival_s):
                break
            if self.clock() >= self.end:
                break
            th = threading.Thread(target=self.run_session, args=(sess,),
                                  daemon=True)
            th.start()
            threads.append(th)
        self._wait(self.end)
        deadline = self.end + self.spec.get("drain_s", 0)
        for th in threads:
            th.join(timeout=max(0.0, deadline - self.clock()))
        self.stop.set()
        with self.lock:
            return list(self.records)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--job", required=True)
    args = ap.parse_args()
    with open(args.job) as f:
        job = json.load(f)
    player = Player(job["traffic"], job["seed"], job["seconds"], job["t0"],
                    job["urls"], job["vocab"], job.get("page", 16))
    records = [dict(r, token_times=list(r["token_times"]))
               for r in player.play()]
    for r in records:
        r["n_tokens"] = len(r.pop("tokens"))
    tmp = job["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"t0": job["t0"], "ramp_s": player.ramp,
                   "seconds": job["seconds"], "records": records,
                   "ended": time.time()}, f)
    os.replace(tmp, job["out"])
    # Requests still streaming (a cell above its knee) are cut here:
    # their sockets close with the process.
    os._exit(0)


if __name__ == "__main__":
    main()
