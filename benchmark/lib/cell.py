"""One cell, end to end: set-up (store child, weights, replicas),
warm-up of every shape the traffic file enumerates through the normal
HTTP path (the same sample decides `correct`), then the ramp and the
measured window driven by the load generator child, with counters,
spans and optionally a few traced seconds taken inside the window.
"""

import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

from . import correct, loadgen, serve, traffic
from .store import BenchFailure, StoreChild, build_native

ROOT = serve.ROOT


class Observations:
    """What the metric readers read. Times are unix seconds."""

    def __init__(self):
        self.conf = self.spec = self.cell = None
        self.chips = 1
        self.window = (0.0, 0.0)
        self.seconds = 0.0
        self.records = []       # every request record of the run
        self.counters = {}      # engine counter deltas over the window
        self.spans = []         # store spans that started in the window
        self.steps = []         # step records that started in the window
        self.store_hist = {}    # {op: histogram delta over the window}
        self.store_delta = {}   # the store's top-level counters, delta
        self.trace = None       # lib.trace.reduce(...) of the traced part
        self.trace_window = None  # unix (start, end) of the traced part
        self.peaks = None
        self.setup_s = None
        self.drain_s = 0.0

    # -- helpers shared by the readers -----------------------------------
    def due_in_window(self):
        w0, w1 = self.window
        return [r for r in self.records if w0 <= r["due"] < w1]

    def failed(self, r):
        """A refused, failed, empty or short response; with a drain
        (a cell below its knee) also one that never got a first token
        by the end of the run."""
        if r["error"] is not None:
            return True
        if r["ended"] and len(r["token_times"]) != r["want_tokens"]:
            return True
        return self.drain_s > 0 and not r["token_times"]

    def ttfts_ms(self, pick=lambda r: True):
        """TTFT from DUE time of requests due in the window; a failed
        request is censored at the end of the run (it ranks last)."""
        end = self.window[1] + self.drain_s
        out = []
        for r in self.due_in_window():
            if not pick(r):
                continue
            if r["token_times"] and not self.failed(r):
                out.append((r["token_times"][0] - r["due"]) * 1e3)
            elif self.failed(r):
                out.append((end - r["due"]) * 1e3)
        return out

    def gaps_ms(self):
        """Gaps between consecutive streamed tokens of one request, the
        later token inside the window."""
        w0, w1 = self.window
        out = []
        for r in self.records:
            t = r["token_times"]
            for a, b in zip(t, t[1:]):
                if w0 <= b < w1:
                    out.append((b - a) * 1e3)
        return out

    def tokens_in_window(self):
        w0, w1 = self.window
        return sum(1 for r in self.records for t in r["token_times"]
                   if w0 <= t < w1)

    def spans_named(self, name, traced=False):
        if traced:
            if self.trace_window is None:
                return []
            a, b = self.trace_window
            return [s for s in self.spans if s.name == name
                    and a <= s.t0 < b]
        return [s for s in self.spans if s.name == name]

    def steps_traced(self):
        if self.trace_window is None:
            return []
        a, b = self.trace_window
        return [s for s in self.steps if a <= s.t0 < b]


def _delta(after, before):
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


def _hist_delta(after, before):
    out = {}
    for op, s in (after.get("op_stats") or {}).items():
        h1 = s.get("hist") or []
        h0 = ((before.get("op_stats") or {}).get(op) or {}).get("hist") \
            or [0] * len(h1)
        out[op] = [a - b for a, b in zip(h1, h0)]
    return out


def store_sizes(conf, cfg, spec, rehearsal=False):
    """(pool GB, block KB) of the store child, from the configuration's
    costs module: the pool holds `store_pool_seconds` of the mix's
    writes, the block is the smallest object an offload writes."""
    costs = serve.costs_module(conf)
    page, itemsize = cfg.page_size, cfg.jdtype.itemsize
    pool_gb = 0.125 if rehearsal else traffic.store_pool_gb(
        spec, costs.page_bytes_all_layers(conf, page, itemsize), page,
        costs.snapshot_bytes(conf, itemsize))
    block = costs.store_block_bytes(conf, page, itemsize)
    return pool_gb, max(1, block >> 10)


class Cell:
    def __init__(self, cell, config_entry, seed, rehearsal=False,
                 log=print):
        self.cell, self.seed, self.rehearsal = cell, int(seed), rehearsal
        self.log = log
        self.conf = serve.load_config(config_entry["file"], rehearsal)
        spec = traffic.load(f"benchmark/traffic/{cell['traffic']}.json")
        self.spec = traffic.scaled(spec, 8) if rehearsal else spec
        self.replicas = []
        self.store = None
        self.run_dir = None
        self.meter = None
        self.details = {}

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import jax

        t0 = time.perf_counter()
        built = build_native()
        # The rehearsal keeps no cache: CPU executables, not the chip's.
        self.cache_dir = None if self.rehearsal \
            else serve.enable_compile_cache()
        self.meter = serve.CompileMeter()
        self.model, self.cfg = serve.model_config(self.conf)
        n = self.spec["replicas"]
        devices = jax.devices()[:n]
        if len(devices) < n:
            raise BenchFailure(f"the traffic wants {n} replicas, JAX has "
                               f"{len(jax.devices())} devices")
        self.devices = devices
        self.run_dir = tempfile.mkdtemp(prefix="bench_run_")
        pool_gb, block_kb = store_sizes(self.conf, self.cfg, self.spec,
                                        self.rehearsal)
        self.store = StoreChild(pool_gb, block_kb, self.run_dir)
        t1 = time.perf_counter()
        params = serve.init_weights(self.model, self.cfg, self.seed,
                                    devices[0])
        t2 = time.perf_counter()
        model_id = f"{self.cell['config']}-s{self.seed}"
        sconfig = serve.serving_config(self.conf, model_id)
        for i, dev in enumerate(devices):
            p = params if i == 0 else jax.block_until_ready(
                jax.device_put(params, dev))
            self.replicas.append(serve.Replica(
                i, dev, p, self.model, self.cfg, sconfig,
                self.store.service_port))
        self.params = self.replicas[0].engine.params
        if not all(r.shm for r in self.replicas):
            raise BenchFailure("an engine's store connection is not SHM")
        self.urls = [r.url for r in self.replicas]
        self.log("setup: " + json.dumps({
            "native_build_s": round(built, 1),
            "store_pool_gb": pool_gb,
            "store_up_s": round(t1 - t0 - built, 1),
            "weights_s": round(t2 - t1, 1),
            "weights_gb": round(serve.costs_module(self.conf).weight_bytes(
                self.conf) / 1e9, 2),
            "replicas_s": round(time.perf_counter() - t2, 1),
            "compile_cache_dir": self.cache_dir,
            "host_mem_gb": round(os.sysconf("SC_PAGE_SIZE")
                                 * os.sysconf("SC_PHYS_PAGES") / 2 ** 30),
            "dev_shm_free_gb": round(
                shutil.disk_usage("/dev/shm").free / 2 ** 30, 1),
        }))

    # -- warm-up and the correctness sample ------------------------------
    def warm_and_check(self, direct=True):
        t0 = time.perf_counter()
        spec, n = self.spec, len(self.replicas)
        samples = correct.sample_sessions(spec, self.seed, copies=n)
        player = loadgen.Player(spec, self.seed, 0, time.time(), self.urls,
                                self.cfg.vocab_size, self.cfg.page_size)
        by_session = {}
        # The cold rows first: the sample's own first prompts, while
        # the store holds nothing of them (correct.py, point 2).
        cold_rows = correct.cold_first_logits(
            spec, samples, self.replicas, self.model, self.cfg,
            self.cfg.vocab_size) if direct else None
        for r in self.replicas:
            r.store.arm_tap()
        # Every shape is per sequence (the decode program is fixed), so
        # the sample sessions run side by side and share decode steps.
        threads = []
        for s in samples:
            def one(s=s):
                by_session[s.index] = player.run_session(
                    s, due=time.time(), think=False)
            th = threading.Thread(target=one, daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        t1 = time.perf_counter()
        warm = {"warmup_s": round(t1 - t0, 1),
                "compilations": self.meter.n,
                "compile_s": round(self.meter.secs, 1),
                "persistent_cache_hits": self.meter.cache_hits}
        tol = correct.tolerances_for(self.conf)
        ok, details = correct.check(
            self.conf, spec, self.model, self.cfg, self.params,
            serve.reference_module(self.conf), self.replicas, samples,
            by_session, self.cfg.vocab_size, tol, cold_rows=cold_rows,
            log=self.log)
        warm["check_s"] = round(time.perf_counter() - t1, 1)
        self.log("warm-up: " + json.dumps(warm))
        brief = {k: v for k, v in details.items() if k != "per_turn"}
        brief["tolerances"] = {k: v for k, v in tol.items() if k != "why"}
        self.log("correct: " + json.dumps(brief))
        self.details = details
        return ok

    # -- the ramp and the window -----------------------------------------
    def measure(self, seconds, trace=False, rate=None, trace_s=4.0):
        import jax

        spec = dict(self.spec)
        if rate is not None:
            spec["session_rate_per_s"] = rate
        obs = Observations()
        obs.conf, obs.spec, obs.cell = self.conf, spec, self.cell
        obs.chips = len(self.replicas)
        obs.seconds = float(seconds)
        obs.drain_s = float(spec.get("drain_s", 0))
        out = os.path.join(self.run_dir, f"records_{time.time_ns()}.json")
        job = out + ".job"
        t0 = time.time() + 1.5
        with open(job, "w") as f:
            json.dump({"traffic": spec, "seed": self.seed,
                       "seconds": seconds, "t0": t0, "urls": self.urls,
                       "vocab": self.cfg.vocab_size,
                       "page": self.cfg.page_size, "out": out}, f)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        child = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "lib",
                                          "loadgen.py"), "--job", job],
            cwd=ROOT, env=env)
        try:
            w0 = t0 + spec["ramp_s"]
            w1 = w0 + seconds
            serve.wait_until(w0)
            c0 = [r.counters() for r in self.replicas]
            s0 = self.store.stats()
            n0, built0 = self.meter.n, self.meter.built
            obs.window = (w0, w1)
            if trace:
                trace_s = min(trace_s, max(0.5, seconds / 3))
                serve.wait_until(w0 + min(10.0, seconds / 3))
                tdir = os.path.join(self.run_dir, "trace")
                ta = time.time()
                jax.profiler.start_trace(tdir)
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    time.sleep(trace_s)
                tb = time.time()
                jax.profiler.stop_trace()
                obs.trace_window = (ta, tb)
                self.trace_dir = tdir
            serve.wait_until(w1)
            c1 = [r.counters() for r in self.replicas]
            s1 = self.store.stats()
            self.compiled_in_window = self.meter.n - n0
            self.built_in_window = self.meter.built - built0
            child.wait(timeout=spec.get("drain_s", 0) + 60)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        if child.returncode != 0 or not os.path.exists(out):
            raise BenchFailure(f"load generator exited with "
                               f"{child.returncode} and no records")
        with open(out) as f:
            obs.records = json.load(f)["records"]
        for r in obs.records:
            r.setdefault("ended", True)
        totals = {}
        for a, b in zip(c1, c0):
            for k, v in _delta(a, b).items():
                totals[k] = totals.get(k, 0) + v
        obs.counters = totals
        obs.store_hist = _hist_delta(s1, s0)
        obs.store_delta = {k: s1[k] - s0.get(k, 0) for k in s1
                           if isinstance(s1[k], (int, float))}
        for r in self.replicas:
            obs.spans += [s for s in r.store.spans if w0 <= s.t0 < w1]
            obs.steps += [s for s in r.steps.records if w0 <= s.t0 < w1]
        obs.max_slots = self.conf["serving"]["max_slots"]
        return obs

    def health(self):
        """Point 4's end-of-run half: no store error on any replica and
        every engine up."""
        errs = sum(r.counters()["store_errors"] for r in self.replicas)
        up = all(r.engine_ok() for r in self.replicas)
        return errs == 0 and up, {"store_errors": errs, "engine_ok": up}

    def close(self):
        while self.replicas:
            r = self.replicas.pop()
            try:
                r.close()
            except Exception as e:  # shutting down: report, go on
                self.log(f"close: replica {r.index}: {e}")
            del r  # the engine and its step wrapper are a cycle
        self.params = None
        self.details = {}
        rc = leaked = None
        if self.store is not None:
            rc, leaked = self.store.stop()
            self.store = None
        if self.run_dir:
            shutil.rmtree(self.run_dir, ignore_errors=True)
        gc.collect()
        return rc, leaked
