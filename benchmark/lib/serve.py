"""The system under test, built from a configuration file: the program's
model config through the repo's own HF bridge, weights on the device in
one jitted call from the seed, one engine + HTTP server per replica, and
the benchmark's own spans around the store and the engine step.

Everything program-specific the benchmark needs is named in the
configuration file's "program" group, so a later configuration is new
files, not an edit: `model`, `bridge` and `reference` (required), and
optionally `costs` (a module with lib/costs.py's functions for this
family's layers and cache), `tolerances` ({"file", "family"}) and
`programs` ({"decode": [...], "prefill": [...]}: substrings of the XLA
module names the trace readers look for). PERF.md, section 4, lists
what each must offer.
"""

import collections
import importlib
import json
import os
import time
import types

from . import ROOT


def load_config(path, rehearsal=False):
    """The configuration file as a dict; with `rehearsal` the tiny
    widths of its "rehearsal" group replace the published ones."""
    with open(os.path.join(ROOT, path)) as f:
        conf = json.load(f)
    if rehearsal:
        tiny = dict(conf["rehearsal"])
        conf["serving"] = tiny.pop("serving")
        conf.update(tiny)
        conf["torch_dtype"] = "float32"
    return conf


def _resolve(dotted):
    mod, _, attr = dotted.partition(":")
    m = importlib.import_module(mod)
    return getattr(m, attr) if attr else m


# The file's groups that are the harness's own; every other key is
# the published configuration's and reaches the bridge.
HARNESS_GROUPS = ("source", "reduced", "assumed", "deployment",
                  "guarantees", "program", "serving", "rehearsal")
PROGRAMS = {"decode": ["decode_fused"],
            "prefill": ["admit_fused", "prefill_px"]}


def model_config(conf):
    """(model module, its config object) through the bridge the file
    names. The bridge reads attributes, so every published key, lists
    and groups included, is handed over as a namespace, untouched."""
    hf = types.SimpleNamespace(**{
        k: v for k, v in conf.items() if k not in HARNESS_GROUPS
    })
    bridge = _resolve(conf["program"]["bridge"])
    cfg = bridge(hf, page_size=conf["serving"]["page_size"],
                 dtype=conf["torch_dtype"])
    return _resolve(conf["program"]["model"]), cfg


def reference_module(conf):
    return _resolve(conf["program"]["reference"])


def costs_module(conf):
    """The module that counts this configuration's bytes and FLOPs
    (lib/costs.py's functions, from the file's published keys)."""
    return _resolve(conf["program"].get("costs", "benchmark.lib.costs"))


def program_names(conf, kind):
    """Substrings of the XLA module names of the configuration's
    "decode" or "prefill" programs, for trace.program_times."""
    return conf["program"].get("programs", {}).get(kind, PROGRAMS[kind])


def serving_config(conf, model_id):
    """Every key of the file's "serving" group is a ServingConfig
    field, but page_size, which the bridge takes."""
    from infinistore_tpu.serving import ServingConfig

    s = {k: v for k, v in conf["serving"].items() if k != "page_size"}
    return ServingConfig(model_id=model_id, **s)


def init_weights(model, cfg, seed, device=None):
    """All weights in ONE jitted call from the seed, in the type they
    are served in, on `device`."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    fn = jax.jit(model.init_params, static_argnums=1)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(fn(key, cfg))


class CompileMeter:
    """Counts XLA executable builds and persistent-cache hits from JAX's
    monitoring events (the pattern of chip_smoke.py)."""

    def __init__(self):
        import jax

        self.n = 0
        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def built(self):
        """Executables really compiled (not read from the cache)."""
        return self.n - self.cache_hits


def enable_compile_cache():
    """The program's one cache helper (JAX_COMPILATION_CACHE_DIR if set,
    else <checkout>/.xla_cache), and every program cached however small:
    the engine's eager paths build some hundreds of sub-second programs
    that the default thresholds would compile anew in every run."""
    import jax

    from infinistore_tpu.tpu import enable_compile_cache as program_cache

    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


# One engine step: unix start, seconds, slots it decoded, tokens of KV
# live after it, and the counters it moved ({name: delta}).
Step = collections.namedtuple("Step", "t0 seconds active live_tokens moved")


class StepSpans:
    """engine.step wrapped from outside: one TraceAnnotation and one
    host record per step, with the counters a step moved."""

    KEYS = ("prefill_tokens", "prefix_hit_pages", "decoded_tokens",
            "decode_steps", "offloaded_pages")

    def __init__(self, engine, name="bench.step"):
        import jax

        self.records = []  # Step tuples
        inner = engine.step
        stats = engine.stats
        annotate = jax.profiler.TraceAnnotation

        def step():
            before = [stats[k] for k in self.KEYS]
            t0 = time.time()
            p0 = time.perf_counter()
            with annotate(name):
                n = inner()
            dur = time.perf_counter() - p0
            live = 0
            for s in getattr(engine, "slots", ()):
                if s is not None:
                    live += getattr(s, "seq_len", 0)
            self.records.append(Step(t0, dur, n, live, {
                k: stats[k] - b for k, b in zip(self.KEYS, before)}))
            return n

        engine.step = step


class Replica:
    """One engine on one device behind its own HTTP server, with a
    store connection and span proxy of its own."""

    def __init__(self, index, device, params, model, cfg, sconfig,
                 service_port):
        from infinistore_tpu import ClientConfig, InfinityConnection
        from infinistore_tpu.serving import ServingEngine
        from infinistore_tpu.serving_http import ServingHTTPServer
        from infinistore_tpu.tpu import TpuKVStore

        from .store import SpanStore

        self.index = index
        self.device = device
        self.conn = InfinityConnection(ClientConfig(
            host_addr="127.0.0.1", service_port=service_port
        ))
        self.conn.connect()
        self.shm = bool(self.conn.shm_connected)
        self.inner_store = TpuKVStore(self.conn)
        self.store = SpanStore(self.inner_store)
        self.engine = ServingEngine(params, cfg, sconfig, store=self.store,
                                    model=model)
        self.steps = StepSpans(self.engine)
        self.web = ServingHTTPServer(self.engine)
        self.url = f"http://127.0.0.1:{self.web.start()}"

    def counters(self):
        return dict(self.engine.stats)

    def engine_ok(self):
        return bool(self.web.stats()["engine_ok"])

    def close(self):
        self.web.shutdown()
        self.conn.close()


def device_report(devices):
    """The `device` object of the result line, as JAX reports it."""
    peak = 0
    for d in devices:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def wait_until(t):
    while True:
        left = t - time.time()
        if left <= 0:
            return
        time.sleep(min(left, 0.2))

