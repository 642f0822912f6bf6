"""The store as a JAX-free CLI child (the pattern of chip_smoke.py), and
the benchmark's own spans around every call the engine makes into it.
"""

import collections
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

from . import ROOT


class BenchFailure(Exception):
    pass


# One call into the store: unix start, seconds, payload bytes, keys in
# the call, and the result where it is a count (the probe's hit).
Span = collections.namedtuple(
    "Span", "name t0 seconds nbytes n_keys result")


def build_native():
    """Build native/ where the library is missing (the driver's checkout
    holds only what git commits). One build, before any child starts."""
    so = os.path.join(ROOT, "infinistore_tpu", "_native",
                      "libinfinistore_tpu.so")
    if os.path.exists(so):
        return 0.0
    t0 = time.perf_counter()
    cmd = ["make", "-C", os.path.join(ROOT, "native"),
           f"-j{os.cpu_count() or 1}", "all"]
    r = subprocess.run(cmd, capture_output=True, text=True)
    if r.returncode != 0:
        raise BenchFailure(f"{' '.join(cmd)} failed:\n{r.stderr[-4000:]}")
    return time.perf_counter() - t0


def get_json(url, timeout=30):
    return json.load(urllib.request.urlopen(url, timeout=timeout))


class StoreChild:
    """`python -m infinistore_tpu.server` with ephemeral ports, LRU
    eviction on (a full pool evicts dead sessions instead of failing
    allocations) and pool blocks of one KV page each."""

    def __init__(self, pool_gb, block_kb, run_dir):
        port_file = os.path.join(run_dir, "store_ports.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.pool_gb = pool_gb
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "infinistore_tpu.server",
             "--host", "127.0.0.1", "--service-port", "0",
             "--manage-port", "0", "--port-file", port_file,
             "--prealloc-size", str(pool_gb),
             "--minimal-allocate-size", str(block_kb),
             "--enable-eviction"],
            cwd=ROOT, env=env, stdout=subprocess.DEVNULL,
        )
        try:
            deadline = time.monotonic() + 180
            while not os.path.exists(port_file):
                if self.proc.poll() is not None:
                    raise BenchFailure(
                        f"store exited with {self.proc.returncode} at "
                        f"start-up")
                if time.monotonic() > deadline:
                    raise BenchFailure("store did not come up in 180 s")
                time.sleep(0.05)
            with open(port_file) as f:
                ports = json.load(f)
            self.service_port = ports["service_port"]
            self.manage_port = ports["manage_port"]
            health = get_json(
                f"http://127.0.0.1:{self.manage_port}/health")
            with open(f"/proc/{self.proc.pid}/maps") as f:
                maps = f.read()
            if health.get("status") != "ok" or "libtpu" in maps \
                    or "jaxlib" in maps:
                raise BenchFailure(
                    f"store child unhealthy or holds JAX: {health}")
        except BaseException:
            self.proc.kill()
            self.proc.wait()
            raise

    def stats(self):
        return get_json(f"http://127.0.0.1:{self.manage_port}/stats")

    def stop(self):
        """Stops the child, waits for it, and reports /dev/shm
        leftovers (there should be none)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        leaked = [n for n in os.listdir("/dev/shm")
                  if n.startswith(f"istpu_{self.proc.pid}_")]
        for n in leaked:  # never leave a pool behind for the next run
            try:
                os.unlink(os.path.join("/dev/shm", n))
            except OSError:
                pass
        return self.proc.returncode, leaked


def _nbytes(x):
    n = getattr(x, "nbytes", None)
    if n is not None:
        return int(n)
    size = 1
    for d in getattr(x, "shape", ()):
        size *= d
    return size * getattr(getattr(x, "dtype", None), "itemsize", 1)


class SpanStore:
    """Stands in for the TpuKVStore handed to ServingEngine(store=...):
    delegates every call and wraps the ones the engine's hot path makes
    (cached_prefix_len, get_kv_pages, put_kv_pages, prefetch and
    conn.sync) in a jax.profiler.TraceAnnotation plus a host timer and
    a byte count. Anything else falls through unspanned.

    Every spanned call appends one Span to `spans`."""

    def __init__(self, inner, annotate=None):
        if annotate is None:
            import jax

            annotate = jax.profiler.TraceAnnotation
        self._inner = inner
        self._annotate = annotate
        self.spans = []
        self.conn = _SpanConn(inner.conn, self)
        # The first put batch after arm_tap(): keys and the device
        # array the engine gathered from its HBM pool, for the
        # read-back check.
        self.tapped = None
        self._tap_armed = False

    def arm_tap(self):
        self.tapped = None
        self._tap_armed = True

    def _span(self, name, fn, nbytes, n_keys):
        t0 = time.time()
        p0 = time.perf_counter()
        with self._annotate("bench.store." + name):
            out = fn()
        self.spans.append(Span(
            name, t0, time.perf_counter() - p0, nbytes, n_keys,
            out if isinstance(out, (int, bool)) else None))
        return out

    def cached_prefix_len(self, keys):
        return self._span(
            "probe", lambda: self._inner.cached_prefix_len(keys), 0,
            len(keys))

    def get_kv_pages(self, keys, page_shape, dtype, device=None):
        import numpy as np

        nbytes = (len(keys) * int(np.prod(page_shape))
                  * np.dtype(dtype).itemsize)
        return self._span(
            "get_kv_pages",
            lambda: self._inner.get_kv_pages(keys, page_shape, dtype,
                                             device=device),
            nbytes, len(keys))

    def put_kv_pages(self, keys, pages, sync=False):
        if self._tap_armed:
            self._tap_armed = False
            self.tapped = (list(keys), pages)
        return self._span(
            "put_kv_pages",
            lambda: self._inner.put_kv_pages(keys, pages, sync=sync),
            _nbytes(pages), len(keys))

    def prefetch(self, keys):
        return self._span(
            "prefetch", lambda: self._inner.prefetch(keys), 0, len(keys))

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _SpanConn:
    def __init__(self, inner, owner):
        self._inner = inner
        self._owner = owner

    def sync(self, *a, **kw):
        return self._owner._span(
            "sync", lambda: self._inner.sync(*a, **kw), 0, 0)

    def __getattr__(self, name):
        return getattr(self._inner, name)
