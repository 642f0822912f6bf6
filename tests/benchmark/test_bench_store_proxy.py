"""The span proxy delegates every call the engine makes into the store,
and the step wrapper records what a step moved."""

import contextlib

import numpy as np
import pytest

from benchmark.lib import serve
from benchmark.lib.store import SpanStore


class FakeConn:
    def __init__(self):
        self.calls = []
        self.shm_connected = True

    def sync(self):
        self.calls.append("sync")
        return 7

    def stats(self):
        return {"ops": 1}


class FakeStore:
    def __init__(self):
        self.conn = FakeConn()
        self.calls = []

    def cached_prefix_len(self, keys):
        self.calls.append(("cached_prefix_len", len(keys)))
        return 3

    def get_kv_pages(self, keys, page_shape, dtype, device=None):
        self.calls.append(("get_kv_pages", len(keys), device))
        return np.zeros((len(keys), *page_shape), dtype)

    def put_kv_pages(self, keys, pages, sync=False):
        self.calls.append(("put_kv_pages", len(keys), sync))
        return list(range(len(keys)))

    def prefetch(self, keys):
        self.calls.append(("prefetch", len(keys)))
        return True

    def get_kv_pages_quantized(self, *a, **k):
        self.calls.append(("get_kv_pages_quantized",))
        return "q"


@contextlib.contextmanager
def no_annotation(name):
    no_annotation.names.append(name)
    yield


no_annotation.names = []


@pytest.fixture()
def proxy():
    no_annotation.names = []
    inner = FakeStore()
    return SpanStore(inner, annotate=no_annotation), inner


def test_probe_delegates_and_records_keys_and_hit(proxy):
    p, inner = proxy
    assert p.cached_prefix_len(["a", "b", "c", "d"]) == 3
    assert inner.calls == [("cached_prefix_len", 4)]
    s = p.spans[0]
    assert (s.name, s.nbytes, s.n_keys, s.result) == ("probe", 0, 4, 3)
    assert s.t0 > 1e9 and s.seconds >= 0


def test_get_counts_bytes_and_passes_the_device(proxy):
    p, inner = proxy
    out = p.get_kv_pages(["k"] * 6, (16, 8, 128), np.dtype("uint16"),
                         device="chip1")
    assert out.shape == (6, 16, 8, 128)
    assert inner.calls == [("get_kv_pages", 6, "chip1")]
    assert p.spans[0].name == "get_kv_pages"
    assert p.spans[0].nbytes == 6 * 16 * 8 * 128 * 2
    assert p.spans[0].n_keys == 6


def test_put_counts_bytes_and_passes_sync(proxy):
    p, inner = proxy
    pages = np.zeros((5, 16, 8, 128), np.uint16)
    assert p.put_kv_pages(["k"] * 5, pages, sync=True) == [0, 1, 2, 3, 4]
    assert inner.calls == [("put_kv_pages", 5, True)]
    assert p.spans[0].name == "put_kv_pages"
    assert p.spans[0].nbytes == pages.nbytes


def test_conn_sync_is_spanned_and_other_conn_calls_fall_through(proxy):
    p, inner = proxy
    assert p.conn.sync() == 7 and inner.conn.calls == ["sync"]
    assert p.spans[0].name == "sync"
    assert p.conn.stats() == {"ops": 1} and p.conn.shm_connected
    assert len(p.spans) == 1


def test_prefetch_delegates(proxy):
    p, inner = proxy
    assert p.prefetch(["a", "b"]) is True
    assert inner.calls == [("prefetch", 2)]
    assert p.spans[0].name == "prefetch"


def test_everything_else_falls_through_unspanned(proxy):
    p, inner = proxy
    assert p.get_kv_pages_quantized(1, 2) == "q"
    assert inner.calls == [("get_kv_pages_quantized",)] and not p.spans
    with pytest.raises(AttributeError):
        p.no_such_method


def test_every_spanned_call_carries_a_trace_annotation(proxy):
    p, _ = proxy
    p.cached_prefix_len(["a"])
    p.get_kv_pages(["a"], (1,), np.dtype("uint8"))
    p.put_kv_pages(["a"], np.zeros((1, 1), np.uint8))
    p.prefetch(["a"])
    p.conn.sync()
    assert no_annotation.names == [
        "bench.store.probe", "bench.store.get_kv_pages",
        "bench.store.put_kv_pages", "bench.store.prefetch",
        "bench.store.sync"]


def test_tap_keeps_the_first_put_after_arming_only(proxy):
    p, _ = proxy
    p.put_kv_pages(["x"], np.zeros((1, 2), np.uint8))
    assert p.tapped is None
    p.arm_tap()
    first = np.ones((2, 2), np.uint8)
    p.put_kv_pages(["a", "b"], first)
    p.put_kv_pages(["c"], np.zeros((1, 2), np.uint8))
    assert p.tapped[0] == ["a", "b"] and p.tapped[1] is first


def test_the_engine_takes_the_proxy_for_a_store():
    """The calls ServingEngine makes at construction and on its hot
    path resolve on the proxy (names as serving.py spells them)."""
    import inspect

    from infinistore_tpu import serving

    src = inspect.getsource(serving.ServingEngine)
    used = {"cached_prefix_len", "get_kv_pages", "put_kv_pages",
            "prefetch", "get_kv_pages_quantized",
            "put_kv_pages_quantized"}
    for name in used:
        assert f"store.{name}" in src or f'"{name}"' in src
    assert "self.store.conn.sync()" in src


class FakeSlot:
    def __init__(self, n):
        self.seq_len = n


class FakeEngine:
    def __init__(self):
        self.stats = {k: 0 for k in serve.StepSpans.KEYS}
        self.slots = [FakeSlot(100), None, FakeSlot(50)]
        self.stepped = 0

    def step(self):
        self.stepped += 1
        self.stats["decoded_tokens"] += 2
        self.stats["decode_steps"] += 1
        self.stats["prefill_tokens"] += 128
        return 2


def test_step_wrapper_records_counters_and_live_tokens():
    eng = FakeEngine()
    spans = serve.StepSpans(eng)
    assert eng.step() == 2 and eng.step() == 2 and eng.stepped == 2
    s = spans.records[1]
    assert s.active == 2 and s.live_tokens == 150
    assert s.seconds >= 0 and s.t0 > 1e9
    assert s.moved == {
        "prefill_tokens": 128, "prefix_hit_pages": 0, "decoded_tokens": 2,
        "decode_steps": 1, "offloaded_pages": 0}
