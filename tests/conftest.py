"""Test fixtures.

Unlike the reference suite — which requires real CUDA GPUs and a real RDMA
NIC and spawns the server as a subprocess with hardcoded device names
(/root/reference/infinistore/test_infinistore.py:16-41) — every test here
runs hardware-free: the server runs in-process on an ephemeral port, the
SHM and STREAM paths are both exercised over loopback, and JAX is forced
onto a virtual 8-device CPU mesh so multi-chip sharding logic is testable
without TPUs (SURVEY.md §4 implication).
"""

import os
import threading

# Must happen before jax import anywhere in the test session.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Force CPU whatever the ambient platform: tests run hardware-free on the
# 8-device virtual mesh, and a chip belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"

# Content-addressed dedup (PR 16) is ON by default in production, but the
# pre-dedup suites generate pool pressure with incidentally identical page
# contents (np.zeros fills, np.full mod-251 patterns): with dedup on those
# pages share one block, the pool never fills, and every reclaim/spill/
# eviction assertion (written when N pages always cost N blocks) goes
# vacuous. Default it off for the legacy suites so they keep exercising
# the reclaim machinery they were written for; tests/test_dedup.py and
# the bench dedup leg arm ISTPU_DEDUP=1 explicitly (and cover eviction/
# spill/chaos WITH sharing). An ambient ISTPU_DEDUP is respected.
os.environ.setdefault("ISTPU_DEDUP", "0")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from infinistore_tpu import (  # noqa: E402
    ClientConfig,
    InfiniStoreServer,
    InfinityConnection,
    ServerConfig,
    TYPE_SHM,
    TYPE_STREAM,
)


@pytest.fixture(scope="module")
def server():
    srv = InfiniStoreServer(
        ServerConfig(
            service_port=0,  # ephemeral
            prealloc_size=0.125,  # 128 MB
            minimal_allocate_size=16,
            auto_increase=True,
            extend_size=0.0625,
        )
    )
    srv.start()
    yield srv
    srv.stop()


def _connect(server, ctype):
    conn = InfinityConnection(
        ClientConfig(
            host_addr="127.0.0.1",
            service_port=server.service_port,
            connection_type=ctype,
        )
    )
    conn.connect()
    return conn


@pytest.fixture(params=[TYPE_SHM, TYPE_STREAM])
def conn(server, request):
    """A fresh connection per test, parametrized over both data paths
    (the reference parametrizes local/RDMA the same way,
    test_infinistore.py:61-108)."""
    c = _connect(server, request.param)
    yield c
    c.close()


@pytest.fixture
def shm_conn(server):
    c = _connect(server, TYPE_SHM)
    yield c
    c.close()


@pytest.fixture
def gated_transfers(monkeypatch):
    """Every engine's upload thread held before its first wait for a
    device-to-host transfer (`serving.to_host`) until the event this
    gives is set: what an offload gathered is still on its way while
    the test goes on."""
    from infinistore_tpu import serving

    gate, real = threading.Event(), serving.to_host

    def held(arr):
        assert gate.wait(60)
        return real(arr)
    monkeypatch.setattr(serving, "to_host", held)
    yield gate
    gate.set()


@pytest.fixture
def gated_sync(shm_conn, monkeypatch):
    """`shm_conn.sync` held until the event this gives is set: an
    offload's acknowledgement does not come."""
    gate, real = threading.Event(), shm_conn.sync

    def held():
        assert gate.wait(60)
        return real()
    monkeypatch.setattr(shm_conn, "sync", held)
    yield gate
    gate.set()


@pytest.fixture(autouse=True)
def staged_by_the_next_step(request, monkeypatch):
    """Since PR 56 a hit's probe and store read run on the engine's
    restore thread from `submit` on, and a head whose pages are not
    there yet stays queued while sequences decode: at WHICH step a
    request is admitted depends on two threads' pace. The suites
    written before that script admissions by the step (a request
    submitted behind step N is admitted by N + 1: preemption counts,
    span trees, the gaps a waiting slot sees). For them `submit`
    returns once the staging is done, so the next step admits as the
    synchronous probe did, through the same staged path (the pages are
    the restore thread's). tests/test_restore_ahead.py holds the two
    threads' interplay itself and is left alone."""
    import sys

    serving = sys.modules.get("infinistore_tpu.serving")
    module = getattr(request, "module", None)
    if serving is None or module is None \
            or module.__name__.endswith("test_restore_ahead"):
        return
    stage = serving.ServingEngine._stage

    def staged(self, work):
        stage(self, work)
        assert work.staged.done.wait(120)
    monkeypatch.setattr(serving.ServingEngine, "_stage", staged)


@pytest.fixture
def stream_conn(server):
    c = _connect(server, TYPE_STREAM)
    yield c
    c.close()


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture(autouse=True)
def benchmark_tests_pinned_before_pr26(request, monkeypatch):
    """Two tests under tests/benchmark/ pin BENCHMARK.json as it was
    before the cell mistral7b-replicas4-sessions (PR 26), and a
    `model_config` PR may add benchmark files but edit none; the
    contract also wants new entries at the END of their lists. So, for
    those two tests only (the next `benchmark` issue edits them and
    deletes this fixture):

    - test_bench_observations.py's table test runs every metric of the
      manifest against a number worked by hand from a table inside it:
      the three new metrics get theirs from
      tests/benchmark/replicas4_by_hand.py;
    - test_bench_program_spans.py asserts that PR 24's five metrics are
      the LAST per-layer entries: it is shown the list up to them.
    """
    node = request.node
    name = getattr(node, "originalname", None)
    module = getattr(request, "module", None)
    if module is None:
        return
    if _restore_staged_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if _eva_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if _phi_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if _state_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if _gap_by_cause_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if _idle_by_span_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if _decode_ahead_in_the_pinned_tests(node, name, module, monkeypatch):
        return
    if module.__name__.endswith("test_bench_observations"):
        if _granite4h_in_the_pinned_tests(node, name, module, monkeypatch):
            return
        if _smallthinker_in_the_pinned_tests(node, name, module,
                                             monkeypatch):
            return
        if _xing_in_the_pinned_tests(node, name, module, monkeypatch):
            return
        if _command_a_in_the_pinned_tests(node, name, module, monkeypatch):
            return
        if _glm_in_the_pinned_tests(node, name, module, monkeypatch):
            return
        if _select_in_the_pinned_tests(node, name, module, monkeypatch):
            return
        if _keye_in_the_pinned_tests(node, name, module, monkeypatch):
            return
    if module.__name__.endswith("test_bench_glm") and name == \
            "test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest":
        # ... asserts that PR 46's five metrics are the LAST: it is
        # shown the manifest without the one PR 47 appended and what
        # PR 49 appended behind it
        load = module.manifest.load

        def load_as_of_pr46(*a, **kw):
            bench = _as_before_pr49(load(*a, **kw))
            bench["per_layer"] = [m for m in bench["per_layer"]
                                  if m["name"] != "select_active_share"]
            return bench

        monkeypatch.setattr(module.manifest, "load", load_as_of_pr46)
        return
    if module.__name__.endswith("test_bench_command_a") and name == \
            "test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest":
        # ... asserts that PR 42's cell, configuration and two metrics
        # are the LAST: it is shown the manifest without what PR 46
        # appended
        load = module.manifest.load
        monkeypatch.setattr(module.manifest, "load",
                            lambda *a, **kw: _as_before_pr46(load(*a, **kw)))
        return
    if module.__name__.endswith("test_bench_manifest") \
            and name == "test_reduced_never_names_a_width":
        # It holds every configuration to mistral7b's widths (4096,
        # 14336, 32 / 8), which the three configurations before PR 31
        # share; granite4h-micro's file is held to its catalog row by
        # tests/benchmark/test_bench_granite4h.py, and reduces nothing;
        # smallthinker21b's by tests/benchmark/test_bench_smallthinker.py
        # (its `reduced` is the depth and the two per-layer lists);
        # xing4-29b's by tests/benchmark/test_bench_xing.py (depth,
        # leading dense layers, the prediction module);
        # command-a-plus's by tests/benchmark/test_bench_command_a.py
        # (depth, its per-layer list, the experts HELD, the
        # vocabulary's slice: the chip's share, no width); glm-5.2's
        # by tests/benchmark/test_bench_glm.py (depth, the leading
        # dense layers, its two per-layer lists, the experts HELD, the
        # vocabulary's slice, the prediction module: no width);
        # keye-vl2-30b-a3b's by tests/benchmark/test_bench_keye.py
        # (the depth alone); phi4-mini-flash's by
        # tests/benchmark/test_bench_phi4flash.py (it reduces nothing);
        # evabyte's by tests/benchmark/test_bench_evabyte.py (the depth
        # alone).
        bench = dict(module.BENCH)
        bench["configs"] = [c for c in bench["configs"]
                            if c["name"] not in ("granite4h-micro",
                                                 "smallthinker21b",
                                                 "xing4-29b",
                                                 _COMMAND_A, _GLM, _KEYE,
                                                 _PHI, _EVA)]
        monkeypatch.setattr(module, "BENCH", bench)
        return
    if module.__name__.endswith("test_bench_observations") \
            and name == "test_reader_gives_the_number_worked_by_hand":
        # beside the test module, whose directory pytest put on the path
        import replicas4_by_hand as by_hand

        if node.callspec.params.get("name") not in by_hand.BY_HAND:
            return
        from infinistore_tpu.utils import profiling

        table, window = module.expected, module.full_window

        def full_window():
            obs = window()
            obs.counters.update(by_hand.COUNTERS)
            return obs

        monkeypatch.setattr(module, "full_window", full_window)
        monkeypatch.setattr(module, "expected",
                            lambda obs: {**table(obs), **by_hand.BY_HAND})
        monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    elif module.__name__.endswith("test_bench_program_spans") \
            and name == "test_the_new_metrics_are_in_the_manifest_and_it_is_sound":
        load = module.manifest.load

        def load_as_of_pr24(*a, **kw):
            bench = load(*a, **kw)
            names = [m["name"] for m in bench["per_layer"]]
            last = names.index("decode_host_p50_ms")
            bench["per_layer"] = bench["per_layer"][:last + 1]
            # ... and the cells they listed then (PR 31 and PR 35
            # appended their cells to admit_hit_p50_ms's)
            for m in bench["per_layer"]:
                for later in ("granite4h-micro-sessions4k",
                              "smallthinker21b-sessions12k",
                              _XING_CELL, _COMMAND_A_CELL, _GLM_CELL,
                              _KEYE_CELL):
                    if later in m.get("workloads", ()):
                        m["workloads"].remove(later)
            return bench

        monkeypatch.setattr(module.manifest, "load", load_as_of_pr24)


def _decode_ahead_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 44 (`perf_opt`: may add a reader, edit no benchmark file)
    appended one per-layer metric that every cell reports,
    `decode_ahead_share`: test_bench_observations.py's table test gets
    its window's counters and its number by hand from the metric's own
    test file, tests/benchmark/test_bench_decode_ahead.py. Returns True
    where it dealt with the test."""
    if not module.__name__.endswith("test_bench_observations") \
            or name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import test_bench_decode_ahead as by_hand

    if node.callspec.params.get("name") not in by_hand.BY_HAND:
        return False
    _counters_and_numbers_by_hand(module, monkeypatch, by_hand)
    return True


def _counters_and_numbers_by_hand(module, monkeypatch, by_hand):
    """test_bench_observations.py's synthetic window with
    `by_hand.COUNTERS` among its counters, and its table of expected
    numbers with `by_hand.BY_HAND`."""
    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.counters.update(by_hand.COUNTERS)
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(obs), **by_hand.BY_HAND})


# The tests that hold their PR's entries to be the LAST of
# BENCHMARK.json's lists (the fourth is PR 51's own).
_HELD_TO_BE_LAST = (
    # (PR 53's own, test_bench_phi4flash.py's "... its_two_metrics ...",
    # is not among them: it also holds the older readers' lists, which
    # the hooks below would take away)
    "test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest",
    "test_the_cell_its_configuration_and_its_metric_are_in_the_manifest",
    "test_the_new_metrics_are_in_the_manifest_and_it_is_sound",
    "test_the_nine_are_the_last_per_layer_entries_and_the_manifest_is_sound")


def _state_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 52 (`perf_opt`: may add benchmark files, edit none) appended
    the per-layer metric state_active_share. Returns True where it
    dealt with the test.

    - the tests that hold an earlier PR's entries to be the LAST of
      BENCHMARK.json's per-layer list (PR 51's own among them) are
      shown the manifest without it. That is done FIRST and returns
      False: the hooks below then take away what lies between their PR
      and this one;
    - test_bench_observations.py's table test gets the metric's
      hand-worked number and its ring from
      tests/benchmark/state_by_hand.py."""
    if name in _HELD_TO_BE_LAST and "test_bench_" in module.__name__:
        load = module.manifest.load

        def load_as_of_pr51(*a, **kw):
            bench = load(*a, **kw)
            bench["per_layer"] = [m for m in bench["per_layer"]
                                  if m["name"] != "state_active_share"]
            return bench

        monkeypatch.setattr(module.manifest, "load", load_as_of_pr51)
        return False
    if not module.__name__.endswith("test_bench_observations") \
            or name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import state_by_hand as by_hand

    if node.callspec.params.get("name") not in by_hand.BY_HAND:
        return False
    from infinistore_tpu.utils import profiling

    table = module.expected
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(obs), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    return True


def _gap_by_cause_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 51 (`tracing`: may add benchmark files, edit none) appended
    nine per-layer metrics, the mean gap between tokens by its cause;
    their numbers by hand are in their own test file,
    tests/benchmark/test_bench_gap_by_cause.py. Returns True where it
    dealt with the test.

    - test_bench_observations.py's table test gets the nine's window
      counters, ring and hand-worked numbers from that file;
    - the tests that hold an earlier PR's entries to be the LAST of
      BENCHMARK.json's lists (one a configuration's test file, and
      test_bench_program_spans.py's) are shown the manifest without
      the nine. That is done FIRST and returns False: the hooks below
      then take away what lies between their PR and this one."""
    if name in _HELD_TO_BE_LAST and "test_bench_" in module.__name__ \
            and not module.__name__.endswith("test_bench_gap_by_cause"):
        import test_bench_gap_by_cause as by_hand

        load = module.manifest.load

        def load_as_of_pr50(*a, **kw):
            bench = load(*a, **kw)
            bench["per_layer"] = [m for m in bench["per_layer"]
                                  if m["name"] not in by_hand.BY_HAND]
            return bench

        monkeypatch.setattr(module.manifest, "load", load_as_of_pr50)
        return False
    if not module.__name__.endswith("test_bench_observations") \
            or name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import test_bench_gap_by_cause as by_hand

    if node.callspec.params.get("name") not in by_hand.BY_HAND:
        return False
    from infinistore_tpu.utils import profiling

    _counters_and_numbers_by_hand(module, monkeypatch, by_hand)
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    return True


def _restore_staged_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 56 (`perf_opt`: may add benchmark files, edit none) appended
    the per-layer metric restore_staged_share; as
    `_state_in_the_pinned_tests` for PR 52's. Returns True where it
    dealt with the test.

    - the tests that hold an earlier PR's entries to be the LAST of
      BENCHMARK.json's per-layer list (PR 55's own now among them, and
      those `_eva_in_the_pinned_tests` names) are shown the manifest
      without it. That is done FIRST and returns False: the hooks below
      then take away what lies between their PR and this one;
    - test_bench_observations.py's table test gets the metric's
      hand-worked number and its ring from
      tests/benchmark/restore_by_hand.py."""
    if "test_bench_" in module.__name__ and hasattr(module, "manifest") \
            and (name in _HELD_TO_BE_LAST or name in (
                "test_the_cell_its_configuration_and_its_four_metrics_are_in_the_manifest",
                "test_the_cell_its_configuration_and_its_two_metrics_are_in_the_manifest",
                "test_the_metric_is_in_the_manifest_on_its_cells")):
        load = module.manifest.load

        def load_as_of_pr55(*a, **kw):
            bench = load(*a, **kw)
            bench["per_layer"] = [m for m in bench["per_layer"]
                                  if m["name"] != "restore_staged_share"]
            return bench

        monkeypatch.setattr(module.manifest, "load", load_as_of_pr55)
        return False
    if not module.__name__.endswith("test_bench_observations") \
            or name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import restore_by_hand as by_hand

    if node.callspec.params.get("name") not in by_hand.BY_HAND:
        return False
    from infinistore_tpu.utils import profiling

    table = module.expected
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(obs), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    return True


_EVA, _EVA_CELL = "evabyte", "evabyte-docs24k-bytes"


def _as_before_pr55(bench):
    """The manifest without what PR 55 appended: the configuration
    evabyte, its cell, its four per-layer metrics and the cell's name
    on the older metrics' lists."""
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != _EVA_CELL]
    bench["configs"] = [c for c in bench["configs"] if c["name"] != _EVA]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m.get("workloads") != [_EVA_CELL]]
    for m in bench["per_layer"]:
        if _EVA_CELL in m.get("workloads", ()):
            m["workloads"].remove(_EVA_CELL)
    return bench


def _eva_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 55 (`model_config`: may add benchmark files, edit none) added
    the configuration evabyte and four per-layer metrics; as
    `_phi_in_the_pinned_tests` for PR 53's. Returns True where it
    dealt with the test.

    - the tests that hold an earlier PR's entries to be the LAST of
      BENCHMARK.json's lists (PR 53's own now among them) are shown
      the manifest without what this PR appended. For PR 53's that is
      all (it holds the older readers' lists too: True); for the
      others it is done FIRST and returns False: the hooks below then
      take away what lies between their PR and this one;
    - test_bench_manifest.py's case "too many four-chip cells" is shown
      the 11 cells it was written against;
    - test_bench_observations.py's table test gets the four new
      metrics' hand-worked numbers from
      tests/benchmark/evabyte_by_hand.py, and the configuration's
      cases of "resolves to today's defaults" are skipped (it names a
      costs module, tolerances and programs of its own, which
      tests/benchmark/test_bench_evabyte.py holds)."""
    import pytest

    phi_own = module.__name__.endswith("test_bench_phi4flash") and name \
        == "test_the_cell_its_configuration_and_its_two_metrics_are_in_the_manifest"
    pinned = phi_own or name in _HELD_TO_BE_LAST or (
        module.__name__.endswith("test_bench_gap_by_cause")
        and name == "test_the_metric_is_in_the_manifest_on_its_cells")
    if pinned and "test_bench_" in module.__name__ \
            and not module.__name__.endswith("test_bench_evabyte"):
        load = module.manifest.load
        monkeypatch.setattr(module.manifest, "load",
                            lambda *a, **kw: _as_before_pr55(load(*a, **kw)))
        return phi_own
    params = getattr(getattr(node, "callspec", None), "params", {})
    if module.__name__.endswith("test_bench_manifest") \
            and name == "test_the_check_catches" \
            and params.get("what") == "too many four-chip cells":
        # It makes two more cells ask for four chips and expects a
        # complaint: of 11 cells a quarter is 2, of this PR's 12 it is
        # 3, which the three would meet. It is shown the 11.
        import copy

        monkeypatch.setattr(module, "BENCH",
                            _as_before_pr55(copy.deepcopy(module.BENCH)))
        return True
    if not module.__name__.endswith("test_bench_observations"):
        return False
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == _EVA:
            pytest.skip("evabyte brings its own costs and tolerances: "
                        "test_bench_evabyte.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import evabyte_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops, fold_roofline_share
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.counters.update(by_hand.COUNTERS)
        obs.conf = serve.load_config("benchmark/configs/evabyte.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(
        _scoped_ops, "seconds",
        lambda obs, kind, scopes: by_hand.SCOPED[kind, tuple(scopes)])
    monkeypatch.setattr(fold_roofline_share, "fold_seconds",
                        lambda obs: by_hand.FOLDS)
    return True


_PHI, _PHI_CELL = "phi4-mini-flash", "phi4-mini-flash-traces12k"


def _as_before_pr53(bench):
    """The manifest without what PR 53 appended: the configuration
    phi4-mini-flash, its cell, its two per-layer metrics and the cell's
    name on the older metrics' lists."""
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != _PHI_CELL]
    bench["configs"] = [c for c in bench["configs"] if c["name"] != _PHI]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m.get("workloads") != [_PHI_CELL]]
    for m in bench["per_layer"]:
        if _PHI_CELL in m.get("workloads", ()):
            m["workloads"].remove(_PHI_CELL)
    return bench


def _phi_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 53 (`model_config`: may add benchmark files, edit none) added
    the configuration phi4-mini-flash and two per-layer metrics; as
    `_keye_in_the_pinned_tests` for PR 49's. Returns True where it
    dealt with the test.

    - the tests that hold an earlier PR's entries to be the LAST of
      BENCHMARK.json's lists are shown the manifest without what this
      PR appended. That is done FIRST and returns False: the hooks
      below then take away what lies between their PR and this one;
    - test_bench_observations.py's table test gets the two new
      metrics' hand-worked numbers from tests/benchmark/phi_by_hand.py
      (one reads the device time under a scope, one the window's
      counters), and the configuration's cases of "resolves to today's
      defaults" are skipped (it names a costs module, tolerances and
      programs of its own, which tests/benchmark/
      test_bench_phi4flash.py holds)."""
    import pytest

    pinned = name in _HELD_TO_BE_LAST or (
        # ... and PR 51's test of the cells each gap metric lists
        module.__name__.endswith("test_bench_gap_by_cause")
        and name == "test_the_metric_is_in_the_manifest_on_its_cells")
    if pinned and "test_bench_" in module.__name__ \
            and not module.__name__.endswith("test_bench_phi4flash"):
        load = module.manifest.load
        monkeypatch.setattr(module.manifest, "load",
                            lambda *a, **kw: _as_before_pr53(load(*a, **kw)))
        return False
    if not module.__name__.endswith("test_bench_observations"):
        return False
    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == _PHI:
            pytest.skip("phi4-mini-flash brings its own costs and "
                        "tolerances: test_bench_phi4flash.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import phi_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.counters.update(by_hand.COUNTERS)
        obs.conf = serve.load_config(
            "benchmark/configs/phi4-mini-flash.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(
        _scoped_ops, "seconds",
        lambda obs, kind, scopes: by_hand.SCOPED[kind, tuple(scopes)])
    return True


_XING_CELL = "xing4-29b-docs32k"
_COMMAND_A, _COMMAND_A_CELL = "command-a-plus", "command-a-plus-mixed12k"
_GLM, _GLM_CELL = "glm-5.2", "glm-5.2-docs32k-answers"
_KEYE, _KEYE_CELL = "keye-vl2-30b-a3b", "keye-vl2-30b-a3b-docs32k-answers"


def _as_before_pr49(bench):
    """The manifest without what PR 49 appended: the configuration
    keye-vl2-30b-a3b, its cell, its per-layer metric and the cell's
    name on the older metrics' lists."""
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != _KEYE_CELL]
    bench["configs"] = [c for c in bench["configs"] if c["name"] != _KEYE]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m.get("workloads") != [_KEYE_CELL]]
    for m in bench["per_layer"]:
        if _KEYE_CELL in m.get("workloads", ()):
            m["workloads"].remove(_KEYE_CELL)
    return bench


def _keye_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 49 (`model_config`: may add benchmark files, edit none) added
    the configuration keye-vl2-30b-a3b and one per-layer metric; as
    `_glm_in_the_pinned_tests` for PR 46's. Returns True where it dealt
    with the test: the table test gets the new metric's hand-worked
    number from tests/benchmark/keye_by_hand.py, and the
    configuration's cases of "resolves to today's defaults" are skipped
    (it names a costs module, tolerances and programs of its own, which
    tests/benchmark/test_bench_keye.py holds)."""
    import pytest

    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == _KEYE:
            pytest.skip("keye-vl2-30b-a3b brings its own costs and "
                        "tolerances: test_bench_keye.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import keye_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.conf = serve.load_config(
            "benchmark/configs/keye-vl2-30b-a3b.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(
        _scoped_ops, "seconds",
        lambda obs, kind, scopes: by_hand.SCOPED[kind, tuple(scopes)])
    return True


def _as_before_pr46(bench):
    """The manifest without what PR 46 appended: the configuration
    glm-5.2, its cell, its five per-layer metrics and the cell's name
    on the older metrics' lists (nor what PR 49 appended behind
    them)."""
    _as_before_pr49(bench)
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != _GLM_CELL]
    bench["configs"] = [c for c in bench["configs"] if c["name"] != _GLM]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m.get("workloads") != [_GLM_CELL]]
    for m in bench["per_layer"]:
        if _GLM_CELL in m.get("workloads", ()):
            m["workloads"].remove(_GLM_CELL)
    return bench


def _glm_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 46 (`model_config`: may add benchmark files, edit none) added
    the configuration glm-5.2 and five per-layer metrics; as
    `_xing_in_the_pinned_tests` for PR 40's. Returns True where it
    dealt with the test: the table test gets the five new metrics'
    hand-worked numbers from tests/benchmark/glm_by_hand.py, and the
    configuration's cases of "resolves to today's defaults" are skipped
    (it names a costs module, tolerances and programs of its own, which
    tests/benchmark/test_bench_glm.py holds)."""
    import pytest

    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == _GLM:
            pytest.skip("glm-5.2 brings its own costs and tolerances: "
                        "test_bench_glm.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import glm_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.conf = serve.load_config("benchmark/configs/glm-5.2.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(
        _scoped_ops, "seconds",
        lambda obs, kind, scopes: by_hand.SCOPED[kind, tuple(scopes)])
    return True


def _as_before_pr42(bench):
    """The manifest without what PR 42 appended: the configuration
    command-a-plus, its cell, its two per-layer metrics and the cell's
    name on the older metrics' lists (nor what PR 46 appended behind
    them)."""
    _as_before_pr46(bench)
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] != _COMMAND_A_CELL]
    bench["configs"] = [c for c in bench["configs"]
                        if c["name"] != _COMMAND_A]
    # ... nor the metric PR 44 appended behind them (every cell's)
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m.get("workloads") != [_COMMAND_A_CELL]
                          and m["name"] != "decode_ahead_share"]
    for m in bench["per_layer"]:
        if _COMMAND_A_CELL in m.get("workloads", ()):
            m["workloads"].remove(_COMMAND_A_CELL)
    return bench


def _select_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 47 (`perf_opt`: may add benchmark files, edit none) added the
    per-layer metric select_active_share; as `_glm_in_the_pinned_tests`
    for PR 46's. Returns True where it dealt with the test: the table
    test gets the metric's hand-worked number and its ring from
    tests/benchmark/select_by_hand.py."""
    params = getattr(getattr(node, "callspec", None), "params", {})
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import select_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from infinistore_tpu.utils import profiling

    table = module.expected
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(obs), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    return True


def _command_a_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 42 (`model_config`: may add benchmark files, edit none) added
    the configuration command-a-plus and two per-layer metrics; as
    `_xing_in_the_pinned_tests` for PR 40's. Returns True where it
    dealt with the test: the table test gets the two new metrics'
    hand-worked numbers from tests/benchmark/command_a_by_hand.py (one
    reads the window's counters, one the program's ring), and the
    configuration's cases of "resolves to today's defaults" are skipped
    (it names a costs module and tolerances of its own, which
    tests/benchmark/test_bench_command_a.py holds)."""
    import pytest

    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == _COMMAND_A:
            pytest.skip("command-a-plus brings its own costs and "
                        "tolerances: test_bench_command_a.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import command_a_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        from benchmark.lib import serve

        obs = window()
        obs.counters.update(by_hand.COUNTERS)
        # the skew is read against the file's even share (16 of 128)
        obs.conf = serve.load_config(
            "benchmark/configs/command-a-plus.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    return True


def _xing_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 40 (`model_config`: may add benchmark files, edit none) added
    the configuration xing4-29b and four per-layer metrics; as
    `_smallthinker_in_the_pinned_tests` for PR 35's. Returns True where
    it dealt with the test: the table test gets the four new metrics'
    hand-worked numbers from tests/benchmark/xing_by_hand.py, and the
    configuration's cases of "resolves to today's defaults" are skipped
    (it names a costs module, tolerances and programs of its own, which
    tests/benchmark/test_bench_xing.py holds)."""
    import pytest

    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == "xing4-29b":
            pytest.skip("xing4-29b brings its own costs and tolerances: "
                        "test_bench_xing.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import xing_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.conf = serve.load_config("benchmark/configs/xing4-29b.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(
        _scoped_ops, "seconds",
        lambda obs, kind, scopes: by_hand.SCOPED[kind, tuple(scopes)])
    return True


def _idle_by_span_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 38 (`tracing`: may add benchmark files, edit none) appended
    six per-layer metrics that every cell reports. Returns True where
    it dealt with the test.

    - test_bench_observations.py's table test gets their hand-worked
      numbers from tests/benchmark/idle_by_hand.py: the small recorded
      trace stands in for the run's xplane and its ring for the
      program's, on the same synthetic window;
    - test_bench_smallthinker.py asserts that PR 35's five metrics are
      the LAST per-layer entries, and test_bench_replicas4.py that the
      four-replica cell reports its three and the list-free ones and
      nothing else: both are shown the list up to PR 35's."""
    if module.__name__.endswith(("test_bench_smallthinker",
                                 "test_bench_replicas4")) and name == \
            "test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest":
        load = module.manifest.load

        def load_as_of_pr35(*a, **kw):
            bench = load(*a, **kw)
            names = [m["name"] for m in bench["per_layer"]]
            bench["per_layer"] = bench["per_layer"][
                :names.index("idle_no_work_share")]
            # ... and without what PRs 40 and 42 appended behind PR
            # 35's cell, configuration and lists
            _as_before_pr42(bench)
            bench["workloads"] = [w for w in bench["workloads"]
                                  if w["name"] != _XING_CELL]
            bench["configs"] = [c for c in bench["configs"]
                                if c["name"] != "xing4-29b"]
            for m in bench["per_layer"]:
                if _XING_CELL in m.get("workloads", ()):
                    m["workloads"].remove(_XING_CELL)
            return bench

        monkeypatch.setattr(module.manifest, "load", load_as_of_pr35)
        return True
    if module.__name__.endswith("test_bench_xing") and name == \
            "test_the_cell_its_configuration_and_its_metrics_are_in_the_manifest":
        # ... and test_bench_xing.py that PR 40's cell, configuration
        # and four metrics are the LAST: it is shown the manifest
        # without what PR 42 appended
        load = module.manifest.load
        monkeypatch.setattr(module.manifest, "load",
                            lambda *a, **kw: _as_before_pr42(load(*a, **kw)))
        return True
    if not module.__name__.endswith("test_bench_observations") \
            or name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import idle_by_hand as by_hand

    if node.callspec.params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.metrics import _idle_by_span
    from infinistore_tpu.utils import profiling

    table = module.expected
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(obs), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_idle_by_span, "plain_of_run", by_hand.plain)
    return True


def _granite4h_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 31 (`model_config`: may add benchmark files, edit none) added
    the configuration granite4h-micro and four per-layer metrics, and
    test_bench_observations.py runs two tests over everything in
    BENCHMARK.json. Returns True where it dealt with the test.

    - the table test gets the four new metrics' hand-worked numbers
      from tests/benchmark/granite4h_by_hand.py, on the same synthetic
      window with the new configuration's file as `obs.conf`;
    - "an accepted configuration resolves to today's defaults" pins the
      configurations that name no costs, programs or tolerances of
      their own; this one brings all three (PERF.md, section 4), so its
      five cases are skipped and tests/benchmark/test_bench_granite4h.py
      holds what it names instead."""
    import pytest

    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == "granite4h-micro":
            pytest.skip("granite4h-micro brings its own costs, programs "
                        "and tolerances: test_bench_granite4h.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import granite4h_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.conf = serve.load_config("benchmark/configs/granite4h-micro.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(_scoped_ops, "seconds",
                        lambda obs, kind, scopes: by_hand.SCOPED[kind])
    return True


def _smallthinker_in_the_pinned_tests(node, name, module, monkeypatch):
    """PR 35 (`model_config`: may add benchmark files, edit none) added
    the configuration smallthinker21b and five per-layer metrics; as
    `_granite4h_in_the_pinned_tests` for PR 31's. Returns True where it
    dealt with the test.

    - the table test gets the five new metrics' hand-worked numbers
      from tests/benchmark/smallthinker_by_hand.py, on the same
      synthetic window with the new configuration's file as `obs.conf`;
    - "an accepted configuration resolves to today's defaults": this
      one names a costs module and tolerances of its own, so its cases
      are skipped and tests/benchmark/test_bench_smallthinker.py holds
      what it names instead."""
    import pytest

    params = getattr(getattr(node, "callspec", None), "params", {})
    if name == "test_an_accepted_configuration_resolves_to_todays_defaults":
        if params.get("config") == "smallthinker21b":
            pytest.skip("smallthinker21b brings its own costs and "
                        "tolerances: test_bench_smallthinker.py")
        return False
    if name != "test_reader_gives_the_number_worked_by_hand":
        return False
    import smallthinker_by_hand as by_hand

    if params.get("name") not in by_hand.BY_HAND:
        return False
    from benchmark.lib import serve
    from benchmark.metrics import _scoped_ops
    from infinistore_tpu.utils import profiling

    table, window = module.expected, module.full_window

    def full_window():
        obs = window()
        obs.conf = serve.load_config("benchmark/configs/smallthinker21b.json")
        return obs

    monkeypatch.setattr(module, "full_window", full_window)
    monkeypatch.setattr(module, "expected",
                        lambda obs: {**table(window()), **by_hand.BY_HAND})
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    monkeypatch.setattr(
        _scoped_ops, "seconds",
        lambda obs, kind, scopes: by_hand.SCOPED[kind, tuple(scopes)])
    return True
