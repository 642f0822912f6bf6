"""benchmark/metrics/_idle_by_span.py on a small recorded trace: the
ring laid on the trace's clock, each device plane on the host's, every
idle gap split among the spans of its own engine's thread, and the six
readers of PR 38 against numbers worked by hand
(tests/benchmark/idle_by_hand.py has the timeline and the sums)."""

import collections
import json

import idle_by_hand as by_hand
import pytest

from benchmark.lib import manifest
from benchmark.lib.cell import Observations
from benchmark.metrics import _idle_by_span
from infinistore_tpu.utils import profiling

NEEDLES = ["decode_fused"]


# Five spans of the admission and the finish, each name once.
FIVE = ("istpu.cache.probe", "istpu.cache.restore", "istpu.engine.settle",
        "istpu.xfer.d2h", "istpu.cache.offload_sync")


def join(names=None, ring=by_hand.RING):
    return _idle_by_span.join(by_hand.plain(names), ring, NEEDLES,
                              by_hand.CLOSED_NS)


def test_the_clocks_an_offset_of_102_seconds_from_40_pairs():
    """The ring's clock against the annotations' (the ring holds the
    whole run; only the spans that started inside the session pair),
    and each plane's clock against the host's from the runtime's
    enqueue and completion of every program run."""
    assert join()["clock"] == by_hand.CLOCK


def test_five_pairs_are_too_few_and_every_reader_gives_none(monkeypatch):
    found = join(names=FIVE)
    assert found["clock"]["pairs"] == 5
    assert set(found) == {"clock"}
    monkeypatch.setattr(_idle_by_span, "plain_of_run",
                        lambda: by_hand.plain(FIVE))
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    obs = window()
    assert _idle_by_span.joined(obs) is None
    for name in ("idle_no_work_share", "host_held_idle_share",
                 "decode_dispatch_lead_p50_ms", "decode_return_lag_p50_ms"):
        assert manifest.reader(name).read(obs) is None


def test_quartiles_too_far_apart_give_none():
    plain = by_hand.plain()
    plain["host"] = [[n, s + (400_000 if i % 2 else 0), d]
                     for i, (n, s, d) in enumerate(plain["host"])]
    found = _idle_by_span.join(plain, by_hand.RING, NEEDLES,
                               by_hand.CLOSED_NS)
    assert found["clock"]["quartile_distance_ns"] > 100_000
    assert "idle_by" not in found


def test_every_gap_is_split_and_the_sum_is_the_idle_time():
    found = join()
    assert found["window_s"] == pytest.approx(4.0)
    assert found["idle_s"] == pytest.approx(by_hand.IDLE_S)
    assert found["idle_by"] == pytest.approx(by_hand.IDLE_BY)
    assert sum(found["idle_by"].values()) == pytest.approx(found["idle_s"])
    # Largest first, as the log prints them.
    assert list(found["idle_by"])[:3] == [
        "no_work", "istpu.store.write", "unspanned"]


def test_a_gap_half_under_a_write_and_half_under_nothing():
    """Chip 1 is idle for 80 ms: the first 40 under engine 2's store
    write, the rest behind the last span its ring holds. The largest
    overlap taking all would give the write 80."""
    ms = 10 ** 6
    spans = [s._replace(t0_ns=s.t0_ns - by_hand.CLOCK["offset_ns"])
             for s in by_hand.RING if s.engine == 2]
    segments = _idle_by_span.timeline(spans, 1000 * ms, 5000 * ms)
    assert segments[0][0] == 1000 * ms and segments[-1][1] == 5000 * ms
    assert all(a[1] == b[0] for a, b in zip(segments, segments[1:]))
    assert segments[-2:] == [
        (3000 * ms, 3040 * ms, "istpu.store.write"),
        (3040 * ms, 5000 * ms, "unspanned")]
    into = collections.defaultdict(int)
    _idle_by_span.split([(3000 * ms, 3080 * ms)], segments, into)
    assert into == {"istpu.store.write": 40 * ms, "unspanned": 40 * ms}


def test_each_plane_is_set_against_its_own_engine():
    found = join()
    assert found["planes"] == {"/device:TPU:0": 1, "/device:TPU:1": 2}
    # Engine 2's admission covers chip 0's first empty spell: set
    # against the wrong engine it would read as an admission.
    assert "istpu.sched.admit" not in found["idle_by"]
    assert "istpu.model.prefill:wait" not in found["idle_by"]
    swapped = [s._replace(fields=dict(s.fields, device=1 - s.fields["device"]))
               if s.name == "istpu.engine.step" else s for s in by_hand.RING]
    assert join(ring=swapped)["planes"] == {"/device:TPU:0": 2,
                                            "/device:TPU:1": 1}


def test_a_no_work_spell_open_before_the_session_is_on_the_ring_alone():
    first = next(s for s in by_hand.RING
                 if s.name == "istpu.engine.no_work")
    assert first.t0_ns < by_hand.CLOCK["offset_ns"]  # before trace time 0
    starts = [s for n, s, _ in by_hand.plain()["host"]
              if n == "istpu.engine.no_work"]
    assert len(starts) == 1 and starts[0] > 2_000_000_000
    assert join()["idle_by"]["no_work"] >= 1.0 / 2


def test_a_plane_whose_skew_is_not_known_gives_no_lead_and_no_lag():
    plain = by_hand.plain()
    plain["launches"] = {}
    found = _idle_by_span.join(plain, by_hand.RING, NEEDLES,
                               by_hand.CLOSED_NS)
    assert found["clock"]["device_skew"] == {"/device:TPU:0": None,
                                             "/device:TPU:1": None}
    assert found["lead_ns"] == found["lag_ns"] == []
    # ... and the gaps are split on the plane's clock as it is: 1.5 ms
    # of every spell's edge change hands, the sum holds.
    assert sum(found["idle_by"].values()) == pytest.approx(found["idle_s"])


def test_lead_and_lag_of_the_plain_decode_steps():
    found = join()
    assert sorted(found["lead_ns"]) == [2_000_000] * 7 + [3_000_000] * 3
    assert sorted(found["lag_ns"]) == [1_500_000] * 7 + [2_500_000] * 3
    assert _idle_by_span.p95_ms(found["lag_ns"]) == by_hand.LAG_P95_MS
    # Engine 2's step holds an admission: not a plain step.
    assert len(found["lead_ns"]) == 10


@pytest.mark.parametrize("wider_ns", [400_000, -50_000])
def test_bounds_of_a_skew_far_apart_or_crossed_still_give_lead_and_lag(
        wider_ns):
    """A run whose shortest program is a decode step holds a plane's
    skew between bounds 0.3 ms and more apart, and the host events'
    jitter can lay the two bounds ACROSS each other (the check of PR 38
    met a half distance of -0.008 ms and both metrics went missing):
    the middle is taken all the same, and the `clock:` line says how
    well it is known."""
    plain = by_hand.plain()
    plain["launches"] = {k: [q - wider_ns, done + wider_ns]
                         for k, (q, done) in plain["launches"].items()}
    found = _idle_by_span.join(plain, by_hand.RING, NEEDLES,
                               by_hand.CLOSED_NS)
    for name, skew in by_hand.CLOCK["device_skew"].items():
        assert found["clock"]["device_skew"][name] == dict(
            skew, halfwidth_ns=skew["halfwidth_ns"] + wider_ns)
    assert sorted(found["lead_ns"]) == [2_000_000] * 7 + [3_000_000] * 3
    assert sorted(found["lag_ns"]) == [1_500_000] * 7 + [2_500_000] * 3


def window():
    """test_bench_observations.py's synthetic window, as far as these
    readers look."""
    obs = Observations()
    obs.window, obs.trace_window = (100.0, 110.0), (103.5, 107.5)
    with open("benchmark/configs/mistral7b.json") as f:
        obs.conf = json.load(f)
    obs.trace = {"busy_s": 2.038, "window_s": 4.0}
    return obs


@pytest.mark.parametrize("name", sorted(by_hand.BY_HAND))
def test_reader_against_the_number_worked_by_hand(name, monkeypatch, capsys):
    monkeypatch.setattr(_idle_by_span, "plain_of_run", by_hand.plain)
    monkeypatch.setattr(profiling, "spans", lambda: by_hand.RING)
    obs = window()
    assert manifest.reader(name).read(obs) == pytest.approx(
        by_hand.BY_HAND[name])
    said = capsys.readouterr().out
    if manifest.reader(name).SOURCE == "device_trace":
        clock = json.loads(said.split("clock: ")[1].splitlines()[0])
        assert clock == by_hand.CLOCK
        by = json.loads(said.split(
            "idle_by_program_span: ")[1].splitlines()[0])
        assert by == pytest.approx(by_hand.IDLE_BY)
        # read once a run: a second reader prints nothing again
        manifest.reader("host_held_idle_share").read(obs)
        assert "clock: " not in capsys.readouterr().out
    if name == "decode_return_lag_p50_ms":
        lag = json.loads(said.split("decode_return_lag: ")[1].splitlines()[0])
        assert lag == {"steps": 10, "p50_ms": 1.5, "p95_ms": 2.5}


@pytest.mark.parametrize("name", sorted(by_hand.BY_HAND))
def test_a_program_without_the_new_spans_gives_none(name, monkeypatch):
    """A parent commit records no loop spans, no store spans and no
    `dispatch_ns`: the shares and the store's two metrics say nothing;
    lead and lag need none of them."""
    old = ("istpu.engine.no_work", "istpu.sched.submit",
           "istpu.engine.settle", "istpu.store.allocate",
           "istpu.store.write", "istpu.store.pin", "istpu.store.view")
    ring = [s._replace(fields={k: v for k, v in s.fields.items()
                               if k not in ("dispatch_ns", "steady")})
            for s in by_hand.RING if s.name not in old]
    monkeypatch.setattr(_idle_by_span, "plain_of_run", by_hand.plain)
    monkeypatch.setattr(profiling, "spans", lambda: ring)
    got = manifest.reader(name).read(window())
    if name in ("decode_dispatch_lead_p50_ms", "decode_return_lag_p50_ms"):
        assert got == pytest.approx(by_hand.BY_HAND[name])
    else:
        assert got is None


def test_no_trace_no_number():
    obs = window()
    obs.trace = None
    assert _idle_by_span.joined(obs) is None


def test_read_plain_keeps_the_window_and_the_program_annotations(tmp_path):
    """The xplane read itself, on a CPU trace: no TensorCore plane, the
    window's span and the `istpu.*` annotations with their starts."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import trace

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with profiling.span("istpu.test.read_plain"):
            jax.block_until_ready(jnp.ones(8) + 1)
    jax.profiler.stop_trace()
    plain = _idle_by_span.read_plain(trace.find_xplane(str(tmp_path)))
    assert plain["devices"] == {} and plain["launches"] == {}
    names = [n for n, _, _ in plain["host"]]
    assert sorted(names) == ["bench.trace_window", "istpu.test.read_plain"]
    (w0, w1), (_, s, d) = trace.window_of(plain), plain["host"][
        names.index("istpu.test.read_plain")]
    assert w0 <= s and s + d <= w1
