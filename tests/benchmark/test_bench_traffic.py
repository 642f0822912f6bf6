"""The general traffic generator: seeded, reproducible, the same work
for every seed, and every shape it can emit is enumerated."""

import collections
import json

import pytest

from benchmark.lib import traffic

FILES = ["sessions", "unshared", "sessions-rr4"]
SEEDS = [0, 7, 2147483659, 2 ** 31 + 12345]


def spec_of(name):
    return traffic.load(f"benchmark/traffic/{name}.json")


@pytest.mark.parametrize("name", FILES)
@pytest.mark.parametrize("seed", SEEDS)
def test_plan_is_reproducible(name, seed):
    a = traffic.plan(spec_of(name), seed, 55)
    b = traffic.plan(spec_of(name), seed, 55)
    assert a == b and len(a) > 10
    assert [s.arrival_s for s in a] == sorted(s.arrival_s for s in a)


@pytest.mark.parametrize("name", FILES)
def test_a_fixed_schedule_moves_nothing_but_the_tokens(name):
    spec = spec_of(name)
    assert isinstance(spec["schedule_seed"], int)
    a, b = traffic.plan(spec, SEEDS[1], 55), traffic.plan(spec, SEEDS[2], 55)
    assert [(s.cls, s.arrival_s, s.thinks_s) for s in a] \
        == [(s.cls, s.arrival_s, s.thinks_s) for s in b]
    assert all(x.token_seed != y.token_seed for x, y in zip(a, b))
    assert traffic.session_tokens(spec, a[0], 512) \
        != traffic.session_tokens(spec, b[0], 512)


@pytest.mark.parametrize("name", FILES)
def test_every_seed_gets_the_same_multiset(name):
    spec = spec_of(name)
    spec.pop("schedule_seed")  # then the run's seed permutes the order
    plans = [traffic.plan(spec, s, 55) for s in SEEDS]

    def multiset(p):
        gaps = [round(p[0].arrival_s, 9)] + [
            round(b.arrival_s - a.arrival_s, 9) for a, b in zip(p, p[1:])]
        thinks = [round(t, 9) for s in p for t in s.thinks_s]
        return (sorted(gaps), sorted(thinks),
                collections.Counter(s.cls for s in p))

    assert all(multiset(p) == multiset(plans[0]) for p in plans[1:])
    assert plans[0] != plans[1]  # ... in another order


@pytest.mark.parametrize("name", FILES)
def test_class_counts_follow_the_weights_exactly(name):
    spec = spec_of(name)
    p = traffic.plan(spec, 3, 100)
    counts = collections.Counter(s.cls for s in p)
    for ci, c in enumerate(spec["classes"]):
        assert abs(counts[ci] - c["weight"] * len(p)) < 1.0


@pytest.mark.parametrize("name", FILES)
def test_arrivals_fill_the_horizon_at_the_fixed_rate(name):
    spec = spec_of(name)
    p = traffic.plan(spec, 11, 60)
    assert len(p) == round(spec["session_rate_per_s"] * 60)
    assert p[0].arrival_s > 0
    # the gaps sum to n / rate exactly: the last session closes it
    assert p[-1].arrival_s == pytest.approx(
        len(p) / spec["session_rate_per_s"])


@pytest.mark.parametrize("name", FILES)
def test_shapes_enumerate_everything_a_session_can_emit(name):
    spec = spec_of(name)
    sh = traffic.shapes(spec)
    for c in spec["classes"]:
        for t in traffic.turn_lengths(c, spec["turns"]):
            assert t["prompt"] % 16 == 0 and t["hit"] % 16 == 0
            assert t["prompt"] == t["hit"] + t["suffix"]
            assert t["suffix"] >= 16  # a token is always left to prefill
            if t["hit"]:
                assert (t["suffix"], t["hit"]) in sh["prefix"]
            else:
                assert t["prompt"] in sh["cold"]
            assert t["offload_pages"] in sh["offload_pages"]
            assert t["prompt"] + t["answer"] <= sh["longest_context"]


def test_sessions_shapes_worked_by_hand():
    sh = traffic.shapes(spec_of("sessions"))
    assert sh["cold"] == [1136, 1264, 2160, 2288]
    # class (1024, 112, 48): turn 1 holds 1136 + 47 tokens = 73 full
    # pages = 1168 tokens; turn 2 is 1136 + 48 + 112 = 1296 tokens.
    assert (128, 1168) in sh["prefix"] and (128, 1328) in sh["prefix"]
    assert len(sh["prefix"]) == 8
    assert sh["longest_context"] == 2912 and sh["pages_longest"] == 182
    assert sh["offload_pages"] == [10, 14, 18, 22, 73, 85, 141, 145]


def test_unshared_has_three_prefill_programs_and_no_hit():
    sh = traffic.shapes(spec_of("unshared"))
    assert sh["cold"] == [272, 528, 1040] and sh["prefix"] == []


@pytest.mark.parametrize("name", FILES)
def test_fixed_rate_is_a_number(name):
    raw = json.load(open(f"benchmark/traffic/{name}.json"))
    assert isinstance(raw["session_rate_per_s"], (int, float))
    assert not isinstance(raw["session_rate_per_s"], bool)


def test_rotate_sends_every_turn_to_another_replica():
    spec = spec_of("sessions-rr4")
    for s in range(8):
        seen = [traffic.replica_of(spec, s, t) for t in (1, 2, 3)]
        assert seen == [(s + t) % 4 for t in (1, 2, 3)]
        assert len(set(seen)) == 3
    # four consecutive sessions put every turn on every replica
    for t in (1, 2, 3):
        assert {traffic.replica_of(spec, s, t) for s in range(4)} \
            == {0, 1, 2, 3}


def test_sticky_keeps_a_session_on_one_replica():
    spec = dict(spec_of("sessions"), replicas=4)
    assert {traffic.replica_of(spec, 5, t) for t in (1, 2, 3)} == {1}


@pytest.mark.parametrize("name", FILES)
@pytest.mark.parametrize("divisor", [4, 8])
def test_scaled_mix_stays_valid(name, divisor):
    small = traffic.scaled(spec_of(name), divisor)
    traffic.validate(small)
    big = traffic.shapes(spec_of(name))["longest_context"]
    assert traffic.shapes(small)["longest_context"] < big


@pytest.mark.parametrize("n,weights", [
    (10, [0.4, 0.3, 0.2, 0.1]), (77, [0.4, 0.3, 0.2, 0.1]),
    (3, [0.5, 0.5]), (1, [0.25, 0.75]), (100, [1.0]),
])
def test_apportion_sums_exactly(n, weights):
    counts = traffic._apportion(weights, n)
    assert sum(counts) == n
    assert all(abs(c - w * n) < 1 for c, w in zip(counts, weights))


@pytest.mark.parametrize("n", [1, 2, 10, 333])
def test_exponential_quantiles_keep_the_mean(n):
    q = traffic._exp_quantiles(n, 0.25)
    assert abs(sum(q) - n * 0.25) < 1e-9 and all(x > 0 for x in q)


@pytest.mark.parametrize("bad", [
    {"loop": "closed"}, {"arrivals": "uniform"}, {"route": "random"},
    {"session_rate_per_s": 0}, {"session_rate_per_s": "fast"},
    {"classes": [{"context": 100, "message": 16, "answer": 16,
                  "weight": 1.0}]},
    {"classes": [{"context": 64, "message": 16, "answer": 16,
                  "weight": 0.5}]},
    {"classes": [{"context": 64, "message": 16, "answer": 0,
                  "weight": 1.0}]},
])
def test_validate_rejects(bad):
    spec = dict(spec_of("sessions"), **bad)
    with pytest.raises((ValueError, TypeError)):
        traffic.validate(spec)


def test_tokens_are_seeded_and_inside_the_vocabulary():
    spec = spec_of("sessions")
    sess = traffic.plan(spec, 5, 30)[3]
    ctx, msgs = traffic.session_tokens(spec, sess, 32768)
    again = traffic.session_tokens(spec, sess, 32768)
    assert (ctx, msgs) == again
    c = spec["classes"][sess.cls]
    assert len(ctx) == c["context"] and len(msgs) == 3
    assert all(len(m) == c["message"] for m in msgs)
    assert all(0 <= t < 32768 for t in ctx)
    other = traffic.plan(spec, 5, 30)[4]
    assert traffic.session_tokens(spec, other, 32768)[0] != ctx


def test_store_pool_is_sized_from_the_mix():
    spec = spec_of("sessions")
    per = traffic.pages_written_per_session(spec)
    assert abs(per - 127.8) < 1e-9  # 0.4*93 + 0.3*129 + 0.2*169 + 0.1*181
    gb = traffic.store_pool_gb(spec, 2 ** 20)
    want = (spec["session_rate_per_s"] * per * 2 ** 20
            * spec["store_pool_seconds"] / 2 ** 30)
    assert want <= gb < want + 0.25
    assert traffic.store_pool_gb(spec, 1024) == 0.5
