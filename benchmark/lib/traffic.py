"""The one general traffic generator. A traffic mix is a data file of
parameters; this module turns (file, seed, horizon) into a plan of
sessions, and enumerates every shape the mix can make the engine
compile. It imports neither JAX nor the program: the load generator
child runs it.

File keys (all JSON):

  loop                "open": sessions arrive on a schedule whatever
                      the system does
  arrivals            "poisson": exponential gaps (see below)
  session_rate_per_s  a number, fixed here and never searched for
  turns               requests per session; turn n's prompt is the
                      session's context + all earlier user messages and
                      answers + a new user message
  classes             [{context, message, answer, weight}], in tokens.
                      Every length is a multiple of `page` (16): the
                      engine compiles one program per padded length
  think_s             {floor, mean_exp}: pause between the end of an
                      answer's stream and the next turn
  ramp_s              seconds of traffic before the measured window
  drain_s             how long requests due in the window may run on
                      after it (0: cut at the window's end)
  replicas            engines the mix is spread over (= the cell's chips)
  route               "sticky": a session stays on replica s mod R;
                      "rotate": turn k of session s goes to (s + k) mod R
  store_pool_seconds  the store's pool holds this many seconds of the
                      mix's page writes at the fixed rate
  schedule_seed       optional: the order of gaps, classes and think
                      times comes from this number, not from --seed

Every run plays the SAME multiset of gaps, classes and think times:
gaps and think times are the quantiles of their exponential and class
counts follow the weights exactly; only their order is drawn. Where the
file fixes `schedule_seed` the order is fixed too, and --seed changes
what it must (weights and every token) and nothing that moves a clock:
at some 60 requests to a window the order of arrivals alone moved the
tails by 20-35 % between seeds (PERF.md, Findings, PR 23), two runs of
one order by a few percent. Without `schedule_seed` the run's seed
permutes the order.
"""

import json
import math
import os
import random
from dataclasses import dataclass, field

from . import ROOT

PAGE = 16


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        spec = json.load(f)
    validate(spec)
    return spec


def validate(spec, page=PAGE):
    if spec["loop"] != "open" or spec["arrivals"] != "poisson":
        raise ValueError("only open-loop poisson arrivals are generated")
    if spec["route"] not in ("sticky", "rotate"):
        raise ValueError(f"route {spec['route']!r}")
    if not isinstance(spec["session_rate_per_s"], (int, float)) \
            or spec["session_rate_per_s"] <= 0:
        raise ValueError("session_rate_per_s must be a positive number")
    for c in spec["classes"]:
        for k in ("context", "message", "answer"):
            if c[k] % page or c[k] < 0:
                raise ValueError(f"class length {k}={c[k]} is not a "
                                 f"multiple of {page}")
        if c["context"] + c["message"] < page or c["answer"] < page:
            raise ValueError("a prompt and an answer are at least a page")
    if abs(sum(c["weight"] for c in spec["classes"]) - 1.0) > 1e-9:
        raise ValueError("class weights must sum to 1")


def scaled(spec, divisor, page=PAGE):
    """The same mix with every length divided (rehearsal on the CPU);
    lengths stay page multiples of at least one page."""
    out = json.loads(json.dumps(spec))

    def cut(n):
        return 0 if n == 0 else max(page, n // divisor // page * page)

    for c in out["classes"]:
        for k in ("context", "message", "answer"):
            c[k] = cut(c[k])
    return out


def turn_lengths(cls, turns, page=PAGE):
    """Per turn of a session of class `cls`: (prompt tokens, expected
    hit tokens, suffix tokens, full pages offloaded when it finishes).

    The engine emits `answer` tokens; the last one's KV is never
    appended, so a finished turn holds prompt + answer - 1 tokens and
    offloads the full pages among them that the store lacks. The next
    turn hits those pages (capped so one token is left to prefill)."""
    out = []
    prompt = cls["context"] + cls["message"]
    stored = 0  # full pages of this session the store holds
    for _ in range(turns):
        cap = (prompt - 1) // page
        hit = min(stored, cap)
        full = (prompt + cls["answer"] - 1) // page
        out.append({
            "prompt": prompt, "hit": hit * page,
            "suffix": prompt - hit * page,
            "offload_pages": max(0, full - hit),
            "answer": cls["answer"],
        })
        stored = max(stored, full)
        prompt += cls["answer"] + cls["message"]
    return out


def shapes(spec, page=PAGE):
    """Every shape the mix can make the engine compile: cold prefill
    lengths, (suffix, prefix) pairs of prefix prefills, page counts of
    offloads, and the longest context. Warm-up runs exactly these."""
    cold, prefix, offload = set(), set(), set()
    longest = 0
    for c in spec["classes"]:
        for t in turn_lengths(c, spec["turns"], page):
            if t["hit"]:
                prefix.add((t["suffix"], t["hit"]))
            else:
                cold.add(t["prompt"])
            offload.add(t["offload_pages"])
            longest = max(longest, t["prompt"] + t["answer"])
    return {"cold": sorted(cold), "prefix": sorted(prefix),
            "offload_pages": sorted(offload), "longest_context": longest,
            "pages_longest": -(-longest // page)}


def pages_written_per_session(spec, page=PAGE):
    """Mean full pages a session offloads, over the class weights."""
    return sum(
        c["weight"] * sum(t["offload_pages"]
                          for t in turn_lengths(c, spec["turns"], page))
        for c in spec["classes"]
    )


def offloads_per_session(spec, page=PAGE):
    """Mean number of offloads a session makes (one a finished turn
    that has full pages the store lacks), over the class weights."""
    return sum(
        c["weight"] * sum(1 for t in turn_lengths(c, spec["turns"], page)
                          if t["offload_pages"])
        for c in spec["classes"]
    )


def store_pool_gb(spec, page_bytes_all_layers, page=PAGE, snapshot_bytes=0):
    """Pool size in GB: `store_pool_seconds` of the mix's writes at the
    fixed rate (pages, and `snapshot_bytes` an offload where the
    configuration's cache has a part that does not grow with pages),
    rounded up to a quarter GB, at least half a GB."""
    per_s = spec["session_rate_per_s"] * (
        pages_written_per_session(spec, page) * page_bytes_all_layers
        + offloads_per_session(spec, page) * snapshot_bytes)
    gb = per_s * spec["store_pool_seconds"] / 2 ** 30
    return max(0.5, math.ceil(gb * 4) / 4)


def _exp_quantiles(n, mean):
    """n quantiles of the exponential distribution with `mean`,
    rescaled so that they sum to n * mean exactly."""
    q = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    k = n / sum(q)
    return [x * k * mean for x in q]


def _apportion(weights, n):
    """Counts per class by largest remainder: sums to n exactly."""
    raw = [w * n for w in weights]
    counts = [int(x) for x in raw]
    order = sorted(range(len(raw)), key=lambda i: raw[i] - counts[i],
                   reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


@dataclass
class Session:
    index: int
    cls: int
    arrival_s: float          # offset from the start of the ramp
    thinks_s: list = field(default_factory=list)
    token_seed: int = 0


def plan(spec, seed, horizon_s):
    """Sessions for `horizon_s` seconds (ramp + window), by arrival."""
    n = max(1, round(spec["session_rate_per_s"] * horizon_s))
    rng = random.Random(int(spec.get("schedule_seed", seed)))
    gaps = _exp_quantiles(n, 1.0 / spec["session_rate_per_s"])
    rng.shuffle(gaps)
    classes = []
    for ci, cnt in enumerate(_apportion(
            [c["weight"] for c in spec["classes"]], n)):
        classes += [ci] * cnt
    rng.shuffle(classes)
    n_thinks = n * max(0, spec["turns"] - 1)
    thinks = _exp_quantiles(n_thinks, spec["think_s"]["mean_exp"]) \
        if n_thinks and spec["think_s"]["mean_exp"] > 0 \
        else [0.0] * n_thinks
    rng.shuffle(thinks)
    floor = spec["think_s"]["floor"]
    per = spec["turns"] - 1
    out, t = [], 0.0
    for i in range(n):
        t += gaps[i]
        out.append(Session(
            index=i, cls=classes[i], arrival_s=t,
            thinks_s=[floor + x for x in thinks[i * per:(i + 1) * per]],
            token_seed=(int(seed) * 1000003 + i) % (2 ** 63),
        ))
    return out


def replica_of(spec, session_index, turn):
    """turn is 1-based."""
    r = spec["replicas"]
    if spec["route"] == "rotate":
        return (session_index + turn) % r
    return session_index % r


def tokens(token_seed, n, vocab):
    """n seeded token ids below `vocab` (stdlib only, and fast enough:
    a few thousand per request)."""
    rng = random.Random(token_seed)
    return [rng.randrange(vocab) for _ in range(n)]


def session_tokens(spec, sess, vocab):
    """(context tokens, [message tokens of each turn])."""
    c = spec["classes"][sess.cls]
    stream = tokens(sess.token_seed,
                    c["context"] + c["message"] * spec["turns"], vocab)
    ctx = stream[: c["context"]]
    msgs = [
        stream[c["context"] + k * c["message"]:
               c["context"] + (k + 1) * c["message"]]
        for k in range(spec["turns"])
    ]
    return ctx, msgs
