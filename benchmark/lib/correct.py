"""What decides `correct`. Decided outside the timed window, on a fixed
sample made from --seed (one session per class of the traffic file),
the same whatever the load did:

1. The plain float32 reference of the configuration (benchmark/
   reference/) runs each sample session's last prompt plus the first
   CHECK_TOKENS answered tokens once; causal attention makes that one
   pass the reference for every turn of the session.
2. The sample sessions went through the normal path (HTTP -> engine ->
   store): turn 1 cold, later turns as hits (restore + prefix prefill),
   every answered token through paged decode. A token passes if its
   reference logit is within `token_eps` of the reference's maximum at
   that position: an argmax that flips on rounding passes; a wrong
   page, a wrong position, a dropped token or a skipped layer does not.
   First-token logits of the cold and the hit program are also held to
   the reference directly (`logit_tol`), through the engine's public
   `first_token_logits`, which dispatches what an admission does: the
   cold rows of the sample's first prompts BEFORE the sample is
   played (the store is empty, so the cold program runs), the hit
   rows after it. A row is labelled by the path that ran; a path the
   traffic has and the sample never ran fails the run.
3. Near-ties are not evidence: for a sparse-expert model a checked
   position whose router margin in the reference is under
   `router_margin` in any layer is set aside and counted.
4. The store's guarantee: the first batch of pages the store
   acknowledged during the sample is read back and compared bit for
   bit with the HBM copy; store_errors == 0 and the engine is up at
   the end of the run (run.py adds those two at the end).
5. Nothing here reads a counter the load can move.

Tolerances live in the file the configuration names (default:
benchmark/reference/tolerances.json) with the measurements they were
set from.
"""

import json
import os

import numpy as np

from . import ROOT, traffic

CHECK_TOKENS = 8
SAMPLE_BASE = 1_000_000  # sample sessions' indices, clear of the plan's


TOLERANCES = "benchmark/reference/tolerances.json"


def tolerances(family, path=TOLERANCES):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)[family]


def tolerances_for(conf):
    """The configuration's own: "program": {"tolerances": {"file",
    "family"}}; without it the two families of TOLERANCES, as the
    accepted configurations are told apart."""
    named = conf["program"].get("tolerances", {})
    family = named.get("family") or (
        "moe" if conf.get("num_local_experts", 1) > 1 else "dense")
    return tolerances(family, named.get("file", TOLERANCES))


def sample_sessions(spec, seed, copies=1):
    """`copies` seeded sessions of every class (copies = replicas, so
    that rotated routing makes every replica run every turn's shape)."""
    out = []
    for ci in range(len(spec["classes"])):
        for k in range(copies):
            idx = SAMPLE_BASE + ci * copies + k
            out.append(traffic.Session(
                index=idx, cls=ci, arrival_s=0.0,
                thinks_s=[0.0] * max(0, spec["turns"] - 1),
                token_seed=(int(seed) * 7919 + idx) % (2 ** 63),
            ))
    return out


def turns_of(spec, sess, records, vocab):
    """[(prompt, answered tokens)] per completed turn of a sample
    session, rebuilt from its records (which carry the tokens)."""
    ctx, msgs = traffic.session_tokens(spec, sess, vocab)
    history, out = list(ctx), []
    for rec in sorted(records, key=lambda r: r["turn"]):
        prompt = history + msgs[rec["turn"] - 1]
        out.append((prompt, list(rec["tokens"])))
        history = prompt + list(rec["tokens"])
    return out


def token_deficits(ref_logits, tokens):
    """Per token: reference maximum at its position minus the reference
    logit of the token the engine chose (0 where the argmax agrees)."""
    ref_logits = np.asarray(ref_logits, np.float32)
    return [float(ref_logits[i].max() - ref_logits[i, t])
            for i, t in enumerate(tokens)]


def set_aside(margins, router_margin):
    """Positions (rows) whose router margin is under the bound in any
    layer. margins None (dense model): nothing is set aside."""
    if margins is None:
        return None
    m = np.asarray(margins, np.float32)
    return (m < router_margin).any(axis=1)


def judge_tokens(deficits, aside, token_eps):
    """(n checked, n failed, n set aside, worst deficit checked)."""
    checked = failed = skipped = 0
    worst = 0.0
    for i, d in enumerate(deficits):
        if aside is not None and aside[i]:
            skipped += 1
            continue
        checked += 1
        worst = max(worst, d)
        if not d <= token_eps:
            failed += 1
    return checked, failed, skipped, worst


def program_first_logits(replica, model, cfg, prompt, hit_expected):
    """(float32 row [vocab], hit pages) of `prompt` through the
    programs an admission dispatches: the engine's own
    first_token_logits, which probes the store and runs the cold
    program on a miss, restore + the prefix program on a hit, with the
    engine idle and nothing admitted. The caller labels the row by the
    hit that came back, not by `hit_expected`; a hit expected that ran
    cold is said here. (`model` and `cfg` are the engine's own and go
    unused: tests/test_engine_spans.py holds this signature.)"""
    row, hit = replica.engine.first_token_logits(prompt)
    if hit_expected and not hit > 0:
        print(f"correct: a prompt of {len(prompt)} tokens expected as a "
              f"hit ran the cold program (the store held none of its "
              f"pages)", flush=True)
    return np.asarray(row, np.float32), int(hit)


def checked_copies(spec, samples):
    """The sample sessions the reference runs: one copy per class."""
    copies = max(1, len(samples) // len(spec["classes"]))
    return [s for s in samples if not (s.index - SAMPLE_BASE) % copies]


def cold_first_logits(spec, samples, replicas, model, cfg, vocab):
    """{session index: (row, hit pages)} of every checked sample
    session's FIRST prompt, on the replica that will serve it. Taken
    before the sample is played: the store holds nothing of it yet, so
    the cold program runs, as it will for the session's first turn."""
    out = {}
    for sess in checked_copies(spec, samples):
        ctx, msgs = traffic.session_tokens(spec, sess, vocab)
        replica = replicas[traffic.replica_of(spec, sess.index, 1)
                           % len(replicas)]
        out[sess.index] = program_first_logits(
            replica, model, cfg, list(ctx) + msgs[0], False)
    return out


def read_back(replica, cfg=None):
    """The tapped put batch, read back from the store and compared bit
    for bit with the array the engine handed to the store (shape and
    dtype of a page are that array's; `cfg` goes unused:
    tests/test_serving_replicas.py passes it). Returns (n pages, equal)
    or (0, None) if nothing was tapped."""
    tapped = replica.store.tapped
    if tapped is None:
        return 0, None
    keys, handed = tapped
    want = np.asarray(handed)
    back = replica.inner_store.get_kv_pages_host(
        keys, want.shape[1:], want.dtype)
    same = np.array_equal(
        np.ascontiguousarray(back).view(np.uint8),
        np.ascontiguousarray(want).view(np.uint8))
    return len(keys), bool(same)


def check(conf, spec, model, cfg, params, reference, replicas, samples,
          records_by_session, vocab, tol, cold_rows=None, log=print):
    """Runs points 1-4 on the sample; returns (ok, details).
    `cold_rows` is what cold_first_logits took before the sample was
    played; None (a sweep) leaves the first-token logits out."""
    page = cfg.page_size
    per_turn = []
    ok = True
    worst_token = 0.0
    worst_logit = {"cold": 0.0, "hit": 0.0}
    # Rows of first-token logits by the path that ran: taken, and of
    # those compared (a near-tie of the router is taken, not compared).
    rows = {"cold": [0, 0], "hit": [0, 0]}
    paths_due = set()
    counts = {"checked": 0, "failed": 0, "set_aside": 0,
              "logit_checked": 0, "logit_set_aside": 0,
              "hit_expected_ran_cold": 0}
    seqs = {}
    for sess in samples:
        recs = records_by_session.get(sess.index, [])
        turns = turns_of(spec, sess, recs, vocab)
        if len(turns) != spec["turns"] or any(
                len(g) < CHECK_TOKENS for _, g in turns):
            log(f"correct: sample session {sess.index} (class {sess.cls}) "
                f"did not complete: {len(turns)} turns")
            ok = False
            continue
        seqs[sess.index] = turns
    pad_to = max((len(t[-1][0]) + CHECK_TOKENS for t in seqs.values()),
                 default=0)
    pad_to = -(-pad_to // 128) * 128
    for sess in checked_copies(spec, samples):
        if sess.index not in seqs:
            continue
        turns = seqs[sess.index]
        last_prompt, last_gen = turns[-1]
        seq = list(last_prompt) + list(last_gen[:CHECK_TOKENS])
        toks = np.zeros(pad_to, np.int32)
        toks[:len(seq)] = seq
        positions = []
        for prompt, _ in turns:
            positions += [len(prompt) - 1 + i for i in range(CHECK_TOKENS)]
        ref_logits, margins = reference.forward(params, conf, toks,
                                                positions)
        ref_logits = np.asarray(ref_logits, np.float32)
        aside_all = set_aside(margins, tol.get("router_margin", 0.0))
        expected = traffic.turn_lengths(spec["classes"][sess.cls],
                                        spec["turns"], page)
        for ti, (prompt, gen) in enumerate(turns):
            sl = slice(ti * CHECK_TOKENS, (ti + 1) * CHECK_TOKENS)
            deficits = token_deficits(ref_logits[sl], gen[:CHECK_TOKENS])
            aside = None if aside_all is None else aside_all[sl]
            c, f, s, w = judge_tokens(deficits, aside, tol["token_eps"])
            counts["checked"] += c
            counts["failed"] += f
            counts["set_aside"] += s
            worst_token = max(worst_token, w)
            entry = {"class": sess.cls, "turn": ti + 1,
                     "deficits": [round(d, 4) for d in deficits],
                     "set_aside": None if aside is None
                     else [bool(a) for a in aside]}
            if margins is not None:
                entry["margins"] = [round(float(m), 4) for m in
                                    np.min(np.asarray(margins)[sl], axis=1)]
            # The cold program and the first hit: later turns run the
            # same prefix program at another shape.
            if cold_rows is not None and ti < 2:
                due = "hit" if expected[ti]["hit"] else "cold"
                paths_due.add(due)
                if ti == 0:
                    row, hit = cold_rows[sess.index]
                else:
                    replica = replicas[traffic.replica_of(
                        spec, sess.index, ti + 1) % len(replicas)]
                    row, hit = program_first_logits(
                        replica, model, cfg, prompt, due == "hit")
                path = "hit" if hit > 0 else "cold"
                if due == "hit" and path == "cold":
                    counts["hit_expected_ran_cold"] += 1
                diff = float(np.max(np.abs(row - ref_logits[sl][0])))
                entry["first_logit_diff"] = round(diff, 4)
                entry["hit_pages"] = hit
                entry["path"] = path
                rows[path][0] += 1
                if aside is not None and aside[0]:
                    counts["logit_set_aside"] += 1
                else:
                    counts["logit_checked"] += 1
                    rows[path][1] += 1
                    worst_logit[path] = max(worst_logit[path], diff)
                    if not (np.isfinite(row).all()
                            and diff <= tol["logit_tol"]):
                        ok = False
                        entry["first_logit_failed"] = True
            per_turn.append(entry)
    if counts["failed"]:
        ok = False
    for path in sorted(paths_due):
        if not rows[path][0]:
            log(f"correct: the traffic has {path} admissions and no "
                f"first-token row of the sample ran the {path} program")
            ok = False
    total = counts["checked"] + counts["set_aside"]
    if total == 0 or counts["checked"] < total * tol.get(
            "min_checked_share", 0.25):
        log(f"correct: only {counts['checked']} of {total} positions "
            f"left to check")
        ok = False
    n_back = 0
    for r in replicas:
        n, same = read_back(r)
        n_back += n
        if same is False:
            log(f"correct: replica {r.index}: {n} acknowledged pages "
                f"read back DIFFERENT from the HBM copy")
            ok = False
    if n_back == 0:
        log("correct: no acknowledged page was read back")
        ok = False
    details = {
        "ok": ok, **counts,
        "logit_rows": {k: {"taken": t, "compared": c}
                       for k, (t, c) in rows.items()},
        "pages_read_back": n_back,
        "worst_token_deficit": round(worst_token, 4),
        "worst_first_logit_diff": {k: round(v, 4)
                                   for k, v in worst_logit.items()},
        "tolerances": tol, "per_turn": per_turn,
    }
    return ok, details
