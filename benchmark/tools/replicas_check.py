#!/usr/bin/env python3
"""Chip tool: what a cell of several replicas over one store has to
show beyond `correct`, on one run of the cell as run.py plays it.

    python3 benchmark/tools/replicas_check.py \
        --workload mistral7b-replicas4-sessions --seed 2147486993

1. Cross-client read-back, outside the window: the first put batch the
   store acknowledged to each replica during the sample (the same tap
   correct.py reads back on the writer's own connection) is read
   through EVERY OTHER replica's connection and compared bit for bit
   with the writer's HBM copy.
2. The visibility guarantee, from the window: every admission that
   started in it hit exactly the pages the played schedule implies
   (every full page of the session's previous turn: its prompt length
   names class and turn), every hit page was foreign, and over the
   window foreign_hit_pages == prefix_hit_pages, restore_misses == 0,
   store_errors == 0.
3. The program's span ring: spans per second of window with this many
   engines, and whether it reached back to the window's start
   (utils/profiling.py RING_SPANS is sized from this); the device of
   every engine id, from the step spans.

The last line of stdout is one JSON object with "ok"; exit code 1 where
it is false. --rehearsal: tiny widths on the CPU, as run.py's.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def log(msg):
    print(msg, flush=True)


def cross_read_back(replicas):
    """[(writer, reader, n pages, equal)] for every tapped put batch
    through every other replica's connection."""
    import numpy as np

    out = []
    for w in replicas:
        if w.store.tapped is None:
            continue
        keys, handed = w.store.tapped
        pages = np.asarray(handed)
        want = np.ascontiguousarray(pages).view(np.uint8)
        for r in replicas:
            if r is w:
                continue
            back = r.inner_store.get_kv_pages_host(
                keys, pages.shape[1:], pages.dtype)
            out.append((w.index, r.index, len(keys), bool(np.array_equal(
                np.ascontiguousarray(back).view(np.uint8), want))))
    return out


def implied_hits(spec, page):
    """{prompt tokens: hit pages} over every class and turn; a prompt
    length two turns share is left out (nothing to tell them by)."""
    from benchmark.lib import traffic

    seen = {}
    for c in spec["classes"]:
        for t in traffic.turn_lengths(c, spec["turns"], page):
            seen.setdefault(t["prompt"], set()).add(t["hit"] // page)
    return {n: h.pop() for n, h in seen.items() if len(h) == 1}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.lib import manifest

    bench = manifest.load()
    cell = manifest.cell_of(bench, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    import jax

    want = "cpu" if args.rehearsal else "tpu"
    if jax.default_backend() != want:
        print(f"replicas_check: needs the {want} backend, found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2

    from benchmark.lib import program_spans
    from benchmark.lib.cell import Cell
    from benchmark.lib.store import BenchFailure
    from infinistore_tpu.utils import profiling

    c = Cell(cell, manifest.config_of(bench, cell["config"]), args.seed,
             args.rehearsal, log)
    try:
        c.setup()
        sample_ok = c.warm_and_check()
        back = cross_read_back(c.replicas)
        log("cross read-back: " + json.dumps(
            [{"writer": w, "reader": r, "pages": n, "equal": same}
             for w, r, n, same in back]))
        obs = c.measure(seconds)
        healthy, health = c.health()
        page = c.cfg.page_size
        ring = program_spans.ring(obs)
        implied = implied_hits(obs.spec, page)
        admissions = wrong = 0
        rate = devices = None
        if ring is not None:
            for s in program_spans.started_in_window(
                    obs, ring, "istpu.sched.admit"):
                if s.fields.get("outcome") != "admitted":
                    continue
                admissions += 1
                hit = implied.get(s.fields["prompt_tokens"])
                if hit is None or s.fields["hit_pages"] != hit \
                        or s.fields.get("foreign_pages") != hit:
                    wrong += 1
                    log(f"admission off the schedule: {s.request} "
                        f"{s.fields} implied {hit}")
            w0_ns = obs.window[0] * 1e9
            after = [s for s in ring if s.t0_ns >= w0_ns]
            rate = len(after) / max(
                1e-9, (after[-1].t0_ns - w0_ns) / 1e9) if after else 0.0
            devices = {}
            for s in after:
                if s.name == "istpu.engine.step":
                    devices.setdefault(str(s.engine), s.fields.get("device"))
        cn = obs.counters
        due = obs.due_in_window()
        result = {
            "correct": bool(sample_ok and healthy),
            "pairs_read_back": len(back),
            "pages_read_back": sum(n for _, _, n, _ in back),
            "read_back_equal": bool(back) and all(s for *_, s in back),
            "attempted": len(due),
            "failed": sum(1 for r in due if obs.failed(r)),
            "admissions_in_window": admissions,
            "admissions_off_schedule": wrong,
            "prefix_hit_pages": cn.get("prefix_hit_pages"),
            "foreign_hit_pages": cn.get("foreign_hit_pages"),
            "restore_misses": cn.get("restore_misses"),
            "store_evictions": obs.store_delta.get("evictions"),
            "compilations_in_window": c.compiled_in_window,
            "ring_reaches_back": ring is not None,
            "ring_spans": len(profiling.spans()),
            "ring_capacity": profiling.RING_SPANS,
            "spans_per_s": rate,
            "engine_device": devices,
            **health,
        }
        result["ok"] = bool(
            result["correct"] and result["read_back_equal"]
            and result["failed"] == 0 and admissions > 0 and wrong == 0
            and cn.get("prefix_hit_pages", 0) > 0
            and cn.get("foreign_hit_pages") == cn.get("prefix_hit_pages")
            and cn.get("restore_misses") == 0
            and result["ring_reaches_back"]
            and c.compiled_in_window == 0)
    except BenchFailure as e:
        print(f"replicas_check: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        c.close()
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
