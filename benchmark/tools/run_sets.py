#!/usr/bin/env python3
"""Chip tool: runs one cell as the contract's bound measurement asks -
`--sets` sets of runs, the same seeds in every set, every run a new
process of the benchmark's own command - and prints per metric each
set's median and spread (distance between the first and third quartile
by statistics.quantiles(n=4), as a share of the median), and the bound
that five times the widest spread would give. This process never
touches JAX, so each run has the chip to itself.

    python3 benchmark/tools/run_sets.py --workload mistral7b-sessions \
        --seeds 2147483659,2147483693,... --sets 2 \
        --out chiprun_out/sets_mistral7b-sessions.jsonl
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    from benchmark.lib import manifest, stats

    bench = manifest.load()
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rows = []
    for si in range(args.sets):
        for seed in seeds:
            t0 = time.time()
            cmd = [*bench["command"], "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", str(args.trace)]
            if args.rehearsal:
                cmd.append("--rehearsal")
            r = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                               text=True)
            lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
            row = {"set": si, "seed": seed, "rc": r.returncode,
                   "wall_s": round(time.time() - t0, 1)}
            try:
                row["result"] = json.loads(lines[-1])
            except (IndexError, ValueError):
                row["stderr"] = r.stderr[-1500:]
            for ln in lines:
                for key in ("setup: ", "warm-up: ", "window: ",
                            "correct: ", "samples: "):
                    if ln.startswith(key):
                        row[key.strip(": ")] = json.loads(ln[len(key):])
            rows.append(row)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
            res = row.get("result", {})
            print("run: " + json.dumps({
                "set": si, "seed": seed, "rc": r.returncode,
                "wall_s": row["wall_s"], "correct": res.get("correct"),
                "attempted": res.get("attempted"),
                "failed": res.get("failed"),
                "metrics": {k: round(v["value"], 3) for k, v in
                            res.get("metrics", {}).items()},
                "compiled_in_window": row.get("window", {}).get(
                    "compilations_in_window"),
            }), flush=True)
    names = sorted({k for r in rows
                    for k in r.get("result", {}).get("metrics", {})})
    for name in names:
        per_set = []
        for si in range(args.sets):
            v = [r["result"]["metrics"][name]["value"] for r in rows
                 if r["set"] == si and name in r.get("result", {}).get(
                     "metrics", {})]
            if len(v) >= 2:
                per_set.append({"median": statistics.median(v),
                                "spread": stats.spread(v), "n": len(v)})
        if per_set:
            widest = max(p["spread"] for p in per_set)
            print("spread: " + json.dumps({
                "workload": args.workload, "metric": name,
                "sets": per_set, "widest": widest,
                "bound_by_5x": max(0.01, 5 * widest)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
