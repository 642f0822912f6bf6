#!/usr/bin/env python3
"""step_by_scope — one traced run of a benchmark cell, and its decode
and admission programs' device time by `jax.named_scope` stage (PERF.md
section 5's "one decode step by stage", which earlier PRs read by
hand).

Runs `benchmark/run.py --trace 1` of the checkout under `--root` (this
tree by default; a parent unpacked under build/parent the same way) in
this process, and before the cell's run directory goes reads its trace
once more with the benchmark's own `benchmark/metrics/_scoped_ops.py`:
every device operation inside a run of the configuration's "decode"
programs, and of its "prefill" (admission) programs, in the traced
window, filed under the innermost stage name of its `op_name` (`embed`,
`attn.*`, `mlp`, `moe.*`, `ssm.*`, `hc.*`, `pool.update`, `lm_head`;
since PR 53 `ssm.gmu`, `attn.diff` and `attn.kernel.cross` among them,
the Gated Memory Units, the differential maps' subtraction and norm,
and the layers that attend another layer's pages; since PR 55
`attn.fold`, in a third kind of program where the configuration's
`program.programs` names one, "fold"), or under "no scope". One `step_by_scope:` JSON line on stderr beside
the run's own lines, for each of the two kinds: runs, mean ms a run, ms
a run by stage, and the unscoped operations that took most (a weight's
`copy` shows there by name, as in the ledger's
`breakdown.device_ops`). `--out` appends the line to a file.

  chiprun -- python3 tools/step_by_scope.py --out chiprun_out/scope.jsonl \
      -- --workload command-a-plus-mixed12k --seed 2147486777
  chiprun -- python3 tools/step_by_scope.py --root build/parent ... -- ...
"""

import argparse
import bisect
import collections
import json
import os
import re
import sys

STAGE = re.compile(
    r"^(embed|mlp|lm_head|pool\.update|(attn|moe|ssm|hc)\.[a-z.]+)$")


def stage_of(where):
    """The innermost stage name in an operation's `op_name` path."""
    path = where.rsplit(" ", 1)[0].split("/")
    return next((p for p in reversed(path) if STAGE.match(p)), "no scope")


def folds_traced(obs):
    """{"decode": n, "piece": m}: the program's istpu.cache.fold spans
    that started in the traced seconds, by what they ran behind (a
    cache whose finished windows fold; {} for every other family)."""
    from infinistore_tpu.utils import profiling

    if obs is None or obs.trace_window is None:
        return {}
    t0, t1 = (t * 1e9 for t in obs.trace_window)
    return dict(collections.Counter(
        s.fields.get("during") for s in profiling.spans()
        if s.name == "istpu.cache.fold" and t0 <= s.t0_ns < t1))


def by_stage(ops, modules, window, needles):
    """The runs inside `window` of the programs whose name holds one of
    `needles`, and their operations' time by stage."""
    t0, t1 = window
    runs = sorted((s, s + d) for name, s, d in modules
                  if any(n in name for n in needles) and s >= t0
                  and s + d <= t1)
    starts = [r[0] for r in runs]
    stages = collections.Counter()
    unscoped = collections.Counter()
    for where, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        stage = stage_of(where)
        stages[stage] += d
        if stage == "no scope":
            unscoped[where.rsplit(" ", 1)[-1]] += d
    n = max(len(runs), 1)
    return {
        "runs": len(runs),
        "step_ms": round(sum(b - a for a, b in runs) / n / 1e6, 4),
        "stage_ms": {k: round(v / n / 1e6, 4)
                     for k, v in stages.most_common()},
        "unscoped_top_ms": {k: round(v / n / 1e6, 4)
                            for k, v in unscoped.most_common(8)},
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--out", default="")
    ap.add_argument("run", nargs=argparse.REMAINDER,
                    help="-- then benchmark/run.py's own arguments")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    out = args.out and os.path.abspath(args.out)
    run_args = [a for a in args.run if a != "--"]
    os.chdir(root)
    sys.path.insert(0, root)

    from benchmark import run
    from benchmark.lib import cell, serve, trace
    from benchmark.metrics import _scoped_ops

    close = cell.Cell.close

    def read_then_close(self):
        try:
            tdir = getattr(self, "trace_dir", None)
            path = tdir and trace.find_xplane(tdir)
            if path:
                ops, modules = _scoped_ops.scoped_events(path)
                window = trace.window_of(trace.read_xplane(path))
                line = json.dumps({
                    "root": args.root, "run": run_args,
                    **{kind: by_stage(ops, modules, window,
                                      serve.program_names(self.conf, kind))
                       for kind in ("decode", "prefill")},
                    # ... and, where the configuration names one (a
                    # cache whose finished windows fold, PR 55), the
                    # fold programs: `attn.fold`
                    **{kind: by_stage(ops, modules, window, names)
                       for kind, names in self.conf["program"].get(
                           "programs", {}).items()
                       if kind not in ("decode", "prefill")}})
                print("step_by_scope: " + line, file=sys.stderr, flush=True)
                print("folds_traced: " + json.dumps(folds_traced(
                    getattr(self, "traced", None))), file=sys.stderr,
                    flush=True)
                if out:
                    os.makedirs(os.path.dirname(out), exist_ok=True)
                    with open(out, "a") as f:
                        f.write(line + "\n")
        except Exception as e:  # the run's own result still counts
            print(f"step_by_scope: nothing read ({type(e).__name__}: {e})",
                  file=sys.stderr, flush=True)
        return close(self)

    measure = cell.Cell.measure

    def measure_and_keep(self, *a, **kw):
        self.traced = measure(self, *a, **kw)
        return self.traced

    cell.Cell.measure = measure_and_keep
    cell.Cell.close = read_then_close
    return run.main(run_args + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
