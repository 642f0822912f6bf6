#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

One new process a run. Needs a TPU and at least the chips the cell asks
for: anything else exits non-zero before building anything and prints no
result. The last line of stdout is one JSON object (correct, attempted,
failed, metrics, device, and with --trace 1 breakdown); everything else
is on earlier lines. --trace 0 reports the cell's end-to-end metrics,
--trace 1 its per-layer metrics from a run of their own.

    --rehearsal   tiny widths on the CPU backend, lengths cut by 8: finds
                  wrong paths and control flow; NOT a chip run, prints no
                  device metric and no result the driver would read.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


COMPARED = []  # the "correct:" lines, said again on stderr at the end


def log(msg):
    print(msg, flush=True)
    if msg.startswith("correct: "):
        COMPARED.append(msg)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="override the traffic file's session rate "
                         "(sweeps only; never in a measured run)")
    ap.add_argument("--keep-trace", default="",
                    help="directory to copy the reduced plain trace to")
    args = ap.parse_args(argv)

    from benchmark.lib import manifest

    bench = manifest.load()
    cell = manifest.cell_of(bench, args.workload)
    config_entry = manifest.config_of(bench, cell["config"])
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    import jax

    devs = jax.devices()
    backend = jax.default_backend()
    log(f"benchmark: platform={devs[0].platform} "
        f"kind={devs[0].device_kind!r} count={len(devs)} "
        f"jax={jax.__version__} cell={cell['name']} seed={args.seed} "
        f"seconds={seconds} trace={args.trace}")
    if args.rehearsal:
        if backend != "cpu":
            print(f"benchmark: --rehearsal needs the CPU backend, found "
                  f"{backend!r}", file=sys.stderr)
            return 2
        log("benchmark: CPU REHEARSAL at tiny widths - NOT a chip run; "
            "no device metric is printed")
    elif backend != "tpu":
        print(f"benchmark: needs a TPU, JAX found backend {backend!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} "
              f"chips, JAX found {len(devs)}", file=sys.stderr)
        return 2

    from benchmark.lib import peaks, serve, stats, trace
    from benchmark.lib.cell import Cell
    from benchmark.lib.store import BenchFailure

    peak = None if args.rehearsal else peaks.peaks(devs[0].device_kind)
    c = Cell(cell, config_entry, args.seed, args.rehearsal, log)
    result = None
    try:
        c.setup()
        sample_ok = c.warm_and_check()
        obs = c.measure(seconds, trace=bool(args.trace), rate=args.rate)
        obs.setup_s = obs.window[0] - T_START
        obs.peaks = peak
        healthy, health = c.health()
        due = obs.due_in_window()
        failed = [r for r in due if obs.failed(r)]
        late = [r["sent"] - r["due"] for r in due if r["sent"] is not None]
        n_ttft = len(obs.ttfts_ms())
        gaps = obs.gaps_ms()
        log("window: " + json.dumps({
            "attempted": len(due), "failed": len(failed),
            "unfinished_at_end": sum(1 for r in due if not r["ended"]),
            "ttft_samples": n_ttft,
            "samples_beyond_p95": stats.samples_beyond(n_ttft, 0.95),
            "highest_supported_percentile":
                stats.highest_supported_percentile(n_ttft),
            "token_gaps": len(gaps),
            "generator_late_ms_p50": round(
                (stats.quantile(late, 0.5) or 0) * 1e3, 2),
            "generator_late_ms_max": round(max(late, default=0) * 1e3, 2),
            "compilations_in_window": c.compiled_in_window,
            "of_them_not_from_cache": c.built_in_window,
            "counters": obs.counters,
            "store": {k: obs.store_delta.get(k) for k in
                      ("evictions", "bytes_in", "bytes_out", "ops")},
            "first_errors": [r["error"] for r in failed[:3]],
            **health,
        }))
        # Every TTFT of the window by turn, and the gap quantiles: what
        # any other statistic of this run can be worked out from.
        log("samples: " + json.dumps({
            "ttft_ms_by_turn": [[r["turn"], round(
                (r["token_times"][0] - r["due"]) * 1e3, 1)]
                for r in due if r["token_times"]],
            "gap_ms_quantiles": {str(q): stats.quantile(gaps, q) for q in
                                 (0.5, 0.9, 0.95, 0.99)},
            "gap_ms_mean": sum(gaps) / len(gaps) if gaps else None,
        }))
        device = serve.device_report(c.devices)
        breakdown = None
        if args.trace:
            path = trace.find_xplane(c.trace_dir)
            if path is None:
                raise BenchFailure("the profiler wrote no trace")
            plain = trace.read_xplane(path)
            log("trace: " + json.dumps({
                "file_mb": round(os.path.getsize(path) / 2 ** 20, 1),
                "lines": plain["lines"],
                "host_spans": len(plain["host"])})[:3000])
            obs.trace = trace.reduce(plain)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                keep = dict(plain)
                keep.pop("lines")
                with open(os.path.join(args.keep_trace,
                                       f"{cell['name']}.plain.json"),
                          "w") as f:
                    json.dump(keep, f)
            if not args.rehearsal:
                device["busy_s"] = obs.trace["busy_s"]
                device["window_s"] = obs.trace["window_s"]
                breakdown = {"device_ops": obs.trace["device_ops"],
                             "idle_gaps": obs.trace["idle_gaps"]}
        kind = "per_layer" if args.trace else "end_to_end"
        metrics = {}
        for m in manifest.metrics_for(bench, cell["name"], kind):
            if args.rehearsal and m["source"] == "device_trace":
                continue  # no CPU number under a device metric's name
            value = manifest.reader(m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if args.trace and not args.rehearsal and not device["busy_s"] > 0:
            raise BenchFailure("no operation ran on the device in the "
                               "traced seconds")
        result = {"correct": bool(sample_ok and healthy),
                  "attempted": len(due), "failed": len(failed),
                  "metrics": metrics, "device": device}
        if breakdown is not None:
            result["breakdown"] = breakdown
        if args.rehearsal:
            result["rehearsal"] = True
    except BenchFailure as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        rc, leaked = c.close()
        if leaked:
            log(f"benchmark: /dev/shm leftovers removed: {leaked}")
    # Each number compared beside its limit, as the last lines of stderr
    # too: where a run is not correct the driver keeps the end of that.
    for msg in COMPARED:
        print(msg, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
