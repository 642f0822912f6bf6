#!/usr/bin/env python3
"""time_gap_counters — what the always-on gap counters (PR 51:
`ServingEngine._gap_mark`, `_emit`, `_count_gaps`) cost the engine
thread a landed decode step, on the machine that runs it, with the
profiler off and on.

A tiny engine (no program runs) whose `--slots` slots all emit one
token a "land", as `_land` does it: under an `istpu.model.decode` span,
one mark, an `_emit` a slot, `_count_gaps` with the span's fields.
Against it the same land without the counters: the span, and `_emit`
as it was before (`emit_before`: a call a slot too, which is what a
profiler session's Python tracer charges for). Wall time of
`--lands` lands / lands, the best of `--repeats`; then both again
inside a `jax.profiler` session (a span is then a TraceAnnotation that
records). One JSON line a case.

  chiprun -- python3 tools/time_gap_counters.py --out chiprun_out/gap_counters.jsonl
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def emit_before(slot, tokens):
    """`ServingEngine._emit` as it was before the counters."""
    slot.generated.extend(tokens)
    cb = slot.work.req.on_token
    if cb is not None:
        rid = slot.work.req.request_id
        for t in tokens:
            cb(rid, t)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="1,4,16")
    ap.add_argument("--lands", type=int, default=20000)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out")
    args = ap.parse_args()

    import jax

    from infinistore_tpu.models import llama
    from infinistore_tpu.serving import (
        Request, ServingConfig, ServingEngine, _Slot, _Work)

    cfg = llama.LlamaConfig(vocab_size=128, d_model=64, n_layers=1,
                            n_heads=2, n_kv_heads=1, d_ff=128, max_seq=64,
                            page_size=8, dtype="float32")
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    device = f"{jax.devices()[0].platform}:{jax.devices()[0].device_kind}"
    lines = []

    def lands_ns(land, slots, n):
        best = None
        for _ in range(args.repeats):
            for s in slots:
                s.generated.clear()
            t = time.perf_counter_ns()
            for _ in range(n):
                land()
            ns = (time.perf_counter_ns() - t) / n
            best = ns if best is None else min(best, ns)
        return best

    for n_slots in (int(x) for x in args.slots.split(",")):
        eng = ServingEngine(params, cfg, ServingConfig(
            model_id="gap-cost", max_slots=n_slots, total_pages=8))
        slots = [_Slot(work=_Work(req=Request(f"r{i}", [1], 1 << 30),
                                  prompt=[1]), page_ids=[], seq_len=1)
                 for i in range(n_slots)]

        def bare():
            with eng._span("istpu.model.decode", program="land"):
                for s in slots:
                    emit_before(s, [5])

        def counted():
            with eng._span("istpu.model.decode", program="land") as df:
                at = eng._gap_mark()
                for s in slots:
                    eng._emit(s, [5], at)
                df["waiting"] = 0
                eng._count_gaps(at, df)
                eng._landed_at = at

        for profiler in (False, True):
            n = args.lands // (10 if profiler else 1)
            if profiler:
                tdir = tempfile.mkdtemp(prefix="gap_counters_")
                jax.profiler.start_trace(tdir)
            try:
                a, b = lands_ns(bare, slots, n), lands_ns(counted, slots, n)
            finally:
                if profiler:
                    jax.profiler.stop_trace()
            lines.append({"case": "land", "slots": n_slots,
                          "profiler": profiler, "lands": n,
                          "bare_ns": round(a), "counted_ns": round(b),
                          "added_ns": round(b - a), "device": device})
            print(json.dumps(lines[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
