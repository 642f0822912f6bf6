#!/usr/bin/env python3
"""Chip tool, run once when the tolerance of a configuration with a
learned selection over K and V pages is set: the SECOND readings its
limit is set from (benchmark/tools/precision_reading_index.py's, for
benchmark/reference/keye_dsa.py, whose sublayers and faults differ).
Rows of logits of one prompt from the float32 reference against the
reference itself run (a) on matrices rounded to float8_e4m3fn, the
nearest precision below the configuration's bfloat16, (b) on the SAME
weights with the INDEX KEYS ALONE rounded to float8_e4m3fn before they
are scored (`INDEX_KEY_BITS`), (c) the same at bfloat16's 8 bits, (d)
PLANTED FAULTS, the reference's `FAULT`: every layer takes the newest
rows and not the best scored (`recent_rows`), every layer but the first
scores the first layer's index keys (`other_layer_keys`), the first
half of the index keys lie a page off (`stale_keys`), the lower half
of every selection is left out (`half_rows`) or twice the rows are
taken (`twice_rows`), q and k go unnormalised (`no_qk_norm`): what
`logit_tol` must tell from the served program. Rows are read at positions whose router choice is no
near-tie in any of the runs (`--margin`). ONE seed a process unless
`--seeds` names more; `--readings` names the readings wanted (each is
one pass of the reference); `--norms` adds the squared norms of the
stream and of what each sublayer writes to it, at the rows read.

    python3 benchmark/tools/precision_reading_kvi.py \
        --config keye-vl2-30b-a3b [--length 6400 --margin 0.005
         --seeds N,N --norms --readings half_rows,no_qk_norm,f8_weights]
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


# reading -> (INDEX_KEY_BITS, FAULT) of the reference's pass; float8
# weights come last: the pass rounds the parameters in place.
READINGS = {"f8_index_keys": ((4, 3), None),
            "bf16_index_keys": ((8, 7), None),
            "recent_rows": (None, "recent_rows"),
            "other_layer_keys": (None, "other_layer_keys"),
            "stale_keys": (None, "stale_keys"),
            "half_rows": (None, "half_rows"),
            "twice_rows": (None, "twice_rows"),
            "no_qk_norm": (None, "no_qk_norm"),
            "f8_weights": (None, None)}


def stream_norms(ref, run):
    """(rows, [per layer (|x|^2, |attention|^2, |feed-forward|^2)]):
    `run()` with the reference's sublayers wrapped, mean squared norms
    over the tokens of the LAST block each saw (the last layer: over
    the rows read)."""
    seen, kept = {}, {}

    def wrap(name):
        inner = kept[name] = getattr(ref, name)

        def outer(*a, **kw):
            out = inner(*a, **kw)
            y = out[0] if isinstance(out, tuple) else out
            import jax
            if not isinstance(y, jax.core.Tracer):
                seen.setdefault(name, []).append(
                    (float((a[0].astype("float32") ** 2).sum(-1).mean()),
                     float((y.astype("float32") ** 2).sum(-1).mean())))
            return out
        setattr(ref, name, outer)

    for name in ("_normed", "_attend", "_experts"):
        wrap(name)
    try:
        rows = run()
    finally:
        for name, inner in kept.items():
            setattr(ref, name, inner)
    return rows, seen


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="2147484101")
    ap.add_argument("--length", type=int, default=6400)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--margin", type=float, default=0.0005)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--norms", action="store_true")
    ap.add_argument("--readings", default=",".join(READINGS))
    args = ap.parse_args()
    wanted = args.readings.split(",")
    if set(wanted) - set(READINGS):
        ap.error(f"--readings: one of {', '.join(READINGS)}")

    import jax
    import numpy as np

    from benchmark.lib import serve

    conf = serve.load_config(f"benchmark/configs/{args.config}.json",
                             args.rehearsal)
    model, cfg = serve.model_config(conf)
    ref = serve.reference_module(conf)
    n = args.length
    positions = list(range(n - args.rows, n))

    def to_f8(w):
        if w.ndim < 2:
            return w
        return jax.lax.reduce_precision(w, exponent_bits=4, mantissa_bits=3)

    def run(params, bits=None, fault=None):
        ref.INDEX_KEY_BITS, ref.FAULT = bits, fault
        try:
            rows, margins = ref.forward(params, conf, toks, positions)
        finally:
            ref.INDEX_KEY_BITS = ref.FAULT = None
        return np.asarray(rows), np.asarray(margins).min(axis=1)

    for seed in (int(s) for s in args.seeds.split(",")):
        params = serve.init_weights(model, cfg, seed)
        toks = np.random.default_rng(seed).integers(
            0, cfg.vocab_size, n).astype(np.int32)
        out = {"seed": seed, "length": n, "rows": len(positions)}
        if args.norms:
            (want, m0), seen = stream_norms(ref, lambda: run(params))
            # every layer but the last runs a block at a time, the
            # last at the rows read alone (one call)
            blocks = (len(seen["_attend"]) - 1) // (cfg.n_layers - 1)
            out["stream_sq_norms"] = {
                "x_into_layer": [round(x, 4) for x, _ in
                                 seen["_normed"][blocks - 1::3 * blocks]
                                 ][:cfg.n_layers],
                "attention": [round(y, 5) for _, y in (
                    seen["_attend"][blocks - 1:-1:blocks]
                    + seen["_attend"][-1:])],
                "feed_forward": [round(y, 5) for _, y in (
                    seen["_experts"][blocks - 1:-1:blocks]
                    + seen["_experts"][-1:])]}
        else:
            want, m0 = run(params)
        got, margin = {}, m0
        for name in wanted:
            bits, fault = READINGS[name]
            if name == "f8_weights":
                params = jax.jit(
                    lambda p: jax.tree_util.tree_map(to_f8, p),
                    donate_argnums=0)(params)
            got[name], m = run(params, bits, fault)
            margin = np.minimum(margin, m)
        clear = margin >= args.margin
        out["clear_rows"] = int(clear.sum())
        for name, rows in got.items():
            diff = np.abs(rows - want).max(axis=1)
            out[name + "_max_logit_diff_clear"] = [
                round(float(d), 4) for d in diff[clear]]
            out[name + "_max_logit_diff_all"] = [
                round(float(d), 4) for d in diff]
        out["max_abs_logit"] = round(float(np.abs(want).max()), 3)
        out["device"] = jax.devices()[0].device_kind
        print("precision_reading_kvi: " + json.dumps(out), flush=True)
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
