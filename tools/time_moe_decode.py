#!/usr/bin/env python3
"""time_moe_decode — one layer's routed experts over a decode step's
rows, on the chip, by the form that runs them.

ROWS rows (16; 8 at xing4-29b's shape, its engine's slots) of which 1,
2, 4, 8, 16 are valid (hold a token; the other forms' time does not
depend on that, so they run once), each valid row's `k` experts drawn
without replacement from a seeded generator, x the three shapes the
benchmark's sparse cells run:

  mixtral8x7b      8 experts of 4,096 x 14,336, 2 a token, SwiGLU
  smallthinker21b  64 experts of 2,560 x 768, 6 a token, ReGLU
  xing4-29b        64 experts of 3,584 x 1,024, 4 a token, SwiGLU

x the forms of models/moe.py:

  dense     `experts_dense`: every row through every expert
  sorted    `experts_sorted`: pairs sorted by expert, `ragged_dot`
  gathered  `experts_gathered`: ops/pallas_moe_decode.py, the experts
            some valid row chose and no others (a tree without it, a
            parent under `--root`, skips the form)
  capacity  mixtral8x7b's own block `_moe_mlp` held to its [T, E, C]
            dispatch (its norm and router inside)

Time as tools/time_paged_decode.py takes it: R calls chained inside one
jitted loop (a call's rows are the call before's output, scaled down).
`kernel_us` is the device's own time of one call, from one traced run:
the operations on the device's "XLA Ops" line (the loop's own `while`
left out), summed and divided by R; `call_us` is wall time / R, the best
of a few untraced repeats. `fetched` is the experts the gathered form
fetches (`live_experts`), `floor_us` their bytes (all E for the other
forms) at the HBM's rate. One JSON line a case; `--out` also writes
them to a file (under chiprun_out/ on the chip).

  chiprun -- python3 tools/time_moe_decode.py --out chiprun_out/moe.jsonl
  chiprun -- python3 tools/time_moe_decode.py --root build/parent ...
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

HBM_GBPS = 819.0
VALID = (1, 2, 4, 8, 16)
# name: (experts, a token, d, f, rows, gate activation)
SHAPES = {
    "mixtral8x7b": (8, 2, 4096, 14336, 16, "silu"),
    "smallthinker21b": (64, 6, 2560, 768, 16, "relu"),
    "xing4-29b": (64, 4, 3584, 1024, 8, "silu"),
}
FORMS = ("dense", "sorted", "gathered", "capacity")


def device_us(trace_dir):
    """Summed duration (us) of the operations on the first device
    plane's "XLA Ops" line, the loop's own `while` left out."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                return sum(ev.duration_ns for ev in line.events
                           if not ev.name.lstrip("%").startswith("while")
                           ) / 1e3
    return None


def operands(shape):
    import jax
    import jax.numpy as jnp

    E, k, d, f, rows, _ = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(E * d), 5)
    bf = jnp.bfloat16
    layer = {
        "e_gate": jax.random.normal(ks[0], (E, d, f), bf) * d ** -0.5,
        "e_up": jax.random.normal(ks[1], (E, d, f), bf) * d ** -0.5,
        "e_down": jax.random.normal(ks[2], (E, f, d), bf) * f ** -0.5,
        "router": jax.random.normal(ks[3], (d, E), jnp.float32) * d ** -0.5,
        "ln2": jnp.ones(d, bf),
    }
    return layer, jax.random.normal(ks[4], (rows, d), bf)


def routing(shape, valid_rows):
    """(top_idx [rows, k], gates [rows, k], valid [rows]): every row's
    experts drawn without replacement; the first `valid_rows` valid."""
    import numpy as np

    E, k, _, _, rows, _ = SHAPES[shape]
    rng = np.random.default_rng(1000 * E + valid_rows)
    top_idx = np.stack([rng.choice(E, k, replace=False)
                        for _ in range(rows)]).astype(np.int32)
    gates = rng.dirichlet(np.ones(k), rows).astype(np.float32)
    return top_idx, gates, np.arange(rows) < valid_rows


def time_case(moe, shape, form, valid_rows, ops, reps, rounds):
    import jax
    import jax.numpy as jnp
    import numpy as np

    E, k, d, f, rows, act_name = SHAPES[shape]
    act = jax.nn.relu if act_name == "relu" else jax.nn.silu
    layer, u0 = ops
    top_idx, gates, valid = map(jnp.asarray, routing(shape, valid_rows))
    fetched = E
    if form == "gathered":
        from infinistore_tpu.ops import pallas_moe_decode

        fetched = int(pallas_moe_decode.live_experts(
            top_idx, gates, valid, E)[2])
    if form == "capacity":
        cfg = moe.MoEConfig(d_model=d, d_ff=f, n_experts=E, top_k=k,
                            capacity_factor=E / k, dtype="bfloat16")

    def one(layer, u):
        if form == "capacity":
            return moe._moe_mlp(layer, u[None], cfg, valid[None])[0][0]
        if form == "gathered":
            return moe.experts_gathered(layer, u, top_idx, gates, act,
                                        valid)[0]
        return getattr(moe, "experts_" + form)(layer, u, top_idx, gates, act)

    @jax.jit
    def chain(layer, u):
        return jax.lax.fori_loop(
            0, reps, lambda _, u: u0 + one(layer, u) * 1e-3, u)

    chain(layer, u0).block_until_ready()
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        chain(layer, u0).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        chain(layer, u0).block_until_ready()
        jax.profiler.stop_trace()
        device = device_us(tdir)
    return {
        "shape": shape, "form": form, "rows": rows, "valid": valid_rows,
        "experts": E, "fetched": fetched,
        "distinct_chosen": len(set(np.asarray(top_idx)[:valid_rows].ravel())),
        "kernel_us": round(device / reps, 2) if device else None,
        "call_us": round(best / reps * 1e6, 2),
        "floor_us": round(fetched * 3 * d * f * 2 / HBM_GBPS / 1e3, 2),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--valid", default=",".join(map(str, VALID)))
    ap.add_argument("--block-mb", type=int, help="the gathered kernel's "
                    "weight blocks a grid step, MiB (to choose the constant)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    import jax

    from infinistore_tpu.models import moe

    if jax.default_backend() != "tpu":
        sys.exit("time_moe_decode: no TPU; a CPU time is not a kernel time")
    gathered_rows = getattr(moe, "GATHERED_EXPERTS_MAX_ROWS", None)
    if args.block_mb:
        from infinistore_tpu.ops import pallas_moe_decode

        pallas_moe_decode._WEIGHT_BLOCK_BYTES = args.block_mb << 20
    lines = []
    for shape in args.shapes.split(","):
        ops = operands(shape)
        for form in args.forms.split(","):
            if form == "capacity" and shape != "mixtral8x7b":
                continue
            if form == "gathered" and not hasattr(moe, "experts_gathered"):
                continue
            # `_moe_mlp` held to its capacity dispatch
            moe.GATHERED_EXPERTS_MAX_ROWS = (
                0 if form == "capacity" else gathered_rows)
            for valid_rows in map(int, args.valid.split(",")):
                # only the gathered form's time depends on the valid rows
                if valid_rows > SHAPES[shape][4] or (
                        form != "gathered" and valid_rows != SHAPES[shape][4]):
                    continue
                row = time_case(moe, shape, form, valid_rows, ops, args.reps,
                                args.rounds)
                row["device"] = jax.devices()[0].device_kind
                if args.block_mb:
                    row["block_mb"] = args.block_mb
                lines.append(row)
                print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
