#!/usr/bin/env python3
"""time_state_step — one state layer's recurrent update of a decode step
(ops/ssm.py `step`) alone on the chip, at granite4h-micro's shapes: 36
pools `h` [16, 64, 64, 128] float32 (33.5 MB each, 2.1 MB a slot; 36
because ONE pool chained on itself stays in the chip's 128 MiB of fast
memory from call to call: the whole update then read 43 us a call
where over 36 pools it reads 110; PERF.md, PR 52), by the form that
runs it and the slots that decode (1, 2, 3, 4, 8, 16 of 16, scattered:
the order is not the identity):

  whole   `ssm.step` over every slot, as the tree ran it until PR 52:
          the whole pool read and written whatever decodes
  ladder  `sparse_select.over_active`'s form (composed here; not in the
          tree): a `lax.switch` over `ladder(16)`, each branch a gather
          of the first n slots of the order, `ssm.step` over them and
          `.at[rows].set` into the donated pool
  kernel  `ssm.step_kernel`, the Pallas call the tree runs on a TPU:
          a grid of (the decoding slots in their order, head tiles of
          `ssm.head_tile`: 32 heads a block here; 16 and 64 read within
          2 us of it), the pool aliased

Time as tools/time_select_decode.py takes it: R sweeps over the layers
chained inside one jitted loop that carries the DONATED pools (each
call's x hangs on the y before by 1e-30 of it), wall time / (R x
layers), the best of a few repeats.
`pool_copies` is what the compiled one-call program's text holds of
`copy` / `copy-start` of the pool's shape (PR 25's and PR 43's way: a
scatter that XLA does not do in place shows there before any timing).
`state_gbps` = 2 x n x 2.1 MB / the call: what the decoding slots'
state needs, over the time. One JSON line a case; `--out` also writes
them to a file (under chiprun_out/ on the chip).

`--recurrence selective` (PR 53) times the other recurrence the tree
has, Mamba-1's selective step (ops/ssm.py `selective_step`), at
phi4-mini-flash's shapes: 36 pools `h` [16, 16, 5120] float32 (5.2 MB
each, 328 KB a slot), x and dt a channel, by `whole` (every slot in
XLA) and `kernel` (`selective_step_kernel`: a grid of the decoding
slots, a slot's state whole a step, the pool aliased).

  chiprun -- python3 tools/time_state_step.py --out chiprun_out/state_step.jsonl
"""

import argparse
import functools
import json
import os
import re
import sys
import time

SLOTS, HEADS, HEAD_DIM, STATE, LAYERS = 16, 64, 64, 128, 36
DECODING = (1, 2, 3, 4, 8, 16)


SELECTIVE_STATE, SELECTIVE_CHANNELS = 16, 5120


def selective_operands():
    """`operands` for the selective step: h [slots, N, C], x and dt a
    channel, A [N, C]."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    f32 = jnp.float32
    n, c = SELECTIVE_STATE, SELECTIVE_CHANNELS
    return {
        "h": [jax.random.normal(k, (SLOTS, n, c), f32)
              for k in jax.random.split(ks[0], LAYERS)],
        "x": jax.random.normal(ks[1], (SLOTS, c), jnp.bfloat16),
        "dt": jax.nn.softplus(jax.random.normal(ks[2], (SLOTS, c), f32)),
        "A": -jnp.exp(jax.random.normal(ks[3], (n, c), f32)),
        "B": jax.random.normal(ks[4], (SLOTS, n), jnp.bfloat16),
        "C": jax.random.normal(ks[5], (SLOTS, n), jnp.bfloat16),
    }


def selective_forms(ssm):
    """name: fn(h, x, dt, A, B, C, rows) -> (y, h), the selective
    step's."""
    import jax
    import jax.numpy as jnp

    def whole(h, x, dt, A, B, C, rows):
        return ssm.selective_step(h, x, dt, A, B, C)

    def kernel(h, x, dt, A, B, C, rows):
        f32 = jnp.float32
        with jax.named_scope("ssm.step"):
            y, h = ssm.selective_step_kernel(
                h, x.astype(f32), dt, A, B.astype(f32), C.astype(f32),
                *rows[1:])
            return jnp.where(rows[0][:, None], y, 0.0), h

    return {"whole": whole, "kernel": kernel}


def operands():
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    f32 = jnp.float32
    return {
        "h": [jax.random.normal(k, (SLOTS, HEADS, HEAD_DIM, STATE), f32)
              for k in jax.random.split(ks[0], LAYERS)],
        "x": jax.random.normal(ks[1], (SLOTS, HEADS, HEAD_DIM),
                               jnp.bfloat16),
        "dt": jax.nn.softplus(jax.random.normal(ks[2], (SLOTS, HEADS), f32)),
        "A": -jnp.exp(jax.random.normal(ks[3], (HEADS,), f32)),
        "B": jax.random.normal(ks[4], (SLOTS, STATE), jnp.bfloat16),
        "C": jax.random.normal(ks[5], (SLOTS, STATE), jnp.bfloat16),
    }


def forms(ssm, ss):
    """name: fn(h, x, dt, A, B, C, rows) -> (y, h)."""
    import jax
    import jax.numpy as jnp

    def whole(h, x, dt, A, B, C, rows):
        return ssm.step(h, x, dt, A, B, C)

    def ladder(h, x, dt, A, B, C, rows):
        _, order, count = rows
        b = h.shape[0]
        rungs = ss.ladder(b)
        rung = sum((count[0] > n).astype(jnp.int32) for n in rungs[:-1])

        def at(n):
            def run(h, x, dt, B, C):
                take = order[:n]
                y, new = ssm.step(h[take], x[take], dt[take], A, B[take],
                                  C[take])
                return (jnp.zeros((b, *y.shape[1:]), y.dtype)
                        .at[take].set(y), h.at[take].set(new))
            return run if n < b else (
                lambda h, x, dt, B, C: ssm.step(h, x, dt, A, B, C))

        return jax.lax.switch(rung, [at(n) for n in rungs], h, x, dt, B, C)

    def kernel(h, x, dt, A, B, C, rows):
        f32 = jnp.float32
        with jax.named_scope("ssm.step"):
            y, h = ssm.step_kernel(
                h, jnp.exp(dt * A), x.astype(f32) * dt[..., None],
                B.astype(f32), C.astype(f32), *rows[1:])
            return jnp.where(rows[0][:, None, None], y, 0.0), h

    return {"whole": whole, "ladder": ladder, "kernel": kernel}


def pool_copies(fn, ops, rows):
    """`copy` and `copy-start` instructions of the pool's shape in the
    optimized text of ONE call with the pool donated."""
    import jax

    text = jax.jit(fn, donate_argnums=0).lower(
        ops["h"][0], *(ops[k] for k in ("x", "dt", "A", "B", "C")),
        rows).compile().as_text()
    shape = "f32[%s]" % ",".join(map(str, ops["h"][0].shape))
    return len(re.findall(
        r"= \(?" + re.escape(shape) + r"[^=]* copy(-start)?\(", text))


def time_chain(fn, ops, rows, reps, rounds):
    """us a call, `reps` sweeps over the layers' pools chained on the
    pools and on x."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def chain(pools, x, dt, A, B, C, rows):
        def one(_, carry):
            pools, x = carry
            out = []
            for h in pools:
                y, h = fn(h, x, dt, A, B, C, rows)
                x = x + (jnp.sum(y) * 1e-30).astype(x.dtype)
                out.append(h)
            return out, x
        return jax.lax.fori_loop(0, reps, one, (pools, x))

    rest = [ops[k] for k in ("x", "dt", "A", "B", "C")] + [rows]
    pools, _ = jax.block_until_ready(chain(ops["h"], *rest))
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        pools, _ = jax.block_until_ready(chain(pools, *rest))
        best = min(best, time.perf_counter() - t0)
    ops["h"] = pools  # the donated pools' successors
    return best / (reps * len(pools)) * 1e6


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--recurrence", choices=("mamba2", "selective"),
                    default="mamba2")
    ap.add_argument("--out")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu.ops import sparse_select as ss
    from infinistore_tpu.ops import ssm

    if jax.default_backend() != "tpu":
        sys.exit("time_state_step: no TPU; a CPU time is not a device time")
    if args.recurrence == "selective":
        ops, todo = selective_operands(), selective_forms(ssm)
    else:
        ops, todo = operands(), forms(ssm, ss)
    slot_bytes = ops["h"][0][0].nbytes
    lines = []
    for name, fn in todo.items():
        for n in DECODING:
            # scattered, from the top down: the order is no identity
            valid = np.zeros(SLOTS, bool)
            valid[np.random.default_rng(n).permutation(SLOTS)[:n]] = True
            rows = ssm.decoding(jnp.asarray(valid))
            try:
                us = time_chain(fn, ops, rows, args.reps, args.rounds)
                row = {"call_us": round(us, 2),
                       "state_gbps": round(2 * n * slot_bytes / us / 1e3, 1),
                       "pool_copies": pool_copies(fn, ops, rows)}
            except Exception as e:  # a form the compiler refuses
                row = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            row = {"recurrence": args.recurrence, "form": name,
                   "decoding": n, **row,
                   "device": jax.devices()[0].device_kind}
            lines.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
