"""Prefill-kernel roofline sweep (VERDICT r4 item 5).

Measures, on the attached chip, everything needed to judge the flash
prefill kernel's S=4096 causal GQA MFU against what the hardware can
actually deliver on that shape — not against the chip's marketing peak:

  1. the kernel at a grid of (block_q, block_k) geometries, causal;
  2. the same kernel NON-causal (no mask work, full rectangle) — the
     upper bound for the softmax+matmul pipeline at this shape;
  3. a pure-matmul proxy doing the kernel's exact MXU work per tile
     ([BQ,D]x[D,BK] logits + [BQ,BK]x[BK,D] PV, fp32 accumulate, no
     softmax, no mask) — the MXU ceiling once every VPU op is deleted.

MFU accounting matches bench.py's _bench_prefill_kernel: causal FLOPs =
2*S^2*H*hd (half rectangle x2 matmuls x2 FLOP/MAC), non-causal/matmul =
4*S^2*H*hd, against the published bf16 peak of the device that answers
(infinistore_tpu.tpu.DEVICE_PEAKS; an unknown device fails). All timings
use the two-length slope estimator ending in a value pull (see
bench.py:_slope_time).

Run on the chip: PYTHONPATH=. python docs/prefill_sweep.py   (prints one
JSON line per config, then a summary line; all inputs device-generated).
"""

import functools
import json
import sys
import time


def _slope(build, n_short=4, n_long=16, reps=3):
    def best(n):
        run = build(n)
        run()
        b = None
        for _ in range(reps):
            t0 = time.perf_counter()
            run()
            t = time.perf_counter() - t0
            b = t if b is None else min(b, t)
        return b

    return max((best(n_long) - best(n_short)) / (n_long - n_short), 1e-9)


def main(seq=4096, n_heads=16, n_kv=8, hd=128):
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.ops.pallas_flash_attention import (
        flash_prefill_attention,
    )
    from infinistore_tpu.tpu import device_peaks, enable_compile_cache

    dev = jax.devices()[0]
    peak = device_peaks(dev)["bf16_flops"]
    enable_compile_cache()
    with jax.default_device(dev):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, seq, n_heads, hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, seq, n_kv, hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, seq, n_kv, hd), jnp.bfloat16)

        def kernel_build(bq, bk, causal):
            def chained(q, k, v, n):
                def body(carry, _):
                    return flash_prefill_attention(
                        carry, k, v, causal=causal, block_q=bq, block_k=bk
                    ), None

                out, _ = jax.lax.scan(body, q, None, length=n)
                return jnp.sum(out.astype(jnp.float32))

            return lambda n: (
                lambda f=jax.jit(lambda q, k, v: chained(q, k, v, n)):
                (lambda: float(f(q, k, v)))
            )()

        results = {}
        for bq, bk in ((512, 512), (512, 1024), (1024, 512), (1024, 1024),
                       (2048, 512), (2048, 1024)):
            if bq > seq or bk > seq:
                continue
            for causal in (True, False):
                flops = (2 if causal else 4) * seq * seq * n_heads * hd
                try:
                    t = _slope(kernel_build(bq, bk, causal))
                    mfu = round(100 * flops / t / peak, 2)
                    key = f"{'causal' if causal else 'dense'}_{bq}x{bk}"
                    results[key] = {"ms": round(t * 1e3, 3), "mfu": mfu}
                    print(json.dumps({key: results[key]}), flush=True)
                except Exception as e:
                    print(json.dumps({f"{bq}x{bk}": str(e)[:120]}),
                          flush=True)

        # Pure-matmul proxy: the kernel's MXU work per (BQ=1024, BK=1024)
        # tile pair with nothing else — logits then PV, f32 accumulate.
        # Chained through the carry so XLA cannot hoist it.
        bq = bk = 1024
        tiles = (seq // bq) * (seq // bk) * n_heads

        def mm_build(n):
            a = jax.random.normal(ks[0], (bq, hd), jnp.bfloat16)
            b = jax.random.normal(ks[1], (bk, hd), jnp.bfloat16)

            def body(carry, _):
                logits = jax.lax.dot_general(
                    carry, b, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                o = jax.lax.dot_general(
                    logits.astype(jnp.bfloat16), b,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return o.astype(jnp.bfloat16), None

            def prog(a):
                out, _ = jax.lax.scan(body, a, None, length=n * tiles)
                return jnp.sum(out.astype(jnp.float32))

            f = jax.jit(prog)
            return lambda: float(f(a))

        t = _slope(mm_build, 1, 3)
        mm_flops = 4 * bq * bk * hd * tiles
        results["matmul_proxy"] = {
            "ms": round(t * 1e3, 3),
            "mfu": round(100 * mm_flops / t / peak, 2),
        }
        print(json.dumps({"matmul_proxy": results["matmul_proxy"]}),
              flush=True)
        print(json.dumps({"summary": results}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
