#!/usr/bin/env python3
"""Sandbox script (no chip): compile a configuration's decode program
and its largest cold- and hit-admission programs for a DESCRIBED v5e at
the real widths, and print each one's memory_analysis(). `total_pages`
in the configuration files was taken from this output.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py \
        --config mistral7b --total-pages 3072,2560 --traffic sessions

The pools are the engine's own: the shapes ServingEngine's constructor
gives them (jax.eval_shape, nothing allocated). While the programs are
lowered jax.default_backend answers "tpu", so that the attention
wrappers take the branch the chip runs (the Pallas kernels) and not
the CPU's gather path. Nothing runs; a compile that passes is not a
chip run.
"""

import argparse
import contextlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


@contextlib.contextmanager
def backend_answers_tpu():
    """The program asks jax.default_backend() which attention path to
    trace; the sandbox's backend is the CPU, the program compiled here
    is the chip's."""
    import jax

    asked = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = asked


def engine_pools(model, cfg, sconfig):
    """Shapes of the device arrays a ServingEngine of this
    configuration holds, by name, from its own constructor: traced by
    jax.eval_shape over a placeholder for the weights (the pools do not
    depend on them), so nothing of the pool's size is allocated."""
    import jax
    import jax.numpy as jnp

    from infinistore_tpu.serving import ServingEngine

    weights = {"placeholder": jnp.zeros(())}

    def build():
        eng = ServingEngine(weights, cfg, sconfig, model=model)
        return {k: v for k, v in vars(eng).items()
                if isinstance(v, jax.Array) and v.ndim > 0}

    return jax.eval_shape(build)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="sessions")
    ap.add_argument("--total-pages", default="")
    ap.add_argument("--programs", default="decode,cold,prefix")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import serve, traffic
    from infinistore_tpu import serving

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    conf = serve.load_config(f"benchmark/configs/{args.config}.json")
    model, cfg = serve.model_config(conf)
    spec = traffic.load(f"benchmark/traffic/{args.traffic}.json")
    shapes = traffic.shapes(spec, cfg.page_size)
    s = conf["serving"]
    pages = [int(x) for x in args.total_pages.split(",") if x] \
        or [s["total_pages"]]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda k: model.init_params(k, cfg),
                       jax.ShapeDtypeStruct((2,), jnp.uint32)),
    )
    weight_bytes = sum(
        int(x.size) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(params)
    )
    i32 = jnp.int32
    cold = max(shapes["cold"])
    sfx, pfx = max(shapes["prefix"], key=lambda p: p[0] * (p[0] + p[1]),
                   default=(0, 0))
    for total in pages:
        held = engine_pools(model, cfg, serve.serving_config(
            dict(conf, serving=dict(s, total_pages=total)), args.config))
        k_pool, v_pool = (sds(held[k].shape, held[k].dtype)
                          for k in ("k_pages", "v_pages"))
        print(json.dumps({
            "config": args.config, "weights_bytes": weight_bytes,
            "total_pages": total, "engine_holds": {
                k: [list(v.shape), str(v.dtype)] for k, v in held.items()},
            "pool_bytes": sum(v.size * v.dtype.itemsize
                              for v in held.values())}), flush=True)
        slots = sds((s["max_slots"],), i32)
        rows = sds((s["max_slots"], s["max_pages_per_seq"]), i32)
        ids = sds((s["max_pages_per_seq"],), i32)
        n = pfx // cfg.page_size
        # what a store call returns for n pages: every pool's page, in
        # page-major order (serving._admit_fused_px)
        restored = sds((n * (k_pool.shape[0] + v_pool.shape[0]),
                        *k_pool.shape[2:]), k_pool.dtype)
        programs = {
            "decode": lambda: serving._decode_fused.lower(
                params, cfg, slots, slots, k_pool, v_pool, rows,
                model=model),
            "cold": lambda: serving._admit_fused.lower(
                params, cfg, sds((1, cold), i32), k_pool, v_pool, ids,
                sds((), i32), model=model),
            "prefix": lambda: serving._admit_fused_px.lower(
                params, cfg, sds((1, sfx), i32), restored, k_pool, v_pool,
                sds((n,), i32), ids, sds((), i32), sds((), i32),
                model=model),
        }
        for name in args.programs.split(","):
            t0 = time.perf_counter()
            try:
                with backend_answers_tpu():
                    lowered = programs[name]()
                ma = lowered.compile().memory_analysis()
                out = {
                    "arguments": ma.argument_size_in_bytes,
                    "outputs": ma.output_size_in_bytes,
                    "aliased": ma.alias_size_in_bytes,
                    "temporaries": ma.temp_size_in_bytes,
                }
                out["total_live"] = (out["arguments"] + out["outputs"]
                                     - out["aliased"] + out["temporaries"])
            except Exception as e:  # the compiler's refusal is the answer
                out = {"refused": f"{type(e).__name__}: {str(e)[:400]}"}
            out.update(program=name, total_pages=total,
                       shape={"decode": s["max_slots"], "cold": cold,
                              "prefix": [sfx, pfx]}[name],
                       compile_s=round(time.perf_counter() - t0, 1))
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
