#!/usr/bin/env python3
"""Sandbox script (no chip): compile a configuration's decode program
and its largest cold- and prefix-prefill programs for a DESCRIBED v5e at
the real widths, and print each one's memory_analysis(). `total_pages`
in the configuration files was taken from this output.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory.py \
        --config mistral7b --total-pages 3072,2560 --traffic sessions

Nothing runs; a compile that passes is not a chip run.
"""

import argparse
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
    print(json.dumps({"config": args.config, "weights_bytes": weight_bytes,
                      "kv_bytes_per_token": 2 * cfg.n_layers
                      * cfg.n_kv_heads * cfg.head_dim * 2}), flush=True)
    i32 = jnp.int32
    cold = max(shapes["cold"])
    sfx, pfx = max(shapes["prefix"], key=lambda p: p[0] * (p[0] + p[1]))
    for total in pages:
        pool = sds((cfg.n_layers, total, *cfg.kv_page_shape()), cfg.jdtype)
        slots = sds((s["max_slots"],), i32)
        rows = sds((s["max_slots"], s["max_pages_per_seq"]), i32)
        kv = sds((1, pfx, cfg.n_kv_heads, cfg.head_dim), cfg.jdtype)
        programs = {
            "decode": lambda: serving._decode_fused.lower(
                params, cfg, slots, slots, pool, pool, rows, model=model),
            "cold": lambda: serving._admit_fused.lower(
                params, cfg, sds((1, cold), i32), pool, pool,
                sds((s["max_pages_per_seq"],), i32), sds((), i32),
                model=model),
            "prefix": lambda: serving._prefill_px_jit.lower(
                params, cfg, sds((1, sfx), i32),
                [(kv, kv)] * cfg.n_layers, sds((), i32), model=model),
        }
        for name in args.programs.split(","):
            t0 = time.perf_counter()
            try:
                ma = programs[name]().compile().memory_analysis()
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
