#!/usr/bin/env python3
"""aot_memory.py for a configuration with state layers: compile its
decode program, its largest cold- and hit-admission programs and its
snapshot programs for a DESCRIBED v5e at the real widths, and print
each one's memory_analysis(). aot_memory.py lowers the three programs
of the families without state by name and takes the engine's pools to
be its two page pools; this one lowers the `_st` programs and takes the
state pools and their boundary copies too. `total_pages` of
benchmark/configs/granite4h-micro.json was taken from this output.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_state.py \
        --config granite4h-micro --traffic sessions4k --total-pages 8192

Nothing runs; a compile that passes is not a chip run.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools.aot_memory import backend_answers_tpu  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="sessions4k")
    ap.add_argument("--total-pages", default="")
    ap.add_argument("--programs",
                    default="decode,cold,prefix,boundary,snapshot")
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

    def described(tree):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(
        lambda k: model.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    weight_bytes = sum(int(x.size) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    i32 = jnp.int32
    cold = max(shapes["cold"])
    sfx, pfx = max(shapes["prefix"], key=lambda p: p[0] * (p[0] + p[1]))
    n = pfx // cfg.page_size
    state = described(jax.eval_shape(
        lambda: model.state_pools(cfg, s["max_slots"])))
    state_bytes = sum(int(x.size) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    row = serving._snapshot_row_elems(cfg)
    snap = sds((cfg.n_state_layers, row), cfg.state_jdtype)
    rows_a_chunk = max(1, serving.OFFLOAD_CHUNK_BYTES
                       // (row * cfg.state_jdtype.itemsize))
    for total in pages:
        pool = sds((cfg.n_kv_layers, total, *cfg.kv_page_shape()),
                   cfg.jdtype)
        print(json.dumps({
            "config": args.config, "weights_bytes": weight_bytes,
            "total_pages": total,
            "page_pool_bytes": 2 * pool.size * pool.dtype.itemsize,
            "state_pool_bytes": state_bytes,
            "boundary_copies_bytes": state_bytes,
            "snapshot_bytes": snap.size * snap.dtype.itemsize,
            "snapshot_rows_a_chunk": rows_a_chunk}), flush=True)
        slots = sds((s["max_slots"],), i32)
        rows = sds((s["max_slots"], s["max_pages_per_seq"]), i32)
        ids = sds((s["max_pages_per_seq"],), i32)
        restored = sds((n * 2 * cfg.n_kv_layers, *cfg.kv_page_shape()),
                       cfg.jdtype)
        scalar = sds((), i32)
        programs = {
            "decode": lambda: serving._decode_fused_st.lower(
                params, cfg, slots, slots, pool, pool, state, rows,
                model=model),
            "cold": lambda: serving._admit_fused_st.lower(
                params, cfg, sds((1, cold), i32), pool, pool, state, state,
                ids, scalar, scalar, model=model),
            "prefix": lambda: serving._admit_fused_px_st.lower(
                params, cfg, sds((1, sfx), i32), restored, snap, pool, pool,
                state, state, sds((n,), i32), ids, scalar, scalar,
                model=model),
            "boundary": lambda: serving._copy_boundary.lower(
                state, state, scalar),
            "snapshot": lambda: serving._gather_snapshot.lower(
                cfg, state, scalar, rows_a_chunk),
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
                out = {"refused": f"{type(e).__name__}: {str(e)[:600]}"}
            out.update(program=name, total_pages=total,
                       shape={"decode": s["max_slots"], "cold": cold,
                              "prefix": [sfx, pfx]}.get(name),
                       compile_s=round(time.perf_counter() - t0, 1))
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
