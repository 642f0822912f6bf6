#!/usr/bin/env python3
"""Sandbox script (no chip): benchmark/tools/aot_memory.py's question
for a configuration with THREE kinds of cache in one sequence (full
pages, banded pages, a recurrent state: phi4-mini-flash). Compiles the
decode program and the largest cold- and hit-admission programs of the
traffic for a DESCRIBED v5e at the real widths and prints each one's
memory_analysis(); the pools are the engine's own (its constructor's
shapes, nothing allocated). `--hlo DIR` keeps each program's optimized
text. Nothing runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_memory_three.py \
        --config phi4-mini-flash --traffic traces12k
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools.aot_memory import (  # noqa: E402
    backend_answers_tpu, engine_pools)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--programs", default="decode,cold,prefix")
    ap.add_argument("--hlo", default="")
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
    page = cfg.page_size

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def described(tree):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(
        lambda k: model.init_params(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32)))
    weight_bytes = sum(int(x.size) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))
    held = engine_pools(model, cfg, serve.serving_config(conf, args.config))
    pools = tuple(sds(held[k].shape, held[k].dtype)
                  for k in ("k_pages", "v_pages", "wk_pages", "wv_pages"))
    state = described(jax.eval_shape(
        lambda: model.state_pools(cfg, s["max_slots"])))
    state_bytes = sum(int(x.size) * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    row = serving._snapshot_row_elems(cfg)
    snap = sds((cfg.n_state_layers, row), cfg.state_jdtype)
    print(json.dumps({
        "config": args.config, "weights_bytes": weight_bytes,
        "engine_holds": {k: [list(v.shape), str(v.dtype)]
                         for k, v in held.items()},
        "page_pool_bytes": sum(v.size * v.dtype.itemsize
                               for v in held.values()),
        "state_pool_bytes": state_bytes,
        "boundary_copies_bytes": state_bytes,
        "snapshot_bytes": snap.size * snap.dtype.itemsize}), flush=True)
    i32 = jnp.int32
    n_full, n_win = pools[0].shape[0], pools[2].shape[0]
    entries = pools[2].shape[1] // s["max_slots"]
    cold = max(shapes["cold"])
    sfx, pfx = max(shapes["prefix"], key=lambda p: p[0] * (p[0] + p[1]))
    p = pfx // page
    nw = p - max(0, p * page - cfg.window_band + 1) // page

    def n_sub(n_pages, hit):
        """Pages below the band that an admission hands to the store:
        none over three kinds (serving._prefill_two says why)."""
        return 0

    slots = sds((s["max_slots"],), i32)
    scalar = sds((), i32)
    rows = (sds((s["max_slots"], s["max_pages_per_seq"]), i32),
            sds((s["max_slots"], entries), i32), slots)
    ids = sds((s["max_pages_per_seq"],), i32)
    restored = sds((2 * (p * n_full + nw * n_win), *pools[0].shape[2:]),
                   pools[0].dtype)
    programs = {
        "decode": lambda: serving._decode_fused_wf_st.lower(
            params, cfg, slots, slots, *pools, state, rows, model=model),
        "cold": lambda: serving._admit_fused_wf_st.lower(
            params, cfg, sds((1, cold), i32), *pools, state, state, ids,
            ids, scalar, scalar, model=model, n_sub=n_sub(cold // page, 0)),
        "prefix": lambda: serving._admit_fused_px_wf_st.lower(
            params, cfg, sds((1, sfx), i32), restored, snap, *pools, state,
            state, sds((p,), i32), sds((nw,), i32), ids, ids, scalar,
            scalar, model=model, n_sub=n_sub(p + sfx // page, p)),
    }
    for name in args.programs.split(","):
        t0 = time.perf_counter()
        try:
            with backend_answers_tpu():
                lowered = programs[name]()
            compiled = lowered.compile()
            ma = compiled.memory_analysis()
            out = {"arguments": ma.argument_size_in_bytes,
                   "outputs": ma.output_size_in_bytes,
                   "aliased": ma.alias_size_in_bytes,
                   "temporaries": ma.temp_size_in_bytes}
            out["total_live"] = (out["arguments"] + out["outputs"]
                                 - out["aliased"] + out["temporaries"])
            if args.hlo:
                os.makedirs(args.hlo, exist_ok=True)
                with open(os.path.join(args.hlo, name + ".txt"), "w") as f:
                    f.write(compiled.as_text())
        except Exception as e:  # the compiler's refusal is the answer
            out = {"refused": f"{type(e).__name__}: {str(e)[:600]}"}
        out.update(program=name,
                   shape={"decode": s["max_slots"], "cold": cold,
                          "prefix": [sfx, pfx]}[name],
                   compile_s=round(time.perf_counter() - t0, 1))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
