#!/usr/bin/env python3
"""Hashes of the jaxprs of the serving engine's fused programs (cold and
prefix admission, decode step, offload gather) for the four families
that keep K and V pages and the one that keeps a latent row (xing,
since PR 42), at tiny widths. A change that must leave
their programs alone is checked by running this in both trees and
comparing the output (PR 40: the parent unpacked under build/parent):

    JAX_PLATFORMS=cpu python3 tools/jaxpr_hashes.py <root of a checkout>
"""
import hashlib
import json
import sys

root = sys.argv[1]
sys.path.insert(0, root)
import jax
import jax.numpy as jnp
import numpy as np

from infinistore_tpu import serving
from infinistore_tpu.models import hybrid, llama, moe, smallthinker, xing
from infinistore_tpu.serving import ServingEngine, ServingConfig

def h(fn, *a, **kw):
    static = {k: v for k, v in kw.items()}
    jaxpr = jax.make_jaxpr(lambda *x: fn(*x, **static))(*a)
    return hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]

out = {}
i32 = jnp.int32
def family(name, model, cfg, sc=None):
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, sc or ServingConfig(max_slots=2, total_pages=24, max_pages_per_seq=8), model=model)
    toks = jnp.zeros((1, 32), i32)
    ids = jnp.asarray(eng._pad_ids([1, 2]))
    slots = jnp.zeros((2,), i32); rows = jnp.zeros((2, 8), i32)
    L = eng.k_pages.shape[0]
    # the decode program as the engine calls it: a family with routed
    # experts also returns what it fetched (PR 41; a tree before that
    # has neither the attribute nor the argument)
    counts = {"fetched": True} if getattr(eng, "_experts_held", 0) else {}
    if eng._win_layers:
        fn = lambda p, t, k, v, wk, wv, i, wi, s: serving._admit_fused_wf.__wrapped__(p, cfg, t, k, v, wk, wv, i, wi, s, model, 0)
        out[name + ".cold"] = h(fn, params, toks, eng.k_pages, eng.v_pages, eng.wk_pages, eng.wv_pages, ids, jnp.asarray(np.full(eng._wtable_w, eng._wpool_pages, np.int32))[:8], jnp.int32(30))
        fn = lambda p, t, s, k, v, wk, wv, r: serving._decode_fused_wf.__wrapped__(p, cfg, t, s, k, v, wk, wv, r, model, **counts)
        wrows = (rows, jnp.zeros((2, eng._wtable_w), i32), slots)
        out[name + ".decode"] = h(fn, params, slots, slots, eng.k_pages, eng.v_pages, eng.wk_pages, eng.wv_pages, wrows)
        return
    if eng.state is not None:
        fn = lambda p, t, k, v, st, bst, i, s, sl: serving._admit_fused_st.__wrapped__(p, cfg, t, k, v, st, bst, i, s, sl, model)
        out[name + ".cold"] = h(fn, params, toks, eng.k_pages, eng.v_pages, eng.state, eng.bstate, ids, jnp.int32(30), jnp.int32(0))
        fn = lambda p, t, s, k, v, st, r: serving._decode_fused_st.__wrapped__(p, cfg, t, s, k, v, st, r, model)
        out[name + ".decode"] = h(fn, params, slots, slots, eng.k_pages, eng.v_pages, eng.state, rows)
        return
    fn = lambda p, t, k, v, i, s: serving._admit_fused.__wrapped__(p, cfg, t, k, v, i, s, model)
    out[name + ".cold"] = h(fn, params, toks, eng.k_pages, eng.v_pages, ids, jnp.int32(30))
    restored = jnp.zeros((2 * L * len(cfg.page_kinds), *cfg.kv_page_shape()), cfg.jdtype)
    fn = lambda p, t, r, k, v, ri, si, s, p0: serving._admit_fused_px.__wrapped__(p, cfg, t, r, k, v, ri, si, s, p0, model)
    out[name + ".prefix"] = h(fn, params, toks, restored, eng.k_pages, eng.v_pages, jnp.asarray([1, 2], i32), ids, jnp.int32(30), jnp.int32(0))
    fn = lambda p, t, s, k, v, r: serving._decode_fused.__wrapped__(p, cfg, t, s, k, v, r, model, **counts)
    out[name + ".decode"] = h(fn, params, slots, slots, eng.k_pages, eng.v_pages, rows)
    fn = lambda k, v, i: serving._gather_pages.__wrapped__(k, v, i)
    out[name + ".gather"] = h(fn, eng.k_pages, eng.v_pages, jnp.asarray([1, 2], i32))

family("llama", llama, llama.LlamaConfig())
family("moe", moe, moe.MoEConfig())
family("hybrid", hybrid, hybrid.HybridConfig(n_layers=3, layer_types=("mamba", "attention", "mamba"), use_rope=False))
family("smallthinker", smallthinker, smallthinker.SmallThinkerConfig(n_layers=4, layer_bands=(0, 32, 32, 32), layer_rope=(False, True, True, True), n_experts=8, top_k=2))
family("xing", xing, xing.XingConfig(n_layers=3, n_experts=8, top_k=2))
print(json.dumps(out, indent=1, sort_keys=True))
