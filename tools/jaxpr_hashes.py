#!/usr/bin/env python3
"""Hashes of the jaxprs of the serving engine's fused programs (cold and
prefix admission, decode step, offload gather) for the five families
that keep K and V pages (cohere since PR 43), the one that keeps a
latent row (xing, since PR 42) and the one that keeps index keys
beside it on some layers (glm, since PR 46) and the one that keeps
index keys beside K and V on every layer (keye, since PR 49), at tiny
widths; the prefix admission of the families with state layers or two
kinds of attention layer since PR 48 (29 programs); the one with both
and layers that borrow another layer's cache (phi_flash, since PR 53:
32 programs). A change that must
leave their programs alone is checked by running this in both trees and
comparing the output (PR 40: the parent unpacked under build/parent):

    JAX_PLATFORMS=cpu python3 tools/jaxpr_hashes.py <root of a checkout>

`programs(name)` gives one family's programs as (function, arguments)
for whoever wants more of them than a hash (tests/test_model.py runs
the decode programs). Imported, it takes `infinistore_tpu` from the
path as it stands; run, from the checkout it is given.
"""
import hashlib
import importlib
import json
import sys

# family: (module of infinistore_tpu.models, its config class, arguments)
FAMILIES = {
    "llama": ("llama", "LlamaConfig", {}),
    "moe": ("moe", "MoEConfig", {}),
    "hybrid": ("hybrid", "HybridConfig", dict(
        n_layers=3, layer_types=("mamba", "attention", "mamba"),
        use_rope=False)),
    "smallthinker": ("smallthinker", "SmallThinkerConfig", dict(
        n_layers=4, layer_bands=(0, 32, 32, 32),
        layer_rope=(False, True, True, True), n_experts=8, top_k=2)),
    "xing": ("xing", "XingConfig", dict(n_layers=3, n_experts=8, top_k=2)),
    "cohere": ("cohere", "CohereConfig", dict(
        n_layers=4, layer_bands=(32, 32, 32, 0),
        layer_rope=(True, True, True, False), n_experts=2, top_k=2,
        n_routed=8, first_expert=2)),
    "glm": ("glm", "GlmConfig", dict(
        n_layers=5, dense_layers=(True, False, False, False, False),
        indexer_kinds=("full", "shared", "shared", "shared", "full"),
        index_topk=16, n_experts=2, top_k=2, n_routed=8, first_expert=2)),
    "keye": ("keye", "KeyeConfig", dict(
        n_layers=3, n_kv_heads=2, index_topk=16, n_experts=8, top_k=2)),
    "phi_flash": ("phi_flash", "PhiFlashConfig", dict(
        n_layers=6, layer_types=("mamba1", "attention", "mamba1",
                                 "attention", "gmu", "cross"),
        layer_bands=(0, 32, 0, 0, 0, 0))),
}


def programs(name, wrap=None, **more):
    """{"cold" | "prefix" | "decode" | "gather": (fn, arguments)} of
    one family: the engine's fused programs unjitted, over the arrays a
    tiny engine of the family holds (every pool zeros). Each `fn`'s
    parameter names say what its arguments are, so a caller can put
    its own in by name (tests/test_admit_one_row.py does: tokens,
    `s_real`, filled pools); `wrap(model)` stands in for the family's module
    inside the programs, `more` goes to its configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from infinistore_tpu import serving
    from infinistore_tpu.serving import ServingConfig, ServingEngine

    module, config, kw = FAMILIES[name]
    model = importlib.import_module("infinistore_tpu.models." + module)
    cfg = getattr(model, config)(**kw, **more)
    i32 = jnp.int32
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, ServingConfig(
        max_slots=2, total_pages=24, max_pages_per_seq=8), model=model)
    if wrap:
        model = wrap(model)
    toks = jnp.zeros((1, 32), i32)
    ids = jnp.asarray(eng._pad_ids([1, 2]))
    two = jnp.asarray([1, 2], i32)
    slots = jnp.zeros((2,), i32)
    rows = jnp.zeros((2, 8), i32)
    L = eng.k_pages.shape[0]
    # the decode program as the engine calls it: a family with routed
    # experts also returns what it fetched (PR 41; a tree before that
    # has neither the attribute nor the argument)
    counts = {"fetched": True} if getattr(eng, "_experts_held", 0) else {}
    out = {}
    if eng._win_layers and eng.state is not None:
        # three kinds in one slot: both pairs of pools, the state pools
        # and their boundary copies
        pools = (eng.k_pages, eng.v_pages, eng.wk_pages, eng.wv_pages,
                 eng.state, eng.bstate)
        wids = jnp.asarray(np.full(
            eng._wtable_w, eng._wpool_pages, np.int32))[:8]
        out["cold"] = (
            lambda params, tokens, k_pages, v_pages, wk, wv, state, bstate,
            ids, wids, s_real, slot: serving._admit_fused_wf_st.__wrapped__(
                params, cfg, tokens, k_pages, v_pages, wk, wv, state,
                bstate, ids, wids, s_real, slot, model, 0),
            (params, toks, *pools, ids, wids, jnp.int32(30), jnp.int32(0)))
        n_win = eng.wk_pages.shape[0]
        restored = jnp.zeros((2 * (L + n_win) * 2, *cfg.kv_page_shape()),
                             cfg.jdtype)
        snap = jnp.zeros((cfg.n_state_layers,
                          serving._snapshot_row_elems(cfg)),
                         cfg.state_jdtype)
        out["prefix"] = (
            lambda params, tokens, restored, snap, k_pages, v_pages, wk, wv,
            state, bstate, r_ids, wr_ids, s_ids, ws_ids, s_real, slot:
            serving._admit_fused_px_wf_st.__wrapped__(
                params, cfg, tokens, restored, snap, k_pages, v_pages, wk,
                wv, state, bstate, r_ids, wr_ids, s_ids, ws_ids, s_real,
                slot, model, 0),
            (params, toks, restored, snap, *pools, two, two, ids, wids,
             jnp.int32(30), jnp.int32(0)))
        out["decode"] = (
            lambda p, t, s, k, v, wk, wv, st, r:
            serving._decode_fused_wf_st.__wrapped__(
                p, cfg, t, s, k, v, wk, wv, st, r, model),
            (params, slots, slots, *pools[:5],
             (rows, jnp.zeros((2, eng._wtable_w), i32), slots)))
        return out
    if eng._win_layers:
        pools = (eng.k_pages, eng.v_pages, eng.wk_pages, eng.wv_pages)
        wids = jnp.asarray(np.full(
            eng._wtable_w, eng._wpool_pages, np.int32))[:8]
        out["cold"] = (
            lambda params, tokens, k_pages, v_pages, wk, wv, ids, wids,
            s_real: serving._admit_fused_wf.__wrapped__(
                params, cfg, tokens, k_pages, v_pages, wk, wv, ids, wids,
                s_real, model, 0),
            (params, toks, *pools, ids, wids, jnp.int32(30)))
        # two restored pages of every layer, full and banded alike
        n_win = eng.wk_pages.shape[0]
        restored = jnp.zeros((2 * (L + n_win) * 2, *cfg.kv_page_shape()),
                             cfg.jdtype)
        out["prefix"] = (
            lambda params, tokens, restored, k_pages, v_pages, wk, wv,
            r_ids, wr_ids, s_ids, ws_ids, s_real:
            serving._admit_fused_px_wf.__wrapped__(
                params, cfg, tokens, restored, k_pages, v_pages, wk, wv,
                r_ids, wr_ids, s_ids, ws_ids, s_real, model, 0),
            (params, toks, restored, *pools, two, two, ids, wids,
             jnp.int32(30)))
        out["decode"] = (
            lambda p, t, s, k, v, wk, wv, r:
            serving._decode_fused_wf.__wrapped__(
                p, cfg, t, s, k, v, wk, wv, r, model, **counts),
            (params, slots, slots, *pools,
             (rows, jnp.zeros((2, eng._wtable_w), i32), slots)))
        return out
    restored = jnp.zeros((2 * L * len(cfg.page_kinds), *cfg.kv_page_shape()),
                         cfg.jdtype)
    if eng.state is not None:
        pools = (eng.k_pages, eng.v_pages, eng.state, eng.bstate)
        out["cold"] = (
            lambda params, tokens, k_pages, v_pages, state, bstate, ids,
            s_real, slot: serving._admit_fused_st.__wrapped__(
                params, cfg, tokens, k_pages, v_pages, state, bstate, ids,
                s_real, slot, model),
            (params, toks, *pools, ids, jnp.int32(30), jnp.int32(0)))
        snap = jnp.zeros((cfg.n_state_layers,
                          serving._snapshot_row_elems(cfg)),
                         cfg.state_jdtype)
        out["prefix"] = (
            lambda params, tokens, restored, snap, k_pages, v_pages, state,
            bstate, restored_ids, suffix_ids, s_real, slot:
            serving._admit_fused_px_st.__wrapped__(
                params, cfg, tokens, restored, snap, k_pages, v_pages,
                state, bstate, restored_ids, suffix_ids, s_real, slot,
                model),
            (params, toks, restored, snap, *pools, two, ids, jnp.int32(30),
             jnp.int32(0)))
        out["decode"] = (
            lambda p, t, s, k, v, st, r:
            serving._decode_fused_st.__wrapped__(
                p, cfg, t, s, k, v, st, r, model),
            (params, slots, slots, eng.k_pages, eng.v_pages, eng.state,
             rows))
        return out
    out["cold"] = (
        lambda params, tokens, k_pages, v_pages, ids, s_real:
        serving._admit_fused.__wrapped__(
            params, cfg, tokens, k_pages, v_pages, ids, s_real, model),
        (params, toks, eng.k_pages, eng.v_pages, ids, jnp.int32(30)))
    pools = (eng.k_pages, eng.v_pages)
    if getattr(eng, "_index_kind", None):
        # a further kind of page of its own shape: the restored pages
        # are an array a kind, a gather reads one pool
        restored = tuple(
            jnp.zeros((2 * len(cfg.page_layers(kind)),
                       *cfg.page_shape(kind)), cfg.jdtype)
            for kind in cfg.page_kinds)
        pools = (eng.k_pages, None)
    out["prefix"] = (
        lambda params, tokens, restored, k_pages, v_pages, restored_ids,
        suffix_ids, s_real, pos0: serving._admit_fused_px.__wrapped__(
            params, cfg, tokens, restored, k_pages, v_pages, restored_ids,
            suffix_ids, s_real, pos0, model),
        (params, toks, restored, eng.k_pages, eng.v_pages, two, ids,
         jnp.int32(30), jnp.int32(0)))
    out["decode"] = (
        lambda p, t, s, k, v, r: serving._decode_fused.__wrapped__(
            p, cfg, t, s, k, v, r, model, **counts),
        (params, slots, slots, eng.k_pages, eng.v_pages, rows))
    out["gather"] = (
        lambda k, v, i: serving._gather_pages.__wrapped__(k, v, i),
        (*pools, two))
    return out


def hashes():
    import jax

    out = {}
    for name in FAMILIES:
        try:
            found = programs(name)
        except ImportError:  # a tree from before the family
            continue
        for program, (fn, args) in found.items():
            out[f"{name}.{program}"] = hashlib.sha256(
                str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]
    return out


if __name__ == "__main__":
    sys.path.insert(0, sys.argv[1])
    print(json.dumps(hashes(), indent=1, sort_keys=True))
