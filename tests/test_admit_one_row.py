"""The admission programs' one-row head (PR 48): every family's cold and
prefix admission asks its model's prefill to keep ONE position
(`decoder.forward_stack`'s `keep`), so the final norm and the head run
on the last real position alone. Held here for all eight families and
all eight programs (`serving._admit_fused`, `_admit_fused_px`, their
`_st`, `_wf` and `_wf_st` twins; models/phi_flash.py cuts its rows
below its last 2 layers, which the same comparisons hold), over the arguments `tools/jaxpr_hashes.py`
builds for a tiny engine of each family.

A file of its own: the driver's `--dist loadfile` gives a file to one
worker, and tests/test_model.py is long already.
"""

import functools
import importlib
import inspect
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

FAMILIES = ["llama", "moe", "smallthinker", "xing", "cohere", "glm", "hybrid",
            "phi_flash"]
# (s_real, s_pad): pads in the last page, none, one real token
S_REAL = {"short_of_the_pad": (30, 32), "whole_pad": (32, 32),
          "one_token": (1, 16)}
TOL = dict(rtol=2e-4, atol=2e-4)  # tests/test_model.py's hit against dense
# float32, as every comparison of that tolerance, and a vocabulary no
# other width of a tiny configuration equals (xing's four streams are
# 512 wide together, as its vocabulary is); moe: a capacity that drops
# no token whatever a pass routes over (tests/test_model.py `_MOE`)
EVERY = dict(dtype="float32", vocab_size=600)
MORE = {"moe": dict(capacity_factor=2.0)}


@functools.lru_cache(maxsize=None)
def _tool():
    spec = importlib.util.spec_from_file_location("jaxpr_hashes", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "jaxpr_hashes.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def _every_row(model):
    """The family as the admission programs had it before PR 48: its
    prefill runs the head over every position, and the one kept is cut
    out of the logits."""
    def cut(prefill):
        def every_row_then_one(*a, keep=None, last_only=False, **kw):
            logits, *rest = prefill(*a, **kw)
            if last_only:
                keep = kw["s_real"] - 1
            return (jax.lax.dynamic_slice_in_dim(logits, keep, 1, axis=1),
                    *rest)
        return every_row_then_one

    return SimpleNamespace(**{
        **vars(model), "prefill": cut(model.prefill),
        "prefill_with_prefix": cut(model.prefill_with_prefix)})


@functools.lru_cache(maxsize=None)
def _programs(family, before=False):
    """{"cold" | "prefix": (fn, its arguments by name)} of a family, as
    the tree has them or (`before`) with the head over every row."""
    found = _tool().programs(family, wrap=_every_row if before else None,
                             **EVERY, **MORE.get(family, {}))
    return {which: (fn, dict(zip(inspect.signature(fn).parameters, args)))
            for which, (fn, args) in found.items()
            if which in ("cold", "prefix")}


@functools.lru_cache(maxsize=None)
def _jitted(family, which, before=False):
    return jax.jit(_programs(family, before)[which][0])


@functools.lru_cache(maxsize=None)
def _model(family):
    module, config, kw = _tool().FAMILIES[family]
    model = importlib.import_module("infinistore_tpu.models." + module)
    return model, getattr(model, config)(**kw, **EVERY,
                                         **MORE.get(family, {}))


def _filled(tree, seed):
    """Every floating array of `tree` drawn anew (the pools of
    tools/jaxpr_hashes.py are zeros)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))

    def draw(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return jax.random.normal(next(keys), x.shape).astype(x.dtype)

    return jax.tree_util.tree_map(draw, tree)


def _page_major(ids, *pools):
    """Pages `ids` of one pool, or of a K and a V pool, in the order a
    store call returns them: rows (page, layer[, k then v])."""
    rows = jnp.stack([p[:, ids] for p in pools], axis=2)  # [L, n, kinds, ...]
    return jnp.moveaxis(rows, 1, 0).reshape(-1, *rows.shape[3:])


def _cold_arguments(family, tokens, s_real):
    _, kw = _programs(family)["cold"]
    pools = {k: v for k, v in kw.items()
             if k in ("k_pages", "v_pages", "wk", "wv", "state", "bstate")}
    return {**kw, **_filled(pools, 5), "tokens": tokens,
            "s_real": jnp.int32(s_real)}


def _prefix_arguments(family, prefix_tokens, tokens, s_real):
    """The prefix program's arguments over a REAL prefix: the family's
    cold program admits `prefix_tokens` (two whole pages) into pages 1
    and 2, and what a store call would return of them is read back out
    of the pools it wrote; the suffix goes to pages 3 and 4."""
    from infinistore_tpu import serving

    _, cfg = _model(family)
    two = jnp.asarray([1, 2], jnp.int32)
    cold = _cold_arguments(family, prefix_tokens, prefix_tokens.shape[1])
    if "wids" in cold:  # the banded layers' pages are kept too
        cold["wids"] = cold["wids"].at[:2].set(two)
    _, k, v, *more = _jitted(family, "cold")(**cold)
    _, kw = _programs(family)["prefix"]
    kw = {**kw, **{n: cold[n] for n in cold if n.endswith("_pages")
                   or n in ("wk", "wv", "state", "bstate")},
          "tokens": tokens, "s_real": jnp.int32(s_real)}
    if "wr_ids" in kw:  # two kinds of attention layer
        wk, wv, *more = more
        kw["restored"] = jnp.concatenate([_page_major(two, k, v),
                                          _page_major(two, wk, wv)])
        kw["s_ids"] = kw["s_ids"].at[:2].set(two + 2)
        kw["ws_ids"] = kw["ws_ids"].at[:2].set(two + 2)
        if "snap" in kw:  # ... and state layers beside them: the
            # cold program's last output is the boundary copies
            kw["snap"] = serving._state_rows(cfg, more[-1], 0)
        return kw
    kw["suffix_ids"] = kw["suffix_ids"].at[:2].set(two + 2)
    if "snap" in kw:  # state layers: the state at the prefix's end
        _, bstate = more
        kw["snap"] = serving._state_rows(cfg, bstate, 0)
    if v is None:  # latent rows alone
        kw["restored"] = _page_major(two, k)
    elif isinstance(kw["restored"], tuple):  # ... with index keys
        kw["restored"] = (_page_major(two, k), _page_major(two, v))
    else:
        kw["restored"] = _page_major(two, k, v)
    return kw


@pytest.mark.parametrize("case", list(S_REAL))
@pytest.mark.parametrize("which", ["cold", "prefix"])
@pytest.mark.parametrize("family", FAMILIES)
def test_admission_row_is_the_dense_forwards_and_the_rest_is_unmoved(
        family, which, case):
    """The program's logits row is `forward_dense`'s at the last real
    position (over the prompt alone, cold; over prefix + suffix, over a
    prefix the cold program itself admitted), within the tolerance of
    the hit against the dense forward, with the same argmax; and beside
    the form before PR 48 on the same inputs (the head over every row,
    then the one cut out) everything else the program returns is bit
    for bit what it was: pools, state, boundary copies, `sub`, and the
    two counts behind the row of a family that holds a share of its
    experts."""
    model, cfg = _model(family)
    s_real, s_pad = S_REAL[case]
    rng = np.random.default_rng(48)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (1, 32 + s_real)),
                         jnp.int32)
    real = prompt[:, 32:] if which == "prefix" else prompt[:, :s_real]
    tokens = jnp.zeros((1, s_pad), jnp.int32).at[:, :s_real].set(real)
    if which == "cold":
        kw, dense_over = _cold_arguments(family, tokens, s_real), real
    else:
        kw = _prefix_arguments(family, prompt[:, :32], tokens, s_real)
        dense_over = prompt

    new = jax.tree_util.tree_leaves(_jitted(family, which)(**kw))
    old = jax.tree_util.tree_leaves(_jitted(family, which, True)(**kw))
    row, row_before = np.asarray(new[0]), np.asarray(old[0])
    v = cfg.vocab_size
    assert row.ndim == 1 and row.shape[0] in (v, v + 2)
    params = kw["params"]
    dense = np.asarray(model.forward_dense(params, cfg, dense_over)[0][0, -1])
    np.testing.assert_allclose(row[:v], dense, **TOL)
    assert row[:v].argmax() == dense.argmax()
    np.testing.assert_allclose(row[:v], row_before[:v], **TOL)
    np.testing.assert_array_equal(row[v:], row_before[v:])
    assert len(new) == len(old) >= 2
    for a, b in zip(new[1:], old[1:]):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _head_products(jaxpr, s_pad, vocab):
    """(rows a batch of each `dot_general` under the `lm_head` scope,
    the intermediates of shape [*, s_pad, vocab])."""
    rows, wide = [], []
    for eqn in _equations(jaxpr):
        for out in eqn.outvars:
            shape = getattr(out.aval, "shape", ())
            if shape[-2:] == (s_pad, vocab):
                wide.append((eqn.primitive.name, shape))
        if eqn.primitive.name == "dot_general" \
                and "lm_head" in str(eqn.source_info.name_stack):
            shape = eqn.outvars[0].aval.shape
            rows.append(int(np.prod(shape[1:-1])))
    return rows, wide


@pytest.mark.parametrize("which", ["cold", "prefix"])
@pytest.mark.parametrize("family", FAMILIES)
def test_admission_program_projects_one_row_onto_the_vocabulary(family,
                                                                which):
    """In the jaxpr of each admission program (the six of serving.py,
    through the seven families) the head's product has ONE row a batch
    and nothing has the shape [*, s_pad, vocab]; the same walk over
    the form before PR 48 finds both, so it would see them."""
    _, cfg = _model(family)
    found = {}
    for before in (False, True):
        fn, kw = _programs(family, before)[which]
        jaxpr = jax.make_jaxpr(lambda kw: fn(**kw))(kw)
        found[before] = _head_products(
            jaxpr.jaxpr, kw["tokens"].shape[1], cfg.vocab_size)
    rows, wide = found[False]
    assert rows == [1], rows
    assert not wide, wide
    rows, wide = found[True]
    assert rows == [kw["tokens"].shape[1]] and wide, (rows, wide)
