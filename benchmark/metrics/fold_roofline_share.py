"""Least time ONE fold could take on this chip - a finished window's
pages of every layer read and its summary pages written (`fold_bytes`
of the configuration's costs module: (128 + 8) pages x 3 MiB = 428 MB)
over the published HBM bandwidth - as a share of the device time of the
operations under the `attn.fold` scope in one run of the fold program
(`jit__fold_window`: gather, summarise, scatter), over the folds of
the traced seconds. The fold is plain XLA; far from its roofline here
is what would ask for a Pallas form.

The program's name is the configuration's `program.programs.fold`; a
configuration without one, a trace without such a program (no fold
fell into the traced seconds; the parent) or a costs module without
the count reads nothing.

Moves itl_mean_ms: a fold runs between two decode steps of every
sequence that decodes.
"""

from benchmark.lib import serve, trace
from benchmark.metrics import _scoped_ops

KIND = "per_layer"
LAYER = "Kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "itl_mean_ms"
SCOPES = ("attn.fold",)
COST = "fold_bytes"


def share(need_bytes, hbm_bytes_per_s, scoped_s, runs, programs_s=None):
    return 100.0 * (need_bytes / hbm_bytes_per_s) / (scoped_s / runs)


def fold_seconds(obs):
    """`_scoped_ops.seconds` for the configuration's fold programs
    (that function knows the harness's two kinds alone): (seconds
    under the scope, runs, seconds of the runs), or None."""
    needles = obs.conf["program"].get("programs", {}).get("fold")
    if obs.trace is None or not needles:
        return None
    try:
        path = _scoped_ops._xplane()
        if path is None:
            return None
        if path not in _scoped_ops._cache:
            _scoped_ops._cache.clear()
            plain = trace.read_xplane(path)
            _scoped_ops._cache[path] = _scoped_ops.scoped_events(path) + (
                trace.window_of(plain),)
        ops, modules, window = _scoped_ops._cache[path]
        found = _scoped_ops.seconds_in(ops, modules, window, needles, SCOPES)
    except Exception as e:  # a reader never fails a run
        print(f"fold ops: nothing read ({type(e).__name__}: {e})",
              flush=True)
        return None
    return found if found[0] > 0 and found[1] else None


def read(obs):
    costs = serve.costs_module(obs.conf)
    if obs.peaks is None or not hasattr(costs, COST):
        return None
    found = fold_seconds(obs)
    if found is None:
        return None
    need = getattr(costs, COST)(obs.conf, obs.conf["serving"]["page_size"])
    return share(need, obs.peaks["hbm_bytes_per_s"], *found)
