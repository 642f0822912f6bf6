"""chip_smoke.py and the compile-cache helper it shares with the other
launchers. Each case is its own interpreter: the jax backend and the
compile-cache directory are process-wide."""

import json
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **extra)
    return env


def test_default_invocation_refuses_a_cpu_backend():
    """Without a TPU the smoke exits non-zero before it builds anything,
    and prints no result."""
    p = subprocess.run([sys.executable, SMOKE], env=_env(),
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "platform=cpu" in p.stdout.splitlines()[0]
    assert "native:" not in p.stdout and '"ok"' not in p.stdout


def test_cpu_rehearsal_runs_end_to_end():
    """The same phases at a toy width: kernels in interpret mode against
    the XLA reference, the store as a CLI child, two turns over HTTP
    with offload, hit and restore, the store-edge checks."""
    p = subprocess.run([sys.executable, SMOKE, "--cpu-rehearsal"],
                       env=_env(), capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert "NOT a chip run" in p.stdout
    assert " FAIL " not in p.stdout
    facts = json.loads(
        p.stdout.split("set-up facts (not metrics): ")[1].splitlines()[0]
    )
    assert facts["compilations_after_warmup"] == 0, facts
    assert json.loads(p.stdout.splitlines()[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }


_USE_CACHE = """
import jax, jax.numpy as jnp
from infinistore_tpu.tpu import enable_compile_cache
print(enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
"""


def _snapshot(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code, the
    cache lands there and nothing is written under the checkout."""
    repo_cache = os.path.join(ROOT, ".xla_cache")
    before = _snapshot(repo_cache)
    p = subprocess.run(
        [sys.executable, "-c", _USE_CACHE], capture_output=True, text=True,
        timeout=120,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"),
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path), "the compilation was not cached"
    assert _snapshot(repo_cache) == before


def test_compile_cache_defaults_to_the_checkout():
    p = subprocess.run(
        [sys.executable, "-c",
         "from infinistore_tpu.tpu import enable_compile_cache\n"
         "print(enable_compile_cache())"],
        env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == os.path.join(ROOT, ".xla_cache")


def test_failed_native_build_raises_with_the_compilers_words(monkeypatch):
    """The auto-build used to die with a bare CalledProcessError; what
    g++ said must be in the exception."""
    import pytest

    from infinistore_tpu import _native

    def failing_make(cmd, **kw):
        return subprocess.CompletedProcess(
            cmd, 2, stdout="", stderr="engine_uring.cc:620: error: boom"
        )

    monkeypatch.setattr(_native.subprocess, "run", failing_make)
    with pytest.raises(RuntimeError, match="(?s)exit code 2.*error: boom"):
        _native._build_native()
