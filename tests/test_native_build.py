"""The native library's auto-build (`_native.get_lib`) between
processes: pytest-xdist's workers, or a server child beside its parent,
import the package at once in a tree that holds no library yet."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# One process of four: points the module at an empty directory, puts a
# build in `_build_native`'s place that writes the real library into it
# in two halves with a pause between (what `make` running into the file
# looked like to a process loading it), and loads.
_CHILD = """
import os, sys, time
from infinistore_tpu import _native

real, target, ran = sys.argv[1:4]

def build():
    with open(ran, "a") as f:
        f.write(f"{os.getpid()}\\n")
    blob = open(real, "rb").read()
    with open(target, "wb") as f:
        f.write(blob[: len(blob) // 2])
        f.flush()
        time.sleep(0.5)
        f.write(blob[len(blob) // 2 :])

_native._LIB_PATH = target
_native._build_native = build
time.sleep(max(0.0, float(sys.argv[4]) - time.time()))  # start together
print(int(_native.get_lib().ist_abi_version()))
"""


def test_four_processes_build_once_and_load_a_whole_library(tmp_path):
    from infinistore_tpu import _native

    _native.get_lib()  # the real one is there to copy
    target = tmp_path / "empty" / "libinfinistore_tpu.so"
    ran = tmp_path / "ran"
    env = dict(os.environ, PYTHONPATH=ROOT)
    env.pop("INFINISTORE_TPU_NATIVE_LIB", None)
    import time

    start = time.time() + 3.0  # past four interpreters' start-up
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, _native._LIB_PATH, str(target),
             str(ran), repr(start)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for _ in range(4)
    ]
    outs = [p.communicate(timeout=120) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-2000:]
        assert int(out) >= 18
    assert len(ran.read_text().split()) == 1


def test_the_makefile_renames_the_linked_library_into_place():
    """`make` links under a temporary name and `mv`s it over the
    library, so that a reader outside get_lib's lock (a `make` by hand
    beside a running test) sees no library or a whole one."""
    p = subprocess.run(
        ["make", "-C", os.path.join(ROOT, "native"), "-n", "-B", "all"],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    (link,) = [ln for ln in p.stdout.splitlines() if " -shared " in ln]
    out = "../infinistore_tpu/_native/libinfinistore_tpu.so"
    linked, moved = link.split(" && ")
    tmp = linked.rsplit(" -o ", 1)[1]
    assert tmp.startswith(out + ".") and tmp.endswith(".tmp")
    assert moved == f"mv -f {tmp} {out}"
