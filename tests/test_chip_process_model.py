"""One process per chip, seen from the CPU.

A chip belongs to one process at a time, so a driver must leave JAX's
backends alone (its replica or train worker holds the chip), the compile
cache is placed by the environment and never by code, ``chip_smoke.py``
refuses to run without an accelerator, and a native binary is rebuilt
unless it was made from the sources at hand.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVER = """
import json, os, sys
import ray_tpu as rt
rt.init(num_cpus=1)
resources = rt.cluster_resources()
rt.shutdown()
import jax  # imported is fine; initialised is not
from jax._src import xla_bridge
print(json.dumps({
    "backend_initialised": xla_bridge.backends_are_initialized(),
    "TPU": resources["TPU"],
    "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
}))
"""


def _run(cmd, env, cwd=REPO, timeout=120):
    t0 = time.monotonic()
    proc = subprocess.run(cmd, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)
    return proc, time.monotonic() - t0


def _env(**over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(over)
    return env


@pytest.mark.parametrize("pinned", [True, False])
def test_rt_init_leaves_jax_backends_alone(pinned):
    """rt.init() counts chips without initialising a backend in the
    driver — pinned to the CPU it has nothing to count; otherwise a
    probe child asks JAX and exits — and only off the CPU does it place
    the compile cache, at the fixed path in the checkout."""
    proc, _ = _run([sys.executable, "-c", _DRIVER],
                   _env(JAX_PLATFORMS="cpu") if pinned else _env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["backend_initialised"] is False
    assert out["TPU"] == 0.0  # no accelerator in the test sandbox
    assert out["cache"] == (None if pinned
                            else os.path.join(REPO, ".jax_cache"))


def test_compile_cache_dir_from_outside_wins():
    proc, _ = _run([sys.executable, "-c", _DRIVER],
                   _env(JAX_COMPILATION_CACHE_DIR="/somewhere/else"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["cache"] == "/somewhere/else"
    assert out["backend_initialised"] is False


def test_chip_smoke_fails_fast_without_an_accelerator():
    proc, took = _run([sys.executable, "chip_smoke.py"],
                      _env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no accelerator" in last["error"]
    assert '"ok": true' not in proc.stdout
    assert took < 30, f"took {took:.1f}s to notice there is no chip"


def test_chip_smoke_alone_is_not_a_result(tmp_path):
    """The script without the program: fails, and claims nothing."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = _env(JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = ""
    proc, _ = _run([sys.executable, "chip_smoke.py"], env, cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False


def test_native_build_goes_by_source_content():
    """A binary that was not made from these sources — here: its stamp
    says so — is rebuilt even though it is newer than they are."""
    from ray_tpu import _native
    from ray_tpu.core.gcs_socket import build_native

    if not build_native():
        pytest.skip("native toolchain unavailable")
    artifact = os.path.join(_native._BUILD, "control_store")
    stamp = artifact + ".sha256"
    with open(stamp) as f:
        digest = f.read()
    assert _native.ensure_built("control_store", "control_store.cc") \
        == artifact  # current: no rebuild
    before = os.stat(artifact).st_mtime_ns
    with open(stamp, "w") as f:
        f.write("built from something else")
    assert os.stat(artifact).st_mtime_ns == before
    _native.ensure_built("control_store", "control_store.cc")
    assert os.stat(artifact).st_mtime_ns > before
    with open(stamp) as f:
        assert f.read() == digest
