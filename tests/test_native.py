"""Runs the native C++ test binaries (assert-based, native/test/test_*.cpp).

Builds the native tree on demand so `python -m pytest tests/` is the single
entry point, mirroring how the reference's test/ drives all layers.
"""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD = os.path.join(REPO, "native", "build")


def _test_binaries():
    # Collection-time must stay toolchain-free: the build happens in the
    # _built fixture below, which skips cleanly when cmake is absent.
    sources = glob.glob(os.path.join(REPO, "native", "test", "test_*.cpp"))
    return sorted(os.path.join(BUILD, os.path.splitext(os.path.basename(s))[0])
                  for s in sources)


@pytest.fixture(scope="module", autouse=True)
def _built():
    from conftest import _toolchain_available, require_native_lib

    require_native_lib()
    # A prebuilt tree on a toolchain-less machine is still runnable;
    # only (re)build when the tools to do so exist.
    if _toolchain_available():
        from brpc_tpu.runtime import native

        native.build()


@pytest.mark.parametrize("binary", _test_binaries(),
                         ids=lambda b: os.path.basename(b))
def test_native(binary):
    if not os.path.exists(binary):
        pytest.skip(f"{os.path.basename(binary)} not built")
    proc = subprocess.run([binary], capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, (
        f"{os.path.basename(binary)} failed:\n{proc.stdout}\n{proc.stderr}")


def test_native_lib_loads_before_jax():
    """The library's global operator new/delete serve every library loaded
    after it (libstdc++ binds to the first definition in its scope), so
    loading it BEFORE jax must leave jax working — the order every fleet
    member, benchmark/run.py and chip_smoke.py use. It once aborted here with
    std::bad_alloc."""
    code = ("from brpc_tpu.runtime import native\n"
            "native.lib()\n"
            "import jax.numpy as jnp\n"
            "assert float(jnp.arange(4.0).sum()) == 6.0\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
