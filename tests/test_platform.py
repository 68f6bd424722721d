"""Process set-up the chip entry points share: the persistent compile
cache's directory, the CPU platform for bench children, and the native
build's check that native/build belongs to this checkout."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = ("from brpc_tpu.utils.platform import enable_compile_cache\n"
          "import jax\n"
          "print(enable_compile_cache())\n"
          "print(jax.config.jax_compilation_cache_dir)\n")


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/brpc_tpu_cache"],
                         ids=["checkout-dir", "env-dir"])
def test_compile_cache_dir(env_dir):
    """JAX_COMPILATION_CACHE_DIR wins where set, and nothing else is set
    in code; otherwise one fixed directory inside the checkout. Run in a
    fresh interpreter: the cache directory is process-global."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    returned, configured = proc.stdout.split()[-2:]
    want = env_dir or os.path.join(REPO, ".jax_cache")
    assert returned == configured == want


def test_bench_children_run_on_cpu(monkeypatch):
    """Every bench child gets the CPU platform: the bench process owns
    the chip, and the chip belongs to one process at a time."""
    import bench

    seen = {}

    def fake_run(argv, **kw):
        seen.update(kw)

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    bench._run_child([sys.executable, "-c", "pass"], timeout=1)
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["timeout"] == 1


def test_native_build_tree_must_be_this_checkouts(tmp_path, monkeypatch):
    """A native/build configured at another path (a copied tree) is not
    trusted: build() reconfigures it instead of loading its library."""
    from brpc_tpu.runtime import native

    build = tmp_path / "build"
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    assert not native._configured_here()  # no tree at all
    build.mkdir()
    cache = build / "CMakeCache.txt"
    cache.write_text("CMAKE_CACHEFILE_DIR:INTERNAL=/elsewhere/native/build\n")
    assert not native._configured_here()
    cache.write_text(f"CMAKE_CACHEFILE_DIR:INTERNAL={build}\n")
    assert native._configured_here()


def test_native_build_without_toolchain_keeps_prebuilt_tree(tmp_path,
                                                           monkeypatch):
    """Without cmake/ninja, build() raises and deletes nothing: a prebuilt
    library copied from another path stays in place for lib() to load."""
    from brpc_tpu.runtime import native

    build = tmp_path / "build"
    build.mkdir()
    (build / "CMakeCache.txt").write_text(
        "CMAKE_CACHEFILE_DIR:INTERNAL=/elsewhere/native/build\n")
    so = build / "libbrpc_tpu.so"
    so.write_bytes(b"prebuilt")
    monkeypatch.setattr(native, "_BUILD_DIR", str(build))
    monkeypatch.setattr(native.shutil, "which", lambda name: None)
    with pytest.raises(RuntimeError, match="cmake and ninja"):
        native.build()
    assert so.read_bytes() == b"prebuilt"
