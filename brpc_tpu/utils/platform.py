"""Process-wide JAX platform set-up shared by tests and entry points.

``force_virtual_cpu_devices`` pins the CPU backend with N virtual devices
(the tests' multi-device mesh); ``enable_compile_cache`` turns on JAX's
persistent compilation cache for the chip entry points (``chip_smoke.py``,
``benchmark/run.py``). Both must run before the first JAX backend touch: the
platform choice and the cache directory are process-global.
"""

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# One fixed directory inside the checkout (gitignored): the cache keys on
# the path, so it is never built from a temp name, a pid or the time.
COMPILE_CACHE_DIR = os.path.join(_REPO, ".jax_cache")


def force_virtual_cpu_devices(n: int):
    """Force the CPU platform with >= n virtual devices. Must run before
    the first JAX backend touch; the platform choice is process-global.
    Returns the list of CPU devices (asserting there are at least n)."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(_COUNT_FLAG + r"=(\d+)", flags)
    if m is None:
        flags = (flags + f" {_COUNT_FLAG}={n}").strip()
    elif int(m.group(1)) < n:
        flags = flags[:m.start(1)] + str(n) + flags[m.end(1):]
    os.environ["XLA_FLAGS"] = flags

    import jax
    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices("cpu")
    assert len(devices) >= n, (
        f"need {n} virtual CPU devices, got {len(devices)} "
        "(was the JAX backend initialized before this call?)")
    return devices


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is used as JAX reads it and
    no other directory is set here; otherwise the cache lives at
    ``COMPILE_CACHE_DIR``. Every compile is cached (no minimum compile
    time), so a second run of the same program compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path

