"""Computation-environment knobs for the launchers.

Thin wrappers over ``jax.config`` / ``XLA_FLAGS`` that must run before
any array touches a backend — the launchers call these right after
argument parsing, ahead of the first ``import``-triggered trace.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count

import jax


def jax_enable_x64(use_x64: bool) -> None:
    """Default array precision: 64-bit when True (or when the
    ``JAX_ENABLE_X64`` env var asks for it), else JAX's 32-bit default."""
    if not use_x64:
        use_x64 = bool(os.getenv("JAX_ENABLE_X64", 0))
    jax.config.update("jax_enable_x64", use_x64)


def set_platform(platform: str = "cpu") -> None:
    """Pin the backend ('cpu' | 'gpu' | 'tpu').  Only effective before
    the first computation initializes a platform."""
    jax.config.update("jax_platform_name", platform)


def set_cpu_cores(n: int) -> None:
    """Expose ``n`` host devices (XLA_FLAGS), clamped to the machine.
    Only effective on the CPU platform, before JAX initializes."""
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(f"only {total} CPUs available, will use {total - 1}",
                      Warning)
        n = total - 1
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={n}"


# fixed, git-ignored home of the persistent compile cache inside the
# checkout: the cache key includes the path, so it must not move
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX
    (nothing is set in code); otherwise the cache goes to the fixed
    in-checkout ``.jax_cache``.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def set_debug_nan(flag: bool) -> None:
    """Raise on the first NaN any computation produces (re-runs the
    offending op un-jitted to localize it).  Debug-only: disables some
    fusions and forces a sync per dispatch — never leave it on in a
    benchmark run."""
    jax.config.update("jax_debug_nans", flag)
