"""Process bootstrap shared by every launcher / script.

A CPU host fakes a multi-chip host via an XLA flag that must be set BEFORE
jax initializes: call ``ensure_host_devices`` first thing in ``main()``
(before any jax import), then ``enable_compile_cache``.
"""
from __future__ import annotations

import os
import sys
import warnings

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def ensure_host_devices(n: int) -> None:
    """Request ``n`` fake host devices (no-op when n is falsy).

    Appends ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS. Must
    run before jax first initializes its backends; if jax is already
    imported AND initialized with a different device count, warns instead
    of silently doing nothing.
    """
    if not n:
        return
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            have = len(jax.devices())
        except Exception:
            return  # backends not initialized yet: the flag will apply
        if have != n:
            warnings.warn(
                f"jax already initialized with {have} devices; "
                f"--devices {n} has no effect in this process",
                RuntimeWarning, stacklevel=2)


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in a fixed directory and
    return it. ``JAX_COMPILATION_CACHE_DIR``, when set, is that directory:
    JAX reads the variable itself and nothing is set here. Otherwise the
    cache goes to ``<repo>/.jax_cache``; the path is part of the cache key,
    so it never moves between runs."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
