"""Process set-up for the entry points (service launcher, chip smoke run,
benchmark runner). Nothing here runs at import time."""

from __future__ import annotations

import os

#: The persistent compile cache's fixed home when the environment names
#: none. The path is part of the cache key, so it must not move.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX and nothing
    else is configured. Otherwise the cache lives in ``.jax_cache/`` at
    the repository root.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX sees."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
