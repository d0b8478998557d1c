"""Pallas TPU kernels for the containment hot loops, their jnp twins and
the wrappers that pad natural shapes for them."""

from __future__ import annotations


def interpret_mode(platform: str | None = None) -> bool:
    """Whether Pallas kernels run in interpret mode: only on the CPU, where
    tests validate them. On an accelerator they compile, and a kernel the
    compiler refuses is an error, never a fallback. ``platform`` is that
    of the devices the data lives on (default: JAX's default backend)."""
    if platform is None:
        import jax

        platform = jax.default_backend()
    return platform == "cpu"
