"""jit'd public wrappers around the Pallas kernels.

Handles padding/alignment so callers pass natural shapes. Interpret mode
is decided by :func:`repro.kernels.interpret_mode` (the CPU backend only)
unless a caller asks for it explicitly.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.hashing import PAD
from repro.kernels import gbkmv_score as _score_mod
from repro.kernels import hash_threshold as _hash_mod
from repro.kernels import interpret_mode


def _pad_axis(a, axis, mult, fill):
    n = a.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(a, pad, constant_values=fill)


def score_index(
    x_values, x_thresh, x_buf,
    q_values, q_thresh, q_buf, q_sizes,
    *, block_m: int = 8, interpret: bool | None = None,
):
    """Containment scores f32[M, Gq] of a query batch against the index.

    Pads records to block_m and guarantees ≥1 buffer word (zero word ==
    empty buffer).
    """
    if interpret is None:
        interpret = interpret_mode()
    m = x_values.shape[0]

    x_values = _pad_axis(jnp.asarray(x_values, jnp.uint32), 0, block_m, PAD)
    # Padded records: threshold 0 → nothing live → score 0.
    x_thresh = _pad_axis(jnp.asarray(x_thresh, jnp.uint32), 0, block_m, 0)
    x_buf = jnp.asarray(x_buf, jnp.uint32)
    if x_buf.shape[1] == 0:
        x_buf = jnp.zeros((x_buf.shape[0], 1), jnp.uint32)
    x_buf = _pad_axis(x_buf, 0, block_m, 0)

    q_values = jnp.asarray(q_values, jnp.uint32)
    q_thresh = jnp.asarray(q_thresh, jnp.uint32)
    q_buf = jnp.asarray(q_buf, jnp.uint32)
    if q_buf.shape[1] == 0:
        q_buf = jnp.zeros((q_buf.shape[0], 1), jnp.uint32)
    q_sizes = jnp.asarray(q_sizes, jnp.int32)

    # Align buffer widths.
    w = max(x_buf.shape[1], q_buf.shape[1])
    x_buf = _pad_axis(x_buf, 1, w, 0)
    q_buf = _pad_axis(q_buf, 1, w, 0)

    out = _score_mod.gbkmv_score(
        x_values, x_thresh, x_buf, q_values, q_thresh, q_buf, q_sizes,
        block_m=block_m, interpret=interpret,
    )
    return out[:m]


def hash_and_filter(ids, seed: int, tau, *, interpret: bool | None = None):
    """(hashes u32[N], keep bool[N]) for a flat element-id stream."""
    if interpret is None:
        interpret = interpret_mode()
    ids = jnp.asarray(ids)
    n = ids.shape[0]
    lanes = _hash_mod.LANES
    rows = max(-(-n // lanes), 1)
    rows = -(-rows // 8) * 8
    flat = jnp.zeros(rows * lanes, jnp.uint32).at[:n].set(ids.astype(jnp.uint32))
    h2d, keep2d = _hash_mod.hash_threshold(
        flat.reshape(rows, lanes), seed, tau, interpret=interpret
    )
    return h2d.reshape(-1)[:n], keep2d.reshape(-1)[:n].astype(bool)
