"""Pallas TPU kernel: fused GB-KMV containment scoring (the paper's search
hot loop, Algorithm 2 line 4).

One sweep of the record-sketch matrix scores a whole *batch* of queries
(beyond-paper: the paper scores one query per index pass; batching divides
the HBM-bound roofline term by the query-batch size Gq).

Per (record block, query) the kernel fuses:
  1. bitmap-buffer intersection: popcount(x_buf & q_buf)          (exact part)
  2. pairwise threshold      : τ_pair = min(x_thresh, q_thresh)
  3. live counts             : n_x, n_q = #values ≤ τ_pair
  4. sorted-set membership   : K∩ via an equality broadcast of one record
       row against the query sketch held as a column — both rows are
       sorted *and duplicate-free* (the hash is a uint32 bijection), so
       the equality count is the exact intersection size; no gather or
       binary search
  5. KMV estimator           : D̂∩ = K∩/k · (k-1)/U_(k)           (Eq. 25)
  6. score                   : (popcount + D̂∩) / |Q|             (Eq. 27)

Layout: records blocked over the grid; the query pack (values as rows and
as columns, buffers) is small and resident in VMEM for every block; the
per-query scalars (threshold, size) sit in SMEM.

The TPU's kernel compiler has no unsigned reductions, minimum or
uint32→float32 cast, so the kernel works on *keys*: a hash ``h`` becomes
the int32 ``(h ^ 2³¹)``, which orders exactly as ``h`` does. U_(k) turns
back into float32 through its 16-bit halves (both exact in float32, one
rounding in the final add), which is the correctly rounded value XLA's
uint32→float32 cast gives — so every float op matches the jnp estimator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import TWO32

_SIGN = 0x80000000


def hash_key(h):
    """int32 key of a uint32 hash array: same order, signed compare."""
    return lax.bitcast_convert_type(
        jnp.asarray(h, jnp.uint32) ^ jnp.uint32(_SIGN), jnp.int32)


def key_to_f32(key):
    """float32(h) of the hash behind an int32 key, correctly rounded."""
    hi = ((key >> 16) + 32768).astype(jnp.float32)
    lo = (key & 0xFFFF).astype(jnp.float32)
    return hi * 65536.0 + lo


def pair_score(xv, x_live, qv, q_live, kcap, o1, qs):
    """f32 containment scores from one side's live mask per pair.

    ``xv``/``qv`` are key rows broadcast against each other's pair axis;
    ``kcap`` and ``o1`` are int32 per pair; ``qs`` the query size. The
    float op sequence is the jnp estimator's (estimators.gkmv_pair_estimate).
    """
    lo = jnp.int32(-2 ** 31)
    nx = jnp.sum(x_live.astype(jnp.int32), axis=1, keepdims=True)
    nq = jnp.sum(q_live.astype(jnp.int32), axis=1, keepdims=True)
    k = nx + nq - kcap
    u = jnp.maximum(
        jnp.max(jnp.where(x_live, xv, lo), axis=1, keepdims=True),
        jnp.max(jnp.where(q_live, qv, lo), axis=1, keepdims=True))
    u_unit = (key_to_f32(u) + 1.0) / TWO32
    kf = k.astype(jnp.float32)
    d_hat = (kcap.astype(jnp.float32) / jnp.maximum(kf, 1.0)) * (
        (kf - 1.0) / jnp.maximum(u_unit, 1e-30))
    d_hat = jnp.where((k >= 2) & (kcap >= 1), d_hat,
                      jnp.where(kcap >= 1, kcap.astype(jnp.float32), 0.0))
    return (o1.astype(jnp.float32) + d_hat) / jnp.maximum(
        qs.astype(jnp.float32), 1.0)


def popcount_rows(a, b):
    """int32[rows, 1] popcount of ``a & b`` summed along the lanes."""
    bits = lax.bitcast_convert_type(lax.population_count(a & b), jnp.int32)
    return jnp.sum(bits, axis=1, keepdims=True)


def _score_kernel(
    x_values_ref,   # u32[BM, C]
    x_thresh_ref,   # u32[BM, 1]
    x_buf_ref,      # u32[BM, W]
    q_cols_ref,     # i32[Cq, Gq]  query keys, one column per query
    q_rows_ref,     # i32[Gq, Cq]  the same keys, one row per query
    q_thresh_ref,   # i32[Gq]      SMEM, keys
    q_buf_ref,      # u32[Gq, W]
    q_sizes_ref,    # i32[Gq]      SMEM
    out_ref,        # f32[BM, Gq]
):
    xv = hash_key(x_values_ref[...])          # [BM, C]
    xt = hash_key(x_thresh_ref[...])          # [BM, 1]
    xb = x_buf_ref[...]
    bm = xv.shape[0]
    gq = q_rows_ref.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (bm, 1), 0)

    for g in range(gq):                       # static unroll over query batch
        qv = q_rows_ref[g:g + 1, :]           # [1, Cq]
        qcol = q_cols_ref[:, g:g + 1]         # [Cq, 1]
        tau = jnp.minimum(xt, q_thresh_ref[g])
        live_x = xv <= tau                    # [BM, C]  (PAD never live)
        live_q = qv <= tau                    # [BM, Cq]
        kcap = jnp.zeros((bm, 1), jnp.int32)
        for b in range(bm):
            member = jnp.max((qcol == xv[b:b + 1, :]).astype(jnp.int32),
                             axis=0, keepdims=True)               # [1, C]
            kb = jnp.sum(member * live_x[b:b + 1, :].astype(jnp.int32),
                         axis=1, keepdims=True)
            kcap = kcap + jnp.where(rows == b, kb, 0)
        o1 = popcount_rows(xb, q_buf_ref[g:g + 1, :])
        out_ref[:, g:g + 1] = pair_score(xv, live_x, qv, live_q, kcap, o1,
                                         q_sizes_ref[g])


@functools.partial(
    jax.jit, static_argnames=("block_m", "interpret")
)
def gbkmv_score(
    x_values, x_thresh, x_buf,
    q_values, q_thresh, q_buf, q_sizes,
    *, block_m: int = 8, interpret: bool,
):
    """pallas_call wrapper. Shapes as in kernels/ref.py:gbkmv_score_ref.

    Preconditions (ops.py enforces by padding): M % block_m == 0, W >= 1.
    """
    m, c = x_values.shape
    gq, cq = q_values.shape
    w = x_buf.shape[1]
    assert m % block_m == 0 and w >= 1

    q_keys = hash_key(q_values)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _score_kernel,
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, c), lambda i: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_m, w), lambda i: (i, 0)),
            pl.BlockSpec((cq, gq), lambda i: (0, 0)),
            pl.BlockSpec((gq, cq), lambda i: (0, 0)),
            smem,
            pl.BlockSpec((gq, w), lambda i: (0, 0)),
            smem,
        ],
        out_specs=pl.BlockSpec((block_m, gq), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, gq), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x_values, x_thresh[:, None], x_buf, q_keys.T, q_keys,
      hash_key(q_thresh), q_buf, q_sizes.astype(jnp.int32))
