"""Pallas TPU kernel: ragged candidate gather-scoring (planner verify step).

The dense kernel (gbkmv_score.py) sweeps every record row for every
query. After postings pruning the surviving work is a *ragged* list of
(record, query) pairs — a few hits per query at selective thresholds —
so the verify step is a gather problem, not a sweep problem:

    cand_rec i32[P]   record row to score
    cand_q   i32[P]   query row it belongs to
    out      f32[P]   Ĉ(Q_{cand_q[p]} → X_{cand_rec[p]})

The record gather happens *in the kernel*: the sketch rows (widened once
to 128 lanes, ``lane_rows``) stay in HBM and each group of PAIRS pairs
copies only its addressed rows into VMEM, so the pruned path never
materializes a gathered copy of the index.
The query side is small and resident; each pair picks its query row by
a one-hot select. Per pair the kernel runs exactly the dense kernel's
math (buffer popcount + τ_pair counts + Eq. 25 tail estimator).

``score_pairs`` is the public door with the repo-standard ``backend=``
switch: "pallas" (this kernel, interpret mode on CPU), "jnp" (XLA
gather + vectorized pair math), "numpy" (host oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import PAD, TWO32
from repro.kernels import interpret_mode
from repro.kernels.gbkmv_score import hash_key, pair_score, popcount_rows

# Candidate pairs per grid step (the pair ids ride in SMEM blocks, which
# must match XLA's 1024-element tiling of an int32 vector) and per inner
# group: one (8, C) tile of rows.
STEP = 1024
PAIRS = 8


def _pair_kernel(
    rec_ref,        # i32[STEP]       SMEM: record row of each pair
    qi_ref,         # i32[STEP]       SMEM: query row of each pair
    x_values_hbm,   # u32[M, Cp]      HBM, PAD-padded to 128-lane rows
    x_thresh_ref,   # u32[STEP, 1]    gathered per pair
    x_buf_ref,      # u32[STEP, W]    gathered per pair
    q_cols_ref,     # i32[Cq, Gq]     query keys, one column per query
    q_rows_ref,     # i32[Gq, Cq]     the same keys, one row per query
    q_thresh_ref,   # i32[Gq]         SMEM, keys
    q_buf_ref,      # u32[Gq, W]
    q_sizes_ref,    # i32[Gq]         SMEM
    out_ref,        # f32[STEP, 1]
    rows,           # u32[PAIRS, 1, Cp] VMEM scratch
    sem,            # DMA semaphores [PAIRS]
):
    gq = q_rows_ref.shape[0]
    lane_g = lax.broadcasted_iota(jnp.int32, (1, gq), 1)
    row_g = lax.broadcasted_iota(jnp.int32, (gq, 1), 0)
    row_p = lax.broadcasted_iota(jnp.int32, (PAIRS, 1), 0)

    def group(j, carry):
        base = pl.multiple_of(j * PAIRS, PAIRS)
        copies = []
        for s in range(PAIRS):
            cp = pltpu.make_async_copy(
                x_values_hbm.at[pl.ds(rec_ref[base + s], 1), :],
                rows.at[s], sem.at[s])
            cp.start()
            copies.append(cp)
        x_thr = hash_key(x_thresh_ref[pl.ds(base, PAIRS), :])     # [8, 1]
        x_bufs = lax.bitcast_convert_type(
            x_buf_ref[pl.ds(base, PAIRS), :], jnp.int32)          # [8, W]
        q_bufs = lax.bitcast_convert_type(q_buf_ref[...], jnp.int32)
        scores = jnp.zeros((PAIRS, 1), jnp.float32)
        for s in range(PAIRS):
            g = qi_ref[base + s]
            # One-hot selects of the pair's query (one term is kept).
            qcol = jnp.sum(jnp.where(lane_g == g, q_cols_ref[...], 0),
                           axis=1, keepdims=True)                 # [Cq, 1]
            qv = jnp.sum(jnp.where(row_g == g, q_rows_ref[...], 0),
                         axis=0, keepdims=True)                   # [1, Cq]
            qb = jnp.sum(jnp.where(row_g == g, q_bufs, 0), axis=0,
                         keepdims=True)                           # [1, W]
            copies[s].wait()
            xv = hash_key(rows[s])                                # [1, C]
            tau = jnp.minimum(x_thr[s:s + 1, :], q_thresh_ref[g])
            live_x = xv <= tau
            live_q = qv <= tau
            member = jnp.max((qcol == xv).astype(jnp.int32), axis=0,
                             keepdims=True)                       # [1, C]
            kcap = jnp.sum(member * live_x.astype(jnp.int32), axis=1,
                           keepdims=True)
            o1 = popcount_rows(x_bufs[s:s + 1, :], qb)
            score = pair_score(xv, live_x, qv, live_q, kcap, o1,
                               q_sizes_ref[g])
            scores = jnp.where(row_p == s, score, scores)
        out_ref[pl.ds(base, PAIRS), :] = scores
        return carry

    lax.fori_loop(0, STEP // PAIRS, group, 0)


@jax.jit
def lane_rows(x_values):
    """The record rows as the pair kernel reads them: widened with PAD
    to whole 128-lane rows (a row copy moves whole rows; PAD lanes are
    never live). A resident index builds this once, not per call."""
    c = x_values.shape[1]
    return jnp.pad(x_values, ((0, 0), (0, -(-c // 128) * 128 - c)),
                   constant_values=PAD)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_score_pallas(
    x_rows, x_thresh, x_buf,
    q_values, q_thresh, q_buf, q_sizes,
    cand_rec, cand_q,
    *, interpret: bool,
):
    """STEP candidate pairs per grid step. The record rows (``lane_rows``)
    stay in HBM and only the addressed rows are copied into VMEM; the
    small per-pair columns (thresholds, bitmaps) are gathered by XLA."""
    cp_ = x_rows.shape[1]
    assert cp_ % 128 == 0, "record rows must come from lane_rows"
    gq, cq = q_values.shape
    w = x_buf.shape[1]
    p = cand_rec.shape[0]
    assert w >= 1 and w == q_buf.shape[1]
    pp = -(-p // STEP) * STEP
    # Padding pairs score (record 0, query 0) and are sliced off.
    cand_rec = jnp.pad(cand_rec, (0, pp - p))
    cand_q = jnp.pad(cand_q, (0, pp - p))

    q_keys = hash_key(q_values)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    per_pair = pl.BlockSpec((STEP,), lambda i: (i,),
                            memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _pair_kernel,
        grid=(pp // STEP,),
        in_specs=[
            per_pair,
            per_pair,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((STEP, 1), lambda i: (i, 0)),
            pl.BlockSpec((STEP, w), lambda i: (i, 0)),
            pl.BlockSpec((cq, gq), lambda i: (0, 0)),
            pl.BlockSpec((gq, cq), lambda i: (0, 0)),
            smem,
            pl.BlockSpec((gq, w), lambda i: (0, 0)),
            smem,
        ],
        out_specs=pl.BlockSpec((STEP, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((PAIRS, 1, cp_), jnp.uint32),
                        pltpu.SemaphoreType.DMA((PAIRS,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(cand_rec, cand_q, x_rows, x_thresh[cand_rec][:, None],
      x_buf[cand_rec], q_keys.T, q_keys, hash_key(q_thresh), q_buf,
      q_sizes.astype(jnp.int32))
    return out[:p, 0]


@jax.jit
def _gather_score_jnp(
    x_values, x_thresh, x_buf,
    q_values, q_thresh, q_buf, q_sizes,
    cand_rec, cand_q,
):
    """XLA path: gather both sides, then vectorized per-pair math.

    Same op sequence per row as estimators.gkmv_pair_estimate +
    buffer_intersection, broadcast per-pair instead of one-query-vs-all.
    """
    xv = x_values[cand_rec]                   # [P, C]
    xt = x_thresh[cand_rec]                   # [P]
    xb = x_buf[cand_rec]                      # [P, W]
    qv = q_values[cand_q]                     # [P, Cq]
    qt = q_thresh[cand_q]
    qb = q_buf[cand_q]
    qs = q_sizes[cand_q]

    tau = jnp.minimum(xt, qt)                               # [P]
    nq = jnp.sum(qv <= tau[:, None], axis=-1).astype(jnp.int32)
    nx = jnp.sum(xv <= tau[:, None], axis=-1).astype(jnp.int32)
    live = xv <= tau[:, None]
    member = jnp.any(xv[:, :, None] == qv[:, None, :], axis=-1)
    kcap = jnp.sum(live & member, axis=-1).astype(jnp.int32)
    k = nq + nx - kcap

    def last_live(vals, n):
        idx = jnp.maximum(n - 1, 0)
        v = jnp.take_along_axis(vals, idx[:, None].astype(jnp.int32),
                                axis=-1)[:, 0]
        return jnp.where(n > 0, v, jnp.uint32(0))

    u = jnp.maximum(last_live(qv, nq), last_live(xv, nx))
    u_unit = (u.astype(jnp.float32) + 1.0) / TWO32

    valid = (k >= 2) & (kcap >= 1)
    d_hat = jnp.where(
        valid,
        (kcap.astype(jnp.float32) / jnp.maximum(k, 1).astype(jnp.float32))
        * ((k.astype(jnp.float32) - 1.0) / jnp.maximum(u_unit, 1e-30)),
        jnp.where(kcap >= 1, kcap.astype(jnp.float32), 0.0),
    )
    if xb.shape[-1]:
        o1 = jnp.sum(lax.population_count(xb & qb), axis=-1).astype(jnp.int32)
    else:
        o1 = jnp.zeros(xv.shape[0], dtype=jnp.int32)
    return (o1.astype(jnp.float32) + d_hat) / jnp.maximum(
        qs.astype(jnp.float32), 1.0)


def _gather_score_np(
    x_values, x_thresh, x_buf,
    q_values, q_thresh, q_buf, q_sizes,
    cand_rec, cand_q,
):
    """Host twin of the jnp path (float32 arithmetic, estimators.py idiom)."""
    from repro.core.estimators import _popcount_np

    xv = x_values[cand_rec].astype(np.uint32)
    xt = x_thresh[cand_rec].astype(np.uint32)
    xb = x_buf[cand_rec]
    qv = q_values[cand_q].astype(np.uint32)
    qt = q_thresh[cand_q].astype(np.uint32)
    qb = q_buf[cand_q]
    qs = q_sizes[cand_q]

    tau = np.minimum(xt, qt)
    nq = (qv <= tau[:, None]).sum(-1).astype(np.int32)
    nx = (xv <= tau[:, None]).sum(-1).astype(np.int32)
    live = xv <= tau[:, None]
    member = (xv[:, :, None] == qv[:, None, :]).any(-1)
    kcap = (live & member).sum(-1).astype(np.int32)
    k = nq + nx - kcap

    p = xv.shape[0]
    uq = qv[np.arange(p), np.maximum(nq - 1, 0)]
    uq = np.where(nq > 0, uq, np.uint32(0))
    ux = xv[np.arange(p), np.maximum(nx - 1, 0)]
    ux = np.where(nx > 0, ux, np.uint32(0))
    u = np.maximum(uq, ux)
    u_unit = (u.astype(np.float32) + np.float32(1.0)) / np.float32(TWO32)

    kf = k.astype(np.float32)
    cf = kcap.astype(np.float32)
    valid = (k >= 2) & (kcap >= 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        d_hat = np.where(
            valid,
            (cf / np.maximum(kf, np.float32(1.0)))
            * ((kf - np.float32(1.0)) / np.maximum(u_unit, np.float32(1e-30))),
            np.where(kcap >= 1, cf, np.float32(0.0)),
        ).astype(np.float32)

    if xb.shape[-1]:
        o1 = _popcount_np(xb & qb)
    else:
        o1 = np.zeros(p, dtype=np.int32)
    qsf = np.maximum(qs.astype(np.float32), np.float32(1.0))
    return ((o1.astype(np.float32) + d_hat) / qsf).astype(np.float32)


def _pad_pow2(n: int, lo: int = 8) -> int:
    """Bucket P so jit caches a handful of shapes, not one per batch."""
    p = lo
    while p < n:
        p *= 2
    return p


def score_pairs(
    x, q, cand_rec, cand_q, *, backend: str = "jnp",
    interpret: bool | None = None,
) -> np.ndarray:
    """f32[P] pair scores for a ragged candidate list.

    ``x`` / ``q`` are PackedSketches (record index / query batch pack,
    buffer widths already aligned) on one device or the host; a host
    pack crosses to the device on every call. ``cand_rec[p]`` indexes x
    rows, ``cand_q[p]`` indexes q rows. Device paths pad P to a
    power-of-two bucket (extra pairs repeat pair 0 and are sliced off)
    so steady-state serving reuses a handful of compiled shapes. A
    record-sharded index scores its pairs shard by shard
    (``repro.sketchindex.distributed``).
    """
    from repro.core.estimators import normalize_backend

    backend = normalize_backend(backend)
    p = len(cand_rec)
    if p == 0:
        return np.zeros(0, dtype=np.float32)
    cand_rec = np.asarray(cand_rec, dtype=np.int32)
    cand_q = np.asarray(cand_q, dtype=np.int32)

    if backend == "numpy":
        return _gather_score_np(
            np.asarray(x.values), np.asarray(x.thresh), np.asarray(x.buf),
            np.asarray(q.values), np.asarray(q.thresh), np.asarray(q.buf),
            np.asarray(q.sizes), cand_rec, cand_q)

    pp = _pad_pow2(p)
    if pp != p:
        cand_rec = np.concatenate(
            [cand_rec, np.zeros(pp - p, np.int32) + cand_rec[0]])
        cand_q = np.concatenate(
            [cand_q, np.zeros(pp - p, np.int32) + cand_q[0]])

    xv = jnp.asarray(x.values, jnp.uint32)
    xt = jnp.asarray(x.thresh, jnp.uint32)
    xb = jnp.asarray(x.buf, jnp.uint32)
    qv = jnp.asarray(q.values, jnp.uint32)
    qt = jnp.asarray(q.thresh, jnp.uint32)
    qb = jnp.asarray(q.buf, jnp.uint32)
    qs = jnp.asarray(q.sizes, jnp.int32)

    if backend == "pallas":
        from repro.kernels.ops import _pad_axis

        if interpret is None:
            interpret = interpret_mode()
        w = max(xb.shape[1], qb.shape[1], 1)
        xb = _pad_axis(xb if xb.shape[1] else
                       jnp.zeros((xb.shape[0], 1), jnp.uint32), 1, w, 0)
        qb = _pad_axis(qb if qb.shape[1] else
                       jnp.zeros((qb.shape[0], 1), jnp.uint32), 1, w, 0)
        out = _gather_score_pallas(
            lane_rows(xv), xt, xb, qv, qt, qb, qs,
            jnp.asarray(cand_rec), jnp.asarray(cand_q), interpret=interpret)
    else:
        out = _gather_score_jnp(
            xv, xt, xb, qv, qt, qb, qs,
            jnp.asarray(cand_rec), jnp.asarray(cand_q))
    return np.asarray(out[:p])
