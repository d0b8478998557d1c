"""Shared KMV estimator math (paper §II-C, §IV-A).

Used by three layers: the packed-index scoring path, the Pallas kernel's
pure-jnp oracle (kernels/ref.py delegates here), and NumPy test oracles.

All pair estimators are vectorized: one query row against ``m`` record rows.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

from repro.core.hashing import PAD, TWO32

#: Query columns the K∩ compare reads per step (``gkmv_pair_estimate``).
QUERY_CHUNK = 8


def _count_le(sorted_vals, lengths, bound):
    """#values <= bound per row of a PAD-padded ascending matrix.

    ``sorted_vals`` uint32[m, C]; ``bound`` uint32[m] or scalar.
    PAD never counts because bound < PAD always (thresholds are real hashes).
    """
    b = jnp.asarray(bound, dtype=jnp.uint32)
    if b.ndim == 0:
        b = b[None]
    return jnp.sum(sorted_vals <= b[:, None], axis=-1).astype(jnp.int32)


def gkmv_pair_estimate(
    q_values, q_length, q_thresh,
    x_values, x_lengths, x_thresh,
):
    """G-KMV intersection estimator D̂∩ (Eq. 25) under pairwise thresholds.

    Args:
      q_values:  uint32[Cq]    query sketch (sorted, PAD-padded)
      q_length:  int32 scalar
      q_thresh:  uint32 scalar
      x_values:  uint32[m, C]  record sketches
      x_lengths: int32[m]
      x_thresh:  uint32[m]

    Returns (d_hat f32[m], k i32[m], k_cap i32[m]).

    U₍ₖ₎ is each row's masked max, not the value at index n − 1: sorted rows
    keep their live values as a prefix, and on the TPU a per-row index lowers
    to a gather.

    The query-side terms (nq, its part of U₍ₖ₎, and K∩) read the query row
    ``QUERY_CHUNK`` columns a step, and stop past its last hash below PAD:
    a PAD column never counts, as every bound is below PAD. The row is
    shifted left a chunk per step rather than sliced at a traced position,
    since under ``vmap`` that position is batched and lowers to a gather.
    So a batch costs its widest live query row, not the padded width.
    """
    q_values = jnp.asarray(q_values, dtype=jnp.uint32)
    x_values = jnp.asarray(x_values, dtype=jnp.uint32)
    tau_pair = jnp.minimum(jnp.asarray(x_thresh, jnp.uint32),
                           jnp.asarray(q_thresh, jnp.uint32))  # [m]
    zero = jnp.uint32(0)
    nx = _count_le(x_values, None, tau_pair)                   # [m]
    ux = jnp.max(jnp.where(x_values <= tau_pair[:, None], x_values, zero),
                 axis=-1)                                       # [m]

    width = max(1, -(-q_values.shape[0] // QUERY_CHUNK)) * QUERY_CHUNK
    q_rest = jnp.full((width,), PAD, jnp.uint32).at[
        :q_values.shape[0]].set(q_values)

    def more(carry):
        return carry[0] > 0

    def step(carry):
        n_rest, q_rest, nq, uq, k_cap = carry
        # Membership: each record value ≤ τ_pair that also appears in the
        # chunk. Both rows are sorted & duplicate-free, so a record value
        # matches at most one query value and the chunks' counts add up.
        member = jnp.zeros(x_values.shape, bool)
        for q in q_rest[:QUERY_CHUNK]:
            in_q = q <= tau_pair                                # [m]
            nq = nq + in_q.astype(jnp.int32)
            uq = jnp.maximum(uq, jnp.where(in_q, q, zero))
            member = member | (x_values == q)                   # [m, C]
        live = x_values <= tau_pair[:, None]                    # [m, C]
        k_cap = k_cap + jnp.sum(live & member, axis=-1).astype(jnp.int32)
        q_rest = jnp.concatenate(
            [q_rest[QUERY_CHUNK:], jnp.full((QUERY_CHUNK,), PAD, jnp.uint32)])
        return n_rest - QUERY_CHUNK, q_rest, nq, uq, k_cap

    zeros = jnp.zeros(x_values.shape[:1], jnp.int32)
    _, _, nq, uq, k_cap = lax.while_loop(more, step, (
        jnp.sum(q_values < PAD, dtype=jnp.int32), q_rest,
        zeros, jnp.zeros_like(ux), zeros))                      # [m] each

    k = nq + nx - k_cap                                         # |L_Q ∪ L_X| [m]

    # U_(k): largest hash ≤ τ_pair in either row, 0 where neither has one.
    u = jnp.maximum(uq, ux)
    u_unit = (u.astype(jnp.float32) + 1.0) / TWO32

    valid = (k >= 2) & (k_cap >= 1)
    d_hat = jnp.where(
        valid,
        (k_cap.astype(jnp.float32) / jnp.maximum(k, 1).astype(jnp.float32))
        * ((k.astype(jnp.float32) - 1.0) / jnp.maximum(u_unit, 1e-30)),
        jnp.where(k_cap >= 1, k_cap.astype(jnp.float32), 0.0),
    )
    return d_hat, k, k_cap


def query_cols(lengths) -> int:
    """Query columns :func:`gkmv_pair_estimate` compares for a batch whose
    rows hold ``lengths`` live hashes: the widest, rounded up to
    ``QUERY_CHUNK`` (0 for a batch with none)."""
    widest = int(np.max(lengths, initial=0))
    return -(-widest // QUERY_CHUNK) * QUERY_CHUNK


def buffer_intersection(q_buf, x_buf):
    """|H_Q ∩ H_X| via AND + popcount. q_buf uint32[W], x_buf uint32[m, W]."""
    if x_buf.shape[-1] == 0:
        return jnp.zeros(x_buf.shape[0], dtype=jnp.int32)
    inter = jnp.bitwise_and(x_buf, q_buf[None, :])
    return jnp.sum(lax.population_count(inter), axis=-1).astype(jnp.int32)


def gbkmv_containment(
    q, index, *, exact_when_full: bool = False,
):
    """Full GB-KMV containment estimate Ĉ(Q→X) per record (Eq. 26/27).

    ``q`` / ``index`` are PackedSketches (q has one row). Returns f32[m].

    ``exact_when_full`` (beyond-paper, default off): when both rows kept
    every element below their threshold *and* the threshold covers the whole
    set (lengths == sizes - buffered elements isn't tracked; we use the
    conservative check k_cap == d_hat rounding), use K∩ + buffer exactly.
    """
    d_hat, k, k_cap = gkmv_pair_estimate(
        q.values[0], q.lengths[0], q.thresh[0],
        index.values, index.lengths, index.thresh,
    )
    o1 = buffer_intersection(q.buf[0], index.buf)
    qsize = jnp.maximum(q.sizes[0].astype(jnp.float32), 1.0)
    est_inter = o1.astype(jnp.float32) + d_hat
    if exact_when_full:
        # If the pair's k equals the estimated union (all elements seen),
        # the sketch intersection is exact.
        est_inter = jnp.where(k_cap == k, o1.astype(jnp.float32) + k_cap, est_inter)
    return est_inter / qsize


# ---------------------------------------------------------------------------
# Backend dispatch: one scoring door for numpy / jnp / pallas.
# ---------------------------------------------------------------------------

BACKENDS = ("numpy", "jnp", "pallas")


def normalize_backend(backend: str | None) -> str:
    """Resolve the public ``backend=`` option (None → "jnp")."""
    backend = "jnp" if backend is None else backend
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
    return backend


def _popcount_np(words: np.ndarray) -> np.ndarray:
    """Per-row popcount of uint32[..., W] (host path)."""
    if words.shape[-1] == 0:
        return np.zeros(words.shape[:-1], dtype=np.int32)
    bytes_ = np.ascontiguousarray(words).view(np.uint8)
    return np.unpackbits(bytes_, axis=-1).sum(axis=-1).astype(np.int32)


def gbkmv_containment_np(q_values, q_thresh, q_buf, q_size, x) -> np.ndarray:
    """NumPy twin of :func:`gbkmv_containment` for one query row.

    Float32 arithmetic mirrors the jnp/pallas paths bit-for-bit in the
    regimes the tests exercise. ``x`` is a PackedSketches.
    """
    qv = np.asarray(q_values, dtype=np.uint32)
    xv = np.asarray(x.values, dtype=np.uint32)
    xt = np.asarray(x.thresh, dtype=np.uint32)
    tau_pair = np.minimum(xt, np.uint32(q_thresh))               # [m]

    nq = (qv[None, :] <= tau_pair[:, None]).sum(-1).astype(np.int32)
    nx = (xv <= tau_pair[:, None]).sum(-1).astype(np.int32)
    live = xv <= tau_pair[:, None]
    member = np.isin(xv, qv)
    k_cap = (live & member).sum(-1).astype(np.int32)
    k = nq + nx - k_cap

    m = xv.shape[0]
    uq = np.where(nq > 0, qv[np.maximum(nq - 1, 0)], np.uint32(0))
    ux = xv[np.arange(m), np.maximum(nx - 1, 0)]
    ux = np.where(nx > 0, ux, np.uint32(0))
    u = np.maximum(uq, ux)
    u_unit = (u.astype(np.float32) + np.float32(1.0)) / np.float32(TWO32)

    kf = k.astype(np.float32)
    cf = k_cap.astype(np.float32)
    valid = (k >= 2) & (k_cap >= 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        d_hat = np.where(
            valid,
            (cf / np.maximum(kf, np.float32(1.0)))
            * ((kf - np.float32(1.0)) / np.maximum(u_unit, np.float32(1e-30))),
            np.where(k_cap >= 1, cf, np.float32(0.0)),
        ).astype(np.float32)

    x_buf = np.asarray(x.buf)
    if x_buf.shape[-1]:
        o1 = _popcount_np(x_buf & np.asarray(q_buf, np.uint32)[None, :])
    else:
        o1 = np.zeros(m, dtype=np.int32)
    qs = np.float32(max(int(q_size), 1))
    return ((o1.astype(np.float32) + d_hat) / qs).astype(np.float32)


def _align_buf_widths(q, x):
    """Zero-pad the narrower bitmap so both packs share a buffer width."""
    import dataclasses

    wq, wx = q.buf.shape[1], x.buf.shape[1]
    if wq == wx:
        return q, x
    w = max(wq, wx)

    def widen(p):
        buf = np.zeros((p.buf.shape[0], w), dtype=np.uint32)
        if p.buf.shape[1]:
            buf[:, : p.buf.shape[1]] = np.asarray(p.buf)
        return dataclasses.replace(p, buf=buf)

    return (widen(q) if wq < w else q), (widen(x) if wx < w else x)


def containment_matrix(q, x, backend: str = "jnp", *, as_numpy: bool = True):
    """Ĉ(Q→X) scores f32[m, Gq]: every query row of ``q`` against every
    record row of ``x`` — the single scoring door all layers share.

    ``backend``: "numpy" (host, dependency-free), "jnp" (XLA), or
    "pallas" (fused TPU kernel; interpret mode on CPU only).
    ``as_numpy=False`` keeps device backends' output on device so
    consumers (e.g. batch_query's packed thresholding) can compare
    there instead of fetching the full float matrix.
    """
    backend = normalize_backend(backend)
    q, x = _align_buf_widths(q, x)
    if backend == "numpy":
        cols = [
            gbkmv_containment_np(
                np.asarray(q.values)[g], np.asarray(q.thresh)[g],
                np.asarray(q.buf)[g], np.asarray(q.sizes)[g], x)
            for g in range(q.num_records)
        ]
        return np.stack(cols, axis=-1) if cols else \
            np.zeros((x.num_records, 0), np.float32)
    if backend == "pallas":
        from repro.kernels.ops import score_index

        out = score_index(
            x.values, x.thresh, x.buf,
            q.values, q.thresh, q.buf, q.sizes)
        return np.asarray(out) if as_numpy else out

    from repro.sketchindex.distributed import _scores_jnp

    out = _scores_jnp(
        x.values, x.lengths, x.thresh, x.buf,
        jnp.asarray(q.values, jnp.uint32), jnp.asarray(q.thresh, jnp.uint32),
        jnp.asarray(q.buf, jnp.uint32), jnp.asarray(q.sizes, jnp.int32))
    return np.asarray(out) if as_numpy else out


# ---------------------------------------------------------------------------
# Plain KMV baseline (Eq. 8-11): k = min(k_Q, k_X), merge k smallest.
# ---------------------------------------------------------------------------

def kmv_pair_estimate(q_values, q_length, x_values, x_lengths):
    """Plain-KMV D̂∩ (Eq. 10) of one query row vs m record rows.

    Sketches here are per-record top-k minimum hash lists (no threshold).
    """
    m, c = x_values.shape
    cq = q_values.shape[0]
    k = jnp.minimum(jnp.asarray(q_length, jnp.int32), x_lengths)  # [m]

    # Distinct union of the two rows, sorted: concat → sort → dedup-mask.
    merged = jnp.sort(
        jnp.concatenate(
            [jnp.broadcast_to(q_values[None, :], (m, cq)), x_values], axis=-1
        ).astype(jnp.uint32),
        axis=-1,
    )                                                           # [m, cq+c]
    dup = jnp.concatenate(
        [jnp.zeros((m, 1), bool), merged[:, 1:] == merged[:, :-1]], axis=-1
    )
    is_pad = merged == PAD
    distinct = (~dup) & (~is_pad)
    rank = jnp.cumsum(distinct.astype(jnp.int32), axis=-1)       # 1-based among distinct
    in_topk = distinct & (rank <= k[:, None])

    # U_(k) = max value among the k smallest distinct.
    u = jnp.max(jnp.where(in_topk, merged, 0), axis=-1)
    u_unit = (u.astype(jnp.float32) + 1.0) / TWO32

    # K∩ among the k smallest: value present in BOTH rows (dup pair) whose
    # first occurrence is within top-k.
    next_dup = jnp.concatenate(
        [merged[:, 1:] == merged[:, :-1], jnp.zeros((m, 1), bool)], axis=-1
    )
    kcap = jnp.sum(in_topk & next_dup, axis=-1).astype(jnp.int32)

    valid = (k >= 2) & (kcap >= 1)
    d_hat = jnp.where(
        valid,
        (kcap.astype(jnp.float32) / jnp.maximum(k, 1).astype(jnp.float32))
        * ((k.astype(jnp.float32) - 1.0) / jnp.maximum(u_unit, 1e-30)),
        jnp.where(kcap >= 1, kcap.astype(jnp.float32), 0.0),
    )
    return d_hat, k, kcap


# ---------------------------------------------------------------------------
# NumPy oracles (tests) — straight transliteration of the paper's formulas
# over explicit python sets.
# ---------------------------------------------------------------------------

def gkmv_pair_oracle_np(q_hashes, q_tau, x_hashes, x_tau):
    """Set-based G-KMV estimator for one pair; returns (d_hat, k, kcap)."""
    tau = min(int(q_tau), int(x_tau))
    lq = {int(v) for v in q_hashes if int(v) <= tau}
    lx = {int(v) for v in x_hashes if int(v) <= tau}
    union = lq | lx
    k = len(union)
    kcap = len(lq & lx)
    if k < 2 or kcap < 1:
        return float(kcap), k, kcap
    u = (max(union) + 1.0) / TWO32
    return (kcap / k) * ((k - 1.0) / u), k, kcap


def kmv_pair_oracle_np(q_hashes, x_hashes):
    """Set-based plain-KMV estimator (Eq. 8-10) for one pair."""
    lq = sorted(int(v) for v in np.asarray(q_hashes).tolist())
    lx = sorted(int(v) for v in np.asarray(x_hashes).tolist())
    k = min(len(lq), len(lx))
    union = sorted(set(lq) | set(lx))
    topk = union[:k]
    if k < 1:
        return 0.0, 0, 0
    common = set(lq) & set(lx)
    kcap = sum(1 for v in topk if v in common)
    if k < 2 or kcap < 1:
        return float(kcap), k, kcap
    u = (topk[-1] + 1.0) / TWO32
    return (kcap / k) * ((k - 1.0) / u), k, kcap
