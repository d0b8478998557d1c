"""Pallas TPU kernel: fused fingerprint hash + global-τ filter
(GB-KMV construction hot loop, Algorithm 1 line 6) — and the fused
device-path sketch build on top of it.

Element ids stream through in lane-aligned 2D tiles; each tile is mixed
(murmur3 fmix32) and compared against the global threshold in registers —
one HBM read (ids) and two writes (hashes, keep-mask) per element, no
intermediate materialization.

:func:`fused_build_columns` is the construction pipeline's device path:
one jitted hash→τ-select→lexsort stage (the Pallas kernel or its
``hash_u32`` jnp twin does the mixing; τ comes from ``jnp.sort`` in
exact mode or the two-level ``histogram_tau`` shared with the
distributed reduction), then one jitted scatter-pack stage that writes
the packed sketch columns. The only host crossing between the two is
the per-row count vector, which fixes the static pack width — every
per-element quantity stays on device, and the columns come back as
device-resident jnp arrays ready to live in a
:class:`repro.core.arena.SketchArena`. Bit-identical to the host
``pack_csr`` pipeline (same hashes, same τ rule, same stable sort, same
capacity-overflow thresholds).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.hashing import PAD, hash_u32
from repro.kernels import interpret_mode

LANES = 128


def _hash_kernel(seed_ref, tau_ref, ids_ref, h_ref, keep_ref):
    x = ids_ref[...].astype(jnp.uint32)
    x = x + jnp.uint32(0x9E3779B9) * (seed_ref[0, 0].astype(jnp.uint32) + jnp.uint32(1))
    h = x ^ (x >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    h_ref[...] = h
    keep_ref[...] = (h <= tau_ref[0, 0]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def hash_threshold(ids2d, seed, tau, *, block_rows: int = 8, interpret: bool):
    """ids2d u32[R, 128] → (hashes u32[R, 128], keep i32[R, 128]).

    ops.py reshapes/pads flat id streams into the [R, LANES] view.
    """
    r, l = ids2d.shape
    assert l == LANES and r % block_rows == 0
    seed_arr = jnp.asarray(seed, jnp.uint32).reshape(1, 1)
    tau_arr = jnp.asarray(tau, jnp.uint32).reshape(1, 1)
    grid = (r // block_rows,)
    return pl.pallas_call(
        _hash_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, LANES), jnp.uint32),
            jax.ShapeDtypeStruct((r, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(seed_arr, tau_arr, ids2d)


# ---------------------------------------------------------------------------
# Fused device-path sketch construction (hash → τ → sort → pack)
# ---------------------------------------------------------------------------


def _hash_flat(ids, seed, *, use_pallas: bool, interpret: bool):
    """u32[N] fingerprints of a flat id stream (Pallas kernel or jnp twin).

    The Pallas spelling pads to the [R, 128] lane view the kernel wants
    and slices back; padding lanes hash garbage that never escapes.
    """
    n = ids.shape[0]
    if not use_pallas:
        return hash_u32(ids, seed=seed)
    rows = max(-(-n // LANES), 1)
    rows = -(-rows // 8) * 8
    flat = jnp.zeros(rows * LANES, jnp.uint32).at[:n].set(
        ids.astype(jnp.uint32))
    h2d, _ = hash_threshold(flat.reshape(rows, LANES), seed,
                            jnp.uint32(PAD), interpret=interpret)
    return h2d.reshape(-1)[:n]


@functools.partial(
    jax.jit,
    static_argnames=("m", "budget", "tau_mode", "filter_tau", "use_pallas",
                     "interpret"))
def _fused_hash_sort(ids, row, seed, *, m: int, budget: int, tau_mode: str,
                     filter_tau: bool, use_pallas: bool, interpret: bool):
    """Stage 1: hash every element, select τ, stable-sort to row-major.

    Returns (hs, rs, counts, starts, tau): hashes/rows sorted by
    (row asc, hash asc) with τ-dropped elements parked on sentinel row
    ``m`` at the tail, per-row kept counts, their exclusive prefix sum,
    and the selected threshold. ``filter_tau=False`` (plain-KMV mode)
    keeps everything and pins τ at PAD-1 — positional truncation happens
    in stage 2.
    """
    n = ids.shape[0]
    h = _hash_flat(ids, seed, use_pallas=use_pallas, interpret=interpret)
    if not filter_tau or budget >= n:
        tau = jnp.uint32(PAD - np.uint32(1))
        keep = jnp.ones(n, bool)
    else:
        if tau_mode == "histogram":
            from repro.sketchindex.build import histogram_tau

            tau = histogram_tau(h, budget)
        else:
            # Exact: the budget-th smallest hash, same as np.partition.
            tau = jnp.sort(h)[budget - 1]
        keep = h <= tau
    rkey = jnp.where(keep, row.astype(jnp.int32), jnp.int32(m))
    hkey = jnp.where(keep, h, jnp.uint32(PAD))
    order = jnp.lexsort((hkey, rkey))
    rs, hs = rkey[order], hkey[order]
    counts = jnp.zeros(m + 1, jnp.int32).at[rs].add(1)[:m]
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts).astype(jnp.int32)])
    return hs, rs, counts, starts, tau


@functools.partial(jax.jit, static_argnames=("m", "cap", "limit", "lower_thresh"))
def _fused_pack(hs, rs, counts, starts, tau, *, m: int, cap: int, limit: int,
                lower_thresh: bool):
    """Stage 2: scatter the row-sorted hashes into packed [m, cap] columns.

    ``limit`` is the per-row kept length (== cap for τ-mode, == k for
    plain KMV where cap is k rounded up to the pad multiple).
    ``lower_thresh`` applies the capacity-overflow rule: a row with more
    kept hashes than ``cap`` drops its effective threshold to the
    largest value it packs (pack_csr's exact semantics).
    """
    n = hs.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32) - starts[rs]
    sel = (rs < m) & (pos < limit)
    tr = jnp.where(sel, rs, jnp.int32(m))        # sentinel row, sliced off
    tp = jnp.where(sel, pos, 0)
    values = jnp.full((m + 1, cap), jnp.uint32(PAD))
    values = values.at[tr, tp].set(jnp.where(sel, hs, jnp.uint32(PAD)))[:m]
    lengths = jnp.minimum(counts, limit).astype(jnp.int32)
    if lower_thresh:
        idx = jnp.clip(starts[:m] + (cap - 1), 0, n - 1)
        thresh = jnp.where(counts > cap, hs[idx],
                           jnp.broadcast_to(tau, (m,)))
    else:
        thresh = jnp.broadcast_to(tau, (m,))
    return values, lengths, thresh.astype(jnp.uint32)


def fused_build_columns(batch, tail_mask, budget: int, *, seed: int = 0,
                        capacity: int | None = None, tau_mode: str = "exact",
                        bitmaps=None, backend: str = "jnp",
                        row_cap: int | None = None,
                        interpret: bool | None = None):
    """Device-path sketch construction: (PackedSketches, τ).

    ``batch`` is a :class:`repro.core.sketches.RaggedBatch`; ``tail_mask``
    selects the hashed (non-buffered) elements. ``row_cap`` switches to
    plain-KMV semantics (keep the k smallest per row, τ never binds).
    The returned pack's columns are jnp arrays already resident on the
    default device — :class:`SketchArena` adopts them without a copy —
    and are bit-identical to the host ``pack_csr`` pipeline's output.
    """
    from repro.core.gkmv import TAU_MODES
    from repro.core.sketches import PackedSketches, _resolve_capacity, pack_csr

    if tau_mode not in TAU_MODES:
        raise ValueError(f"tau_mode must be one of {TAU_MODES}, "
                         f"got {tau_mode!r}")
    if interpret is None:
        interpret = interpret_mode()
    tail_mask = np.asarray(tail_mask, bool)
    ids = np.asarray(batch.ids)[tail_mask]
    row = batch.row_index()[tail_mask]
    m, n = batch.num_records, len(ids)
    sizes = batch.sizes

    if m == 0 or n == 0:
        thr_fill = np.uint32(PAD - np.uint32(1))
        pack = pack_csr(np.zeros(0, np.uint32), np.zeros(0, np.int64), m,
                        np.full(m, thr_fill, np.uint32), sizes,
                        bitmaps=bitmaps,
                        capacity=row_cap if row_cap is not None else capacity)
        return pack, thr_fill

    # uint32 id view with the same wrap rule as hash_u32_np.
    ids32 = jnp.asarray((ids.astype(np.uint64) & np.uint64(0xFFFFFFFF))
                        .astype(np.uint32))
    hs, rs, counts, starts, tau = _fused_hash_sort(
        ids32, jnp.asarray(row, jnp.int32), jnp.uint32(seed), m=m,
        budget=int(budget), tau_mode=tau_mode, filter_tau=row_cap is None,
        use_pallas=(backend == "pallas"), interpret=bool(interpret))

    # The one host crossing: per-row counts fix the static pack width.
    counts_h = np.asarray(counts)
    if row_cap is not None:
        cap = _resolve_capacity(int(row_cap), None, 8)
        limit, lower = int(row_cap), False
    else:
        cap = _resolve_capacity(int(counts_h.max()) if m else 0, capacity, 8)
        limit, lower = cap, True
    values, lengths, thresh = _fused_pack(
        hs, rs, counts, starts, tau, m=m, cap=cap, limit=limit,
        lower_thresh=lower)

    if bitmaps is None:
        buf = jnp.zeros((m, 0), jnp.uint32)
    else:
        buf = jnp.asarray(np.asarray(bitmaps, np.uint32))
    pack = PackedSketches(values=values, lengths=lengths, thresh=thresh,
                          buf=buf, sizes=jnp.asarray(sizes, jnp.int32))
    return pack, np.uint32(tau)


# ---------------------------------------------------------------------------
# Fused device-path POSTINGS encode (packed columns → blocked tail store)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m", "cap"))
def _encode_tail_device(values, lengths, *, m: int, cap: int):
    """Block-compress the tail postings ON DEVICE from packed columns.

    The device twin of ``planner/postings.py::encode_store`` fed by
    ``_row_pairs`` + ``_csr_from_pairs`` — same (hash asc, record asc)
    sort, same 128-entry blocks, same delta-bitpack / dense-bitmap rule,
    bit for bit. Everything is scatter arithmetic over the flattened
    [m·cap] element stream; dynamic sizes (#keys U, #blocks NB, #payload
    words P) live in the returned ``sizes`` vector, and every output is
    statically sized N+1 = m·cap+1 with slot N as the scatter trash can
    (the host wrapper slices by the real sizes — device slices, no
    copy-back). Notable 32-bit spellings, since x64 is off on device:

    * bit lengths via 31 shift-compare accumulations (the host float64
      ``floor(log2)+1`` is exactly equal for deltas < 2³¹)
    * the bitpack writes each delta as (lo = d << s, hi = d >> (32-s))
      u32 halves with scatter-ADD — fields are disjoint because
      d < 2^bitwidth, so add IS or, matching the host's uint64 shift +
      or.at exactly (a zero hi lands as +0 in the next block's first
      word, which the host simply skips — same bits either way)
    """
    from jax import lax

    from repro.planner.postings import BLOCK, DENSE_MAX_WORDS

    n = m * cap
    iota = jnp.arange(n, dtype=jnp.int32)
    col = iota % cap
    rec = iota // cap
    live = col < lengths[rec]
    h = jnp.where(live, values.reshape(-1), jnp.uint32(PAD))
    r = jnp.where(live, rec, jnp.int32(m))
    # (hash asc, record asc); dead (PAD, m) lanes sort to the tail —
    # even a real PAD-valued hash sorts before them on the row key.
    order = jnp.lexsort((r, h))
    hs, rsrt = h[order], r[order]
    nnz = jnp.sum(live.astype(jnp.int32))
    valid = iota < nnz

    prev_h = jnp.concatenate([hs[:1], hs[:-1]])
    newkey = valid & ((iota == 0) | (hs != prev_h))
    key_id = jnp.cumsum(newkey.astype(jnp.int32)) - 1
    kstart = lax.cummax(jnp.where(newkey, iota, -1))
    posr = iota - kstart                      # position within key run
    bstart = valid & (posr % BLOCK == 0)
    blk_id = jnp.cumsum(bstart.astype(jnp.int32)) - 1
    posb = iota - lax.cummax(jnp.where(bstart, iota, -1))
    prev_r = jnp.concatenate([rsrt[:1], rsrt[:-1]])
    d = jnp.where(valid & (posb > 0), rsrt - prev_r, 0)

    # -- per-block headers (scatter into [n+1], slot n = trash) ---------
    tgt = jnp.where(valid, blk_id, n)
    tgtb = jnp.where(bstart, blk_id, n)
    first_b = jnp.zeros(n + 1, jnp.int32).at[tgtb].set(rsrt)
    last_b = jnp.zeros(n + 1, jnp.int32).at[tgt].max(
        jnp.where(valid, rsrt, 0))
    cnt_b = jnp.zeros(n + 1, jnp.int32).at[tgt].add(1)
    md_b = jnp.zeros(n + 1, jnp.int32).at[tgt].max(d)
    mind_b = jnp.full(n + 1, 1 << 30, jnp.int32).at[
        jnp.where(valid & (posb > 0), blk_id, n)].min(d)

    bw = jnp.zeros(n + 1, jnp.int32)
    for k in range(31):
        bw = bw + (md_b >> k > 0).astype(jnp.int32)
    w_sparse = ((cnt_b - 1) * bw + 31) // 32
    w_dense = (last_b - first_b + 1 + 31) // 32
    dense = (mind_b >= 1) & (w_dense < w_sparse) \
        & (w_dense <= DENSE_MAX_WORDS)
    words_b = jnp.where(dense, w_dense, w_sparse).at[n].set(0)
    off_b = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(words_b[:n]).astype(jnp.int32)])      # [n+1]
    meta_b = ((cnt_b - 1).astype(jnp.uint32) & jnp.uint32(0x7F)) \
        | (bw.astype(jnp.uint32) << 8) \
        | (dense.astype(jnp.uint32) << 13)

    # -- payload scatters ----------------------------------------------
    blk = jnp.clip(blk_id, 0, n)
    b_dense, b_bw = dense[blk], bw[blk]
    b_off, b_first = off_b[blk], first_b[blk]
    payload = jnp.zeros(n + 1, jnp.uint32)

    sel = valid & (posb > 0) & ~b_dense & (b_bw > 0)
    bitpos = (posb - 1) * b_bw
    wloc = b_off + (bitpos >> 5)
    sh = (bitpos & 31).astype(jnp.uint32)
    du = d.astype(jnp.uint32)
    lo = du << sh
    hi = jnp.where(sh > 0,
                   du >> ((jnp.uint32(32) - sh) & jnp.uint32(31)),
                   jnp.uint32(0))
    payload = payload.at[jnp.where(sel, wloc, n)].add(
        jnp.where(sel, lo, jnp.uint32(0)))
    payload = payload.at[jnp.where(sel, wloc + 1, n)].add(
        jnp.where(sel, hi, jnp.uint32(0)))

    dsel = valid & b_dense
    bit = rsrt - b_first
    payload = payload.at[jnp.where(dsel, b_off + (bit >> 5), n)].add(
        jnp.where(dsel,
                  jnp.uint32(1) << (bit & 31).astype(jnp.uint32),
                  jnp.uint32(0)))

    # -- keyspace -------------------------------------------------------
    keys_b = jnp.zeros(n + 1, jnp.uint32).at[
        jnp.where(newkey, key_id, n)].set(hs)
    nblk_k = jnp.zeros(n + 1, jnp.int32).at[
        jnp.where(bstart, key_id, n)].add(1)
    row_blocks_b = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(nblk_k[:n]).astype(jnp.int32)])
    u = jnp.sum(newkey.astype(jnp.int32))
    nb = jnp.sum(bstart.astype(jnp.int32))
    sizes = jnp.stack([u, nb, off_b[nb]])
    return (keys_b, row_blocks_b, first_b, last_b, meta_b, off_b,
            payload, sizes)


def fused_encode_postings(values, lengths, *, m: int, cap: int) -> dict:
    """Device-resident blocked tail postings from packed columns.

    Runs :func:`_encode_tail_device` and slices the statically-shaped
    outputs down to their true sizes — ONE host readback (the 3-int
    sizes vector); every returned array is a device slice, so a device
    build's postings mirrors never round-trip through host. Keys are the
    arrays of :class:`repro.core.arena.DevicePostings`.
    """
    import jax.numpy as jnp  # noqa: F811 (kept local for doc symmetry)

    out = _encode_tail_device(jnp.asarray(values, jnp.uint32),
                              jnp.asarray(lengths, jnp.int32),
                              m=m, cap=cap)
    keys_b, rb_b, first_b, last_b, meta_b, off_b, payload_b, sizes = out
    u, nb, p = (int(x) for x in np.asarray(sizes))
    return {
        "keys": keys_b[:u],
        "row_blocks": rb_b[: u + 1],
        "first": first_b[:nb],
        "last": last_b[:nb],
        "meta": meta_b[:nb],
        "off": off_b[: nb + 1],
        "payload": payload_b[:p],
    }
