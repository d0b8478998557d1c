"""Device-resident postings merge over BLOCK-COMPRESSED postings:
candidate generation for the pruned query path without leaving the
accelerator — and without ever materializing the flat posting lists.

The host planner decodes blocks with vectorized numpy; that round-trips
every batch through host memory — exactly the transfer the arena exists
to kill. Here the same merge runs as fused device stages over the
arena's blocked tail mirror, all inside ONE jitted program per output
mode:

    probe    for every query hash, its postings row (index + existence)
             — a windowed compare against the sorted key column
             (Pallas kernel for ``backend="pallas"``, XLA searchsorted
             for ``backend="jnp"``). The block-header probe the host
             planner used to run (row_blocks ranges per matched key)
             happens HERE, as array ops on the mirrored headers — the
             host never feeds the device a per-batch task bound.
    expand   matched rows' block ranges → a flat stream of block tasks,
             consumed by a ``lax.while_loop`` in fixed ``chunk``-sized
             windows. The trip count is data-dependent (a device
             scalar); every shape inside the body is static — so ONE
             compiled program serves every batch, however many blocks
             it touches. No host-side header probe, no per-bucket
             recompiles.
    decode   each task's block body → up to 128 record ids. Sparse
             bodies unpack their bitpacked deltas and prefix-sum back to
             ids (the Pallas block-decode kernel for ``"pallas"`` — eight
             tasks per grid step, one DMA of the two payload rows that
             hold each body, a one-hot word select instead of a
             data-dependent gather — or a vectorized jnp twin). The rare
             dense-bitmap bodies run in a SECOND while_loop over a
             dense-task-only stream (their
             rank-select materializes a [dchunk, 3968] bit matrix — far
             too hot to pay per sparse chunk): a cumulative dense-kind
             count over the mirrored block metadata locates the j-th
             dense block of each matched row by searchsorted, so a batch
             touching zero dense blocks runs zero dense iterations. The
             loop is compiled out entirely when the store holds no dense
             blocks at all (``has_dense=False``, a static property of
             the postings, not of the batch).
    score    scatter-add the decoded stream into the exact K∩ count
             matrix (a posting entry for (h, X) against query Q *is* one
             shared retained hash — multiplicity is the count), take the
             exact o1 matrix straight from the resident packed bitmaps
             (the dense kernel's own popcount — which is why the buffer
             posting lists never need a device mirror at all), then
             evaluate the estimator in closed form per cell: n_x, n_q
             and U₍k₎ come from per-row searchsorted tables against
             τ_pair, every float op copied from the dense kernel —
             O(m·Gq) elementwise instead of the dense sweep's
             O(m·Gq·C·Cq) membership broadcast

The score matrix therefore equals the dense sweep's score matrix
bit for bit EVERYWHERE: inside the candidate set the counts are the
dense kernel's counts, outside it K∩ = 0 and o1 is the identical
popcount, which is exactly what the dense estimator produces.

Three fused outputs, each ONE jit (no host transfer anywhere inside —
tests assert it with a transfer guard):

    fused_hit_words    score ≥ threshold, bit-packed along the record
                       axis into u32 words — an 8× smaller fetch than
                       the bool mask, decoded lazily on host
    fused_topk         lax.top_k over the score columns. The dense tie
                       rule (-score, id) IS lax.top_k's order (equal
                       values rank lower-index-first), and the closed
                       form scores ALL m records — so the "bound-sort +
                       chunked while_loop with a running k-th threshold"
                       the host pruned_topk needs degenerates here: the
                       bound sort would serialize work the estimator
                       already did elementwise. Exactness comes from the
                       matrix equality above, not from bound soundness.
    fused_scores       the raw f32[m, Gq] matrix (parity tests, bench)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import PAD, TWO32
from repro.kernels.gbkmv_score import hash_key
from repro.planner.postings import BLOCK, DENSE_MAX_WORDS

_PAD_KEY = 0x7FFFFFFF          # hash_key(PAD)

# Probe kernel tiling: query hashes per grid step (a column) / key rows
# of 128 lanes per grid step along the key column.
QBLOCK = 256
KROWS = 32
# Sparse block bodies span at most ceil(127·31/32) = 124 payload words;
# one 128-word window therefore always covers a body (plus slack the
# payload is padded with). The decode kernel copies the two 128-word
# payload rows that hold the window, so its DMA has a static size.
DECODE_WINDOW = 128
# Block-decode tasks per grid step: one (8, 128) output tile.
DECODE_TASKS = 8
# while_loop window sizes: sparse block tasks / dense block tasks per
# iteration. Fixed static shapes inside a data-dependent trip count —
# the whole point: one compiled program for any batch. Each window
# always decodes a full ``chunk`` of blocks (short final windows waste
# the remainder), so the window is sized near the per-batch task count
# of the serving workload, not for loop-overhead amortization.
TASK_CHUNK = 128
DENSE_TASK_CHUNK = 64


def _probe_kernel(keys_ref, q_ref, pos_ref, hit_ref, pos_acc, hit_acc):
    """pos = #keys < q, hit = any(keys == q), per query hash.

    Grid (query block i, key block j). ``keys_ref`` u32[KROWS, 128] is
    the j-th window of the padded key column — the pipeline streams the
    column from HBM window by window, so no grid step holds more than
    one window in VMEM. ``q_ref`` u32[QBLOCK, 1] is a column of query
    hashes. Each key row is compared against the whole column at once
    and the [QBLOCK, 128] partial counts reduce along the lanes only
    after the last window: contiguous loads, no data-dependent
    addressing. Key padding is PAD, which is never below a query hash
    and never matches a real one; the (PAD == PAD) query-padding case
    is masked at the end.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        pos_acc[...] = jnp.zeros_like(pos_acc)
        hit_acc[...] = jnp.zeros_like(hit_acc)

    q = hash_key(q_ref[...])                            # [QBLOCK, 1]
    keys = hash_key(keys_ref[...])                      # [KROWS, 128]
    pos, hit = pos_acc[...], hit_acc[...]
    for r in range(keys.shape[0]):
        row = keys[r:r + 1, :]
        pos = pos + (row < q).astype(jnp.int32)
        hit = jnp.maximum(hit, (row == q).astype(jnp.int32))
    pos_acc[...] = pos
    hit_acc[...] = hit

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        pos_ref[...] = jnp.sum(pos, axis=1, keepdims=True)
        hit_ref[...] = jnp.max(hit, axis=1, keepdims=True) * (
            q != _PAD_KEY).astype(jnp.int32)


def _probe_pallas(keys, q_flat, *, interpret: bool):
    """(pos i32[n], hit bool[n]) for a flat query-hash vector."""
    n = q_flat.shape[0]
    npad = -(-n // QBLOCK) * QBLOCK
    q2 = jnp.pad(q_flat, (0, npad - n), constant_values=PAD)[:, None]
    u = keys.shape[0]
    window = KROWS * 128
    upad = max(-(-u // window), 1) * window
    k2 = jnp.pad(keys, (0, upad - u), constant_values=PAD).reshape(-1, 128)

    pos, hit = pl.pallas_call(
        _probe_kernel,
        grid=(npad // QBLOCK, upad // window),
        in_specs=[
            pl.BlockSpec((KROWS, 128), lambda i, j: (j, 0)),
            pl.BlockSpec((QBLOCK, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((QBLOCK, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((QBLOCK, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
            jax.ShapeDtypeStruct((npad, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((QBLOCK, 128), jnp.int32),
                        pltpu.VMEM((QBLOCK, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(k2, q2)
    return pos[:n, 0], hit[:n, 0].astype(jnp.bool_)


def _probe_jnp(keys, q_flat):
    u = keys.shape[0]
    pos = jnp.searchsorted(keys, q_flat).astype(jnp.int32)
    safe = jnp.clip(pos, 0, max(u - 1, 0))
    hit = (pos < u) & (keys[safe] == q_flat) & (q_flat != PAD) \
        if u else jnp.zeros(q_flat.shape, jnp.bool_)
    return pos, hit


# ---------------------------------------------------------------------------
# block decode: sparse bodies (the common kind)
# ---------------------------------------------------------------------------


def _block_decode_kernel(first_ref, off_ref, bw_ref, cnt_ref,
                         payload_ref, out_ref, window, sem):
    """Decode DECODE_TASKS sparse block tasks per grid step.

    The per-task scalars arrive by scalar prefetch (SMEM). The payload
    stays in HBM: each task copies the two 128-word rows that hold its
    body into VMEM, all copies in flight at once. Deltas unpack via a
    one-hot word select (delta index on the sublanes, payload word on
    the lanes: a masked lane sum, no data-dependent addressing) and a
    triangular masked sum along the sublanes turns them back into record
    ids, already laid out as one output row. All arithmetic is 32-bit:
    the two straddled words recombine with shift-or instead of a 64-bit
    widen. Lanes past the block's count carry values the caller masks
    (the same values as the jnp twin's).
    """
    i = pl.program_id(0)
    copies = []
    for s in range(DECODE_TASKS):
        row = off_ref[i * DECODE_TASKS + s] >> 7
        cp = pltpu.make_async_copy(payload_ref.at[pl.ds(row, 2), :],
                                   window.at[s], sem.at[s])
        cp.start()
        copies.append(cp)

    p = lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)      # delta index
    lanes = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    tri = p < lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)

    def word(w, body):
        """body word ``w`` per sublane ([BLOCK, 1] indices < 256)."""
        pick = (jnp.where(w == lanes, body[0:1, :], 0)
                + jnp.where(w - 128 == lanes, body[1:2, :], 0))
        return jnp.sum(pick, axis=1, keepdims=True)

    for s in range(DECODE_TASKS):
        t = i * DECODE_TASKS + s
        copies[s].wait()
        body = lax.bitcast_convert_type(window[s], jnp.int32)   # [2, 128]
        bw = bw_ref[t]
        bitpos = p * bw
        w = (off_ref[t] & 127) + (bitpos >> 5)
        sh = bitpos & 31
        lo = lax.shift_right_logical(word(w, body), sh)
        hi = jnp.where(sh > 0, word(w + 1, body) << ((32 - sh) & 31), 0)
        mask = jnp.where(bw > 0, (1 << bw) - 1, 0)
        v = jnp.where(p < cnt_ref[t] - 1, (lo | hi) & mask, 0)  # [BLOCK, 1]
        ids = first_ref[t] + jnp.sum(jnp.where(tri, v, 0), axis=0,
                                     keepdims=True)             # [1, BLOCK]
        out_ref[s:s + 1, :] = ids


def _decode_payload_rows(payload):
    """u32[R, 128] view of a payload for the decode kernel: padded so the
    two rows after any offset ≤ len(payload) exist."""
    rows = payload.shape[0] // 128 + 2
    return jnp.pad(payload, (0, rows * 128 - payload.shape[0])).reshape(
        rows, 128)


def _decode_sparse_pallas(first, off, bw, cnt, payload_rows, *,
                          interpret: bool):
    """i32[tb, BLOCK] raw sparse-decoded ids (Pallas, DECODE_TASKS tasks
    per step). ``payload_rows`` is :func:`_decode_payload_rows`' view."""
    tb = first.shape[0]
    tp = -(-tb // DECODE_TASKS) * DECODE_TASKS
    first, off, bw, cnt = (jnp.pad(a, (0, tp - tb))
                           for a in (first, off, bw, cnt))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tp // DECODE_TASKS,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((DECODE_TASKS, BLOCK),
                               lambda i, *_: (i, 0)),
        scratch_shapes=[pltpu.VMEM((DECODE_TASKS, 2, 128), jnp.uint32),
                        pltpu.SemaphoreType.DMA((DECODE_TASKS,))],
    )
    return pl.pallas_call(
        _block_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, BLOCK), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(first, off, bw, cnt, payload_rows)[:tb]


def _decode_sparse_jnp(first, off, bw, cnt, payload):
    """jnp twin of the decode kernel: same 32-bit math, XLA gathers."""
    pmax = payload.shape[0] - 1
    p = jnp.arange(BLOCK - 1, dtype=jnp.int32)[None, :]     # [1, 127]
    bitpos = p * bw[:, None]                                # [tb, 127]
    w = off[:, None] + (bitpos >> 5)
    w0 = payload[jnp.clip(w, 0, pmax)]
    w1 = payload[jnp.clip(w + 1, 0, pmax)]
    sh = (bitpos & 31).astype(jnp.uint32)
    lo = w0 >> sh
    hi = jnp.where(sh > 0,
                   w1 << ((jnp.uint32(32) - sh) & jnp.uint32(31)),
                   jnp.uint32(0))
    bwu = bw.astype(jnp.uint32)[:, None]
    mask = jnp.where(bwu > 0, (jnp.uint32(1) << bwu) - jnp.uint32(1),
                     jnp.uint32(0))
    v = ((lo | hi) & mask).astype(jnp.int32)
    v = jnp.where(p < cnt[:, None] - 1, v, 0)
    zeros = jnp.zeros((first.shape[0], 1), jnp.int32)
    return first[:, None] + jnp.concatenate(
        [zeros, jnp.cumsum(v, axis=1)], axis=1)


def _decode_dense_jnp(first, off, wcnt, payload, *, m: int):
    """i32[n, BLOCK] rank-selected set-bit ids of dense-bitmap tasks.

    Lanes past a block's population carry the sentinel ``m`` (they never
    reach the scatter); a zero-word task (the sentinel block) decodes to
    all-sentinel. Shared by the dense while_loop stream — dense blocks
    hold strictly ascending ids, so each set bit is one entry and the
    rank IS the lane."""
    n = first.shape[0]
    pmax = payload.shape[0] - 1
    win = jnp.arange(DENSE_MAX_WORDS, dtype=jnp.int32)[None, :]
    wi = off[:, None] + win
    words = payload[jnp.clip(wi, 0, pmax)]
    words = jnp.where(win < wcnt[:, None], words, jnp.uint32(0))
    bits = ((words[:, :, None] >> jnp.arange(32, dtype=jnp.uint32))
            & jnp.uint32(1)).astype(jnp.int32).reshape(n, -1)
    rank = jnp.cumsum(bits, axis=1)                     # [n, DW*32]
    col = jnp.where((bits == 1) & (rank <= BLOCK), rank - 1, BLOCK)
    j = jnp.arange(DENSE_MAX_WORDS * 32, dtype=jnp.int32)[None, :]
    vals = first[:, None] + j
    row = jnp.arange(n, dtype=jnp.int32)[:, None] + jnp.zeros_like(col)
    return jnp.full((n, BLOCK + 1), m, jnp.int32) \
        .at[row.reshape(-1), col.reshape(-1)].set(vals.reshape(-1))[:, :BLOCK]


# ---------------------------------------------------------------------------
# shared scoring tail: bitmap o1 + the closed-form estimator
# ---------------------------------------------------------------------------


def _bitmap_o1(x_buf, q_buf, m: int, gq: int):
    """i32[m, Gq] exact buffer intersections — the dense kernel's own
    popcount over the resident packed bitmaps."""
    if x_buf.shape[1]:
        return jnp.sum(lax.population_count(
            x_buf[:, None, :] & q_buf[None, :, :]), axis=-1).astype(jnp.int32)
    return jnp.zeros((m, gq), jnp.int32)


def _estimate_scores(kcap, o1, x_values, x_thresh, q_values, q_thresh,
                     q_sizes):
    """f32[m, Gq] closed-form estimator over the count matrices.

    n_x, n_q, U₍k₎ per pair from searchsorted tables against τ_pair
    (rows are sorted and duplicate-free, so the insertion point IS the
    ≤-count the dense kernel computes — the unrolled binary search is
    the fastest XLA:CPU lowering of the batch and returns the same
    integer counts as any other method); every float op below is copied
    from the dense kernel so the matrix matches it bit for bit.
    """
    tau = jnp.minimum(x_thresh[:, None], q_thresh[None, :])    # [m, Gq]
    nx = jax.vmap(
        lambda row, t: jnp.searchsorted(
            row, t, side="right", method="scan_unrolled"))(
            x_values, tau).astype(jnp.int32)                   # [m, Gq]
    nq = jax.vmap(
        lambda row, t: jnp.searchsorted(
            row, t, side="right", method="scan_unrolled"))(
            q_values, tau.T).astype(jnp.int32).T               # [m, Gq]
    lx = jnp.take_along_axis(x_values, jnp.maximum(nx - 1, 0), axis=1)
    lx = jnp.where(nx > 0, lx, jnp.uint32(0))
    lq = jnp.take_along_axis(q_values, jnp.maximum(nq.T - 1, 0), axis=1)
    lq = jnp.where(nq.T > 0, lq, jnp.uint32(0)).T
    uu = jnp.maximum(lx, lq)
    u_unit = (uu.astype(jnp.float32) + 1.0) / TWO32

    k = nx + nq - kcap
    kf = k.astype(jnp.float32)
    d_hat = (kcap.astype(jnp.float32) / jnp.maximum(kf, 1.0)) * (
        (kf - 1.0) / jnp.maximum(u_unit, 1e-30))
    d_hat = jnp.where((k >= 2) & (kcap >= 1), d_hat,
                      jnp.where(kcap >= 1, kcap.astype(jnp.float32), 0.0))
    return (o1.astype(jnp.float32) + d_hat) / jnp.maximum(
        q_sizes.astype(jnp.float32), 1.0)[None, :]


# ---------------------------------------------------------------------------
# the fused pipeline: probe → while_loop expand/decode → K∩ → estimator
# ---------------------------------------------------------------------------


def _carve_query_blob(qblob, *, gq: int, cq: int, w: int):
    """(values u32[gq, cq], thresh u32[gq], buf u32[gq, w], sizes
    i32[gq], thresholds f32[gq]) out of the single staged u32 blob.

    The staging pool ships ONE contiguous buffer per batch (one
    device_put instead of five); the slicing and the int32/float32
    bitcasts fuse into the compiled program at static offsets.
    """
    o0 = gq * cq
    o1 = o0 + gq
    o2 = o1 + gq * w
    o3 = o2 + gq
    return (qblob[:o0].reshape(gq, cq),
            qblob[o0:o1],
            qblob[o1:o2].reshape(gq, w),
            lax.bitcast_convert_type(qblob[o2:o3], jnp.int32),
            lax.bitcast_convert_type(qblob[o3:o3 + gq], jnp.float32))


def _pipeline_scores(keys, row_blocks, blk_first, blk_meta, blk_off,
                     payload, x_values, x_thresh, x_buf,
                     q_values, q_thresh, q_buf, q_sizes,
                     *, chunk: int, dchunk: int, m: int, backend: str,
                     interpret: bool, has_dense: bool):
    """f32[m, Gq] pruned score matrix — every stage device-side.

    The expand runs as a ``lax.while_loop`` over fixed ``chunk``-sized
    task windows: trip count data-dependent, shapes static, so the
    compiled program is independent of how many blocks the batch
    touches. Dense-bitmap blocks stream through a second while_loop
    (``dchunk`` tasks per step) located via a dense-kind cumsum over the
    block metadata; ``has_dense=False`` (a static property of the
    STORE, not the batch) compiles that loop out entirely.
    """
    gq, cq = q_values.shape
    u = keys.shape[0]
    nb = blk_first.shape[0]
    nflat = gq * cq

    # -- probe: postings row + block range per query hash (on device) ----
    q_flat = q_values.reshape(-1)
    if backend == "pallas" and u:
        pos, hit = _probe_pallas(keys, q_flat, interpret=interpret)
    else:
        pos, hit = _probe_jnp(keys, q_flat)
    pos_c = jnp.clip(pos, 0, max(u - 1, 0))
    if u:
        rs = jnp.where(hit, row_blocks[pos_c], 0)
        re = jnp.where(hit, row_blocks[pos_c + 1], 0)
    else:
        rs = jnp.zeros(q_flat.shape, jnp.int32)
        re = rs
    seg_nblk = re - rs

    kflat = jnp.zeros(m * gq, jnp.int32)
    o1 = _bitmap_o1(x_buf, q_buf, m, gq)
    if nflat == 0 or nb == 0:
        # K∩ ≡ 0: the score is the o1 base everywhere (d_hat = 0.0).
        return o1.astype(jnp.float32) / jnp.maximum(
            q_sizes.astype(jnp.float32), 1.0)[None, :]

    cum = jnp.cumsum(seg_nblk)
    total = cum[-1]

    # Sentinel block: first = m (every lane drops), count 1, no body.
    first_s = jnp.concatenate([blk_first, jnp.full((1,), m, jnp.int32)])
    meta_s = jnp.concatenate([blk_meta, jnp.zeros((1,), jnp.uint32)])
    off_s = jnp.concatenate([blk_off, blk_off[-1:]])
    pay = jnp.pad(payload, (0, DECODE_WINDOW)) if payload.shape[0] \
        else jnp.zeros(DECODE_WINDOW, jnp.uint32)
    pay_rows = _decode_payload_rows(payload) if backend == "pallas" else None

    nseg = max(nflat - 1, 0)
    cqd = jnp.int32(max(cq, 1))
    lanes = jnp.arange(BLOCK, dtype=jnp.int32)[None, :]

    def sparse_body(carry):
        step, acc = carry
        out = step * chunk + jnp.arange(chunk, dtype=jnp.int32)
        seg = jnp.searchsorted(cum, out, side="right").astype(jnp.int32)
        seg_c = jnp.clip(seg, 0, nseg)
        within = out - (cum[seg_c] - seg_nblk[seg_c])
        valid = out < total
        task_blk = jnp.where(valid, rs[seg_c] + within, nb)
        task_q = jnp.where(valid, seg_c // cqd, 0)
        t_first = first_s[task_blk]
        t_meta = meta_s[task_blk]
        t_off = off_s[task_blk]
        t_cnt = (t_meta & jnp.uint32(0x7F)).astype(jnp.int32) + 1
        t_bw = ((t_meta >> jnp.uint32(8))
                & jnp.uint32(0x1F)).astype(jnp.int32)
        t_kind = ((t_meta >> jnp.uint32(13)) & jnp.uint32(1)).astype(
            jnp.int32)
        if backend == "pallas":
            ids = _decode_sparse_pallas(t_first, t_off, t_bw, t_cnt,
                                        pay_rows, interpret=interpret)
        else:
            ids = _decode_sparse_jnp(t_first, t_off, t_bw, t_cnt, pay)
        # Dense tasks are the dense loop's; sentinel/invalid lanes carry
        # the out-of-range record id m and drop at the scatter.
        ids = jnp.where((lanes < t_cnt[:, None]) & (t_kind[:, None] == 0),
                        ids, m)
        lin = ids * jnp.int32(gq) + task_q[:, None]
        acc = acc.at[lin.reshape(-1)].add(1, mode="drop")
        return step + 1, acc

    _, kflat = lax.while_loop(
        lambda c: c[0] * chunk < total, sparse_body,
        (jnp.int32(0), kflat))

    if has_dense:
        # Dense-rank coordinates: D[b] = dense blocks among [0, b), so a
        # matched row's j-th dense block is the unique b with
        # D[b] = D[row_start] + j and kind[b] = 1.
        kind_all = ((blk_meta >> jnp.uint32(13)) & jnp.uint32(1)).astype(
            jnp.int32)
        dall = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(kind_all)])   # [nb+1]
        dcnt = dall[re] - dall[rs]
        dcum = jnp.cumsum(dcnt)
        dtotal = dcum[-1]
        dbase = dall[rs]

        def dense_body(carry):
            step, acc = carry
            r = step * dchunk + jnp.arange(dchunk, dtype=jnp.int32)
            seg = jnp.searchsorted(dcum, r, side="right").astype(jnp.int32)
            seg_c = jnp.clip(seg, 0, nseg)
            j = r - (dcum[seg_c] - dcnt[seg_c])
            valid = r < dtotal
            blk = jnp.searchsorted(dall, dbase[seg_c] + j,
                                   side="right").astype(jnp.int32) - 1
            task_blk = jnp.where(valid, blk, nb)
            task_q = jnp.where(valid, seg_c // cqd, 0)
            t_first = first_s[task_blk]
            t_off = off_s[task_blk]
            t_wcnt = off_s[jnp.minimum(task_blk + 1, nb)] - t_off
            ids = _decode_dense_jnp(t_first, t_off, t_wcnt, pay, m=m)
            lin = ids * jnp.int32(gq) + task_q[:, None]
            acc = acc.at[lin.reshape(-1)].add(1, mode="drop")
            return step + 1, acc

        _, kflat = lax.while_loop(
            lambda c: c[0] * dchunk < dtotal, dense_body,
            (jnp.int32(0), kflat))

    return _estimate_scores(kflat.reshape(m, gq), o1, x_values, x_thresh,
                            q_values, q_thresh, q_sizes)


_STATIC = ("gq", "cq", "w", "chunk", "dchunk", "m", "backend",
           "interpret", "has_dense")


@functools.partial(jax.jit, static_argnames=_STATIC,
                   donate_argnames=("qblob",))
def fused_scores(keys, row_blocks, blk_first, blk_meta, blk_off, payload,
                 x_values, x_thresh, x_buf, qblob,
                 *, gq: int, cq: int, w: int,
                 chunk: int = TASK_CHUNK, dchunk: int = DENSE_TASK_CHUNK,
                 m: int, backend: str = "jnp", interpret: bool,
                 has_dense: bool = True):
    """f32[m, Gq] device score matrix (parity/bench seam)."""
    q_values, q_thresh, q_buf, q_sizes, _ = _carve_query_blob(
        qblob, gq=gq, cq=cq, w=w)
    return _pipeline_scores(
        keys, row_blocks, blk_first, blk_meta, blk_off, payload,
        x_values, x_thresh, x_buf, q_values, q_thresh, q_buf, q_sizes,
        chunk=chunk, dchunk=dchunk, m=m, backend=backend,
        interpret=interpret, has_dense=has_dense)


@functools.partial(jax.jit, static_argnames=_STATIC,
                   donate_argnames=("qblob",))
def fused_hit_words(keys, row_blocks, blk_first, blk_meta, blk_off, payload,
                    x_values, x_thresh, x_buf, qblob,
                    *, gq: int, cq: int, w: int,
                    chunk: int = TASK_CHUNK,
                    dchunk: int = DENSE_TASK_CHUNK, m: int,
                    backend: str = "jnp", interpret: bool,
                    has_dense: bool = True):
    """u32[ceil(m/32), Gq] packed hit words: bit ``i & 31`` of word
    ``i >> 5`` is (score[i, g] >= thresholds[g]). The float32-exact
    per-query thresholds ride the staged blob. The packed result is
    what crosses to host — an 8× smaller fetch than the bool mask, and
    the caller decodes it lazily."""
    q_values, q_thresh, q_buf, q_sizes, thresholds = _carve_query_blob(
        qblob, gq=gq, cq=cq, w=w)
    s = _pipeline_scores(
        keys, row_blocks, blk_first, blk_meta, blk_off, payload,
        x_values, x_thresh, x_buf, q_values, q_thresh, q_buf, q_sizes,
        chunk=chunk, dchunk=dchunk, m=m, backend=backend,
        interpret=interpret, has_dense=has_dense)
    mask = s >= thresholds[None, :]
    mw = max(-(-m // 32), 1)
    mp = jnp.pad(mask, ((0, mw * 32 - m), (0, 0)))
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(mp.reshape(mw, 32, gq).astype(jnp.uint32)
                   * weights[None, :, None], axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=_STATIC + ("k",),
                   donate_argnames=("qblob",))
def fused_topk(keys, row_blocks, blk_first, blk_meta, blk_off, payload,
               x_values, x_thresh, x_buf, qblob,
               *, k: int, gq: int, cq: int, w: int,
               chunk: int = TASK_CHUNK,
               dchunk: int = DENSE_TASK_CHUNK, m: int,
               backend: str = "jnp", interpret: bool,
               has_dense: bool = True):
    """(scores f32[Gq, k], ids i32[Gq, k]) device top-k over the fused
    score matrix. ``lax.top_k`` ranks equal values lower-index-first —
    exactly the dense (-score, id) tie rule — and the matrix is the
    dense matrix bit for bit, so the ranking matches the host paths
    entry for entry."""
    q_values, q_thresh, q_buf, q_sizes, _ = _carve_query_blob(
        qblob, gq=gq, cq=cq, w=w)
    s = _pipeline_scores(
        keys, row_blocks, blk_first, blk_meta, blk_off, payload,
        x_values, x_thresh, x_buf, q_values, q_thresh, q_buf, q_sizes,
        chunk=chunk, dchunk=dchunk, m=m, backend=backend,
        interpret=interpret, has_dense=has_dense)
    return lax.top_k(s.T, k)
