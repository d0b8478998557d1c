"""Device-resident pruned execution over a :class:`SketchArena`.

The contract the arena makes possible: with ``backend`` ∈ {"jnp",
"pallas"}, ``plan="pruned"`` runs the WHOLE query chain — postings
probe, block-task expand, on-device block decode, the K∩ scatter, the
closed-form estimator, and the output head (packed thresholding or
top-k) — as ONE device computation over the arena's resident mirrors
(kernels/postings_merge.py). The only host work is *before* it (query
sketching, staging the query pack — one batched ``device_put``) and
*after* it (reading back the bit-packed hit words or the [Gq, k] top-k
pair — the packed result, never an m×Gq matrix).

Two things keep steady-state serving on ONE compiled program:

    shape bucketing   the only per-batch shapes are the query count Gq
                      (bucketed to powers of two, padded with inert
                      PAD-hash queries that provably score 0) and the
                      top-k ``k`` (same bucketing). Sketch capacity and
                      bitmap width are index constants; block/task
                      counts are DATA, consumed by while_loops, not
                      shapes.
    staging pool      per-(bucket, capacity, width) pinned host buffers
                      — ONE flat u32 blob per shape, filled in place
                      through dtype views and shipped in a single
                      ``device_put`` (the jit carves it at static
                      offsets); the device blob is donated to the jit
                      so XLA can alias it into outputs.

``PIPELINE_STATS`` counts calls vs. newly-seen compile signatures (the
jit cache key mirrored host-side) and staging-pool reuse, surfaced
through ``repro.obs``/``/metrics``; every new signature logs a slow-path
line so a bucketing regression shows up in production logs, not just as
mysteriously slow batches.

``stage_query_inputs`` / ``fused_mask_words`` / ``fused_topk_scores``
split exactly at the transfer seams so tests can wrap the middle in
``jax.transfer_guard("disallow")`` and prove the residency claim rather
than assert it in prose.
"""

from __future__ import annotations

import logging
import warnings
from typing import NamedTuple

import numpy as np

from repro.core.arena import SketchArena
from repro.core.hashing import PAD
from repro.obs.trace import stage
from repro.planner import prune

_LOG = logging.getLogger("repro.planner.device")


class _quiet(warnings.catch_warnings):
    """Silence the per-compile 'donated buffers were not usable' warning
    — CPU can't donate, and the fused jits donate their query buffers so
    real accelerators can alias them into outputs."""

    def __enter__(self):
        super().__enter__()
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")


#: Device-pipeline counters (process-global, monotonically increasing —
#: the serving layer exports them through /metrics). ``compiles`` counts
#: newly-seen jit signatures; ``calls - compiles`` is the cache-hit
#: count. ``staging_reuse``/``staging_alloc`` track the host staging
#: pool: in steady state reuse grows and alloc does not.
PIPELINE_STATS = {
    "calls": 0,
    "compiles": 0,
    "staging_reuse": 0,
    "staging_alloc": 0,
}
_SIGNATURES: set = set()
_STAGING: dict = {}


def pipeline_stats() -> dict:
    """Snapshot of the device-pipeline counters (plus the derived
    cache-hit count and live signature/pool sizes)."""
    s = dict(PIPELINE_STATS)
    s["cache_hits"] = s["calls"] - s["compiles"]
    s["signatures"] = len(_SIGNATURES)
    s["staging_buffers"] = len(_STAGING)
    return s


def reset_pipeline_stats() -> None:
    for key in PIPELINE_STATS:
        PIPELINE_STATS[key] = 0
    _SIGNATURES.clear()
    _STAGING.clear()


def _note_call(sig) -> None:
    PIPELINE_STATS["calls"] += 1
    if sig not in _SIGNATURES:
        _SIGNATURES.add(sig)
        PIPELINE_STATS["compiles"] += 1
        _LOG.info(
            "device-pipeline compile (slow path): %r — %d signatures live; "
            "steady-state serving should stop seeing these once the "
            "Gq/k buckets are warm", sig, len(_SIGNATURES))


def _bucket(n: int, lo: int = 64) -> int:
    """Power-of-two bucket so steady-state serving reuses a handful of
    compiled shapes instead of one per batch."""
    p = lo
    while p < n:
        p *= 2
    return p


class StagedQuery(NamedTuple):
    """One staged batch: the device-resident query blob plus the static
    dims the fused jits need to carve it (bucketed query count, sketch
    capacity, bitmap words)."""

    blob: object          # u32[gq * (cq + w + 3)] on device
    gq: int
    cq: int
    w: int


def _staging(gq_b: int, cq: int, w: int) -> dict:
    """The (bucketed-batch, capacity, bitmap-width) host staging
    buffer — ONE flat u32 array per shape (so the batch ships in a
    single ``device_put``), filled in place through dtype views laid
    out [values | thresh | buf | sizes | thr]."""
    key = (gq_b, cq, w)
    bufs = _STAGING.get(key)
    if bufs is None:
        PIPELINE_STATS["staging_alloc"] += 1
        o0 = gq_b * cq
        o1 = o0 + gq_b
        o2 = o1 + gq_b * w
        o3 = o2 + gq_b
        flat = np.empty(o3 + gq_b, np.uint32)
        bufs = {
            "flat": flat,
            "values": flat[:o0].reshape(gq_b, cq),
            "thresh": flat[o0:o1],
            "buf": flat[o1:o2].reshape(gq_b, w),
            "sizes": flat[o2:o3].view(np.int32),
            "thr": flat[o3:].view(np.float32),
        }
        _STAGING[key] = bufs
    else:
        PIPELINE_STATS["staging_reuse"] += 1
    return bufs


def stage_query_inputs(arena: SketchArena, qp, thresholds=None):
    """Place one batch's device inputs (host → device happens HERE).

    Returns (device_postings, device_pack, :class:`StagedQuery`). The
    arena mirrors are cached — only the query blob actually moves per
    batch, ONE flat ``device_put`` out of the pooled staging buffer;
    the fused jit carves it at static offsets. When ``thresholds`` is
    None the blob's threshold lane stays +inf (the top-k/scores heads
    ignore it).

    The query count is padded to its power-of-two bucket with inert
    queries: all-PAD hash rows (PAD never probes — real keys are < PAD —
    and never counts under any τ_pair ≤ x_thresh < PAD), zero bitmaps,
    zero sizes, +inf thresholds. Padded columns score exactly 0, pass no
    threshold, and are sliced off at fetch; callers slice by
    ``qp.num_records``.
    """
    import jax

    dpost = arena.device_postings()
    dpack = arena.device_pack()
    gq = qp.num_records
    gq_b = _bucket(max(gq, 1), lo=8)
    w = int(np.asarray(arena.buf).shape[1])
    qv = np.asarray(qp.values)
    cq = int(qv.shape[1])
    host = _staging(gq_b, cq, w)

    host["values"][:gq] = qv
    host["values"][gq:] = np.uint32(PAD)
    host["thresh"][:gq] = np.asarray(qp.thresh)
    host["thresh"][gq:] = 0
    q_buf = np.asarray(qp.buf)
    wq = min(w, int(q_buf.shape[1]))
    host["buf"][:] = 0                # align bitmap widths (r=0 engines)
    host["buf"][:gq, :wq] = q_buf[:, :wq]
    host["sizes"][:gq] = np.asarray(qp.sizes)
    host["sizes"][gq:] = 0
    host["thr"][:] = np.inf
    if thresholds is not None:
        host["thr"][:gq] = np.broadcast_to(
            prune.f32_threshold(thresholds), (gq,))

    blob = jax.device_put(host["flat"])
    return dpost, dpack, StagedQuery(blob, gq_b, cq, w)


def _sig(kind: str, dpost, sq: StagedQuery, *, m: int, backend: str,
         extra=()):
    return (kind, m, sq.gq, sq.cq, sq.w, int(dpost.keys.shape[0]),
            int(dpost.first.shape[0]), int(dpost.payload.shape[0]),
            bool(dpost.has_dense), backend) + tuple(extra)


def pruned_scores(dpost, dpack, sq: StagedQuery, *, m: int, backend: str):
    """f32[m, Gq] device score matrix — no host transfer inside.

    Probe, block-task expand (device while_loop — no host header probe
    feeds this), block decode, the K∩ scatter, the bitmap o1 popcount,
    and the closed-form estimator are one jitted call over
    already-resident inputs (kernels/postings_merge.fused_scores).
    """
    from repro.kernels import interpret_mode, postings_merge

    _note_call(_sig("scores", dpost, sq, m=m, backend=backend))
    with _quiet():
        return postings_merge.fused_scores(
            dpost.keys, dpost.row_blocks, dpost.first, dpost.meta,
            dpost.off, dpost.payload,
            dpack.values, dpack.thresh, dpack.buf, sq.blob,
            gq=sq.gq, cq=sq.cq, w=sq.w,
            m=m, backend=backend, interpret=interpret_mode(),
            has_dense=dpost.has_dense)


def fused_mask_words(dpost, dpack, sq: StagedQuery, *, m: int,
                     backend: str):
    """u32[ceil(m/32), Gq] packed device hit words — probe → decode →
    score → float32-exact packed thresholding with no host transfer
    anywhere in between (the staged blob already encodes the cut).
    """
    from repro.kernels import interpret_mode, postings_merge

    _note_call(_sig("mask", dpost, sq, m=m, backend=backend))
    with _quiet():
        return postings_merge.fused_hit_words(
            dpost.keys, dpost.row_blocks, dpost.first, dpost.meta,
            dpost.off, dpost.payload,
            dpack.values, dpack.thresh, dpack.buf, sq.blob,
            gq=sq.gq, cq=sq.cq, w=sq.w,
            m=m, backend=backend, interpret=interpret_mode(),
            has_dense=dpost.has_dense)


def fused_topk_scores(dpost, dpack, sq: StagedQuery, *, k: int, m: int,
                      backend: str):
    """(scores f32[Gq, k], ids i32[Gq, k]) device top-k over the fused
    score matrix — same pipeline, ``lax.top_k`` head (which ranks equal
    scores lowest-id-first, the dense (-score, id) tie rule)."""
    from repro.kernels import interpret_mode, postings_merge

    _note_call(_sig("topk", dpost, sq, m=m, backend=backend, extra=(k,)))
    with _quiet():
        return postings_merge.fused_topk(
            dpost.keys, dpost.row_blocks, dpost.first, dpost.meta,
            dpost.off, dpost.payload,
            dpack.values, dpack.thresh, dpack.buf, sq.blob,
            k=k, gq=sq.gq, cq=sq.cq, w=sq.w,
            m=m, backend=backend, interpret=interpret_mode(),
            has_dense=dpost.has_dense)


def unpack_hit_words(words, m: int) -> np.ndarray:
    """bool[m, Gq] from the fetched u32[ceil(m/32), Gq] hit words —
    bit ``i & 31`` of word ``i >> 5``. The lazy host-side half of the
    packed fetch (8× less transfer than the bool mask, 32× less than
    the float scores)."""
    words = np.asarray(words)
    shifts = np.arange(32, dtype=np.uint32)[None, :, None]
    bits = (words[:, None, :] >> shifts) & np.uint32(1)
    return bits.astype(bool).reshape(-1, words.shape[1])[:m]


def pruned_batch_device(
    arena: SketchArena, qp, thresholds, *, plan=None, backend: str,
) -> list[np.ndarray]:
    """Device-resident filter-and-verify for one query batch.

    ``thresholds`` is a scalar or per-query vector (all > 0 — the
    planner forces t ≤ 0 dense before routing here). Returns per-query
    hit ids, bit-identical to the dense sweep (same estimator math, same
    packed float32-exact thresholding). ``plan`` (a
    :class:`QueryPlan`, optional) only short-circuits the zero-hit case
    — no shape in the device program depends on it.
    """
    gq = qp.num_records
    m = arena.num_records
    if m == 0 or (plan is not None and plan.hits <= 0):
        return [np.zeros(0, np.int64) for _ in range(gq)]

    # Stage spans sit exactly at the transfer seams: "device.stage" is
    # host→device placement (one batched device_put out of the pooled
    # staging buffers), "device.kernel" the fused probe+decode+score+
    # pack jit (closed by sync — stage() is a shared no-op when no
    # observation context is attached, so the extra block_until_ready
    # only happens when observing), "device.fetch" the packed-word
    # readback + lazy bit decode.
    with stage("device.stage", queries=gq):
        dpost, dpack, sq = stage_query_inputs(arena, qp, thresholds)
    with stage("device.kernel", backend=backend) as span:
        words = span.sync(fused_mask_words(dpost, dpack, sq,
                                           m=m, backend=backend))
    with stage("device.fetch"):
        mask = unpack_hit_words(words, m)[:, :gq]
    return prune.mask_to_hits(mask)


def pruned_topk_device(
    arena: SketchArena, qp, k: int, *, backend: str,
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Device-resident top-k for one query batch.

    Returns ``[(ids int64[k'], scores float32[k'])]`` per query with
    ``k' = min(k, num_records)`` — the host ``pruned_topk`` contract:
    (score desc, id asc) order, zero-score records filling any shortfall
    in ascending-id order (``lax.top_k`` over the full score matrix
    produces exactly that, because non-candidates score exactly 0 and
    equal scores rank lowest-id-first). ``k`` is bucketed on device and
    sliced on fetch, so steady state reuses one compiled program.
    """
    gq = qp.num_records
    m = arena.num_records
    k_eff = min(int(k), m)
    if k_eff <= 0:
        return [(np.zeros(0, np.int64), np.zeros(0, np.float32))
                for _ in range(gq)]
    with stage("device.stage", queries=gq):
        dpost, dpack, sq = stage_query_inputs(arena, qp, None)
    k_call = min(_bucket(k_eff, lo=8), m)
    with stage("device.kernel", backend=backend, k=k_call) as span:
        vals, ids = fused_topk_scores(dpost, dpack, sq, k=k_call, m=m,
                                      backend=backend)
        span.sync(vals)
    with stage("device.fetch"):
        vals_h = np.asarray(vals)
        ids_h = np.asarray(ids)
    return [(ids_h[g, :k_eff].astype(np.int64),
             vals_h[g, :k_eff].astype(np.float32)) for g in range(gq)]
