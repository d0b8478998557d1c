"""Per-batch query planning: dense sweep vs postings-pruned verify.

``choose_plan`` probes the postings (searchsorted only — no merge) for
the batch's query hashes, feeds the touched-entry count into the
core/cost_model.py query-path costs, and picks the cheaper path.
``plan="dense"``/``"pruned"`` force a path; ``"auto"`` is the default
everywhere. One hard guard keeps forced/auto pruning sound: thresholds
≤ 0 always run dense — every record trivially clears t, so a filter
built on "shares at least one hash/bit" would drop records the dense
sweep returns.

``pruned_batch`` is the shared execution skeleton: generate candidates
per query, score the ragged union in ONE backend call (the engines pass
a closure over kernels/gather_score.py or their estimator), and cut at
the float32-exact threshold so results match the dense sweep bit for
bit.

``pruned_topk`` extends the same machinery to top-k: candidates come
from the postings with their containment upper bounds, get scored in
bound-descending chunks, and scoring stops once the running k-th score
exceeds every remaining bound — the moving-threshold analogue of the
fixed-threshold cut. Non-candidates score exactly 0 under the
estimator, so the result is *identical* to the dense ranking under the
deterministic (score desc, record id asc) order.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

from repro.core import cost_model
from repro.obs.trace import stage
from repro.planner import prune
from repro.planner.postings import PostingsIndex

PLAN_MODES = ("auto", "dense", "pruned")


@dataclasses.dataclass
class QueryPlan:
    """One batch's routing decision (attached to indexes as .last_plan)."""

    path: str              # "dense" | "pruned"
    est_dense: float       # cost-model units
    est_pruned: float
    hits: int              # posting entries the batch's hashes/bits touch
    reason: str
    per_query_hits: np.ndarray | None = None   # int64[Gq] probe breakdown
    blocks: int = 0               # posting blocks touched (tail + buffer)
    tail_blocks: int = 0          # tail blocks touched (device expand bound)
    tail_dense_blocks: int = 0    # of which dense-bitmap blocks


def normalize_plan(plan: str | None) -> str:
    plan = "auto" if plan is None else plan
    if plan not in PLAN_MODES:
        raise ValueError(f"plan must be one of {PLAN_MODES}, got {plan!r}")
    return plan


def unpack_query_rows(qp):
    """Per-query planner inputs from an already-sketched query pack:
    (retained-hash rows, buffer-bit rows, query sizes)."""
    vals, lens = np.asarray(qp.values), np.asarray(qp.lengths)
    bufs = np.asarray(qp.buf)
    hash_rows = [vals[g, : lens[g]] for g in range(qp.num_records)]
    bit_rows = [prune.query_bits(bufs[g]) for g in range(qp.num_records)]
    return hash_rows, bit_rows, np.asarray(qp.sizes)


def _probe(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hash_rows: Sequence[np.ndarray],
    q_bit_rows: Sequence[np.ndarray],
) -> tuple[np.ndarray, int, int, int]:
    """ONE key-probe pass over the batch: (per-query posting entries,
    tail_blocks, tail_dense_blocks, buf_blocks).

    Header arithmetic only (cached row lengths, row_blocks diffs, a
    dense-kind cumsum) — nothing decodes, the buffer store included.
    This host probe feeds the COST MODEL and explain/bookkeeping only;
    the device path runs its own probe on the mirrored headers inside
    the fused jit (kernels/postings_merge.py), so no shape there depends
    on these numbers. ``posts`` may be a list (one per shard);
    everything sums over the mesh.
    """
    if isinstance(posts, PostingsIndex):
        posts = [posts]
    gq = len(q_hash_rows)
    per = np.zeros(gq, dtype=np.int64)
    tb = td = bb = 0
    with stage("planner.probe", queries=gq, shards=len(posts)) as span:
        # ONE flattened searchsorted per shard for the whole batch (the
        # per-query segment sums come back via np.add.at — int64-exact,
        # unlike a float-weighted bincount).
        if gq:
            allh = np.concatenate(
                [np.asarray(q, np.uint32).ravel() for q in q_hash_rows])
            hidx = np.repeat(np.arange(gq, dtype=np.int64),
                             [len(np.asarray(q).ravel())
                              for q in q_hash_rows])
            allb = np.concatenate(
                [np.asarray(q, np.int64).ravel() for q in q_bit_rows])
            bidx = np.repeat(np.arange(gq, dtype=np.int64),
                             [len(np.asarray(q).ravel())
                              for q in q_bit_rows])
        else:
            allh = np.zeros(0, np.uint32)
            hidx = allb = bidx = np.zeros(0, np.int64)
        for post in posts:
            keys = post.keys
            row_lens = post.tail_row_lengths()
            buf_lens = post.buf_row_lengths()
            rbt = post.tail.row_blocks.astype(np.int64)
            dcum = np.concatenate(
                [[0], np.cumsum((post.tail.meta >> np.uint32(13))
                                & np.uint32(1))]).astype(np.int64)
            rbb = post.buf.row_blocks.astype(np.int64)
            pos = np.searchsorted(keys, allh)
            ok = pos < len(keys)
            hit = np.zeros(len(allh), dtype=bool)
            hit[ok] = keys[pos[ok]] == allh[ok]
            r = pos[hit]
            np.add.at(per, hidx[hit], row_lens[r].astype(np.int64))
            tb += int((rbt[r + 1] - rbt[r]).sum())
            td += int((dcum[rbt[r + 1]] - dcum[rbt[r]]).sum())
            live = allb < post.buf.num_rows
            qb = allb[live]
            np.add.at(per, bidx[live], buf_lens[qb].astype(np.int64))
            bb += int((rbb[qb + 1] - rbb[qb]).sum())
        span.set(hits=int(per.sum()), tail_blocks=tb, buf_blocks=bb)
    return per, tb, td, bb


def probe_hits_per_query(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hash_rows: Sequence[np.ndarray],
    q_bit_rows: Sequence[np.ndarray],
) -> np.ndarray:
    """int64[Gq] posting entries a merge would touch per query —
    searchsorted + header arithmetic, no merge, no decode."""
    return _probe(posts, q_hash_rows, q_bit_rows)[0]


def probe_hits(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hash_rows: Sequence[np.ndarray],
    q_bit_rows: Sequence[np.ndarray],
) -> int:
    """Total posting entries a merge would touch for the batch."""
    return int(probe_hits_per_query(posts, q_hash_rows, q_bit_rows).sum())


def probe_block_stats(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hash_rows: Sequence[np.ndarray],
    q_bit_rows: Sequence[np.ndarray],
) -> tuple[int, int, int]:
    """(tail_blocks, tail_dense_blocks, buf_blocks) the batch touches."""
    return _probe(posts, q_hash_rows, q_bit_rows)[1:]


def choose_plan(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hash_rows: Sequence[np.ndarray],
    q_bit_rows: Sequence[np.ndarray],
    threshold: float,
    m: int,
    capacity: int,
    plan: str = "auto",
) -> QueryPlan:
    gq = len(q_hash_rows)
    plan = normalize_plan(plan)
    if float(threshold) <= 0.0:
        # Every record passes t ≤ 0; postings can't see zero-overlap pairs.
        return QueryPlan("dense", 0.0, np.inf, 0,
                         "threshold <= 0: pruning unsound, forced dense")
    per, tb, td, bb = _probe(posts, q_hash_rows, q_bit_rows)
    # The decision from the probe's counts, apart from the probe itself.
    with stage("planner.choose", queries=gq):
        hits = int(per.sum())
        est_dense = cost_model.dense_sweep_cost(m, capacity, gq)
        est_pruned = cost_model.pruned_path_cost(hits, capacity, gq,
                                                 blocks=tb + bb)
        blk = dict(blocks=tb + bb, tail_blocks=tb, tail_dense_blocks=td)
        if plan == "auto":
            path = "pruned" if est_pruned < est_dense else "dense"
            reason = f"auto: dense≈{est_dense:.3g} vs pruned≈{est_pruned:.3g}"
        else:
            path, reason = plan, "forced"
        return QueryPlan(path, est_dense, est_pruned, hits, reason, per,
                         **blk)


def merged_candidates(
    posts: PostingsIndex | Sequence[PostingsIndex],
    row_offsets: Sequence[int] | None = None,
) -> Callable[..., prune.CandidateSet]:
    """Candidate generator over one postings index or a sharded list.

    ``row_offsets[s]`` maps shard-local record ids to global ids; shard
    ranges partition the records, so the cross-mesh union is a
    concatenation that stays sorted.
    """
    if isinstance(posts, PostingsIndex):
        posts = [posts]
    if row_offsets is None:
        row_offsets = [0] * len(posts)

    def gen(qh, qb, t, qs) -> prune.CandidateSet:
        parts = [prune.candidates_for(p, qh, qb, t, qs) for p in posts]
        return prune.CandidateSet(
            rec_ids=np.concatenate(
                [c.rec_ids + off for c, off in zip(parts, row_offsets)]),
            counts=np.concatenate([c.counts for c in parts]),
            o1=np.concatenate([c.o1 for c in parts]),
            hits=sum(c.hits for c in parts),
            pruned=sum(c.pruned for c in parts),
            blocks=sum(c.blocks for c in parts),
            skipped_blocks=sum(c.skipped_blocks for c in parts),
        )

    return gen


def pruned_batch(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hash_rows: Sequence[np.ndarray],
    q_bit_rows: Sequence[np.ndarray],
    q_sizes: Sequence[int],
    thresholds,
    score_fn: Callable[[np.ndarray, np.ndarray], np.ndarray],
    row_offsets: Sequence[int] | None = None,
) -> tuple[list[np.ndarray], list[prune.CandidateSet]]:
    """Filter-and-verify for one query batch.

    ``score_fn(cand_rec i32[P], cand_q i32[P]) -> f32[P]`` scores the
    flattened ragged candidate list with the engine's own estimator (one
    backend dispatch for the whole batch). Returns (per-query hit ids,
    per-query candidate sets) — ids are bit-identical to the dense
    sweep's ``np.nonzero(scores >= t)`` for each query.
    """
    gq = len(q_hash_rows)
    thr = np.broadcast_to(np.asarray(thresholds, np.float64), (gq,))
    gen = merged_candidates(posts, row_offsets)
    with stage("planner.candidates", queries=gq) as span:
        cands = [
            gen(qh, qb, float(t), int(qs))
            for qh, qb, t, qs in zip(q_hash_rows, q_bit_rows, thr, q_sizes)
        ]
        lens = [len(c.rec_ids) for c in cands]
        span.set(candidates=int(sum(lens)),
                 blocks=sum(c.blocks for c in cands),
                 skipped_blocks=sum(c.skipped_blocks for c in cands))
    if sum(lens) == 0:
        return [np.zeros(0, dtype=np.int64) for _ in range(gq)], cands

    cand_rec = np.concatenate(
        [c.rec_ids for c in cands]).astype(np.int32)
    cand_q = np.repeat(np.arange(gq, dtype=np.int32), lens)
    with stage("planner.score", candidates=len(cand_rec)):
        # np.asarray forces any device result to host — the span closes
        # only after the scores actually exist.
        scores = np.asarray(score_fn(cand_rec, cand_q), dtype=np.float32)

    out = []
    pos = 0
    thr32 = prune.f32_threshold(thr)
    for g, c in enumerate(cands):
        s = scores[pos : pos + lens[g]]
        pos += lens[g]
        out.append(c.rec_ids[s >= thr32[g]].astype(np.int64))
    return out, cands


def topk_select(rec_ids, scores, k: int,
                num_records: int) -> tuple[np.ndarray, np.ndarray]:
    """The top-k output head shared by every host route.

    One implementation of the ranking contract the device route's
    ``lax.top_k`` produces over a full score matrix: score descending,
    ties by ascending record id, and records absent from ``rec_ids`` (or
    scoring exactly 0 — the same tie pool, since absent records score 0
    under every estimator) filling any shortfall in ascending-id order.
    The dense sweep (:meth:`repro.api._IndexBase.topk`) and the host
    :func:`pruned_topk` both route here, so host and device rankings
    can only drift apart in one place.
    """
    k = min(int(k), int(num_records))
    if k <= 0:
        return np.zeros(0, np.int64), np.zeros(0, np.float32)
    ids = np.asarray(rec_ids, np.int64)
    s = np.asarray(scores, np.float32)
    pos_mask = s > 0
    ids, s = ids[pos_mask], s[pos_mask]
    order = np.lexsort((ids, -s))           # score desc, id asc
    ids, s = ids[order][:k], s[order][:k]
    if len(ids) < k:
        # Zero-score records, ascending id — the dense tail among ties
        # at 0.
        fill = np.setdiff1d(np.arange(num_records, dtype=np.int64),
                            ids)[: k - len(ids)]
        ids = np.concatenate([ids, fill])
        s = np.concatenate([s, np.zeros(len(fill), np.float32)])
    return ids.astype(np.int64), s.astype(np.float32)


def pruned_topk(
    posts: PostingsIndex | Sequence[PostingsIndex],
    q_hashes: np.ndarray,
    q_bits: np.ndarray,
    q_size: int,
    k: int,
    score_fn: Callable[[np.ndarray, np.ndarray], np.ndarray],
    num_records: int,
    row_offsets: Sequence[int] | None = None,
    chunk: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-k via postings-driven upper-bound pruning.

    Candidates are generated at threshold 0 (i.e. every record sharing a
    retained hash or buffer bit), each carrying the same containment
    upper bound the threshold filter uses. They are scored in
    bound-descending chunks; once k scores are in hand and every
    remaining bound (slack-inflated, so float32 rounding of the dense
    scores cannot sneak past it) sits strictly below the running k-th
    score, the rest can neither enter nor tie into the top-k and scoring
    stops. Records outside the candidate set score exactly 0 under the
    estimator and fill any shortfall in ascending-id order — matching
    the dense ranking's deterministic (score desc, id asc) tie rule
    entry for entry.
    """
    k = min(int(k), int(num_records))
    empty = (np.zeros(0, np.int64), np.zeros(0, np.float32))
    if k <= 0:
        return empty
    gen = merged_candidates(posts, row_offsets)
    cand = gen(np.asarray(q_hashes, np.uint32), np.asarray(q_bits, np.int64),
               0.0, int(q_size))
    n = len(cand.rec_ids)

    scored_ids: list[np.ndarray] = []
    scored_s: list[np.ndarray] = []
    if n:
        bound = prune.tail_bound(np.sort(np.asarray(q_hashes, np.uint32)))
        ub = (cand.o1.astype(np.float64)
              + bound[np.minimum(cand.counts, len(bound) - 1)]) \
            / max(int(q_size), 1) * prune._BOUND_SLACK
        order = np.argsort(-ub, kind="stable")
        chunk = int(chunk) if chunk else max(4 * k, 64)
        kth = -np.inf
        done = 0
        pos = 0
        while pos < n:
            sel = order[pos : pos + chunk]
            if done >= k and ub[sel[0]] < kth:
                break               # bounds descend: nothing left can enter
            s = np.asarray(score_fn(cand.rec_ids[sel].astype(np.int32),
                                    np.zeros(len(sel), np.int32)),
                           dtype=np.float32)
            scored_ids.append(cand.rec_ids[sel])
            scored_s.append(s)
            done += len(sel)
            pos += len(sel)
            if done >= k:
                alls = np.concatenate(scored_s)
                kth = float(np.partition(alls, len(alls) - k)[len(alls) - k])

    ids = np.concatenate(scored_ids) if scored_ids else np.zeros(0, np.int64)
    s = np.concatenate(scored_s) if scored_s else np.zeros(0, np.float32)
    # Zero-scored candidates (possible for plain KMV: a shared value can
    # fall outside the top-k union) belong to the same tie pool as
    # non-candidates; whenever scoring stopped early the running k-th
    # score was positive, so dropped/unscored rows cannot matter. The
    # shared head applies the verified (score desc, id asc, zero-fill)
    # contract.
    return topk_select(ids, s, k, num_records)
