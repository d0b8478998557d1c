"""The router: one place decides each sketch query batch's route and runs it.

A route is one of three:

    dense        the index sweep: scores f32[m, Gq] for the whole batch,
                 then packed threshold hits and/or the sweep's top-k
    fused        the device pipeline (``planner/device.py``): probe,
                 decode, score and the output head in one device program
    host_pruned  filter-and-verify over the host postings (``plan.py``),
                 the candidates scored by the index's pair scorer

The decision rule, in :func:`serve` only:

    threshold answers (k <= 0, or plan "pruned"): dense when the plan is
        "dense" or the smallest threshold is <= 0; otherwise
        ``choose_plan`` on the postings probe. A pruned decision runs
        fused when :func:`fused` holds, host_pruned otherwise.
    top-k: the dense sweep (finite thresholds' hit masks fall out of the
        same scores), unless the plan is "pruned".

``ShardedIndex``, the api sketch indexes and ``WindowManager`` (through
its current view) all answer through :func:`serve`. Each index says only
how it scores, by the hooks :class:`Routed` lists. Every route returns
the same answers bit for bit; only the work differs.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from repro.core import cost_model
from repro.core.arena import SketchArena
from repro.core.estimators import query_cols
from repro.obs.trace import stage, tally
from repro.planner.plan import (QueryPlan, choose_plan, merged_candidates,
                                normalize_plan, pruned_batch, pruned_topk)
from repro.planner.prune import (f32_threshold, mask_to_hits,
                                 threshold_hits_packed, threshold_mask)

#: Candidates per scoring call of a device backend's host-pruned top-k. A
#: call dispatches to every device and syncs with the host, so the host
#: loop's small default chunk would make thousands of calls per batch at
#: 480k records; scoring extra candidates never changes the answer.
DEVICE_TOPK_CHUNK = 4096


def fused(index) -> bool:
    """The fused-route test: one device, a device backend, an arena, and
    an engine whose scoring has a device twin. A mesh of more devices
    keeps the per-shard host merge: the fused program is unsharded."""
    return (index._mesh_devices == 1
            and index.backend in ("jnp", "pallas")
            and index._device_prunable
            and isinstance(index._sketch_pack(), SketchArena))


def serve(index, queries, thresholds, k: int, plan: str = "auto",
          explain: bool = False) -> list[dict]:
    """Answer one batch on ``index``: a dict per query with "hits" (ids
    whose score is at least the query's threshold; ``thresholds`` is a
    scalar, per-query, or None for top-k alone), "topk_ids" and
    "topk_scores" (empty for ``k <= 0``), and with ``explain=True`` an
    "explain" dict (:mod:`repro.obs.explain`: on the fused route it
    reruns the host candidate accounting; answers never change).

    The route lands on ``index.last_plan`` and, for host filter-and-
    verify, the candidate sets on ``index.last_candidates``."""
    t0 = time.perf_counter()
    plan = normalize_plan(plan)
    queries = [np.asarray(q) for q in queries]
    n = len(queries)
    m = index.num_records
    thr = (None if thresholds is None else
           np.broadcast_to(np.asarray(thresholds, np.float64), (n,)))
    dense_topk = k > 0 and plan != "pruned"
    index.last_candidates = None
    if not n:
        index.last_plan = QueryPlan("dense", np.nan, np.nan, 0, "no queries")
        return []
    qp = rows = posts = offs = hits = cands = tops = None

    if dense_topk:
        decision = QueryPlan(
            "dense", cost_model.dense_sweep_cost(
                m, index._sketch_pack().capacity, n), np.nan, 0,
            "topk batch: dense sweep amortized")
    elif thr is None:
        decision = QueryPlan("pruned", np.nan, np.nan, 0, "forced topk")
    elif plan == "dense" or float(thr.min()) <= 0.0:
        decision = QueryPlan("dense", np.nan, np.nan, 0,
                             "forced" if plan == "dense" else
                             "threshold <= 0")
    else:
        with stage("serve.sketch", queries=n):
            qp = index._sketch(queries)
            rows = index._query_rows(qp)
        with stage("shard.postings", shards=index._mesh_devices):
            posts, offs = index._shard_postings()
        decision = choose_plan(posts, rows[0], rows[1], float(thr.min()),
                               m, index._sketch_pack().capacity, plan=plan)
        # What the probe saw, as the cost model was fed it: count =
        # planner-routed batches, sum = blocks (entries).
        tally("probe.tail_blocks", decision.tail_blocks)
        tally("probe.dense_blocks", decision.tail_dense_blocks)
        tally("probe.buf_blocks", decision.blocks - decision.tail_blocks)
        tally("probe.entries", decision.hits)
    index.last_plan = decision
    route = ("dense" if decision.path == "dense" else
             "fused" if fused(index) else "host_pruned")
    # One count per batch: count = batches, sum = queries.
    tally(f"route.{route}", n)

    if qp is None:
        with stage("serve.sketch", queries=n):
            qp = index._sketch(queries)
    if route == "dense":
        with stage("serve.score", queries=n) as span:
            # count = sweeps, sum = the query columns its K∩ compare reads
            tally("sweep.query_cols", index._sweep_cols(qp))
            scores = index._dense_scores(qp)
            if not dense_topk:            # nothing downstream waits on it
                scores = span.sync(scores)
        if dense_topk:
            with stage("serve.topk", k=k):
                tops = index._dense_topk(scores, k)
        if thr is not None:
            with stage("serve.hits"):
                hits = _dense_hits(scores, m, thr)
    elif thr is not None and route == "fused":
        from repro.planner import device as planner_device

        hits = planner_device.pruned_batch_device(
            index._sketch_pack(), qp, thr, plan=decision,
            backend=index.backend)
        if explain:
            # The device never materializes candidate sets: account for
            # them on the host, as the host route would have.
            gen = merged_candidates(posts, offs)
            cands = [gen(qh, qb, float(t), int(qs))
                     for qh, qb, t, qs in zip(rows[0], rows[1], thr, rows[2])]
    elif thr is not None:
        hits, cands = pruned_batch(
            posts, rows[0], rows[1], rows[2], thr,
            functools.partial(index._score_pairs, qp), row_offsets=offs)
        index.last_candidates = cands

    if k > 0 and not dense_topk:
        with stage("serve.topk", k=k):
            tops = _pruned_topk(index, qp, rows, k)

    empty_ids, empty_scores = np.zeros(0, np.int64), np.zeros(0, np.float32)
    out = [{"hits": empty_ids if hits is None else hits[g],
            "topk_ids": empty_ids if tops is None else tops[g][0],
            "topk_scores": empty_scores if tops is None else tops[g][1]}
           for g in range(n)]
    if explain:
        from repro.obs.explain import build_explain

        ex = build_explain(
            decision, engine=index.engine, backend=index.backend,
            n_queries=n, hits=hits, cands=cands,
            hash_rows=None if rows is None else rows[0],
            sizes=None if rows is None else rows[2], posts=posts,
            measured_seconds=time.perf_counter() - t0)
        for g, (res, e) in enumerate(zip(out, ex)):
            if thr is not None:
                e["threshold"] = float(thr[g])
            res["explain"] = e
        index.last_explain = ex
    return out


def _dense_hits(scores, m: int, thr) -> list:
    """Per-query hit ids from the sweep's scores f32[>= m, Gq], the mask
    built only over the live columns: those whose float32 threshold is
    below +inf. Every dense score is finite (the estimator clamps its
    divisors), so a column at +inf has no hits, as on the pruned routes:
    every top-k rider's column."""
    thr32 = f32_threshold(thr)
    live = thr32 < np.inf
    # count = dense batches with thresholds, sum = columns masked
    tally("serve.hits_cols", int(live.sum()))
    if live.all():
        return threshold_hits_packed(scores[:m], thr32)
    hits = [np.zeros(0, np.int64) for _ in range(len(thr32))]
    if live.any():
        # The compare keeps the warmed [m, Gq] shape; the host packs
        # only the live columns.
        mask = threshold_mask(scores[:m], thr32)
        for g, h in zip(np.flatnonzero(live), mask_to_hits(mask[:, live])):
            hits[g] = h
    return hits


def _pruned_topk(index, qp, rows, k: int) -> list[tuple]:
    """Planner-aware top-k of every query in the batch's pack ``qp``:
    fused on the device when it can be, else the host's upper-bound-
    pruned loop per query over the index's postings."""
    if fused(index):
        from repro.planner import device as planner_device

        return planner_device.pruned_topk_device(
            index._sketch_pack(), qp, k, backend=index.backend)
    hash_rows, bit_rows, sizes = (index._query_rows(qp) if rows is None
                                  else rows)
    posts, offs = index._shard_postings()
    chunk = None if index.backend == "numpy" else DEVICE_TOPK_CHUNK

    def score_fn(g, cand_rec, _cand_q):
        return index._score_pairs(qp, cand_rec,
                                  np.full(len(cand_rec), g, np.int32))

    return [pruned_topk(posts, hash_rows[g], bit_rows[g], int(sizes[g]), k,
                        functools.partial(score_fn, g), index.num_records,
                        row_offsets=offs, chunk=chunk)
            for g in range(len(hash_rows))]


class Routed:
    """The query surface every routed index shares: ``serve_batch``,
    ``query``, ``batch_query`` and ``topk`` are calls into :func:`serve`.

    An index supplies how it scores, and nothing about routes:

        num_records, engine, backend
        _sketch_pack()          the :class:`SketchArena` it answers from
        _sketch(queries)        the batch's query pack
        _query_rows(qp)         (retained-hash rows, buffer-bit rows,
                                sizes): the planner's per-query inputs
        _shard_postings()       (postings per record slice, row offsets)
        _dense_scores(qp)       the sweep: scores f32[>= m, Gq]; rows
                                past ``num_records`` are padding
        _dense_topk(scores, k)  [(ids int64[k'], scores f32[k'])] per query
        _score_pairs(qp, rec, q)  f32[P] for candidate pairs (record, query)
        _mesh_devices           devices the records are sharded over
        _device_prunable        its scoring has a device twin
    """

    last_plan = None              # QueryPlan of the most recent batch
    last_candidates = None        # host route's per-query CandidateSets
    last_explain = None           # explain dicts of the last explained batch
    _mesh_devices = 1
    _device_prunable = False

    @property
    def last_candidate_sizes(self) -> list[int] | None:
        c = self.last_candidates
        return None if c is None else [len(s.rec_ids) for s in c]

    def _plan_queries(self, queries):
        """(query pack, retained-hash rows, buffer-bit rows, sizes)."""
        qp = self._sketch(queries)
        return (qp,) + tuple(self._query_rows(qp))

    def _sweep_cols(self, qp) -> int:
        """Query columns the dense sweep's K∩ compare reads: the jnp
        sweep stops past the batch's widest live row; the others read
        them all."""
        return (query_cols(qp.lengths) if self.backend == "jnp"
                else qp.values.shape[1])

    def serve_batch(self, queries, thresholds, k: int, plan: str = "auto",
                    explain: bool = False) -> list[dict]:
        """Threshold hits and top-k for a whole batch (:func:`serve`);
        ``thresholds`` is scalar or per-query."""
        return serve(self, queries, thresholds, k, plan=plan,
                     explain=explain)

    def query(self, q_ids, threshold: float, *, plan: str = "auto",
              explain: bool = False):
        out = self.batch_query([q_ids], threshold, plan=plan,
                               explain=explain)
        return (out[0][0], out[1][0]) if explain else out[0]

    def batch_query(self, queries, threshold: float, *, plan: str = "auto",
                    explain: bool = False):
        """Per-query hit ids; with ``explain=True`` also the per-query
        explain dicts, as ``(hits, explains)``."""
        out = serve(self, queries, threshold, 0, plan=plan, explain=explain)
        hits = [o["hits"] for o in out]
        return (hits, [o["explain"] for o in out]) if explain else hits

    def topk(self, q_ids, k: int, *,
             plan: str = "auto") -> tuple[np.ndarray, np.ndarray]:
        """(ids, scores) of the ``k`` best records, score descending and
        ties by ascending id, on every route."""
        out = serve(self, [q_ids], None, k, plan=plan)[0]
        return out["topk_ids"], out["topk_scores"]
