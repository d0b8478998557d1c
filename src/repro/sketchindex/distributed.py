"""Device-resident, mesh-sharded GB-KMV index (the paper at cluster scale).

Layout: the packed sketch matrices (core/sketches.py) with the *record*
dim sharded over every mesh axis — P(("pod","data","model")) — because
containment scoring is embarrassingly parallel over records. Queries are
replicated (a query batch is KBs).

Search = one sweep of the sharded matrix:
    scores[M, Gq] = kernel/jnp scoring   (records stay put, zero collective)
    then either
      * threshold mask (Algorithm 2)     — zero-collective output, or
      * global top-k: per-shard lax.top_k → all_gather of (devices × k × Gq)
        candidates (tiny) → final top_k — the ONLY collective in the
        query path, bytes = devices·k·8 per query.

Query batching (beyond-paper): scoring Gq queries per sweep divides the
HBM-bound roofline term by Gq — see EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.estimators import buffer_intersection, gkmv_pair_estimate
from repro.core.hashing import PAD
from repro.core.sketches import PackedSketches
from repro.obs.trace import stage as obs_stage, tally as obs_tally
from repro.parallel.sharding import logical_to_spec


#: Candidates per device scoring call of the mesh's pruned top-k. A call
#: dispatches to every device and syncs with the host, so the host
#: loop's small default chunk would make thousands of calls per batch at
#: 480k records; scoring extra candidates never changes the answer.
DEVICE_TOPK_CHUNK = 4096


@dataclasses.dataclass
class DeviceIndex:
    """Sharded PackedSketches + the metadata needed to sketch queries."""

    values: jax.Array    # u32[Mp, C]   rows sharded
    lengths: jax.Array   # i32[Mp]
    thresh: jax.Array    # u32[Mp]
    buf: jax.Array       # u32[Mp, W]
    sizes: jax.Array     # i32[Mp]
    num_records: int     # true M (before padding)
    tau: int             # hashable metadata (jit cache key)
    top_elems: tuple
    seed: int

    @property
    def padded_records(self) -> int:
        return self.values.shape[0]


def _pad_rows(a: np.ndarray, target: int, fill):
    if a.shape[0] == target:
        return a
    pad = np.full((target - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


def to_device_index(index, mesh: Mesh) -> DeviceIndex:
    """Place a host GBKMVIndex onto the mesh, record-dim fully sharded.

    Rows are padded to a multiple of the mesh size; padded rows get
    thresh=0 (nothing live → score 0, never a false candidate).
    """
    s: PackedSketches = index.sketches
    n_dev = mesh.devices.size
    m = s.num_records
    mp = -(-m // n_dev) * n_dev

    row_spec = logical_to_spec(("records",), mesh)
    rows2d = NamedSharding(mesh, P(row_spec[0], None))
    rows1d = NamedSharding(mesh, P(row_spec[0]))

    return DeviceIndex(
        values=jax.device_put(_pad_rows(np.asarray(s.values), mp, PAD), rows2d),
        lengths=jax.device_put(_pad_rows(np.asarray(s.lengths), mp, 0), rows1d),
        thresh=jax.device_put(_pad_rows(np.asarray(s.thresh), mp, 0), rows1d),
        buf=jax.device_put(
            _pad_rows(np.asarray(s.buf if s.buf.shape[1] else
                                 np.zeros((m, 1), np.uint32)), mp, 0), rows2d),
        sizes=jax.device_put(_pad_rows(np.asarray(s.sizes), mp, 0), rows1d),
        num_records=m,
        tau=int(index.tau),
        top_elems=tuple(int(e) for e in index.top_elems),
        seed=index.seed,
    )


def batch_queries(index, queries) -> PackedSketches:
    """Sketch a list of query id-arrays into one replicated query pack.

    One vectorized pass for the whole batch (CSR ingest + one hash pass +
    one lexsort-pack) — ``repro.core.gbkmv.sketch_query_batch``, the same
    packer the api query path uses."""
    from repro.core.gbkmv import sketch_query_batch

    return sketch_query_batch(index, [np.asarray(q) for q in queries])


def _scores_jnp(values, lengths, thresh, buf, q_values, q_thresh, q_buf, q_sizes):
    """Pure-jnp scoring [Mshard, Gq] — the pjit/dry-run lowering path."""
    def one_query(qv, qt, qb, qs):
        d_hat, _, _ = gkmv_pair_estimate(qv, None, qt, values, lengths, thresh)
        o1 = buffer_intersection(qb, buf)
        return (o1.astype(jnp.float32) + d_hat) / jnp.maximum(
            qs.astype(jnp.float32), 1.0)

    return jax.vmap(one_query)(q_values, q_thresh, q_buf, q_sizes).T


def _scores_pallas(mesh: Mesh, values, thresh, buf, q_values, q_thresh,
                   q_buf, q_sizes):
    """The Pallas dense scorer, one kernel per record shard: a kernel is
    opaque to the partitioner, so ``shard_map`` runs it on each device's
    rows with the queries replicated."""
    from repro.kernels import interpret_mode
    from repro.kernels.ops import score_index

    rows = logical_to_spec(("records",), mesh)[0]
    interpret = interpret_mode(mesh.devices.flat[0].platform)
    return compat.shard_map(
        functools.partial(score_index, interpret=interpret), mesh=mesh,
        in_specs=(P(rows, None), P(rows), P(rows, None),
                  P(), P(), P(), P()),
        out_specs=P(rows, None),
    )(values, thresh, buf, q_values, q_thresh, q_buf, q_sizes)


@functools.partial(jax.jit, static_argnames=("backend", "mesh"))
def _score_batch_jit(didx: DeviceIndex, q: PackedSketches, backend: str,
                     mesh: Mesh):
    qv = jnp.asarray(q.values, jnp.uint32)
    qt = jnp.asarray(q.thresh, jnp.uint32)
    qb = jnp.asarray(q.buf, jnp.uint32)
    qs = jnp.asarray(q.sizes, jnp.int32)
    if qb.shape[1] != didx.buf.shape[1]:
        qb = jnp.pad(qb, ((0, 0), (0, didx.buf.shape[1] - qb.shape[1])))
    if backend == "pallas":
        return _scores_pallas(mesh, didx.values, didx.thresh, didx.buf,
                              qv, qt, qb, qs)
    return _scores_jnp(didx.values, didx.lengths, didx.thresh, didx.buf,
                       qv, qt, qb, qs)


def _pairs_local(backend, interpret, x_rows, x_thresh, x_buf, q_values,
                 q_thresh, q_buf, q_sizes, cand_rec, cand_q):
    from repro.kernels import gather_score

    if backend == "pallas":
        return gather_score._gather_score_pallas(
            x_rows, x_thresh, x_buf, q_values, q_thresh, q_buf, q_sizes,
            cand_rec, cand_q, interpret=interpret)
    return gather_score._gather_score_jnp(
        x_rows, x_thresh, x_buf, q_values, q_thresh, q_buf, q_sizes,
        cand_rec, cand_q)


@functools.partial(jax.jit, static_argnames=("backend", "mesh"))
def _score_pairs_jit(x_rows, x_thresh, x_buf, q: PackedSketches,
                     cand_rec, cand_q, backend: str, mesh: Mesh):
    """Pair scores f32[n·P] for candidates already split by owner:
    ``cand_rec``/``cand_q`` i32[n·P] hold P pairs per record shard, with
    record ids local to that shard. Each device gathers its own rows —
    no pair is scored twice and no row crosses a chip."""
    from repro.kernels import interpret_mode

    rows = logical_to_spec(("records",), mesh)[0]
    qb = jnp.asarray(q.buf, jnp.uint32)
    if qb.shape[1] != x_buf.shape[1]:
        qb = jnp.pad(qb, ((0, 0), (0, x_buf.shape[1] - qb.shape[1])))
    return compat.shard_map(
        functools.partial(_pairs_local, backend,
                          interpret_mode(mesh.devices.flat[0].platform)),
        mesh=mesh,
        in_specs=(P(rows, None), P(rows), P(rows, None),
                  P(), P(), P(), P(), P(rows), P(rows)),
        out_specs=P(rows),
    )(x_rows, x_thresh, x_buf, jnp.asarray(q.values, jnp.uint32),
      jnp.asarray(q.thresh, jnp.uint32), qb,
      jnp.asarray(q.sizes, jnp.int32), cand_rec, cand_q)


def score_batch(didx: DeviceIndex, q: PackedSketches,
                backend: str | None = None, impl: str | None = None):
    """Containment scores f32[Mp, Gq]; records sharded, queries replicated.

    ``backend`` ∈ {"numpy", "jnp", "pallas"} — the one option threaded
    through every scoring layer (``impl=`` is the deprecated spelling;
    "kernel" → "pallas"). "numpy" computes on host from fetched shards —
    a debug/parity path, not a serving path.
    """
    from repro.core.estimators import containment_matrix, normalize_backend

    backend = normalize_backend(backend, impl)
    if backend == "numpy":
        x = PackedSketches(
            values=np.asarray(didx.values), lengths=np.asarray(didx.lengths),
            thresh=np.asarray(didx.thresh), buf=np.asarray(didx.buf),
            sizes=np.asarray(didx.sizes))
        qh = PackedSketches(
            values=np.asarray(q.values), lengths=np.asarray(q.lengths),
            thresh=np.asarray(q.thresh), buf=np.asarray(q.buf),
            sizes=np.asarray(q.sizes))
        return containment_matrix(qh, x, backend="numpy")
    return _score_batch_jit(didx, q, backend, didx.values.sharding.mesh)


jax.tree_util.register_dataclass(
    DeviceIndex,
    data_fields=["values", "lengths", "thresh", "buf", "sizes"],
    meta_fields=["num_records", "tau", "top_elems", "seed"],
)


@functools.partial(jax.jit, static_argnames=("k", "mesh"))
def distributed_topk(scores, k: int, mesh: Mesh):
    """Global top-k over the sharded record dim via shard_map.

    scores f32[Mp, Gq] (rows sharded) -> (vals f32[Gq, k], ids i32[Gq, k]).
    Per-shard top-k then one tiny all_gather of (n_dev · k) candidates.
    One compiled program per (shape, k, mesh).
    """
    row_axes = logical_to_spec(("records",), mesh)[0]
    n_shards = int(np.prod([mesh.shape[a] for a in (
        row_axes if isinstance(row_axes, tuple) else (row_axes,))]))
    mp = scores.shape[0]
    shard_rows = mp // n_shards

    def local(scores_blk):                       # [mp/n, Gq]
        v, i = jax.lax.top_k(scores_blk.T, min(k, shard_rows))  # [Gq, k]
        # Shard-local row ids -> global ids.
        if isinstance(row_axes, tuple):
            pos = 0
            stride = 1
            for a in reversed(row_axes):
                pos = pos + jax.lax.axis_index(a) * stride
                stride = stride * mesh.shape[a]
        else:
            pos = jax.lax.axis_index(row_axes)
        gid = i + pos * shard_rows
        vg = jax.lax.all_gather(v, row_axes, axis=0, tiled=False)
        ig = jax.lax.all_gather(gid, row_axes, axis=0, tiled=False)
        vg = vg.reshape((-1,) + v.shape)          # [n, Gq, k]
        ig = ig.reshape((-1,) + gid.shape)
        vflat = jnp.moveaxis(vg, 0, 1).reshape(v.shape[0], -1)   # [Gq, n*k]
        iflat = jnp.moveaxis(ig, 0, 1).reshape(v.shape[0], -1)
        vtop, sel = jax.lax.top_k(vflat, k)
        return vtop, jnp.take_along_axis(iflat, sel, axis=-1)

    fn = compat.shard_map(
        local, mesh=mesh,
        in_specs=(P(row_axes, None),),
        out_specs=(P(), P()),
    )
    return fn(scores)


def distributed_search(didx: DeviceIndex, q: PackedSketches, threshold: float,
                       backend: str | None = None, impl: str | None = None):
    """Algorithm 2 at cluster scale: boolean candidate mask [Mp, Gq]."""
    scores = score_batch(didx, q, backend=backend, impl=impl)
    return scores >= threshold, scores


class ShardedIndex:
    """Device-sharded GB-KMV index implementing the ``repro.api`` protocol.

    Wraps a host :class:`GBKMVIndex` placed on a mesh (``to_device_index``)
    so serving, benchmarks, and the api registry talk to sharded and host
    indexes through the same surface — ``SketchServer`` no longer
    special-cases device placement.
    """

    engine = "gbkmv"

    def __init__(self, index, mesh: Mesh, backend: str = "jnp",
                 budget: int | None = None):
        from repro.core.arena import SketchArena

        core = getattr(index, "core", index)       # api wrapper or core index
        core.sketches = SketchArena.from_pack(core.sketches)
        self.host = core
        self.mesh = mesh
        self.backend = backend
        self.budget = budget if budget is not None else getattr(
            index, "budget", None)
        self.didx = to_device_index(core, mesh)
        self._lane_rows = None
        self.last_plan = None
        # Explain/observability bookkeeping from the most recent planned
        # batch: per-query CandidateSets (pruned path only) and the
        # planner inputs needed for upper-bound stats.
        self.last_candidates = None
        self._last_plan_inputs = None

    @property
    def num_records(self) -> int:
        return self.host.num_records

    # -- planner plumbing: per-shard postings, candidates unioned --
    def _shard_postings(self):
        """(postings, row_offsets) over the arena's record slices.

        One block-compressed postings index per record-offset slice,
        built from column *views* of the shared arena (no per-shard
        host copies) and cached on the arena itself — so the host api
        index and the sharded view maintain ONE postings store.
        Candidate generation probes every slice (block headers first —
        skipping applies per shard) and unions the (disjoint) results —
        the host-side mirror of the mesh's all_gather. After inserts
        the slices update in place (τ-truncation of each slice's blocks
        + re-encoding only the appended rows); their boundaries may
        then lag the mesh's ceil-partition, which is harmless because
        the union reports global record ids either way.
        """
        return self.host.sketches.shard_postings(self.mesh.devices.size)

    def _score_pairs(self, qp, cand_rec, cand_q) -> np.ndarray:
        """f32[P] pair scores for the host merge's candidates. A device
        backend scores against the resident sharded columns: the pairs
        are grouped by the shard that holds their record, each device
        scores only its group, and the scores return in input order."""
        from repro.kernels import gather_score

        if self.backend == "numpy":
            return gather_score.score_pairs(self.host.sketches, qp, cand_rec,
                                            cand_q, backend="numpy")
        rec = np.asarray(cand_rec, np.int64)
        if len(rec) == 0:
            return np.zeros(0, np.float32)
        n = self.mesh.devices.size
        shard = self.didx.padded_records // n
        owner = rec // shard
        order = np.argsort(owner, kind="stable")
        counts = np.bincount(owner, minlength=n)
        # At least one kernel grid step per shard: top-k's chunks then
        # share a few compiled widths instead of one per power of two.
        width = gather_score._pad_pow2(int(counts.max()), gather_score.STEP)
        # Slot of each (owner-sorted) pair in its shard's group of
        # ``width``; the rest score (local row 0, query 0), never read.
        rank = np.arange(len(rec)) - np.repeat(np.cumsum(counts) - counts,
                                               counts)
        slot = owner[order] * width + rank
        local_rec = np.zeros(n * width, np.int32)
        local_q = np.zeros(n * width, np.int32)
        local_rec[slot] = rec[order] - owner[order] * shard
        local_q[slot] = np.asarray(cand_q, np.int32)[order]
        out = np.asarray(_score_pairs_jit(
            self._pair_rows(), self.didx.thresh, self.didx.buf, qp,
            local_rec, local_q, self.backend, self.mesh))
        scores = np.empty(len(rec), np.float32)
        scores[order] = out[slot]
        return scores

    def _pair_rows(self):
        """The record rows the pair scorer reads: the Pallas kernel's
        128-lane layout, built once per placement, or the columns."""
        if self.backend != "pallas":
            return self.didx.values
        if self._lane_rows is None:
            from repro.kernels.gather_score import lane_rows

            self._lane_rows = jax.device_put(
                lane_rows(self.didx.values), self.didx.values.sharding)
        return self._lane_rows

    def _device_route(self) -> bool:
        """True when the fused all-device pipeline can serve this index:
        a single-device mesh (the fused program is unsharded) plus a
        device scoring backend. Multi-device meshes keep the per-shard
        host merge — its block skipping applies shard by shard."""
        from repro.core.arena import SketchArena

        return (self.mesh.devices.size == 1
                and self.backend in ("jnp", "pallas")
                and isinstance(self.host.sketches, SketchArena))

    def _pruned_batch(self, queries, thresholds, plan: str):
        """Planner route for a batch. Returns (hits, qp): hits is None
        when the cost model (or a guard) sends the batch dense, and qp
        is the already-sketched query pack (or None) so the dense path
        never re-sketches the batch."""
        from repro import planner
        from repro.planner.plan import gbkmv_plan_queries

        plan = planner.normalize_plan(plan)
        thr = np.asarray(thresholds, np.float64)
        t_min = float(thr.min()) if thr.size else 0.0
        self.last_candidates = None
        self._last_plan_inputs = None
        if plan == "dense" or t_min <= 0.0 or not queries:
            # A decision was still made — record it so explain and the
            # drift gauge always have something to read.
            self.last_plan = planner.QueryPlan(
                "dense", np.nan, np.nan, 0,
                "forced" if plan == "dense" else "threshold <= 0")
            return None, None
        with obs_stage("serve.sketch", queries=len(queries)):
            qp, hash_rows, bit_rows, sizes = gbkmv_plan_queries(
                self.host, queries)
        with obs_stage("shard.postings", shards=self.mesh.devices.size):
            posts, offs = self._shard_postings()
        s: PackedSketches = self.host.sketches
        decision = planner.choose_plan(
            posts, hash_rows, bit_rows, t_min,
            s.num_records, s.capacity, plan=plan)
        self.last_plan = decision
        self._last_plan_inputs = (hash_rows, sizes, posts)
        # What the probe saw, as the cost model was fed it: count =
        # planner-routed batches, sum = blocks (entries).
        obs_tally("probe.tail_blocks", decision.tail_blocks)
        obs_tally("probe.dense_blocks", decision.tail_dense_blocks)
        obs_tally("probe.buf_blocks", decision.blocks - decision.tail_blocks)
        obs_tally("probe.entries", decision.hits)
        if decision.path == "dense":
            return None, qp

        if self._device_route():
            from repro.planner import device as planner_device

            # Fused probe→decode→score→threshold entirely on device:
            # per-query candidate sets never materialize on host, so
            # explain carries the probe breakdown only.
            ids = planner_device.pruned_batch_device(
                self.host.sketches, qp, thresholds,
                plan=decision, backend=self.backend)
            return ids, qp

        ids, cands = planner.pruned_batch(
            posts, hash_rows, bit_rows, sizes, thresholds,
            functools.partial(self._score_pairs, qp), row_offsets=offs)
        self.last_candidates = cands
        return ids, qp

    # -- scoring --
    def batch_scores(self, queries) -> np.ndarray:
        """f32[m, Gq] (padding rows trimmed) — one sharded index sweep."""
        qp = batch_queries(self.host, [np.asarray(q) for q in queries])
        s = score_batch(self.didx, qp, backend=self.backend)
        return np.asarray(s)[: self.num_records]

    def _serve_explains(self, hits, thr, t0) -> list[dict]:
        """Per-query explain dicts for the batch just served, built from
        the planner bookkeeping ``_pruned_batch`` left behind."""
        import time

        from repro import obs

        hash_rows, sizes, posts = self._last_plan_inputs or (None, None, None)
        ex = obs.build_explain(
            self.last_plan, engine=self.engine, backend=self.backend,
            n_queries=len(hits), hits=hits, cands=self.last_candidates,
            hash_rows=hash_rows, sizes=sizes, posts=posts,
            measured_seconds=time.perf_counter() - t0)
        for g, e in enumerate(ex):
            e["threshold"] = float(thr[g])
        return ex

    def serve_batch(self, queries, thresholds, k: int, plan: str = "auto",
                    explain: bool = False):
        """One sweep answering threshold + top-k for a whole batch.

        ``thresholds`` is scalar or per-query. Returns one dict per query:
        {"hits", "topk_ids", "topk_scores"}. ``plan`` routes both halves:
        threshold hits through the pruned filter-and-verify and — when
        forced "pruned" — top-k through the planner-aware upper-bound
        pruning as well. ``plan="auto"`` keeps top-k on the dense sweep
        (the batch amortizes it and the hit masks fall out of the same
        scores), matching it bit for bit. With ``explain=True`` each
        dict gains an ``"explain"`` entry (:mod:`repro.obs.explain`).
        """
        import time

        from repro.planner.prune import threshold_hits_packed

        t0 = time.perf_counter()
        queries = [np.asarray(q) for q in queries]
        thr = np.broadcast_to(np.asarray(thresholds, np.float64),
                              (len(queries),))
        empty_ids = np.zeros(0, np.int64)
        empty_scores = np.zeros(0, np.float32)
        if k <= 0 or plan == "pruned":
            hits, qp = self._pruned_batch(queries, thr, plan)
            route = ("dense" if hits is None else
                     "fused" if self._device_route() else "host_pruned")
            # One count per served batch: count = batches, sum = queries.
            obs_tally(f"route.{route}", len(queries))
            if hits is None:
                if qp is None:
                    with obs_stage("serve.sketch", queries=len(queries)):
                        qp = batch_queries(self.host, queries)
                with obs_stage("serve.score", queries=len(queries)) as span:
                    scores = span.sync(score_batch(
                        self.didx, qp, backend=self.backend))
                with obs_stage("serve.hits"):
                    hits = threshold_hits_packed(
                        scores[: self.num_records], thr)
            if k <= 0:
                out = [{"hits": h, "topk_ids": empty_ids,
                        "topk_scores": empty_scores} for h in hits]
                if explain:
                    for res, e in zip(out, self._serve_explains(
                            hits, thr, t0)):
                        res["explain"] = e
                return out
            # Reuse the batch's query pack: one sketching pass serves
            # both the threshold hits and every pruned top-k.
            ex = self._serve_explains(hits, thr, t0) if explain else None
            with obs_stage("serve.topk", k=k):
                tops = self._pruned_topk_batch(queries, k, qp=qp)
            out = [{"hits": h, "topk_ids": t[0], "topk_scores": t[1]}
                   for h, t in zip(hits, tops)]
            if ex is not None:
                for res, e in zip(out, ex):
                    res["explain"] = e
            return out

        # Dense sweep route (top-k batches on plan="auto"): the planner
        # is never consulted, but a routing decision still happened —
        # record it so explain/drift always have the current batch.
        from repro import planner
        from repro.core import cost_model

        s = self.host.sketches
        self.last_candidates = None
        self._last_plan_inputs = None
        self.last_plan = planner.QueryPlan(
            "dense", cost_model.dense_sweep_cost(
                s.num_records, s.capacity, len(queries)), np.nan, 0,
            "topk batch: dense sweep amortized")
        obs_tally("route.dense", len(queries))
        with obs_stage("serve.sketch", queries=len(queries)):
            qp = batch_queries(self.host, queries)
        with obs_stage("serve.score", queries=len(queries)):
            scores = score_batch(self.didx, qp, backend=self.backend)
        with obs_stage("serve.topk", k=k):
            vals, ids = distributed_topk(scores, k, self.mesh)
            jax.block_until_ready(vals)
        with obs_stage("serve.hits"):
            hits = threshold_hits_packed(scores[: self.num_records], thr)
        out = [
            {"hits": hits[j],
             "topk_ids": np.asarray(ids)[j],
             "topk_scores": np.asarray(vals)[j]}
            for j in range(len(queries))
        ]
        if explain:
            for res, e in zip(out, self._serve_explains(hits, thr, t0)):
                res["explain"] = e
        return out

    # -- repro.api protocol --
    def query(self, q_ids, threshold: float, *, plan: str = "auto") -> np.ndarray:
        return self.batch_query([q_ids], threshold, plan=plan)[0]

    def batch_query(self, queries, threshold: float, *,
                    plan: str = "auto") -> list[np.ndarray]:
        from repro import planner

        plan = planner.normalize_plan(plan)
        queries = [np.asarray(q) for q in queries]
        if not queries:
            return []
        hits, qp = self._pruned_batch(queries, float(threshold), plan)
        if hits is not None:
            return hits
        if qp is None:
            qp = batch_queries(self.host, queries)
        s = score_batch(self.didx, qp, backend=self.backend)
        return planner.threshold_hits_packed(s[: self.num_records], threshold)

    def _pruned_topk_batch(self, queries, k: int, qp=None):
        """Planner-aware top-k for a whole batch over ONE query pack
        (``qp`` reuses a pack the caller already sketched)."""
        from repro import planner
        from repro.planner.plan import unpack_query_rows

        if qp is None:
            qp = batch_queries(self.host, queries)
        if self._device_route():
            from repro.planner import device as planner_device

            return planner_device.pruned_topk_device(
                self.host.sketches, qp, k, backend=self.backend)
        hash_rows, bit_rows, sizes = unpack_query_rows(qp)
        posts, offs = self._shard_postings()
        s: PackedSketches = self.host.sketches
        out = []
        for g in range(len(queries)):
            def score_fn(cand_rec, _cand_q, g=g):
                return self._score_pairs(
                    qp, cand_rec, np.full(len(cand_rec), g, np.int32))

            out.append(planner.pruned_topk(
                posts, hash_rows[g], bit_rows[g], int(sizes[g]), k,
                score_fn, s.num_records, row_offsets=offs,
                chunk=None if self.backend == "numpy" else
                DEVICE_TOPK_CHUNK))
        return out

    def topk(self, q_ids, k: int, *, plan: str = "auto"):
        """Global top-k. ``plan="pruned"`` routes through the planner's
        postings-driven upper-bound pruning (host merge over the shard
        slices + device gather-scoring) with exact parity against the
        dense mesh sweep; "auto"/"dense" run the sharded sweep +
        all_gather (``lax.top_k`` breaks ties lower-id-first, the same
        deterministic order the pruned path produces)."""
        from repro import planner

        plan = planner.normalize_plan(plan)
        if plan == "pruned" and k > 0:
            return self._pruned_topk_batch([np.asarray(q_ids)], k)[0]
        qp = batch_queries(self.host, [np.asarray(q_ids)])
        scores = score_batch(self.didx, qp, backend=self.backend)
        vals, ids = distributed_topk(scores, k, self.mesh)
        return (np.asarray(ids)[0].astype(np.int64),
                np.asarray(vals)[0].astype(np.float32))

    def insert(self, new_records, budget: int | None = None):
        """Dynamic insert on the host sketch (delegated to the api index so
        budget semantics live in one place), then re-place on the mesh.
        The arena carries the per-shard postings across the insert
        incrementally (τ-truncation + append on each slice) — no lazy
        rebuild."""
        from repro import api

        wrapper = api.GBKMVEngine.wrap(
            self.host, budget=budget if budget is not None else self.budget)
        wrapper.insert(new_records)
        self.host = wrapper.core
        self.stats = wrapper.stats
        self.didx = to_device_index(self.host, self.mesh)
        self._lane_rows = None
        return self

    def save(self, path: str) -> None:
        from repro import api

        api.GBKMVEngine.wrap(self.host, budget=self.budget).save(path)

    def nbytes(self) -> int:
        return self.host.nbytes()
