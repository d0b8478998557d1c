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
from repro.parallel.sharding import logical_to_spec
from repro.planner.plan import unpack_query_rows
from repro.planner.route import Routed


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
    """Pure-jnp scoring [Mshard, Gq]: the jitted sweep's body, and the jnp
    backend of ``estimators.containment_matrix``."""
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
                backend: str | None = None):
    """Containment scores f32[Mp, Gq]; records sharded, queries replicated.

    ``backend`` ∈ {"numpy", "jnp", "pallas"} — the one option threaded
    through every scoring layer. "numpy" computes on host from fetched
    shards — a debug/parity path, not a serving path.
    """
    from repro.core.estimators import containment_matrix, normalize_backend

    backend = normalize_backend(backend)
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
                       backend: str | None = None):
    """Algorithm 2 at cluster scale: boolean candidate mask [Mp, Gq]."""
    scores = score_batch(didx, q, backend=backend)
    return scores >= threshold, scores


class ShardedIndex(Routed):
    """Device-sharded GB-KMV index implementing the ``repro.api`` protocol.

    Wraps a host :class:`GBKMVIndex` placed on a mesh (``to_device_index``)
    so serving, benchmarks, and the api registry talk to sharded and host
    indexes through the same surface. Every query answers through the
    router (:mod:`repro.planner.route`); this class says only how a mesh
    scores: the sharded sweep, ``distributed_topk``, and pair scoring on
    the devices that hold each record.
    """

    engine = "gbkmv"
    _device_prunable = True

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

    @property
    def num_records(self) -> int:
        return self.host.num_records

    @property
    def _mesh_devices(self) -> int:
        return self.mesh.devices.size

    # -- how a mesh scores (the router's hooks) --
    def _sketch_pack(self):
        return self.host.sketches

    def _sketch(self, queries) -> PackedSketches:
        return batch_queries(self.host, queries)

    def _query_rows(self, qp):
        return unpack_query_rows(qp)

    def _shard_postings(self):
        """(postings, row_offsets) over the arena's record slices.

        One block-compressed postings index per record-offset slice,
        built from column *views* of the shared arena (no per-shard
        host copies) and cached on the arena itself. Candidate
        generation probes every slice (block headers first — skipping
        applies per shard) and unions the (disjoint) results — the
        host-side mirror of the mesh's all_gather. After inserts the
        slices update in place (τ-truncation of each slice's blocks +
        re-encoding only the appended rows); their boundaries may then
        lag the mesh's ceil-partition, which is harmless because the
        union reports global record ids either way.
        """
        return self.host.sketches.shard_postings(self.mesh.devices.size)

    def _dense_scores(self, qp: PackedSketches):
        return score_batch(self.didx, qp, backend=self.backend)

    def _dense_topk(self, scores, k: int):
        """Per-shard ``lax.top_k`` + one all_gather; ties break lower id
        first, the order every pruned route produces."""
        vals, ids = distributed_topk(scores, k, self.mesh)
        jax.block_until_ready(vals)
        return list(zip(np.asarray(ids).astype(np.int64), np.asarray(vals)))

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

    def batch_scores(self, queries) -> np.ndarray:
        """f32[m, Gq] (padding rows trimmed) — one sharded index sweep."""
        qp = self._sketch([np.asarray(q) for q in queries])
        return np.asarray(self._dense_scores(qp))[: self.num_records]

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
