"""``repro.api`` — the unified public API for containment similarity search.

One protocol for every sketch engine, every backend, every deployment
tier (paper §V runs all its experiments through exactly this kind of
single evaluation door):

    engine = repro.api.get_engine("gbkmv")          # registry lookup
    index  = engine.build(records, budget)          # -> ContainmentIndex
    ids    = index.query(q_ids, threshold=0.5)      # Algorithm 2
    hits   = index.batch_query(queries, 0.5)        # one id array per query
    top    = index.topk(q_ids, k=10)                # (ids, scores)
    index.insert(new_records)                       # dynamic maintenance
    index.save(path); repro.api.load_index(path)    # npz round-trip
    index.nbytes()                                  # space accounting

Sketch engines (gbkmv/gkmv/kmv) answer ``query``/``batch_query``/``topk``
and ``serve_batch`` through the planner's router
(:mod:`repro.planner.route`): ``plan="auto"`` (default) lets a cost model
pick between the dense index sweep and the inverted-postings
filter-and-verify path per threshold batch, and keeps top-k on the
sweep; ``plan="dense"`` / ``plan="pruned"`` force a path. Every route
returns identical answers — pruning is exact under the estimator's
containment bound.

Registered engines: ``gbkmv``, ``gkmv``, ``kmv`` (the paper's sketches),
``lshe`` (LSH Ensemble baseline), ``exact`` and ``prefix`` (ground-truth
inverted-index engines). Sketch engines accept ``backend=`` ∈ {"numpy",
"jnp", "pallas"} to pick the scoring implementation; engines without a
device path (lshe/exact/prefix) ignore it.

``insert`` is wired to :mod:`repro.sketchindex.dynamic` for GB-KMV
(τ-retightening under the fixed budget, no raw-data access); every other
engine falls back to a full rebuild from the retained records.

For cluster-scale serving, :class:`repro.sketchindex.ShardedIndex` wraps
a built GB-KMV index and implements this same protocol with the record
dim sharded over a device mesh.
"""

from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np

from repro.core import exact as exact_mod
from repro.core import gbkmv as gbkmv_mod
from repro.core import gkmv as gkmv_mod
from repro.core import kmv as kmv_mod
from repro.core import lshe as lshe_mod
from repro.core import minhash as minhash_mod
from repro.core.arena import SketchArena
from repro.core.estimators import containment_matrix, normalize_backend
from repro.core.hashing import PAD, hash_u32_np
from repro.core.sketches import PackedSketches
from repro.planner.plan import unpack_query_rows
from repro.planner.route import Routed


@runtime_checkable
class ContainmentIndex(Protocol):
    """What every engine's index exposes (structural protocol)."""

    def query(self, q_ids, threshold: float) -> np.ndarray: ...
    def batch_query(self, queries, threshold: float) -> list[np.ndarray]: ...
    def topk(self, q_ids, k: int) -> tuple[np.ndarray, np.ndarray]: ...
    def insert(self, new_records) -> "ContainmentIndex": ...
    def save(self, path: str) -> None: ...
    def nbytes(self) -> int: ...


# ---------------------------------------------------------------------------
# Engine registry
# ---------------------------------------------------------------------------

_ENGINES: dict[str, type] = {}


def register_engine(name: str):
    """Class decorator: make an engine reachable as ``get_engine(name)``."""

    def deco(cls):
        cls.name = name
        _ENGINES[name] = cls
        return cls

    return deco


def get_engine(name: str):
    """Engine class for ``name`` (``.build(records, budget, **cfg)``)."""
    try:
        return _ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(_ENGINES)}"
        ) from None


def list_engines() -> list[str]:
    """Registered engine names (gbkmv/gkmv/kmv/lshe/exact/prefix/...)."""
    return sorted(_ENGINES)


def build(name: str, records, budget: int | None = None, **cfg):
    """Convenience: ``get_engine(name).build(records, budget, **cfg)``.

    ``records`` is a list of element-id arrays or a pre-ingested
    :class:`repro.core.sketches.RaggedBatch`; ``budget`` counts 32-bit
    hash slots across all records (the paper's space accounting). See
    docs/API.md for the shared ``build`` kwargs (``backend``,
    ``build_backend``, ``tau_mode``, ``postings``, ``windowed``)."""
    return get_engine(name).build(records, budget, **cfg)


def _record_list(records) -> list:
    """Per-record id arrays from a record list or a pre-ingested
    :class:`repro.core.sketches.RaggedBatch` (rebuild-fallback engines
    and the windowed path keep them beyond construction)."""
    if hasattr(records, "offsets"):          # RaggedBatch
        ids = np.asarray(records.ids)
        off = np.asarray(records.offsets)
        return [ids[a:b] for a, b in zip(off[:-1], off[1:])]
    return [np.asarray(r) for r in records]


def _windowed_build(engine: str, records, budget, backend: str,
                    epoch: int, cfg: dict):
    """Shared ``windowed=True`` path of the sketch engines' ``build``:
    wrap construction in a :class:`repro.sketchindex.WindowManager`
    whose first epoch holds ``records``. The manager implements this
    module's index protocol (plus ``window=`` kwargs, ``retire``, and
    directory save/load) — see :mod:`repro.sketchindex.windows`."""
    from repro.sketchindex.windows import WindowManager

    wm = WindowManager(engine=engine, budget=int(budget), backend=backend,
                       **cfg)
    records = _record_list(records)
    if records:
        wm.ingest(records, epoch=int(epoch))
    return wm


class CorruptIndexError(ValueError):
    """A saved index file exists but cannot be decoded (truncated
    download, torn write, wrong file). Subclasses ``ValueError`` so
    pre-existing ``except ValueError`` call sites keep working; a
    missing file still raises ``FileNotFoundError``."""

    def __init__(self, path: str, reason: str):
        super().__init__(f"corrupt or invalid index file {path!r}: {reason}")
        self.path = path
        self.reason = reason


def load_index(path: str):
    """Load any index saved via ``Index.save`` (dispatches on the stored
    engine name). A file that exists but cannot be decoded — truncated
    npz, torn write, non-index zip — raises :class:`CorruptIndexError`
    naming the file instead of leaking a raw ``zipfile``/``KeyError``."""
    import zipfile

    try:
        with np.load(path, allow_pickle=False) as data:
            d = {k: data[k] for k in data.files}
    except FileNotFoundError:
        raise
    except (zipfile.BadZipFile, ValueError, KeyError, OSError, EOFError) as e:
        raise CorruptIndexError(
            path, f"{type(e).__name__}: {e}") from e
    if "engine" not in d:
        raise CorruptIndexError(path, "not a repro.api index "
                                      "(no 'engine' key)")
    engine = str(d.pop("engine"))
    try:
        cls = get_engine(engine)
    except ValueError as e:
        raise CorruptIndexError(path, str(e)) from e
    if not hasattr(cls, "_load"):
        raise ValueError(f"engine {engine!r} does not support load")
    try:
        return cls._load(d)
    except (KeyError, ValueError, IndexError) as e:
        raise CorruptIndexError(
            path, f"payload missing or malformed ({type(e).__name__}: "
                  f"{e})") from e


# ---------------------------------------------------------------------------
# Shared index behavior
# ---------------------------------------------------------------------------


class _IndexBase:
    """Default protocol plumbing: score-based query/topk, rebuild-insert.

    Subclasses implement ``_scores(q_ids) -> f32[m]`` (estimated
    containment of the query in every record) and, where a cheaper path
    exists, override ``query``/``insert``.
    """

    engine: str = "?"
    backend: str = "jnp"
    _records: list | None = None        # retained for rebuild-fallback insert
    _build_cfg: dict

    # -- abstract-ish --
    def _scores(self, q_ids) -> np.ndarray:
        raise NotImplementedError

    def nbytes(self) -> int:
        raise NotImplementedError

    # -- protocol --
    def scores(self, q_ids) -> np.ndarray:
        """Estimated containment Ĉ(Q→X) for every record (f32[m])."""
        return np.asarray(self._scores(q_ids))

    def query(self, q_ids, threshold: float) -> np.ndarray:
        return np.nonzero(np.asarray(self._scores(q_ids)) >= threshold)[0]

    def batch_query(self, queries, threshold: float) -> list[np.ndarray]:
        return [self.query(q, threshold) for q in queries]

    def topk(self, q_ids, k: int) -> tuple[np.ndarray, np.ndarray]:
        """(record ids, scores) of the k highest estimated containments.

        Deterministic order: score descending, ties by ascending record
        id — the exact ranking the planner-aware pruned top-k reproduces
        (and the tie rule ``lax.top_k`` applies on the sharded path).
        Dense and host-pruned routes share one output head
        (:func:`repro.planner.topk_select`), so the contract cannot
        drift between them.
        """
        from repro.planner import topk_select

        s = np.asarray(self._scores(q_ids))
        return topk_select(np.arange(len(s), dtype=np.int64), s, k, len(s))

    def insert(self, new_records):
        """Full-rebuild fallback (engines without dynamic maintenance)."""
        if self._records is None:
            raise ValueError(
                f"{self.engine}: insert after load needs the original "
                "records (rebuild fallback); rebuild via Engine.build")
        records = list(self._records) + [np.asarray(r) for r in new_records]
        rebuilt = get_engine(self.engine).build(records, **self._build_cfg)
        self.__dict__.update(rebuilt.__dict__)
        return self

    def save(self, path: str) -> None:
        raise NotImplementedError(
            f"{self.engine}: save is supported for sketch-backed indexes "
            "(gbkmv/gkmv/kmv/lshe) only")


_ARENA_VERSION = 3

# Per-store npz key suffixes for the blocked postings (v3 format).
_STORE_FIELDS = ("row_blocks", "first", "last", "meta", "off", "payload")


def _arena_to_npz(s: PackedSketches) -> dict:
    """Arena serialization: the packed columns plus — when they have been
    built — the BLOCKED postings (delta-bitpacked/dense blocks, the same
    arrays that sit in host memory and mirror to device), so a reloaded
    index answers its first pruned query without re-inverting the
    sketches. Column keys are unchanged from the v1 (postings-less)
    format, which is what keeps old files loadable."""
    d = {
        "values": np.asarray(s.values), "lengths": np.asarray(s.lengths),
        "thresh": np.asarray(s.thresh), "buf": np.asarray(s.buf),
        "sizes": np.asarray(s.sizes),
        "arena_version": np.int64(_ARENA_VERSION),
    }
    post = getattr(s, "_post", None)
    if post is not None:
        d["post_keys"] = post.keys
        d["post_tau"] = np.uint32(post.tau)
        for prefix, store in (("post_blk_", post.tail),
                              ("post_buf_blk_", post.buf)):
            for f in _STORE_FIELDS:
                d[prefix + f] = getattr(store, f)
    return d


def _arena_from_npz(d: dict) -> SketchArena:
    """Rebuild an arena from ``_arena_to_npz`` output or any older format:

    v3  ``post_blk_*`` / ``post_buf_blk_*`` blocked stores — loaded
        verbatim (zero re-encoding work)
    v2  flat-CSR ``post_offsets``/``post_rec_ids``/... — re-encoded into
        blocks on load (one vectorized pass)
    v1  no ``post_*`` entries — postings stay lazy
    """
    arena = SketchArena(
        values=d["values"], lengths=d["lengths"], thresh=d["thresh"],
        buf=d["buf"], sizes=d["sizes"])
    if "post_blk_row_blocks" in d:
        from repro.planner.postings import BlockStore, PostingsIndex

        stores = {}
        for name, prefix in (("tail", "post_blk_"), ("buf", "post_buf_blk_")):
            stores[name] = BlockStore(
                **{f: d[prefix + f] for f in _STORE_FIELDS})
        arena.install_postings(PostingsIndex(
            keys=d["post_keys"], tail=stores["tail"], buf=stores["buf"],
            num_records=arena.num_records, tau=np.uint32(d["post_tau"])))
    elif "post_keys" in d:
        from repro.planner.postings import from_flat

        arena.install_postings(from_flat(
            d["post_keys"], d["post_offsets"], d["post_rec_ids"],
            d["post_buf_offsets"], d["post_buf_rec_ids"],
            arena.num_records, np.uint32(d["post_tau"])))
    return arena


def _validate_postings_arg(postings: str) -> str:
    """Reject a bad ``postings=`` BEFORE the (possibly device) build
    runs — a typo must not cost a full construction pass."""
    if postings not in ("lazy", "eager"):
        raise ValueError(f"postings must be 'lazy' or 'eager', "
                         f"got {postings!r}")
    return postings


def _maybe_eager_postings(sketches, postings: str) -> None:
    """``postings="eager"``: encode the block-compressed postings from
    the freshly packed columns at build time. Device-built columns (jnp
    arrays from the fused build) take the fused DEVICE encode — the
    blocked tail store is bit-packed on the accelerator and its mirrors
    adopted without a host round-trip, then the columns are pinned to
    host once for the host-side consumers. ``"lazy"`` (default) defers
    to the first planned query — the seed-era behavior, and what the
    space-accuracy benchmarks charge for."""
    if _validate_postings_arg(postings) == "eager":
        arena = SketchArena.from_pack(sketches)
        if not isinstance(arena.values, np.ndarray):
            from repro.planner.postings import build_postings_device

            post, dpost = build_postings_device(arena)
            arena.ensure_host()
            arena.install_postings(post)
            arena.adopt_device_postings(dpost)
        else:
            arena.ensure_host()
            arena.postings()


class _PlannedIndexMixin(Routed):
    """Routing for the sketch-backed indexes (gbkmv/gkmv/kmv).

    ``query``/``batch_query``/``topk``/``serve_batch`` answer through the
    router (:mod:`repro.planner.route`) and accept ``plan`` ∈ {"auto",
    "dense", "pruned"}: "auto" (default) lets the cost model pick the
    dense sweep or filter-and-verify for threshold batches and keeps
    top-k on the sweep; forced modes pin the route. Every route returns
    identical results; pruned top-k uses the running k-th score as a
    moving threshold. Postings live ON the arena (:class:`SketchArena`)
    — built lazily on the first planned query, shared with every other
    layer viewing the same arena, and maintained incrementally across
    ``insert``.

    With ``backend`` ∈ {"jnp", "pallas"}, GB-KMV and G-KMV run the pruned
    routes device-resident (``planner.device``).

    Subclasses provide ``_sketch_pack`` (the sketch arena) and
    ``_sketch`` (the batch's query pack); the hooks below serve every
    engine whose pack ``containment_matrix`` and ``gather_score`` read.
    """

    def _query_rows(self, qp):
        return unpack_query_rows(qp)

    def _shard_postings(self):
        return [self._sketch_pack().postings()], [0]

    def _dense_scores(self, qp):
        # Device-resident for jnp/pallas: the hit compare runs there.
        return containment_matrix(qp, self._sketch_pack(),
                                  backend=self.backend, as_numpy=False)

    def _dense_topk(self, scores, k: int):
        from repro.planner import topk_select

        s = np.asarray(scores)
        ids = np.arange(self.num_records, dtype=np.int64)
        return [topk_select(ids, s[:, g], k, len(ids))
                for g in range(s.shape[1])]

    def _score_pairs(self, qp, cand_rec, cand_q):
        from repro.kernels import gather_score

        return gather_score.score_pairs(self._sketch_pack(), qp, cand_rec,
                                        cand_q, backend=self.backend)


# ---------------------------------------------------------------------------
# GB-KMV (the paper's contribution) — dynamic inserts via sketchindex.dynamic
# ---------------------------------------------------------------------------


@register_engine("gbkmv")
class GBKMVEngine:
    """GB-KMV: G-KMV tail + top-r frequent-element bitmap buffer."""

    @classmethod
    def build(cls, records, budget, r="auto", seed=0, capacity=None,
              backend="jnp", tau_mode="exact", build_backend=None,
              postings="lazy", windowed=False, epoch=0, **_):
        """Vectorized construction (no per-record Python). ``backend``
        picks the *scoring* implementation; ``build_backend`` the
        construction path — None/"numpy" = host vectorized,
        "jnp"/"pallas" = the fused device hash→τ→pack computation.
        ``tau_mode`` ∈ {"exact", "histogram"} (histogram: two-level
        refine, τ within 2^8 of exact — the distributed selector).
        ``postings="eager"`` encodes the block-compressed postings from
        the packed columns before returning, so the first pruned query
        pays no inversion. ``windowed=True`` returns a
        :class:`repro.sketchindex.WindowManager` instead — a
        time-windowed index whose first epoch is ``epoch`` and whose
        ``insert`` takes an ``epoch=`` kwarg (docs/API.md §Windows)."""
        if windowed:
            return _windowed_build(
                cls.name, records, budget, backend, epoch,
                {"r": r, "seed": seed, "capacity": capacity,
                 "tau_mode": tau_mode, "build_backend": build_backend})
        _validate_postings_arg(postings)
        core = gbkmv_mod.build_gbkmv(records, budget=budget, r=r, seed=seed,
                                     capacity=capacity, tau_mode=tau_mode,
                                     build_backend=build_backend)
        idx = GBKMVApiIndex(core, budget=int(budget), backend=backend)
        _maybe_eager_postings(core.sketches, postings)
        return idx

    @staticmethod
    def wrap(core: gbkmv_mod.GBKMVIndex, budget: int | None = None,
             backend: str = "jnp") -> "GBKMVApiIndex":
        """Adopt an already-built core GBKMVIndex (legacy door)."""
        return GBKMVApiIndex(core, budget=budget, backend=backend)

    @classmethod
    def _load(cls, d: dict) -> "GBKMVApiIndex":
        core = gbkmv_mod.GBKMVIndex(
            sketches=_arena_from_npz(d), tau=np.uint32(d["tau"]),
            top_elems=d["top_elems"], seed=int(d["seed"]),
            buffer_bits=int(d["buffer_bits"]))
        budget = int(d["budget"]) if "budget" in d else -1
        return GBKMVApiIndex(core, budget=budget if budget >= 0 else None,
                             backend=str(d.get("backend", "jnp")))


class GBKMVApiIndex(_PlannedIndexMixin, _IndexBase):
    engine = "gbkmv"
    _device_prunable = True

    def __init__(self, core: gbkmv_mod.GBKMVIndex, budget: int | None,
                 backend: str = "jnp"):
        core.sketches = SketchArena.from_pack(core.sketches)
        self.core = core
        self.budget = budget
        self.backend = normalize_backend(backend)
        self._records = None            # dynamic path needs no raw records
        self._build_cfg = {}

    @property
    def num_records(self) -> int:
        return self.core.num_records

    def _scores(self, q_ids) -> np.ndarray:
        q = gbkmv_mod.sketch_query(self.core, np.asarray(q_ids))
        return gbkmv_mod.containment_scores(self.core, q, backend=self.backend)

    # -- router hooks --
    def _sketch_pack(self) -> PackedSketches:
        return self.core.sketches

    def _sketch(self, queries) -> PackedSketches:
        from repro.sketchindex.distributed import batch_queries

        return batch_queries(self.core, queries)

    def batch_scores(self, queries) -> np.ndarray:
        """f32[m, Gq] — one index sweep for a whole query batch."""
        qp = self._sketch([np.asarray(q) for q in queries])
        return np.asarray(self._dense_scores(qp))

    def insert(self, new_records, budget: int | None = None):
        """Paper §IV-B dynamic maintenance: τ-retighten, never re-hash old
        rows (``sketchindex.dynamic``). The repacked arena adopts every
        cached postings structure incrementally (τ-truncation + append,
        global and per-shard) inside ``insert_records``."""
        from repro.sketchindex import dynamic

        budget = budget if budget is not None else self.budget
        if budget is None:
            budget = self.core.sketches.lengths.sum() + \
                self.core.num_records * self.core.sketches.buf_words
        self.core, self.stats = dynamic.insert_records(
            self.core, [np.asarray(r) for r in new_records], int(budget))
        return self

    def save(self, path: str) -> None:
        d = _arena_to_npz(self.core.sketches)
        np.savez_compressed(
            path, engine="gbkmv", tau=np.uint32(self.core.tau),
            top_elems=np.asarray(self.core.top_elems, np.int64),
            seed=np.int64(self.core.seed),
            buffer_bits=np.int64(self.core.buffer_bits),
            budget=np.int64(self.budget if self.budget is not None else -1),
            backend=self.backend, **d)

    def nbytes(self) -> int:
        return self.core.nbytes()


# ---------------------------------------------------------------------------
# G-KMV (global threshold, no buffer) and plain KMV (Theorem 1 allocation)
# ---------------------------------------------------------------------------


@register_engine("gkmv")
class GKMVEngine:
    """G-KMV: global hash threshold τ, no frequent-element buffer."""

    @classmethod
    def build(cls, records, budget, seed=0, capacity=None, backend="jnp",
              tau_mode="exact", build_backend=None, postings="lazy",
              windowed=False, epoch=0, **_):
        """Build a G-KMV index (global hash threshold τ from ``budget``).
        Same construction knobs as gbkmv minus the buffer; see
        :meth:`GBKMVEngine.build`. ``windowed=True`` returns a
        :class:`repro.sketchindex.WindowManager` over per-epoch G-KMV
        snapshots."""
        if windowed:
            return _windowed_build(
                cls.name, records, budget, backend, epoch,
                {"seed": seed, "capacity": capacity, "tau_mode": tau_mode,
                 "build_backend": build_backend})
        _validate_postings_arg(postings)
        sk = gkmv_mod.build_gkmv(records, budget=budget, seed=seed,
                                 capacity=capacity, tau_mode=tau_mode,
                                 build_backend=build_backend)
        _maybe_eager_postings(sk, postings)
        tau = int(np.asarray(sk.thresh).max()) if sk.num_records else int(PAD - 1)
        idx = GKMVApiIndex(sk, tau=tau, seed=seed, backend=backend)
        idx._records = _record_list(records)
        idx._build_cfg = {"budget": budget, "seed": seed, "capacity": capacity,
                          "backend": backend}
        return idx

    @staticmethod
    def wrap(sk: PackedSketches, seed: int = 0, backend: str = "jnp"):
        tau = int(np.asarray(sk.thresh).max()) if sk.num_records else int(PAD - 1)
        return GKMVApiIndex(sk, tau=tau, seed=seed, backend=backend)

    @classmethod
    def _load(cls, d: dict) -> "GKMVApiIndex":
        return GKMVApiIndex(_arena_from_npz(d), tau=int(d["tau"]),
                            seed=int(d["seed"]),
                            backend=str(d.get("backend", "jnp")))


class GKMVApiIndex(_PlannedIndexMixin, _IndexBase):
    engine = "gkmv"
    _device_prunable = True

    def __init__(self, sketches: PackedSketches, tau: int, seed: int,
                 backend: str = "jnp"):
        self.sketches = SketchArena.from_pack(sketches)
        self.tau = np.uint32(tau)
        self.seed = seed
        self.backend = normalize_backend(backend)
        self._records = None
        self._build_cfg = {}

    @property
    def num_records(self) -> int:
        return self.sketches.num_records

    def _scores(self, q_ids) -> np.ndarray:
        q = gkmv_mod.sketch_query(np.asarray(q_ids), self.tau, seed=self.seed,
                                  capacity=self.sketches.capacity)
        return containment_matrix(q, self.sketches, backend=self.backend)[:, 0]

    # -- router hooks --
    def _sketch_pack(self) -> PackedSketches:
        return self.sketches

    def _sketch(self, queries) -> PackedSketches:
        return gkmv_mod.sketch_query_batch(
            queries, self.tau, seed=self.seed,
            capacity=self.sketches.capacity)

    def save(self, path: str) -> None:
        np.savez_compressed(path, engine="gkmv", tau=np.uint32(self.tau),
                            seed=np.int64(self.seed), backend=self.backend,
                            **_arena_to_npz(self.sketches))

    def nbytes(self) -> int:
        return self.sketches.nbytes()


@register_engine("kmv")
class KMVEngine:
    """Plain KMV, uniform k = floor(budget/m) per record (Theorem 1)."""

    @classmethod
    def build(cls, records, budget, seed=0, backend="jnp",
              build_backend=None, postings="lazy", windowed=False,
              epoch=0, **_):
        """Build a plain-KMV index (uniform k = floor(budget/m) per
        record, Theorem 1). ``windowed=True`` returns a
        :class:`repro.sketchindex.WindowManager` over per-epoch KMV
        snapshots."""
        if windowed:
            return _windowed_build(cls.name, records, budget, backend,
                                   epoch, {"seed": seed,
                                           "build_backend": build_backend})
        _validate_postings_arg(postings)
        sk = kmv_mod.build_kmv(records, budget=budget, seed=seed,
                               build_backend=build_backend)
        _maybe_eager_postings(sk, postings)
        idx = KMVApiIndex(sk, seed=seed, backend=backend)
        idx._records = _record_list(records)
        idx._build_cfg = {"budget": budget, "seed": seed, "backend": backend}
        return idx

    @staticmethod
    def wrap(sk: PackedSketches, seed: int = 0, backend: str = "jnp"):
        return KMVApiIndex(sk, seed=seed, backend=backend)

    @classmethod
    def _load(cls, d: dict) -> "KMVApiIndex":
        return KMVApiIndex(_arena_from_npz(d), seed=int(d["seed"]),
                           backend=str(d.get("backend", "jnp")))


class KMVApiIndex(_PlannedIndexMixin, _IndexBase):
    engine = "kmv"

    def __init__(self, sketches: PackedSketches, seed: int,
                 backend: str = "jnp"):
        self.sketches = SketchArena.from_pack(sketches)
        self.seed = seed
        self.backend = normalize_backend(backend)
        self._records = None
        self._build_cfg = {}

    @property
    def num_records(self) -> int:
        return self.sketches.num_records

    def _query_sketch(self, q_ids) -> np.ndarray:
        """The query's own KMV synopsis: its k smallest hashes, sorted."""
        k = self.sketches.capacity
        return np.sort(hash_u32_np(np.asarray(q_ids), seed=self.seed))[:k]

    def _scores(self, q_ids) -> np.ndarray:
        """Ĉ = D̂∩ / |Q| with the Eq. 8-10 pair estimator (k = min rule)."""
        q_ids = np.asarray(q_ids)
        h = self._query_sketch(q_ids)
        return self._scores_rows(h, len(q_ids), rows=None)

    def _scores_rows(self, q_hashes, q_len: int, rows) -> np.ndarray:
        """Pair estimator against all record rows (rows=None) or a
        gathered candidate subset — identical math either way."""
        from repro.core.estimators import kmv_pair_estimate
        import jax.numpy as jnp

        k = self.sketches.capacity
        qv = np.pad(q_hashes, (0, k - len(q_hashes)), constant_values=PAD)
        xv = np.asarray(self.sketches.values)
        xl = np.asarray(self.sketches.lengths)
        if rows is not None:
            xv, xl = xv[rows], xl[rows]
        d_hat, _, _ = kmv_pair_estimate(
            jnp.asarray(qv), jnp.int32(len(q_hashes)),
            jnp.asarray(xv), jnp.asarray(xl))
        return np.asarray(d_hat) / max(q_len, 1)

    # -- router hooks: a query pack is (k smallest hashes, |Q|) per query --
    def _sketch_pack(self) -> PackedSketches:
        return self.sketches

    def _sketch(self, queries):
        return ([self._query_sketch(q) for q in queries],
                np.asarray([len(q) for q in queries], np.int64))

    def _query_rows(self, qp):
        hash_rows, sizes = qp
        return hash_rows, [np.zeros(0, np.int64)] * len(hash_rows), sizes

    def _sweep_cols(self, qp) -> int:
        return self.sketches.capacity          # the KMV sweep reads them all

    def _dense_scores(self, qp):
        return np.stack([self._scores_rows(h, int(n), rows=None)
                         for h, n in zip(*qp)], axis=-1)

    def _score_pairs(self, qp, cand_rec, cand_q):
        hash_rows, sizes = qp
        out = np.zeros(len(cand_rec), np.float32)
        for g in np.unique(cand_q):
            sel = np.nonzero(cand_q == g)[0]
            out[sel] = self._scores_rows(
                hash_rows[g], int(sizes[g]), rows=cand_rec[sel])
        return out

    def save(self, path: str) -> None:
        np.savez_compressed(path, engine="kmv", seed=np.int64(self.seed),
                            backend=self.backend,
                            **_arena_to_npz(self.sketches))

    def nbytes(self) -> int:
        return self.sketches.nbytes()


# ---------------------------------------------------------------------------
# LSH Ensemble baseline
# ---------------------------------------------------------------------------


@register_engine("lshe")
class LSHEEngine:
    """LSH Ensemble (Zhu et al.): size-partitioned MinHash banding.

    ``budget`` (slots, 32-bit words) maps onto the MinHash count:
    k ≈ budget/m, the same space accounting the sketch engines use.
    """

    @classmethod
    def build(cls, records, budget=None, num_hashes=None, num_partitions=32,
              seed=0, **_):
        if num_hashes is None:
            num_hashes = (max(8, int(budget) // max(len(records), 1))
                          if budget is not None else 256)
        core = lshe_mod.build_lshe(records, num_hashes=num_hashes,
                                   num_partitions=num_partitions, seed=seed)
        idx = LSHEApiIndex(core, seed=seed)
        idx._records = [np.asarray(r) for r in records]
        idx._build_cfg = {"num_hashes": num_hashes,
                          "num_partitions": num_partitions, "seed": seed}
        return idx

    @staticmethod
    def wrap(core: lshe_mod.LSHEnsemble, seed: int = 0):
        return LSHEApiIndex(core, seed=seed)

    @classmethod
    def _load(cls, d: dict) -> "LSHEApiIndex":
        core = lshe_mod.LSHEnsemble(
            signatures=d["signatures"], sizes=d["sizes"], order=d["order"],
            boundaries=d["boundaries"], upper_bounds=d["upper_bounds"],
            num_hashes=int(d["num_hashes"]))
        return LSHEApiIndex(core, seed=int(d["seed"]))


class LSHEApiIndex(_IndexBase):
    engine = "lshe"

    def __init__(self, core: lshe_mod.LSHEnsemble, seed: int = 0):
        self.core = core
        self.seed = seed
        self._records = None
        self._build_cfg = {}

    @property
    def num_records(self) -> int:
        return len(self.core.sizes)

    def query(self, q_ids, threshold: float) -> np.ndarray:
        return lshe_mod.query_lshe(self.core, np.asarray(q_ids), threshold,
                                   seed=self.seed)

    def _scores(self, q_ids) -> np.ndarray:
        """Signature-level containment t̂ (Eq. 14) — the topk ranking."""
        q_ids = np.asarray(q_ids)
        q_sig = minhash_mod.build_signatures([q_ids], self.core.num_hashes,
                                             seed=self.seed)[0]
        s_hat = minhash_mod.jaccard_estimate(q_sig, self.core.signatures)
        return minhash_mod.containment_from_jaccard(
            s_hat, self.core.sizes, len(q_ids)).astype(np.float32)

    def save(self, path: str) -> None:
        np.savez_compressed(
            path, engine="lshe", signatures=self.core.signatures,
            sizes=self.core.sizes, order=self.core.order,
            boundaries=self.core.boundaries,
            upper_bounds=self.core.upper_bounds,
            num_hashes=np.int64(self.core.num_hashes),
            seed=np.int64(self.seed))

    def nbytes(self) -> int:
        return self.core.nbytes()


# ---------------------------------------------------------------------------
# Exact engines (ground truth / strong baselines)
# ---------------------------------------------------------------------------


class _ExactBase(_IndexBase):
    def __init__(self, core: exact_mod.InvertedIndex, records=None):
        self.core = core
        self._records = records
        self._build_cfg = {"budget": None}

    @property
    def num_records(self) -> int:
        return len(self.core.sizes)

    def _scores(self, q_ids) -> np.ndarray:
        counts = exact_mod.intersection_counts(self.core, np.asarray(q_ids))
        return counts.astype(np.float32) / max(len(q_ids), 1)

    def nbytes(self) -> int:
        return int(self.core.sizes.nbytes + sum(
            p.nbytes for p in self.core.postings.values()))


@register_engine("exact")
class ExactEngine:
    """Posting-list counting: exact |Q∩X| in one pass (FrequentSet-style)."""

    @classmethod
    def build(cls, records, budget=None, **_):
        return ExactApiIndex(exact_mod.build_inverted(records),
                             records=[np.asarray(r) for r in records])

    @staticmethod
    def wrap(core: exact_mod.InvertedIndex):
        return ExactApiIndex(core)


class ExactApiIndex(_ExactBase):
    engine = "exact"

    def query(self, q_ids, threshold: float) -> np.ndarray:
        return exact_mod.exact_search(self.core, np.asarray(q_ids), threshold)


@register_engine("prefix")
class PrefixEngine:
    """PPjoin*-adapted prefix filter + exact verification."""

    @classmethod
    def build(cls, records, budget=None, **_):
        return PrefixApiIndex(exact_mod.build_inverted(records),
                              records=[np.asarray(r) for r in records])

    @staticmethod
    def wrap(core: exact_mod.InvertedIndex):
        return PrefixApiIndex(core)


class PrefixApiIndex(_ExactBase):
    engine = "prefix"

    def query(self, q_ids, threshold: float) -> np.ndarray:
        return exact_mod.prefix_filter_search(self.core, np.asarray(q_ids),
                                              threshold)


# ---------------------------------------------------------------------------
# Legacy adoption: wrap pre-API index objects without rebuilding
# ---------------------------------------------------------------------------


def as_index(engine: str, index, seed: int = 0, backend: str = "jnp"):
    """Wrap a legacy core index object (GBKMVIndex, PackedSketches,
    LSHEnsemble, InvertedIndex — or an api index, returned as-is) so the
    old ``run_search(engine, index, ...)`` door keeps working."""
    if isinstance(index, (_IndexBase,)):
        return index
    if hasattr(index, "query") and hasattr(index, "topk"):
        return index                                  # already protocol-shaped
    if engine == "gbkmv":
        return GBKMVEngine.wrap(index, backend=backend)
    if engine == "gkmv":
        return GKMVEngine.wrap(index, seed=seed, backend=backend)
    if engine == "kmv":
        return KMVEngine.wrap(index, seed=seed, backend=backend)
    if engine == "lshe":
        return LSHEEngine.wrap(index, seed=seed)
    if engine == "exact":
        return ExactEngine.wrap(index)
    if engine == "prefix":
        return PrefixEngine.wrap(index)
    raise ValueError(f"unknown engine {engine!r}")
