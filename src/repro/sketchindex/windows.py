"""Time-windowed containment index: sealed per-epoch arenas, merged
lazily into sliding-window views.

The KMV family is mergeable by construction — both halves of a GB-KMV
sketch are order-independent (the bitmap buffer is a union of bits, the
G-KMV tail a union of hash sets re-tightened to the budget's k-th
smallest) — so a moving-data index never needs to re-hash history:

    ArenaSnapshot   one sealed epoch: an immutable api-level index over
                    the records ingested during that epoch
    WindowManager   the lifecycle: ``ingest(records, epoch=e)`` appends
                    to the open epoch (or seals it and opens ``e``),
                    ``query(..., window=(lo, hi))`` answers over any
                    contiguous epoch range by *merging* the snapshots
                    (`repro.core.{gbkmv,gkmv,kmv}.merge_*`, bit-identical
                    to rebuilding from the concatenated records),
                    ``retire(before)`` drops expired epochs, and
                    ``save``/``load`` round-trip the snapshot directory

Merged window views are cached per epoch-tuple and invalidated whenever
a member epoch changes (new ingest) or disappears (retirement) — the
DAU/MAU day-snapshot pattern, with containment-search semantics.

Budget semantics: ``budget`` is the per-window space target. Every epoch
is built with the full budget (that is what makes the merge bit-identical
to a rebuild — see :func:`repro.core.arena.merge_arenas`), and every
merged window re-tightens to the same budget, so a served window never
exceeds the configured sketch size no matter how many epochs it spans.

The manager implements the :class:`repro.api.ContainmentIndex` protocol
plus ``serve_batch``, so :class:`repro.service.AsyncSketchServer` can sit
directly on it (``repro.service.launch --windowed``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil

import numpy as np


def _fsync_dir(dirpath: str) -> None:
    """Best-effort directory fsync so the save's rename is durable."""
    try:
        fd = os.open(dirpath, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


_MANIFEST = "window_manifest.json"
_SKETCH_ENGINES = ("gbkmv", "gkmv", "kmv")


@dataclasses.dataclass
class ArenaSnapshot:
    """One epoch's records as an immutable api-level sketch index.

    ``sealed`` flips when a later epoch opens: a sealed snapshot never
    changes again, which is what makes the merged-window caches safe.
    """

    epoch: int
    index: object               # repro.api sketch index over this epoch
    sealed: bool = False

    @property
    def num_records(self) -> int:
        return int(self.index.num_records)

    def nbytes(self) -> int:
        return int(self.index.nbytes())

    def arena(self):
        """The snapshot's :class:`~repro.core.arena.SketchArena`."""
        host = getattr(self.index, "core", None) or self.index
        return getattr(host, "sketches", None)


class WindowManager:
    """Sliding-window union index over per-epoch arena snapshots.

    Usage::

        wm = WindowManager(engine="gbkmv", budget=4096, backend="numpy")
        wm.ingest(day0_records, epoch=0)
        wm.ingest(day1_records, epoch=1)
        hits = wm.query(q, threshold=0.5)                # all live epochs
        hits = wm.query(q, threshold=0.5, window=(1, 1)) # day 1 only
        wm.retire(before=1)                              # drop day 0
        wm.save("snapshots/"); WindowManager.load("snapshots/")

    Epochs open in non-decreasing order: ingesting into the newest epoch
    extends it in place (GB-KMV via τ-retightening dynamic inserts);
    ingesting a *larger* epoch seals the current one forever; ingesting
    a smaller (sealed) epoch raises. ``query``/``batch_query``/``topk``
    /``scores`` take ``window=(lo, hi)`` (inclusive epoch bounds,
    default: every live epoch) and answer through a merged index that is
    bit-identical to one built from the window's records in one shot —
    merged views are cached per epoch-tuple and invalidated on ingest
    and retirement.

    GB-KMV epochs pin the first epoch's buffer element set (``top_elems``)
    so every epoch's bitmaps stay merge-compatible — the same frozen-
    buffer philosophy as the dynamic-insert path.
    """

    #: feature-detect flag for the serving layer (`/ingest` epoch field,
    #: `/admin/retire`) — plain api indexes don't have it.
    windowed = True

    def __init__(self, engine: str = "gbkmv", budget: int = 4096,
                 backend: str = "jnp", **build_cfg):
        if engine not in _SKETCH_ENGINES:
            raise ValueError(f"windowed index supports {_SKETCH_ENGINES}, "
                             f"got {engine!r}")
        self.engine = engine
        self.budget = int(budget)
        self.backend = backend
        self.build_cfg = dict(build_cfg)
        self._snaps: dict[int, ArenaSnapshot] = {}
        self._cache: dict[tuple[int, ...], object] = {}
        self._frozen_top: np.ndarray | None = None   # gbkmv buffer pin
        self._frozen_r: int | None = None
        self.last_plan = None
        self.merges_total = 0
        self.retired_epochs_total = 0
        self.retired_records_total = 0

    # -- epoch lifecycle ---------------------------------------------------

    @property
    def epochs(self) -> list[int]:
        """Live epoch ids, ascending."""
        return sorted(self._snaps)

    @property
    def num_records(self) -> int:
        return sum(s.num_records for s in self._snaps.values())

    def ingest(self, records, epoch: int | None = None) -> "WindowManager":
        """Add records to ``epoch`` (default: the newest open epoch, or 0).

        A new epoch id seals every older epoch; re-ingesting the open
        epoch extends it in place; a sealed epoch id raises.
        """
        records = [np.asarray(r) for r in records]
        cur = self.epochs[-1] if self._snaps else None
        epoch = int(epoch) if epoch is not None else (
            cur if cur is not None else 0)
        if cur is not None and epoch < cur:
            raise ValueError(
                f"epoch {epoch} is sealed (current epoch is {cur}); "
                "windowed ingest is append-only")
        if not records:
            return self
        if epoch == cur:
            self._snaps[cur].index.insert(records)
        else:
            for s in self._snaps.values():
                s.sealed = True
            self._snaps[epoch] = ArenaSnapshot(
                epoch=epoch, index=self._build_epoch(records))
        self._invalidate({epoch})
        return self

    def insert(self, new_records, epoch: int | None = None
               ) -> "WindowManager":
        """:class:`repro.api.ContainmentIndex` spelling of :meth:`ingest`
        (the serving layer's ``/ingest`` lands here)."""
        return self.ingest(new_records, epoch=epoch)

    def retire(self, before: int) -> int:
        """Drop every epoch ``< before``; returns how many were retired.

        Retired snapshots and every cached merged view that contained
        them are released; subsequent queries whose window still names a
        retired epoch simply see the surviving slice (an entirely
        retired window raises).
        """
        gone = [e for e in self.epochs if e < int(before)]
        for e in gone:
            self.retired_records_total += self._snaps[e].num_records
            del self._snaps[e]
        self.retired_epochs_total += len(gone)
        if gone:
            self._invalidate(set(gone))
        return len(gone)

    def _invalidate(self, epochs: set[int]) -> None:
        for key in [k for k in self._cache if epochs.intersection(k)]:
            del self._cache[key]

    # -- per-engine build / merge ------------------------------------------

    def _build_epoch(self, records):
        from repro import api

        cfg = self.build_cfg
        if self.engine == "gbkmv":
            from repro.core import gbkmv as gbkmv_mod

            core = gbkmv_mod.build_gbkmv(
                records, self.budget,
                r=(self._frozen_r if self._frozen_r is not None
                   else cfg.get("r", "auto")),
                seed=cfg.get("seed", 0), capacity=cfg.get("capacity"),
                tau_mode=cfg.get("tau_mode", "exact"),
                build_backend=cfg.get("build_backend"),
                top_elems=self._frozen_top)
            if self._frozen_top is None:
                self._frozen_top = np.asarray(core.top_elems, np.int64)
                self._frozen_r = int(core.buffer_bits)
            return api.GBKMVEngine.wrap(core, budget=self.budget,
                                        backend=self.backend)
        # gkmv/kmv go through Engine.build so the epoch retains its
        # records — their in-epoch insert is the rebuild fallback.
        keys = (("seed", "capacity", "tau_mode", "build_backend")
                if self.engine == "gkmv" else ("seed", "build_backend"))
        kw = {k: cfg[k] for k in keys if k in cfg}
        return api.get_engine(self.engine).build(
            records, self.budget, backend=self.backend, **kw)

    def _merge(self, snaps: list[ArenaSnapshot]):
        from repro import api

        self.merges_total += 1
        seed = int(self.build_cfg.get("seed", 0))
        if self.engine == "gbkmv":
            from repro.core import gbkmv as gbkmv_mod

            core = gbkmv_mod.merge_gbkmv(
                [s.index.core for s in snaps], self.budget,
                capacity=self.build_cfg.get("capacity"))
            return api.GBKMVEngine.wrap(core, budget=self.budget,
                                        backend=self.backend)
        if self.engine == "gkmv":
            from repro.core import gkmv as gkmv_mod

            merged = gkmv_mod.merge_gkmv(
                [s.index.sketches for s in snaps], self.budget,
                capacity=self.build_cfg.get("capacity"))
            return api.GKMVEngine.wrap(merged, seed=seed,
                                       backend=self.backend)
        from repro.core import kmv as kmv_mod

        merged = kmv_mod.merge_kmv([s.index.sketches for s in snaps],
                                   self.budget)
        return api.KMVEngine.wrap(merged, seed=seed, backend=self.backend)

    # -- window resolution -------------------------------------------------

    def _select(self, window) -> list[ArenaSnapshot]:
        eps = self.epochs
        if window is not None:
            lo, hi = int(window[0]), int(window[1])
            eps = [e for e in eps if lo <= e <= hi]
        if not eps:
            raise ValueError(
                f"window {window} selects no live epochs "
                f"(live: {self.epochs or 'none'})")
        return [self._snaps[e] for e in eps]

    def index(self, window=None):
        """The api-level index answering for ``window`` (inclusive epoch
        bounds; default all live epochs). Single-epoch windows return the
        snapshot's own index; multi-epoch windows return the cached
        merged union (built lazily, bit-identical to a one-shot build
        over the window's records)."""
        snaps = self._select(window)
        if len(snaps) == 1:
            return snaps[0].index
        key = tuple(s.epoch for s in snaps)
        idx = self._cache.get(key)
        if idx is None:
            idx = self._cache[key] = self._merge(snaps)
        return idx

    # -- ContainmentIndex protocol (window-parameterized) ------------------

    def query(self, q_ids, threshold: float, *, window=None,
              plan: str = "auto", explain: bool = False):
        """Record ids with estimated containment ≥ ``threshold`` inside
        ``window`` — same planner routing (``plan=``) and ``explain=``
        semantics as the underlying engine's ``query``."""
        idx = self.index(window)
        out = idx.query(q_ids, threshold, plan=plan, explain=explain)
        self.last_plan = idx.last_plan
        return out

    def batch_query(self, queries, threshold: float, *, window=None,
                    plan: str = "auto", explain: bool = False):
        idx = self.index(window)
        out = idx.batch_query(queries, threshold, plan=plan, explain=explain)
        self.last_plan = idx.last_plan
        return out

    def topk(self, q_ids, k: int, *, window=None, plan: str = "auto"):
        """Top-k (ids, scores) inside ``window`` under the deterministic
        (score desc, id asc) order. Ids are window-relative row numbers:
        position within the concatenation of the window's epochs."""
        idx = self.index(window)
        out = idx.topk(q_ids, k, plan=plan)
        self.last_plan = idx.last_plan
        return out

    def scores(self, q_ids, *, window=None) -> np.ndarray:
        return self.index(window).scores(q_ids)

    def nbytes(self) -> int:
        """Live snapshot bytes plus every cached merged view."""
        return (sum(s.nbytes() for s in self._snaps.values())
                + sum(ix.nbytes() for ix in self._cache.values()))

    # -- serving protocol --------------------------------------------------

    def serve_batch(self, queries, thresholds, k: int, plan: str = "auto",
                    explain: bool = False):
        """Threshold hits and top-k for a whole batch over every live
        epoch — the ``AsyncSketchServer`` execution protocol, answered by
        the current view's ``serve_batch`` (one route per batch)."""
        idx = self.index()
        out = idx.serve_batch(queries, thresholds, k, plan=plan,
                              explain=explain)
        self.last_plan = idx.last_plan
        return out

    # -- observability -----------------------------------------------------

    def window_stats(self) -> dict:
        """Gauge/counter snapshot for the ``/metrics`` exporter."""
        return {
            "epochs": len(self._snaps),
            "records": self.num_records,
            "cached_windows": len(self._cache),
            "merges_total": self.merges_total,
            "retired_epochs_total": self.retired_epochs_total,
            "retired_records_total": self.retired_records_total,
        }

    # -- persistence -------------------------------------------------------

    def save(self, dirpath: str) -> None:
        """Write the snapshot directory **atomically**: build the full
        tree in ``<dir>.tmp``, fsync the manifest, then swap it in with
        ``os.rename`` (the ``ft/checkpoint.py`` pattern). A reader — or
        a crash — never observes a half-written directory, and because
        the tree is rebuilt from scratch, ``epoch_*.npz`` files left
        behind by since-retired epochs cannot survive the swap."""
        tmp = dirpath.rstrip("/\\") + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        for e, snap in self._snaps.items():
            snap.index.save(os.path.join(tmp, f"epoch_{e:08d}.npz"))
        cfg = {k: v for k, v in self.build_cfg.items()
               if isinstance(v, (int, float, str, bool, type(None)))}
        manifest = {
            "version": 1, "engine": self.engine, "budget": self.budget,
            "backend": self.backend, "build_cfg": cfg,
            "epochs": self.epochs,
            "retired_epochs_total": self.retired_epochs_total,
            "retired_records_total": self.retired_records_total,
        }
        with open(os.path.join(tmp, _MANIFEST), "w") as f:
            json.dump(manifest, f, indent=2, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        # Swap: rename can't clobber a non-empty dir, so an existing
        # target steps aside first; its removal only happens after the
        # fresh tree is fully in place.
        old = dirpath.rstrip("/\\") + ".old"
        if os.path.exists(old):
            shutil.rmtree(old)
        if os.path.exists(dirpath):
            os.rename(dirpath, old)
        os.rename(tmp, dirpath)
        _fsync_dir(os.path.dirname(os.path.abspath(dirpath)))
        if os.path.exists(old):
            shutil.rmtree(old)

    @classmethod
    def load(cls, dirpath: str) -> "WindowManager":
        """Reload a snapshot directory. Sealed epochs stay sealed; the
        newest epoch re-opens for GB-KMV (dynamic inserts need no raw
        records) — gkmv/kmv epochs reload query-only, so continue those
        in fresh epochs."""
        from repro import api

        with open(os.path.join(dirpath, _MANIFEST)) as f:
            manifest = json.load(f)
        wm = cls(engine=manifest["engine"], budget=manifest["budget"],
                 backend=manifest["backend"], **manifest["build_cfg"])
        wm.retired_epochs_total = manifest.get("retired_epochs_total", 0)
        wm.retired_records_total = manifest.get("retired_records_total", 0)
        epochs = manifest["epochs"]
        for e in epochs:
            idx = api.load_index(os.path.join(dirpath, f"epoch_{e:08d}.npz"))
            wm._snaps[e] = ArenaSnapshot(epoch=e, index=idx,
                                         sealed=e != epochs[-1])
        if wm.engine == "gbkmv" and epochs:
            first = wm._snaps[epochs[0]].index.core
            wm._frozen_top = np.asarray(first.top_elems, np.int64)
            wm._frozen_r = int(first.buffer_bits)
        return wm
