"""Async flush loop in front of a sharded GB-KMV index.

:class:`repro.serving.SketchServer` executes a flush inline on the
submitting caller — accumulation *blocks* on the jitted device
score/topk pipeline. This module is the production refactor: submitters
only append to a **bounded admission queue** and a dedicated flush
worker drains it, so micro-batch accumulation overlaps device execution
(while a batch runs on device, the queue keeps filling for the next
one). Overload degrades gracefully instead of queueing unboundedly:

* queue full  → :class:`Overloaded` (the HTTP layer answers 429 with a
  ``Retry-After`` derived from the measured flush latency),
* request older than its deadline → flushed immediately and answered
  from the **dense fallback path** (``plan="dense"`` — one predictable
  index sweep, bit-identical results, no postings-probe variance),
* shutdown → the queue drains, nothing is dropped.

Everything is injectable-clock deterministic: tests drive the loop with
:meth:`AsyncSketchServer.step` and a fake clock, production calls
:meth:`start` for the background worker. Execution and flush accounting
are shared with the synchronous server (``serving.execute_batch`` /
``serving.BatchStats``).
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from collections import deque
from typing import Callable

import numpy as np

from repro import obs
from repro.serving.batcher import BatchStats, execute_batch
from repro.service.wal import IdempotencyCache, ReadOnly


class Overloaded(RuntimeError):
    """Admission queue full — shed with a retry hint (seconds)."""

    def __init__(self, retry_after: float):
        super().__init__(f"admission queue full; retry after "
                         f"{retry_after:.3f}s")
        self.retry_after = retry_after


@dataclasses.dataclass(eq=False)
class Pending:
    """One admitted request (identity equality — payloads are arrays).
    Field names mirror ``serving.Request`` so ``execute_batch`` consumes
    these directly."""

    kind: str          # "query" | "topk" | "ingest" | "retire" | "snapshot"
    q_ids: np.ndarray | None
    arrival: float
    rid: int = -1                  # assigned under the lock by _admit
    threshold: float = 0.5
    k: int = 0
    deadline: float | None = None  # absolute clock time, None = no SLO
    records: list | None = None    # ingest payload
    epoch: int | None = None       # windowed-index target epoch (ingest)
    idem: str | None = None        # idempotency key (ingest dedupe)
    done: threading.Event = dataclasses.field(default_factory=threading.Event)
    result: dict | None = None
    error: Exception | None = None
    expired: bool = False
    explain: bool = False          # attach a plan explain to the result
    trace: object | None = None    # obs.Trace when tracing is enabled

    def past_deadline(self, now: float) -> bool:
        return self.deadline is not None and now >= self.deadline


class AsyncSketchServer:
    """Bounded-admission micro-batching server over ``index.serve_batch``.

    ``index`` is anything speaking the ``serve_batch(queries, thresholds,
    k, plan=)`` protocol (a :class:`repro.sketchindex.ShardedIndex` in
    production); ingest additionally needs ``index.insert``. The flush
    worker is the ONLY thread touching the index, so queries and ingest
    serialize in admission (FIFO) order — a client that ingests then
    queries observes its own writes.
    """

    def __init__(self, index, *, max_batch: int = 16, max_wait: float = 0.01,
                 max_inflight: int = 256, default_deadline: float | None = 0.5,
                 plan: str = "auto",
                 clock: Callable[[], float] = time.monotonic,
                 tracer=None, profile: bool = True,
                 slow_threshold: float | None = 1.0,
                 slow_log_size: int = 128,
                 durability=None, idem_window: int = 1024):
        from repro.planner import normalize_plan

        self.index = index
        self.max_batch = int(max_batch)
        self.max_wait = float(max_wait)
        self.max_inflight = int(max_inflight)
        self.default_deadline = default_deadline
        self.plan = normalize_plan(plan)
        self.clock = clock
        self.stats = BatchStats()
        self.shed = 0                  # admissions refused (429s)
        self.expired_served = 0        # requests answered past deadline
        self.records_ingested = 0
        # Observability. ``tracer=None`` (the default) records no traces
        # and allocates nothing per request; the profiler's stage
        # histograms stay on (a few clock reads per *flush*, amortized
        # over the batch). ``slow_threshold`` seconds of total latency
        # (admission → answered) lands a request in the bounded slow log.
        self.tracer = tracer
        self.profiler = obs.StageProfiler() if profile else None
        self.cost_drift = obs.CostDrift()
        self.slow_threshold = slow_threshold
        self.slow_queries = 0
        self.slow_log: deque[dict] = deque(maxlen=int(slow_log_size))
        # Durability (PR 10). ``durability=None`` keeps the pre-WAL
        # behavior exactly: mutations apply in-memory only and the
        # idempotency window is process-local. With a
        # :class:`repro.service.wal.Durability` attached, the flush
        # worker logs every mutation to the WAL *before* applying it
        # (append batch → one fsync → apply → ack, i.e. group commit
        # under fsync="batch"), and an unwritable data dir flips the
        # server into sticky read-only instead of killing it.
        self.durability = durability
        self.idem = (durability.idem if durability is not None
                     else IdempotencyCache(idem_window))
        self.read_only = False
        self.read_only_reason: str | None = None
        self.deduped_total = 0
        self._queue: deque[Pending] = deque()
        self._cv = threading.Condition()
        self._next_rid = 0
        self._thread: threading.Thread | None = None
        self._stop = False

    # -- admission ---------------------------------------------------------

    @property
    def inflight(self) -> int:
        return len(self._queue)

    def retry_after(self) -> float:
        """Backoff hint for shed requests: the time the current backlog
        needs to drain at the measured flush latency (floor: one
        deadline window)."""
        per_flush = self.stats.flush_latency_hist.mean or self.max_wait
        backlog_flushes = math.ceil(
            max(len(self._queue), 1) / max(self.max_batch, 1))
        return max(self.max_wait, backlog_flushes * per_flush)

    def _admit(self, p: Pending) -> Pending:
        with self._cv:
            if len(self._queue) >= self.max_inflight:
                self.shed += 1
                raise Overloaded(self.retry_after())
            # rid minted under the lock: submitters run on concurrent HTTP
            # handler threads, and execute_batch keys results by rid — a
            # duplicate would hand two requests each other's answers.
            p.rid = self._next_rid
            self._next_rid += 1
            self._queue.append(p)
            self._cv.notify()
        if self.tracer is not None:
            # Begin after admission: shed requests never allocate a trace.
            p.trace = self.tracer.begin(p.kind, rid=p.rid)
        return p

    def _deadline(self, arrival: float, deadline: float | None):
        budget = self.default_deadline if deadline is None else deadline
        return None if budget is None else arrival + float(budget)

    def submit_query(self, q_ids, threshold: float = 0.5,
                     deadline: float | None = None,
                     explain: bool = False) -> Pending:
        now = self.clock()
        return self._admit(Pending(
            kind="query", q_ids=np.asarray(q_ids), arrival=now,
            threshold=float(threshold),
            deadline=self._deadline(now, deadline), explain=bool(explain)))

    def submit_topk(self, q_ids, k: int = 10,
                    deadline: float | None = None,
                    explain: bool = False) -> Pending:
        now = self.clock()
        return self._admit(Pending(
            kind="topk", q_ids=np.asarray(q_ids), arrival=now,
            threshold=math.inf, k=int(k),
            deadline=self._deadline(now, deadline), explain=bool(explain)))

    def _check_writable(self) -> None:
        if self.read_only:
            raise ReadOnly(self.read_only_reason or "data dir unwritable")

    def submit_ingest(self, records, epoch: int | None = None,
                      idem: str | None = None) -> Pending:
        self._check_writable()
        now = self.clock()
        return self._admit(Pending(
            kind="ingest", q_ids=None, arrival=now,
            records=[np.asarray(r) for r in records],
            epoch=None if epoch is None else int(epoch), idem=idem))

    def submit_retire(self, before: int) -> Pending:
        """Windowed-index admin: drop every epoch ``< before``. Routed
        through the mutation lane so the flush worker stays the only
        thread touching the index."""
        self._check_writable()
        now = self.clock()
        return self._admit(Pending(
            kind="retire", q_ids=None, arrival=now, epoch=int(before)))

    def submit_snapshot(self) -> Pending:
        """Admin: atomic snapshot + WAL truncation, routed through the
        mutation lane — the flush worker runs it, so the index is
        quiescent and FIFO order puts every prior ack inside it."""
        if self.durability is None:
            raise RuntimeError("snapshots need a data dir "
                               "(server started without durability)")
        self._check_writable()
        now = self.clock()
        return self._admit(Pending(kind="snapshot", q_ids=None, arrival=now))

    # -- flush loop --------------------------------------------------------

    def _gather(self, now: float, force: bool):
        """Pop the next executable batch (caller holds the lock), or
        (None, wait_hint). Kinds never mix across an ingest boundary —
        FIFO order is the consistency model."""
        if not self._queue:
            return None, None
        mutation = ("ingest", "retire", "snapshot")
        if self._queue[0].kind in mutation:
            batch = []
            while self._queue and self._queue[0].kind in mutation \
                    and len(batch) < self.max_batch:
                batch.append(self._queue.popleft())
            return batch, "ingest"
        run = 0
        expired = False
        for p in self._queue:
            if p.kind in mutation or run >= self.max_batch:
                break
            expired |= p.past_deadline(now)
            run += 1
        oldest_age = now - self._queue[0].arrival
        if run >= self.max_batch:
            reason = "full"
        elif expired:
            reason = "expired"
        elif oldest_age >= self.max_wait or force:
            reason = "deadline"
        else:
            return None, self.max_wait - oldest_age
        return [self._queue.popleft() for _ in range(run)], reason

    def step(self, block: bool = False, timeout: float | None = None,
             force: bool = False) -> int:
        """One flush-loop iteration: gather → execute → complete events.
        Returns the number of requests answered. ``block`` waits (real
        time) for a flushable batch; ``force`` flushes a partial batch
        immediately (drain/test hook). The server's profiler is attached
        for the whole iteration, so the wait shows as ``flush.gather``."""
        with obs.attach(None, self.profiler):
            with obs.stage("flush.gather"):
                batch, hint = self._next_batch(block, timeout, force)
            if batch is None:
                return 0
            # Lock released: submitters keep filling the queue while the
            # batch executes on device — the overlap this server exists
            # for.
            if hint == "ingest":
                self._execute_ingest(batch)
            else:
                self._execute_serve(batch, reason=hint)
            return len(batch)

    def _next_batch(self, block: bool, timeout: float | None, force: bool):
        """(batch, reason) popped under the lock, or (None, None) when
        nothing is flushable (after waiting up to ``timeout`` if
        ``block``)."""
        deadline = (time.monotonic() + timeout) if (block and timeout) else None
        with self._cv:
            while True:
                batch, hint = self._gather(self.clock(), force)
                if batch is not None:
                    return batch, hint
                if not block:
                    return None, None
                wait = hint if hint is not None else 0.05
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return None, None
                    wait = min(wait, remaining)
                if not self._cv.wait(timeout=wait) and self._stop \
                        and not self._queue:
                    return None, None

    def drain(self):
        """Flush until the queue is empty (shutdown / test barrier)."""
        while self.step(force=True):
            pass

    def _complete(self, batch: list[Pending], err: Exception | None = None):
        for p in batch:
            if err is not None and p.result is None:
                p.error = err
            p.done.set()

    def _record_drift(self, measured: float) -> None:
        """Fold one serve flush into the cost-model drift gauge: the
        planner's chosen-path estimate vs the flush's measured seconds."""
        decision = getattr(self.index, "last_plan", None)
        if decision is None:
            return
        predicted = (decision.est_pruned if decision.path == "pruned"
                     else decision.est_dense)
        self.cost_drift.record(float(predicted), measured)

    def _finish_request(self, p: Pending, why: str, plan: str,
                        flush_start: float, t0: float, t1: float,
                        batch_size: int) -> None:
        """Per-request observability at completion: trace spans, per-kind
        latency histogram, and the slow-query log."""
        total = t1 - p.arrival
        if self.profiler is not None:
            self.profiler.observe(f"request.{p.kind}", max(total, 0.0))
        if p.trace is not None:
            p.trace.add_span("queue_wait", p.arrival, flush_start)
            p.trace.add_span("execute", t0, t1, plan=plan, reason=why,
                             batch=batch_size)
            p.trace.end(kind=p.kind, expired=p.expired)
        if self.slow_threshold is not None and total >= self.slow_threshold:
            self.slow_queries += 1
            self.slow_log.append({
                "rid": p.rid, "kind": p.kind,
                "latency_s": round(total, 6),
                "queue_wait_s": round(flush_start - p.arrival, 6),
                "plan": plan, "reason": why, "expired": p.expired,
                "batch": batch_size,
                "n_ids": int(len(p.q_ids)) if p.q_ids is not None else 0,
            })

    def _execute_serve(self, batch: list[Pending], reason: str):
        now = self.clock()
        fresh = [p for p in batch if not p.past_deadline(now)]
        late = [p for p in batch if p.past_deadline(now)]
        ftrace = None
        with obs.contained():
            if self.tracer is not None:
                ftrace = self.tracer.begin("flush", reason=reason,
                                           batch=len(batch),
                                           rids=[p.rid for p in batch])
                # Batch assembly: oldest admission → this flush starting.
                ftrace.add_span("assemble", min(p.arrival for p in batch),
                                now, batch=len(batch))
        try:
            # Deadline-expired requests take the dense fallback: one
            # predictable sweep, no postings-probe variance, answered
            # ahead of further accumulation. Results are bit-identical
            # (the planner's contract) — only the latency path differs.
            for sub, plan, why in ((late, "dense", "expired"),
                                   (fresh, self.plan, reason)):
                if not sub:
                    continue
                k = max((p.k for p in sub), default=0)
                self.stats.record_batch(
                    [now - p.arrival for p in sub], why)
                explain = any(p.explain for p in sub)
                t0 = self.clock()
                with obs.attach(ftrace, self.profiler):
                    with obs.stage(
                            "flush.execute", reason=why, plan=plan,
                            batch=len(sub),
                            queries=sum(p.kind == "query" for p in sub),
                            topks=sum(p.kind == "topk" for p in sub)):
                        out = execute_batch(self.index, sub, k, plan,
                                            stats=self.stats,
                                            clock=self.clock,
                                            explain=explain)
                    t1 = self.clock()
                    with obs.stage("flush.answer", batch=len(sub)):
                        self._answer(sub, out, why)
                        with obs.contained():
                            self._record_drift(t1 - t0)
                        for p in sub:
                            with obs.contained():
                                self._finish_request(p, why, plan, now, t0,
                                                     t1, len(sub))
                if why == "expired":
                    self.expired_served += len(sub)
            self._complete(batch)
        except Exception as e:                     # pragma: no cover - guard
            self._complete(batch, err=e)
        finally:
            if ftrace is not None:
                with obs.contained():
                    ftrace.end()

    @staticmethod
    def _answer(sub: list[Pending], out: dict, why: str) -> None:
        """Set every request's result, then release every waiter — before
        any per-request observability, which therefore cannot cost an
        answer."""
        for p in sub:
            res = out[p.rid]
            if p.kind == "topk":
                p.result = {"topk_ids": res["topk_ids"][: p.k],
                            "topk_scores": res["topk_scores"][: p.k]}
            else:
                p.result = {"hits": res["hits"]}
            if p.explain and "explain" in res:
                p.result["explain"] = res["explain"]
            p.expired = why == "expired"
        for p in sub:
            p.done.set()

    def _enter_read_only(self, err: OSError) -> None:
        """Sticky degrade: the data dir failed a write (ENOSPC, EROFS,
        pulled volume). Mutations 503 from here on; queries keep
        serving from the in-memory index. Recovery is an operator
        restart against a healthy volume."""
        self.read_only = True
        self.read_only_reason = f"{type(err).__name__}: {err}"

    def _execute_ingest(self, batch: list[Pending]):
        """Drain one mutation batch in FIFO order. Contiguous
        ingest/retire runs group-commit through the WAL (append every
        entry → one fsync → apply → ack), so fsync="batch" amortizes
        the disk flush across the batch; a "snapshot" breaks the run
        and executes alone at its FIFO position."""
        now = self.clock()
        self.stats.record_batch([now - p.arrival for p in batch], "ingest")
        run: list[Pending] = []
        for p in batch:
            if p.kind == "snapshot":
                self._commit_run(run, now)
                run = []
                self._execute_snapshot(p)
            else:
                run.append(p)
        self._commit_run(run, now)

    def _commit_run(self, run: list[Pending], now: float):
        if not run:
            return
        # Phase 1 — dedupe + WAL append (ack nothing yet). The flush
        # worker is the only thread here, so the idempotency check and
        # the apply are atomic with respect to each other: two racing
        # retries can both pass admission, but only the first to reach
        # this loop applies.
        to_apply: list[Pending] = []
        for p in run:
            if self.read_only:
                p.error = ReadOnly(self.read_only_reason or "read-only")
                p.done.set()
                continue
            if p.idem is not None:
                prior = self.idem.get(p.idem)
                if prior is not None:
                    p.result = {**prior, "deduped": True}
                    self.deduped_total += 1
                    p.done.set()
                    continue
            if self.durability is not None:
                try:
                    if p.kind == "retire":
                        self.durability.log_retire(p.epoch)
                    else:
                        self.durability.log_ingest(p.records, p.epoch,
                                                   p.idem)
                except OSError as e:
                    self._enter_read_only(e)
                    p.error = ReadOnly(self.read_only_reason)
                    p.done.set()
                    continue
            to_apply.append(p)
        # Phase 2 — one group-commit fsync covering the whole run.
        if self.durability is not None and to_apply:
            try:
                self.durability.sync()
            except OSError as e:
                self._enter_read_only(e)
                for p in to_apply:
                    # Not durable → not acknowledged; the client's
                    # idempotency key makes its retry safe.
                    p.error = ReadOnly(self.read_only_reason)
                    p.done.set()
                return
        # Phase 3 — apply to the index and acknowledge.
        for p in to_apply:
            try:
                if p.kind == "retire":
                    retired = self.index.retire(p.epoch)
                    p.result = {"retired": int(retired),
                                "epochs": [int(e) for e in self.index.epochs]}
                    p.done.set()
                    continue
                t0 = self.clock()
                # Epoch only reaches windowed indexes; plain ShardedIndex
                # keeps its narrower insert(records) signature.
                if p.epoch is None:
                    self.index.insert(p.records)
                else:
                    self.index.insert(p.records, epoch=p.epoch)
                t1 = self.clock()
                self.records_ingested += len(p.records)
                p.result = {"ingested": len(p.records)}
                if p.idem is not None:
                    self.idem.put(p.idem, {"ingested": len(p.records)})
                # Host insert latency stays out of flush_latency_hist —
                # that histogram is the device-flush basis for the 429
                # Retry-After hint.
                with obs.contained():
                    self.stats.ingest_latency_hist.observe(t1 - t0)
                    if self.profiler is not None:
                        self.profiler.observe("request.ingest",
                                              max(t1 - p.arrival, 0.0))
                    if p.trace is not None:
                        p.trace.add_span("queue_wait", p.arrival, now)
                        p.trace.add_span("insert", t0, t1,
                                         records=len(p.records))
                        p.trace.end(kind="ingest")
            except Exception as e:
                p.error = e
            p.done.set()

    def _execute_snapshot(self, p: Pending):
        t0 = self.clock()
        try:
            if self.durability is None:
                raise RuntimeError("snapshots need a data dir")
            if self.read_only:
                raise ReadOnly(self.read_only_reason or "read-only")
            p.result = self.durability.snapshot(self.index)
        except OSError as e:
            self._enter_read_only(e)
            p.error = ReadOnly(self.read_only_reason)
        except Exception as e:
            p.error = e
        if self.profiler is not None:
            with obs.contained():
                self.profiler.observe("request.snapshot",
                                      max(self.clock() - t0, 0.0))
        p.done.set()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "AsyncSketchServer":
        if self._thread is not None:
            return self
        self._stop = False

        # Background snapshots ride the flush loop itself: the worker
        # enqueues a "snapshot" pending at the interval and pops it on a
        # later step, so snapshots hold the same single-mutator
        # invariant as every other mutation.
        interval = (self.durability.snapshot_interval
                    if self.durability is not None else 0.0)
        next_snap = time.monotonic() + interval if interval > 0 else None

        def loop():
            nonlocal next_snap
            while not self._stop:
                self.step(block=True, timeout=0.1)
                if next_snap is not None and time.monotonic() >= next_snap:
                    next_snap = time.monotonic() + interval
                    try:
                        if not self.read_only:
                            self.submit_snapshot()
                    except (Overloaded, ReadOnly):
                        pass
            self.drain()

        self._thread = threading.Thread(target=loop, name="flush-loop",
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._thread is None:
            return
        self._stop = True
        with self._cv:
            self._cv.notify_all()
        # No timeout: the worker is the only mutator, so the drain below
        # must not overlap a flush it is still running, and the process
        # must not exit while that flush is inside a device call.
        self._thread.join()
        self._thread = None
        self.drain()

    def result(self, p: Pending, timeout: float | None = 30.0) -> dict:
        """Wait for a pending request; raises its execution error."""
        if not p.done.wait(timeout=timeout):
            raise TimeoutError(f"request {p.rid} not served in {timeout}s")
        if p.error is not None:
            raise p.error
        return p.result
