"""HTTP front for the async sketch server — stdlib only.

Endpoints (JSON in/out unless noted):

    POST /query    {"q": [ids], "threshold": 0.5, "deadline_ms"?: int,
                    "explain"?: bool}
                   → {"rid", "hits": [...], "expired": bool, "explain"?}
    POST /topk     {"q": [ids], "k": 10, "deadline_ms"?: int}
                   → {"rid", "ids": [...], "scores": [...]}
    POST /ingest   NDJSON stream (one JSON id-array per line) or
                   {"records": [[...], ...]} → {"ingested", "chunks"}
                   Windowed indexes accept a target epoch via the
                   ``?epoch=N`` query param or an ``"epoch"`` JSON key.
                   An ``Idempotency-Key`` header (or ``"idempotency_key"``
                   JSON key) makes retries safe: chunks already applied
                   inside the dedupe window are skipped and the response
                   gains ``"deduped_chunks"``.
    POST /admin/retire  {"before": N} → {"retired", "epochs"} — drop
                   window epochs < N (windowed indexes only; auth-gated,
                   exempt from rate limits like /debug/*)
    POST /admin/snapshot  → {"wal_seq", "path", ...} — atomic snapshot
                   through the mutation lane, then WAL truncation
                   (needs --data-dir; auth-gated, outside rate limits)
    POST /debug/explain  same body as /query with explain forced on
    GET  /debug/traces   → Chrome trace-event JSON of recent requests
                           (load in chrome://tracing or ui.perfetto.dev)
    GET  /debug/slow     → the slow-query log (threshold-configurable)
    GET  /healthz  → {"status": "ok", "records", "inflight",
                      "writable"} — liveness, always 200         (open)
    GET  /readyz   → readiness: 200 while writable, 503 once the
                     server degrades to read-only serving        (open)
    GET  /metrics  → Prometheus text format                      (open)

Durability degradation: when the data dir fails a write (disk full,
read-only remount) the flush worker flips the server into sticky
read-only — mutations (`/ingest`, `/admin/retire`, `/admin/snapshot`)
answer **503**, queries keep answering 200 from the in-memory index,
and `/readyz` goes 503 so a load balancer drains writes.

Middleware runs before admission: bearer-token auth (401), a global
token-bucket rate limit, and a per-tenant (per-auth-token) bucket —
both 429 + Retry-After, tenant rejections counted in
``service_ratelimited_total{tenant}``. A full admission queue also
answers 429 with a Retry-After derived from measured flush latency — the
load-shed half of graceful degradation. ``/debug/*`` endpoints sit
behind auth but outside the rate limits (introspection must work while
the service sheds).

The `/ingest` endpoint **streams**: NDJSON lines are parsed incrementally
and handed to the flush loop in chunks of ``ingest_chunk`` records, so a
record batch far larger than one flush never materializes on host —
at most one chunk of parsed records is alive at a time (the carried-over
streaming-RaggedBatch item: each chunk becomes one CSR ingest downstream).
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs

import numpy as np

from repro import obs
from repro.service.metrics import Metrics
from repro.service.middleware import (AuthToken, TenantBuckets, TokenBucket,
                                      tenant_id)
from repro.service.server import AsyncSketchServer, Overloaded, ReadOnly


class Response:
    def __init__(self, status: int, body, content_type: str = "application/json",
                 headers: dict | None = None):
        self.status = status
        self.headers = dict(headers or {})
        self.content_type = content_type
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
        elif isinstance(body, str):
            body = body.encode()
        self.body = body


def _json_error(status: int, message: str, **headers) -> Response:
    return Response(status, {"error": message}, headers=headers)


# Sanity cap on one chunk-size/trailer line (incl. chunk extensions).
_MAX_LINE = 8192


def _read_line(rfile, what: str) -> bytes:
    line = rfile.readline(_MAX_LINE)
    if line and not line.endswith(b"\n"):
        raise ValueError(f"{what} line too long (> {_MAX_LINE} bytes)")
    return line


def _iter_body(rfile, headers, max_chunk: int = 1 << 16):
    """Yield raw body bytes without materializing the request:
    Content-Length bodies stream in ``max_chunk`` pieces, and
    ``Transfer-Encoding: chunked`` is decoded incrementally (chunk
    extensions stripped, trailer headers consumed)."""
    if headers.get("Transfer-Encoding", "").lower() == "chunked":
        while True:
            size_line = _read_line(rfile, "chunk size")
            if not size_line:
                return                                 # peer closed
            try:
                size = int(size_line.split(b";")[0].strip() or b"0", 16)
            except ValueError:
                raise ValueError(
                    f"bad chunk size {size_line[:32]!r}") from None
            if size == 0:
                while True:                            # trailer section
                    line = _read_line(rfile, "trailer")
                    if line in (b"", b"\r\n", b"\n"):
                        return
            remaining = size
            while remaining:
                piece = rfile.read(min(remaining, max_chunk))
                if not piece:
                    return
                remaining -= len(piece)
                yield piece
            rfile.readline(2)                          # chunk-data CRLF
        return
    remaining = int(headers.get("Content-Length", 0) or 0)
    while remaining > 0:
        piece = rfile.read(min(remaining, max_chunk))
        if not piece:
            return
        remaining -= len(piece)
        yield piece


class _Body:
    """One-shot iterator over the request body that tracks consumption.

    The handler may answer before reading the body (401/404/405/429);
    on a keep-alive connection the unread bytes would then be parsed as
    the *next* request line, corrupting the stream — so :meth:`handle`
    always drains the remainder before responding. A body that can't be
    decoded (malformed chunking) marks itself ``broken`` and the
    response carries ``Connection: close`` instead."""

    def __init__(self, rfile, headers):
        self._iter = self._decode(rfile, headers)
        self.broken = False

    def _decode(self, rfile, headers):
        try:
            yield from _iter_body(rfile, headers)
        except ValueError:
            self.broken = True
            raise

    def __iter__(self):
        return self._iter

    def drain(self) -> bool:
        """Consume whatever the handler left unread; False means the
        stream is undecodable and the connection must be closed."""
        if self.broken:
            return False
        try:
            for _ in self._iter:
                pass
        except (ValueError, OSError):
            self.broken = True
            return False
        return True


def _iter_lines(chunks):
    buf = b""
    for piece in chunks:
        buf += piece
        while True:
            nl = buf.find(b"\n")
            if nl < 0:
                break
            yield buf[:nl]
            buf = buf[nl + 1:]
    if buf.strip():
        yield buf


class ServiceApp:
    """Routing + middleware + metrics over an :class:`AsyncSketchServer`."""

    def __init__(self, server: AsyncSketchServer, *,
                 auth_token: str | None = None,
                 rate_limit: float | None = None, burst: int | None = None,
                 tenant_rate_limit: float | None = None,
                 tenant_burst: int | None = None,
                 ingest_chunk: int = 256, result_timeout: float = 60.0,
                 clock=time.monotonic):
        self.server = server
        self.auth = AuthToken(auth_token)
        self.bucket = TokenBucket(rate_limit, burst, clock=clock)
        self.tenant_buckets = TenantBuckets(tenant_rate_limit, tenant_burst,
                                            clock=clock)
        self.ingest_chunk = int(ingest_chunk)
        self.result_timeout = float(result_timeout)
        self.clock = clock
        self.metrics = Metrics()
        self._wire_metrics()

    def _wire_metrics(self):
        m, srv = self.metrics, self.server
        stats = srv.stats
        m.register_histogram(
            "service_queue_wait_seconds", stats.queue_wait_hist,
            help="Per-request wait from admission to flush")
        m.register_histogram(
            "service_flush_latency_seconds", stats.flush_latency_hist,
            help="Device execution latency per flush")
        m.register_histogram(
            "service_ingest_latency_seconds", stats.ingest_latency_hist,
            help="Host insert latency per ingest request")
        for reason, fn in (("full", lambda: stats.flushes_full),
                           ("deadline", lambda: stats.flushes_deadline),
                           ("expired", lambda: stats.flushes_expired),
                           ("ingest", lambda: stats.flushes_ingest)):
            m.set_counter_fn("service_flush_total", fn, {"reason": reason},
                             help="Flushes by trigger reason")
        m.set_counter_fn("service_shed_total", lambda: srv.shed,
                         help="Requests refused at the admission queue")
        m.set_counter_fn("service_expired_total",
                         lambda: srv.expired_served,
                         help="Requests answered past their deadline "
                              "(dense fallback path)")
        m.set_counter_fn("service_records_ingested_total",
                         lambda: srv.records_ingested,
                         help="Records ingested through /ingest")
        m.set_gauge("service_inflight", lambda: srv.inflight,
                    help="Admission queue depth")
        m.set_gauge("service_mean_batch_occupancy",
                    lambda: stats.mean_batch,
                    help="Mean requests per flush")
        m.set_counter_fn("service_slow_queries_total",
                         lambda: srv.slow_queries,
                         help="Requests slower end-to-end than the "
                              "slow-query threshold")
        m.set_gauge("service_cost_model_drift",
                    lambda: srv.cost_drift.drift,
                    help="Predicted/measured seconds ratio for planned "
                         "flushes (1.0 = calibrated; 0 until measurable)")
        m.set_gauge("service_read_only", lambda: int(srv.read_only),
                    help="1 once the data dir failed a write and the "
                         "server degraded to read-only serving")
        m.set_counter_fn("service_ingest_deduped_total",
                         lambda: srv.deduped_total,
                         help="Ingest chunks skipped by the idempotency "
                              "window (safe client retries)")
        m.set_counter_fn("service_obs_faults_total", obs.faults,
                         help="Observability faults (a raise in a span, "
                              "trace or histogram) contained before they "
                              "reached a request, in this process")
        # Durability gauges — only when the server mounts a data dir.
        d = srv.durability
        if d is not None:
            m.set_info("service_durability_info",
                       {"fsync": d.wal.policy, "data_dir": d.data_dir},
                       help="Durability configuration")
            m.set_counter_fn("wal_appends_total",
                             lambda: d.wal.appends_total,
                             help="WAL entries appended")
            m.set_counter_fn("wal_fsyncs_total", lambda: d.wal.fsyncs_total,
                             help="WAL fsync(2) calls (group commit "
                                  "amortizes these across batches)")
            m.set_counter_fn("wal_rotations_total",
                             lambda: d.wal.rotations_total,
                             help="WAL segment rotations (epoch seals, "
                                  "size bounds, snapshots)")
            m.set_counter_fn("wal_truncated_segments_total",
                             lambda: d.wal.truncated_segments_total,
                             help="WAL segments dropped after snapshots")
            m.set_gauge("wal_segments", lambda: d.wal.segment_count,
                        help="Live WAL segment files")
            m.set_gauge("wal_nbytes", lambda: d.wal.nbytes(),
                        help="Bytes across live WAL segments")
            m.set_gauge("wal_last_seq", lambda: d.wal.last_seq,
                        help="Newest appended WAL sequence number")
            m.set_counter_fn("snapshot_total", lambda: d.snapshots_total,
                             help="Snapshots taken this process")
            m.set_gauge("snapshot_wal_seq", lambda: d.snap_seq,
                        help="WAL seq the newest snapshot covers through")
            m.set_gauge("snapshot_last_seconds",
                        lambda: d.snapshot_last_seconds,
                        help="Duration of the most recent snapshot")
            m.set_gauge("snapshot_last_nbytes",
                        lambda: d.snapshot_last_nbytes,
                        help="On-disk bytes of the most recent snapshot")
            m.set_gauge("recovery_replayed_entries",
                        lambda: d.replayed_entries,
                        help="WAL entries replayed at the last boot")
            m.set_gauge("recovery_replayed_records",
                        lambda: d.replayed_records,
                        help="Records re-ingested from the WAL at boot")
            m.set_gauge("recovery_failed_entries",
                        lambda: d.replay_failed_entries,
                        help="WAL entries whose replay raised (skipped)")
            m.set_gauge("recovery_torn_tail_bytes",
                        lambda: d.wal.torn_tail_bytes,
                        help="Torn-tail bytes truncated from the newest "
                             "WAL segment at boot (0 = clean shutdown)")
            m.set_gauge("recovery_seconds", lambda: d.recovery_seconds,
                        help="Wall time of the last WAL replay")
            m.set_gauge("recovery_invalid_snapshots_skipped",
                        lambda: d.invalid_snapshots_skipped,
                        help="Corrupt/torn snapshots skipped while "
                             "picking the newest valid one")
        if srv.profiler is not None:
            m.register_histogram_provider(
                "service_stage_latency_seconds", srv.profiler.histograms,
                help="Host-side stage latency from the flush-loop profiler")
        # Re-resolve the arena per scrape: ingest swaps the host index
        # (and its arena) underneath the ShardedIndex.
        def _sketch_b():
            a = self._arena()
            return a.sketch_nbytes() if a is not None else 0

        def _post_b():
            a = self._arena()
            return (a.postings_nbytes()
                    if a is not None and getattr(a, "_post", None) is not None
                    else 0)

        m.set_gauge("arena_sketch_nbytes", _sketch_b,
                    help="Packed sketch column bytes")
        m.set_gauge("arena_postings_nbytes", _post_b,
                    help="Block-compressed postings bytes (0 until first "
                         "planned query builds them)")

        # Time-windowed index gauges — only when the served index is a
        # WindowManager (feature-detected via its ``windowed`` attr).
        if getattr(srv.index, "windowed", False):
            def _win(key):
                return lambda: srv.index.window_stats()[key]

            m.set_gauge("window_epochs", _win("epochs"),
                        help="Live epoch snapshots in the window manager")
            m.set_gauge("window_records", _win("records"),
                        help="Records across all live epochs")
            m.set_gauge("window_cached_views", _win("cached_windows"),
                        help="Cached merged window views")
            m.set_gauge("window_nbytes", lambda: srv.index.nbytes(),
                        help="Bytes across epoch arenas and cached views")
            m.set_counter_fn("window_merges_total", _win("merges_total"),
                             help="Window merges performed (cache misses)")
            m.set_counter_fn("window_retired_epochs_total",
                             _win("retired_epochs_total"),
                             help="Epoch snapshots retired via "
                                  "retire()/admin endpoint")
            m.set_counter_fn("window_retired_records_total",
                             _win("retired_records_total"),
                             help="Records dropped with retired epochs")

        # Fused device-pipeline counters (repro.planner.device): compile
        # cache behaviour and staging-pool reuse. Lazy per scrape — the
        # stats dict is plain ints, no jax import on the scrape path.
        def _pipe(key):
            def fn():
                return obs.device_pipeline_stats()[key]
            return fn

        for key, hlp in (
            ("calls", "Fused device-pipeline invocations"),
            ("compiles",
             "Device-pipeline compile-cache misses (each one logged as a "
             "slow-path recompile)"),
            ("cache_hits", "Device-pipeline compile-cache hits"),
            ("staging_reuse",
             "Query batches staged through an existing pooled buffer"),
            ("staging_alloc", "Staging-pool buffer allocations"),
        ):
            m.set_counter_fn(f"device_pipeline_{key}_total", _pipe(key),
                             help=hlp)
        m.set_gauge("device_pipeline_staging_buffers",
                    _pipe("staging_buffers"),
                    help="Live pooled staging buffers (distinct "
                         "shape-bucket keys)")

        # Route and probe tallies of the served path (obs.tally into the
        # server's profiler): which route served each read batch, and
        # what the planner's probe counted for the batches it routed.
        if srv.profiler is not None:
            def _tally(name, field):
                def fn():
                    return srv.profiler.tallies().get(name, (0, 0.0))[field]
                return fn

            for route in ("dense", "fused", "host_pruned"):
                m.set_counter_fn(
                    "service_read_batches_total", _tally(f"route.{route}", 0),
                    {"route": route},
                    help="Served read batches by route: dense sweep, fused "
                         "device pruned, host pruned (mesh)")
            for kind in ("tail", "dense", "buf"):
                m.set_counter_fn(
                    "planner_probe_blocks_total",
                    _tally(f"probe.{kind}_blocks", 1), {"kind": kind},
                    help="Posting blocks the planner's probe counted for the "
                         "batches it routed: tail blocks, of which "
                         "dense-bitmap, and buffer blocks")
            m.set_counter_fn("planner_probe_entries_total",
                             _tally("probe.entries", 1),
                             help="Posting entries the planner's probe fed "
                                  "the cost model")
            m.set_counter_fn("planner_routed_batches_total",
                             _tally("probe.entries", 0),
                             help="Read batches the planner probed and routed")

    def _arena(self):
        """The live sketch arena, re-resolved per call — ingest swaps the
        host index under the ShardedIndex."""
        idx = self.server.index
        host = getattr(idx, "host", None) or getattr(idx, "core", None)
        sk = getattr(host, "sketches", None)
        return sk if sk is not None and hasattr(sk, "sketch_nbytes") else None

    @property
    def num_records(self) -> int:
        idx = self.server.index
        return int(getattr(idx, "num_records", 0))

    # -- request handling --------------------------------------------------

    def handle(self, method: str, path: str, headers, rfile) -> Response:
        """One request → one response. ``headers`` is mapping-like;
        ``rfile`` a binary stream positioned at the body. A read is
        timed as the ``http.request`` stage into the server's profiler
        (``http.wait`` is its wait for the flush)."""
        raw, _, query = path.partition("?")
        endpoint = raw.rstrip("/") or "/"
        if endpoint not in ("/topk", "/query"):
            return self._handle(method, endpoint, query, headers, rfile)
        with obs.attach(None, self.server.profiler), \
                obs.stage("http.request"):
            return self._handle(method, endpoint, query, headers, rfile)

    def _handle(self, method: str, endpoint: str, query: str, headers,
                rfile) -> Response:
        t0 = self.clock()
        body = _Body(rfile, headers)
        try:
            resp = self._route(method, endpoint, headers, body, query)
        except Exception as e:  # a handler crash must not kill the conn
            resp = _json_error(
                500, f"internal error: {type(e).__name__}: {e}")
        # Early errors (401/404/405/429) answer before reading the body;
        # drain it so leftover bytes don't corrupt the next keep-alive
        # request. An undecodable body forces a fresh connection instead.
        if not body.drain():
            resp.headers["Connection"] = "close"
        with obs.contained():
            self.metrics.inc(
                "service_requests_total",
                {"endpoint": endpoint.lstrip("/") or "root",
                 "status": str(resp.status)},
                help="Requests by endpoint and HTTP status")
            self.metrics.observe(
                "service_request_latency_seconds", self.clock() - t0,
                {"endpoint": endpoint.lstrip("/") or "root"},
                help="End-to-end in-service latency")
        return resp

    def _route(self, method: str, endpoint: str, headers,
               body: "_Body", query: str = "") -> Response:
        if endpoint == "/healthz":
            # Liveness: always 200 while the process serves — read-only
            # degradation is a readiness problem (/readyz), not death.
            return Response(200, {"status": "ok",
                                  "records": self.num_records,
                                  "inflight": self.server.inflight,
                                  "writable": not self.server.read_only})
        if endpoint == "/readyz":
            if self.server.read_only:
                return Response(503, {
                    "status": "read-only",
                    "reason": self.server.read_only_reason})
            return Response(200, {"status": "ok"})
        if endpoint == "/metrics":
            return Response(200, self.metrics.render(),
                            content_type="text/plain; version=0.0.4")
        if endpoint in ("/debug/traces", "/debug/slow"):
            if not self.auth.allows(headers):
                return _json_error(401, "missing or invalid auth token")
            if method != "GET":
                return _json_error(405, f"{endpoint} is GET-only")
            return self._debug(endpoint)
        if endpoint in ("/admin/retire", "/admin/snapshot"):
            # Admin paths: behind auth, outside the rate limits — window
            # retirement and snapshots must work while the service sheds.
            if not self.auth.allows(headers):
                return _json_error(401, "missing or invalid auth token")
            if method != "POST":
                return _json_error(405, f"{endpoint} is POST-only")
            try:
                if endpoint == "/admin/snapshot":
                    return self._snapshot()
                return self._retire(json.loads(b"".join(body) or b"{}"))
            except Overloaded as e:
                return _json_error(429, str(e),
                                   **{"Retry-After": f"{e.retry_after:.3f}"})
            except ReadOnly as e:
                return _json_error(503, f"read-only: {e}")
            except RuntimeError as e:
                return _json_error(400, f"bad request: {e}")
            except (ValueError, KeyError, TypeError,
                    json.JSONDecodeError) as e:
                return _json_error(400, f"bad request: {e}")
        if endpoint not in ("/query", "/topk", "/ingest", "/debug/explain"):
            return _json_error(404, f"no route {endpoint!r}")
        if method != "POST":
            return _json_error(405, f"{endpoint} is POST-only")
        if not self.auth.allows(headers):
            return _json_error(401, "missing or invalid auth token")
        if not self.bucket.allow():
            ra = self.bucket.retry_after()
            return _json_error(429, "rate limit exceeded",
                               **{"Retry-After": f"{ra:.3f}"})
        tid = tenant_id(headers)
        if not self.tenant_buckets.allow(tid):
            self.metrics.inc(
                "service_ratelimited_total", {"tenant": tid},
                help="Per-tenant rate-limit rejections")
            ra = self.tenant_buckets.retry_after(tid)
            return _json_error(429, "tenant rate limit exceeded",
                               **{"Retry-After": f"{ra:.3f}"})
        try:
            if endpoint == "/ingest":
                return self._ingest(headers, body, query)
            payload = json.loads(b"".join(body) or b"{}")
            if endpoint == "/debug/explain":
                payload = dict(payload)
                payload["explain"] = True
                return self._query(payload)
            if endpoint == "/query":
                return self._query(payload)
            return self._topk(payload)
        except Overloaded as e:
            return _json_error(429, str(e),
                               **{"Retry-After": f"{e.retry_after:.3f}"})
        except ReadOnly as e:
            # Graceful degradation: mutations 503 once the data dir
            # fails; queries never reach here (they don't mutate).
            return _json_error(503, f"read-only: {e}")
        except (ValueError, KeyError, json.JSONDecodeError) as e:
            return _json_error(400, f"bad request: {e}")

    @staticmethod
    def _deadline_s(body) -> float | None:
        ms = body.get("deadline_ms")
        return None if ms is None else float(ms) / 1e3

    def _debug(self, endpoint: str) -> Response:
        srv = self.server
        if endpoint == "/debug/traces":
            if srv.tracer is None:
                return Response(200, {"traceEvents": [],
                                      "displayTimeUnit": "ms"})
            return Response(200, srv.tracer.chrome_trace())
        return Response(200, {"threshold_s": srv.slow_threshold,
                              "count": srv.slow_queries,
                              "recent": list(srv.slow_log)})

    def _query(self, body) -> Response:
        explain = bool(body.get("explain", False))
        p = self.server.submit_query(
            np.asarray(body["q"], np.int64),
            threshold=float(body.get("threshold", 0.5)),
            deadline=self._deadline_s(body), explain=explain)
        with obs.stage("http.wait"):
            res = self.server.result(p, timeout=self.result_timeout)
        out = {"rid": p.rid,
               "hits": np.asarray(res["hits"]).tolist(),
               "expired": p.expired}
        if explain:
            out["explain"] = res.get("explain")
        return Response(200, out)

    def _topk(self, body) -> Response:
        p = self.server.submit_topk(
            np.asarray(body["q"], np.int64), k=int(body.get("k", 10)),
            deadline=self._deadline_s(body))
        with obs.stage("http.wait"):
            res = self.server.result(p, timeout=self.result_timeout)
        return Response(200, {
            "rid": p.rid,
            "ids": np.asarray(res["topk_ids"]).tolist(),
            "scores": [float(s) for s in res["topk_scores"]],
            "expired": p.expired})

    def _ingest(self, headers, body: "_Body", query: str = "") -> Response:
        qs = parse_qs(query)
        epoch = int(qs["epoch"][0]) if qs.get("epoch") else None
        idem_key = headers.get("Idempotency-Key") or None
        ctype = headers.get("Content-Type", "")
        if "json" in ctype and "ndjson" not in ctype:
            payload = json.loads(b"".join(body) or b"{}")
            if epoch is None and payload.get("epoch") is not None:
                epoch = int(payload["epoch"])
            if idem_key is None and payload.get("idempotency_key"):
                idem_key = str(payload["idempotency_key"])
            lines = (json.dumps(r).encode()
                     for r in payload.get("records", []))
        else:
            lines = _iter_lines(body)
        if epoch is not None and \
                not getattr(self.server.index, "windowed", False):
            raise ValueError(
                "epoch requires a windowed index "
                "(build with api.build_index(..., windowed=True))")
        # Chunk-granular dedupe: the request key derives one key per
        # chunk (``key#i`` — chunking is deterministic for a given body
        # and ingest_chunk), so a retried stream skips exactly the
        # chunks the first attempt already committed, even when that
        # attempt died mid-stream.
        chunk: list[np.ndarray] = []
        pending = []
        total = 0

        def submit(c):
            idem = f"{idem_key}#{len(pending)}" if idem_key else None
            return self._submit_ingest_chunk(c, epoch, idem=idem)

        for line in lines:
            if not line.strip():
                continue
            chunk.append(np.asarray(json.loads(line), np.int64))
            if len(chunk) >= self.ingest_chunk:
                pending.append(submit(chunk))
                total += len(chunk)
                chunk = []
        if chunk:
            pending.append(submit(chunk))
            total += len(chunk)
        deduped = 0
        for p in pending:
            res = self.server.result(p, timeout=self.result_timeout)
            deduped += bool(res.get("deduped"))
        out = {"ingested": total, "chunks": len(pending)}
        if epoch is not None:
            out["epoch"] = epoch
        if idem_key is not None:
            out["deduped_chunks"] = deduped
        return Response(200, out)

    def _retire(self, body) -> Response:
        """Drop window epochs strictly below ``body["before"]``."""
        if not getattr(self.server.index, "windowed", False):
            raise ValueError(
                "/admin/retire requires a windowed index "
                "(build with api.build_index(..., windowed=True))")
        p = self.server.submit_retire(int(body["before"]))
        res = self.server.result(p, timeout=self.result_timeout)
        return Response(200, {"rid": p.rid, "retired": res["retired"],
                              "epochs": res["epochs"]})

    def _snapshot(self) -> Response:
        p = self.server.submit_snapshot()
        res = self.server.result(p, timeout=self.result_timeout)
        return Response(200, {"rid": p.rid, **res})

    def _submit_ingest_chunk(self, chunk, epoch: int | None = None,
                             idem: str | None = None):
        """Admit one chunk, waiting out transient overload: an ingest
        stream mid-flight can't be half-dropped, so backpressure here is
        wait-and-retry, bounded by ``result_timeout``."""
        give_up = time.monotonic() + self.result_timeout
        while True:
            try:
                return self.server.submit_ingest(chunk, epoch=epoch,
                                                 idem=idem)
            except Overloaded as e:
                if time.monotonic() >= give_up:
                    raise
                time.sleep(min(e.retry_after, 0.05))


# -- stdlib HTTP plumbing ----------------------------------------------------


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    app: ServiceApp = None          # set by make_http_server

    def _respond(self):
        try:
            resp = self.app.handle(self.command, self.path, self.headers,
                                   self.rfile)
        except Exception as e:      # a handler crash must not kill the conn
            resp = _json_error(500, f"internal error: {type(e).__name__}: {e}")
        self.send_response(resp.status)
        self.send_header("Content-Type", resp.content_type)
        self.send_header("Content-Length", str(len(resp.body)))
        for k, v in resp.headers.items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(resp.body)

    do_GET = do_POST = do_PUT = _respond

    def log_message(self, fmt, *args):  # noqa: A003 - quiet by default
        if getattr(self.app, "verbose", False):
            super().log_message(fmt, *args)


def make_http_server(app: ServiceApp, host: str = "127.0.0.1",
                     port: int = 0) -> ThreadingHTTPServer:
    """Bind a threading HTTP server (port 0 = ephemeral; the bound port
    is ``httpd.server_address[1]``). Caller owns ``serve_forever`` /
    ``shutdown`` and the flush worker's ``start()``/``stop()``."""
    handler = type("BoundHandler", (_Handler,), {"app": app})
    return _HTTPServer((host, port), handler)


class _HTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    # The listen backlog. socketserver's default of 5 resets connections
    # opened in a burst (64 clients connecting at once), and each reset
    # is a request that never gets an answer.
    request_queue_size = 1024


class ServiceHandle:
    """In-process service for tests and the load harness: flush worker +
    HTTP listener on an ephemeral port, context-managed."""

    def __init__(self, app: ServiceApp, host: str = "127.0.0.1",
                 port: int = 0):
        self.app = app
        self.httpd = make_http_server(app, host, port)
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name="http-listener",
            daemon=True)

    @property
    def address(self) -> tuple[str, int]:
        return self.httpd.server_address[:2]

    def __enter__(self) -> "ServiceHandle":
        self.app.server.start()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self.httpd.shutdown()
        self.httpd.server_close()
        self.app.server.stop()
        return False
