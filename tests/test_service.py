"""Service-layer tests: histogram/stats primitives, the async flush loop
under an injectable clock (no threads — fully deterministic), middleware,
metrics rendering, and live in-process HTTP round-trips asserting the
service answers bit-identically to the direct index calls."""

import http.client
import json
import math
import socket
import threading

import numpy as np
import pytest

from repro.serving import BatchStats, Histogram
from repro.service import (
    AsyncSketchServer, AuthToken, Overloaded, ServiceApp, ServiceClient,
    ServiceError, ServiceHandle, TokenBucket, parse_prometheus)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class StubIndex:
    """Minimal serve_batch/insert protocol with a call log, so flush
    ordering and plan selection are observable without jax."""

    def __init__(self):
        self.records = [np.arange(5)]
        self.log = []                   # ("serve", n, plan) | ("insert", n)

    @property
    def num_records(self):
        return len(self.records)

    def serve_batch(self, queries, thresholds, k, plan="auto"):
        self.log.append(("serve", len(queries), plan))
        thresholds = np.broadcast_to(np.asarray(thresholds), (len(queries),))
        out = []
        for q, t in zip(queries, thresholds):
            hits = (np.asarray([], np.int64) if math.isinf(t)
                    else np.asarray(sorted(np.asarray(q).tolist())[:2]))
            out.append({"hits": hits,
                        "topk_ids": np.arange(k, dtype=np.int64),
                        "topk_scores": np.linspace(1.0, 0.5, max(k, 1),
                                                   dtype=np.float32)})
        return out

    def insert(self, records):
        self.log.append(("insert", len(records)))
        self.records.extend(records)


def make_server(**kw):
    clk = FakeClock()
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait", 0.01)
    srv = AsyncSketchServer(StubIndex(), clock=clk, **kw)
    return srv, srv.index, clk


# -- histogram / stats primitives -------------------------------------------


def test_histogram_observe_and_quantile():
    h = Histogram(bounds=[0.1, 1.0, 10.0])
    h.observe_many([0.05] * 50 + [0.5] * 50)
    assert h.count == 100 and h.sum == pytest.approx(27.5)
    # p25 sits mid-first-bucket, p75 mid-second (linear interpolation).
    assert h.quantile(0.25) == pytest.approx(0.05)
    assert h.quantile(0.75) == pytest.approx(0.55)
    h.observe(100.0)                    # overflow bucket clamps to last bound
    assert h.quantile(1.0) == pytest.approx(10.0)
    assert h.mean == pytest.approx(127.5 / 101)


def test_histogram_merge_and_prometheus_text():
    a, b = Histogram(bounds=[1.0, 2.0]), Histogram(bounds=[1.0, 2.0])
    a.observe(0.5)
    b.observe(1.5)
    b.observe(99.0)
    a.merge(b)
    lines = a.to_prometheus("lat", 'kind="q"')
    assert 'lat_bucket{kind="q",le="1"} 1' in lines
    assert 'lat_bucket{kind="q",le="2"} 2' in lines
    assert 'lat_bucket{kind="q",le="+Inf"} 3' in lines
    assert any(ln.startswith('lat_count{kind="q"} 3') for ln in lines)
    with pytest.raises(ValueError):
        a.merge(Histogram(bounds=[5.0]))


def test_batch_stats_reasons_and_wait_histogram():
    s = BatchStats()
    s.record_batch([0.001, 0.002], "full")
    s.record_batch([0.010], "deadline")
    s.record_batch([3.0], "expired")
    assert (s.flushes_full, s.flushes_deadline, s.flushes_expired) == (1, 1, 1)
    assert s.flushes == 3 and s.served == 4
    assert s.mean_batch == pytest.approx(4 / 3)
    assert s.queue_wait_hist.count == 4
    assert s.queue_wait_hist.quantile(0.99) > 1.0
    # Ingest flushes count separately and never skew device occupancy.
    s.record_batch([0.5, 0.5, 0.5], "ingest")
    assert s.flushes_ingest == 1 and s.flushes == 3
    assert s.mean_batch == pytest.approx(4 / 3)
    assert s.queue_wait_hist.count == 7 and s.served == 7


def test_histogram_snapshot_consistent_under_concurrent_writes():
    """A /metrics scrape must never see counts torn against sum."""
    h = Histogram(bounds=[1.0, 2.0])
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            h.observe(1.5)

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(300):
            counts, total = h.snapshot()
            assert total == pytest.approx(int(counts.sum()) * 1.5)
    finally:
        stop.set()
        t.join()


# -- async flush loop (deterministic: fake clock, no worker thread) ---------


def test_async_server_flush_on_full_then_deadline():
    srv, stub, clk = make_server()
    p1 = srv.submit_query(np.arange(4), threshold=0.5)
    p2 = srv.submit_query(np.arange(8), threshold=0.5)
    p3 = srv.submit_query(np.arange(2), threshold=0.5)
    assert srv.inflight == 3
    assert srv.step() == 2              # full batch pops immediately
    assert srv.stats.flushes_full == 1
    assert p1.done.is_set() and p2.done.is_set() and not p3.done.is_set()
    assert srv.step() == 0              # straggler not old enough
    clk.t += 0.02
    assert srv.step() == 1              # aged past max_wait → deadline flush
    assert srv.stats.flushes_deadline == 1 and p3.done.is_set()
    np.testing.assert_array_equal(srv.result(p1, timeout=0)["hits"], [0, 1])
    assert srv.inflight == 0


def test_async_server_expired_requests_take_dense_fallback():
    srv, stub, clk = make_server(max_wait=10.0, default_deadline=1.0)
    p = srv.submit_query(np.arange(6), threshold=0.5)
    assert srv.step() == 0              # young: neither full nor expired
    clk.t += 2.0                        # now past its deadline
    assert srv.step() == 1
    assert p.expired and srv.expired_served == 1
    assert srv.stats.flushes_expired == 1
    assert stub.log == [("serve", 1, "dense")]
    np.testing.assert_array_equal(srv.result(p, timeout=0)["hits"], [0, 1])


def test_async_server_overload_sheds_with_retry_hint():
    srv, _, _ = make_server(max_inflight=2, max_wait=10.0)
    srv.submit_query(np.arange(3))
    srv.submit_query(np.arange(3))
    with pytest.raises(Overloaded) as ei:
        srv.submit_query(np.arange(3))
    assert ei.value.retry_after > 0
    assert srv.shed == 1 and srv.inflight == 2


def test_async_server_ingest_is_a_fifo_barrier():
    srv, stub, clk = make_server(max_batch=4)
    q1 = srv.submit_query(np.arange(3))
    ing = srv.submit_ingest([np.arange(10, 14), np.arange(20, 26)])
    q2 = srv.submit_query(np.arange(3))
    srv.drain()
    # Kinds never mix: serve(q1) → insert → serve(q2), in admission order.
    assert stub.log == [("serve", 1, srv.plan), ("insert", 2),
                        ("serve", 1, srv.plan)]
    assert srv.result(ing, timeout=0) == {"ingested": 2}
    assert srv.records_ingested == 2 and stub.num_records == 3
    assert q1.done.is_set() and q2.done.is_set()
    # Ingest accounting stays off the device-flush metrics: two serve
    # flushes in flush_latency_hist, the insert in ingest_latency_hist.
    assert srv.stats.flushes_ingest == 1 and srv.stats.flushes == 2
    assert srv.stats.flush_latency_hist.count == 2
    assert srv.stats.ingest_latency_hist.count == 1


def test_concurrent_submissions_mint_unique_rids():
    """HTTP handler threads submit concurrently; duplicate rids would
    hand two requests each other's results via the execute_batch map."""
    srv = AsyncSketchServer(StubIndex(), max_batch=64, max_wait=10.0,
                            max_inflight=10_000)
    pendings, lock = [], threading.Lock()

    def submit_many():
        mine = [srv.submit_query(np.arange(3)) for _ in range(200)]
        with lock:
            pendings.extend(mine)

    threads = [threading.Thread(target=submit_many) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rids = [p.rid for p in pendings]
    assert len(rids) == 1600 and len(set(rids)) == 1600


def test_async_server_mixed_topk_and_query_batch():
    srv, stub, clk = make_server(max_batch=4, max_wait=0.0)
    q = srv.submit_query(np.arange(4), threshold=0.5)
    t = srv.submit_topk(np.arange(4), k=3)
    assert srv.step() == 2              # one batch, max_wait=0 flushes now
    assert stub.log == [("serve", 2, srv.plan)]
    assert srv.result(q, timeout=0)["hits"].size == 2
    res = srv.result(t, timeout=0)
    assert len(res["topk_ids"]) == 3    # truncated to the request's k
    assert t.threshold == math.inf      # topk never contributes hits


def test_async_server_worker_thread_round_trip():
    srv = AsyncSketchServer(StubIndex(), max_batch=4, max_wait=0.002)
    srv.start()
    try:
        p = srv.submit_query(np.arange(5), threshold=0.5)
        np.testing.assert_array_equal(srv.result(p)["hits"], [0, 1])
    finally:
        srv.stop()


# -- middleware -------------------------------------------------------------


def test_token_bucket_rate_and_refill():
    clk = FakeClock()
    b = TokenBucket(rate=2.0, burst=2, clock=clk)
    assert b.allow() and b.allow() and not b.allow()
    assert b.retry_after() > 0
    clk.t += 0.5                        # refills one token at 2/s
    assert b.allow() and not b.allow()
    assert TokenBucket(rate=None).allow()   # disabled bucket always allows


def test_auth_token_header_forms():
    auth = AuthToken("s3cret")
    assert auth.allows({"Authorization": "Bearer s3cret"})
    assert auth.allows({"X-Auth-Token": "s3cret"})
    assert not auth.allows({"Authorization": "Bearer wrong"})
    assert not auth.allows({})
    assert AuthToken(None).allows({})   # auth disabled


def test_metrics_render_parse_round_trip():
    from repro.service import Metrics
    m = Metrics()
    m.inc("req_total", {"endpoint": "query", "status": "200"}, help="reqs")
    m.inc("req_total", {"endpoint": "query", "status": "200"})
    m.set_gauge("depth", lambda: 7, help="live gauge")
    m.observe("lat_seconds", 0.005, {"endpoint": "query"})
    text = m.render()
    assert "# TYPE req_total counter" in text
    assert "# TYPE lat_seconds histogram" in text
    pm = parse_prometheus(text)
    assert pm['req_total{endpoint="query",status="200"}'] == 2.0
    assert pm["depth"] == 7.0
    assert pm['lat_seconds_count{endpoint="query"}'] == 1.0
    assert m.get_counter("req_total",
                         {"endpoint": "query", "status": "200"}) == 2


# -- live HTTP (stub index: no jax in the hot path) -------------------------


def serve_stub(**app_kw):
    srv = AsyncSketchServer(StubIndex(), max_batch=4, max_wait=0.002)
    return ServiceHandle(ServiceApp(srv, **app_kw))


def test_http_auth_rejection_and_success():
    with serve_stub(auth_token="hunter2") as h:
        anon = ServiceClient(*h.address)
        assert anon.healthz()["status"] == "ok"       # healthz stays open
        with pytest.raises(ServiceError) as ei:
            anon.query(np.arange(3), 0.5)
        assert ei.value.status == 401
        authed = ServiceClient(*h.address, token="hunter2")
        np.testing.assert_array_equal(authed.query(np.arange(3), 0.5), [0, 1])
        anon.close(), authed.close()


def test_http_rate_limit_429():
    with serve_stub(rate_limit=1e-6, burst=2) as h:
        cli = ServiceClient(*h.address)
        cli.query(np.arange(3), 0.5)
        cli.query(np.arange(3), 0.5)    # burst exhausted
        with pytest.raises(ServiceError) as ei:
            cli.query(np.arange(3), 0.5)
        assert ei.value.status == 429 and ei.value.retry_after > 0
        cli.close()


def test_http_overload_shed_429_and_metric():
    with serve_stub() as h:
        h.app.server.max_inflight = 0   # every admission sheds
        cli = ServiceClient(*h.address)
        with pytest.raises(ServiceError) as ei:
            cli.query(np.arange(3), 0.5)
        assert ei.value.status == 429 and ei.value.retry_after > 0
        pm = parse_prometheus(cli.metrics_text())
        assert pm["service_shed_total"] >= 1.0
        assert pm['service_requests_total{endpoint="query",status="429"}'] == 1
        cli.close()


def test_http_routing_errors():
    with serve_stub() as h:
        cli = ServiceClient(*h.address)
        status, _, _ = cli.request("GET", "/nope")
        assert status == 404
        status, _, _ = cli.request("GET", "/query")
        assert status == 405
        status, body, _ = cli.request("POST", "/query", body=b"not json")
        assert status == 400 and b"bad request" in body
        cli.close()


def test_http_early_error_drains_body_for_keepalive():
    """401/404/429 answer before reading the POST body; the unread bytes
    must be drained or they'd be parsed as the next request line on the
    persistent connection."""
    with serve_stub(auth_token="tok") as h:
        conn = http.client.HTTPConnection(*h.address, timeout=10)
        body = json.dumps({"q": list(range(500))}).encode()
        for path, want in (("/query", 401), ("/nope", 404), ("/query", 401)):
            conn.request("POST", path, body=body,
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            assert r.status == want
            r.read()
        # Same connection, no reconnect: still a clean request stream.
        conn.request("GET", "/healthz")
        r = conn.getresponse()
        assert r.status == 200 and json.loads(r.read())["status"] == "ok"
        conn.close()


def test_http_rate_limited_connection_stays_usable():
    with serve_stub(rate_limit=1e-6, burst=1) as h:
        conn = http.client.HTTPConnection(*h.address, timeout=10)
        payload = json.dumps({"q": [0, 1, 2]}).encode()
        hdrs = {"Content-Type": "application/json"}
        conn.request("POST", "/query", body=payload, headers=hdrs)
        assert conn.getresponse().read() is not None    # burst spent
        conn.request("POST", "/query", body=payload, headers=hdrs)
        r = conn.getresponse()
        assert r.status == 429 and float(r.getheader("Retry-After")) > 0
        r.read()
        conn.request("GET", "/healthz")                 # same connection
        r = conn.getresponse()
        assert r.status == 200 and json.loads(r.read())["status"] == "ok"
        conn.close()


def test_http_burst_of_new_connections_all_answer():
    """64 clients connecting at once, as a closed-loop load does when it
    starts: each connection is accepted and its read answered, none reset
    by a short listen backlog."""
    n = 64
    start = threading.Barrier(n)
    statuses = []

    def client(address):
        conn = http.client.HTTPConnection(*address, timeout=30)
        start.wait()
        try:
            conn.request("POST", "/query", body=json.dumps(
                {"q": [0, 1, 2], "threshold": 0.5}).encode(),
                headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            r.read()
            statuses.append(r.status)
        except OSError as e:
            statuses.append(repr(e))
        finally:
            conn.close()

    with serve_stub() as h:
        threads = [threading.Thread(target=client, args=(h.address,))
                   for _ in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert statuses == [200] * n


def test_http_chunked_extensions_and_trailers():
    """Chunk-size lines with long extensions and trailer headers after
    the last chunk are valid chunked framing and must decode."""
    with serve_stub(ingest_chunk=8) as h:
        rec = json.dumps([1, 2, 3]).encode() + b"\n"
        ext = b";name=" + b"x" * 200            # size line far beyond 64B
        chunked = ((b"%x" % len(rec)) + ext + b"\r\n" + rec + b"\r\n"
                   + b"0\r\nx-trailer: v\r\n\r\n")
        req = (b"POST /ingest HTTP/1.1\r\nHost: t\r\n"
               b"Content-Type: application/x-ndjson\r\n"
               b"Transfer-Encoding: chunked\r\n\r\n" + chunked)
        with socket.create_connection(h.address, timeout=10) as s:
            s.sendall(req)
            r = http.client.HTTPResponse(s)
            r.begin()
            assert r.status == 200
            assert json.loads(r.read()) == {"ingested": 1, "chunks": 1}


def test_http_streaming_ingest_chunks():
    with serve_stub(ingest_chunk=2) as h:
        cli = ServiceClient(*h.address)
        out = cli.ingest([np.arange(i, i + 4) for i in range(5)])
        assert out == {"ingested": 5, "chunks": 3}    # 2+2+1 flush chunks
        assert cli.healthz()["records"] == 6          # stub started with 1
        out = cli.ingest([np.arange(3)], stream=False)
        assert out == {"ingested": 1, "chunks": 1}
        pm = parse_prometheus(cli.metrics_text())
        assert pm["service_records_ingested_total"] == 6.0
        cli.close()


class _FaultyAnnotation:
    """A profiler annotation that raises on enter or, every other time,
    on exit; ``raised`` counts both."""

    raised = 0
    made = 0
    lock = threading.Lock()

    def __init__(self, name):
        with self.lock:
            type(self).made += 1
            self.on_enter = type(self).made % 2 == 1

    def _raise(self):
        with self.lock:
            type(self).raised += 1
        raise RuntimeError("annotation fault")

    def __enter__(self):
        if self.on_enter:
            self._raise()
        return self

    def __exit__(self, *exc):
        self._raise()


def test_observability_faults_never_cost_an_answer(monkeypatch):
    """Every profiler annotation and every request's bookkeeping raises:
    a full flush of 16 top-k reads over HTTP still answers all 16 with
    200 and the direct index's ids, and ``service_obs_faults_total``
    counts each raise."""
    from repro import api, obs
    from repro.data.synth import generate_dataset
    from repro.launch.mesh import make_mesh
    from repro.obs import trace as obs_trace
    from repro.sketchindex import ShardedIndex

    recs = generate_dataset(m=100, n_elems=3000, alpha_freq=1.1,
                            alpha_size=2.0, seed=0)
    index = api.get_engine("gbkmv").build(
        recs, sum(len(r) for r in recs) // 5)
    sharded = ShardedIndex(index, make_mesh((1, 1), ("data", "model")))
    queries = recs[:16]
    want = [sharded.topk(q, 5)[0] for q in queries]
    srv = AsyncSketchServer(sharded, max_batch=16, max_wait=30.0,
                            default_deadline=None)
    app = ServiceApp(srv)
    finish_raised = []

    def finish(*a, **kw):
        finish_raised.append(1)
        raise RuntimeError("bookkeeping fault")

    monkeypatch.setattr(obs_trace, "_annotation", _FaultyAnnotation)
    monkeypatch.setattr(AsyncSketchServer, "_finish_request", finish)
    before = obs.faults()
    answers = [None] * len(queries)
    with ServiceHandle(app) as h:
        def read(i):
            cli = ServiceClient(*h.address)
            answers[i] = cli.request("POST", "/topk", body=json.dumps(
                {"q": queries[i].tolist(), "k": 5}).encode())
            cli.close()

        threads = [threading.Thread(target=read, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    for (status, raw, _), ids in zip(answers, want):
        assert status == 200, raw
        np.testing.assert_array_equal(json.loads(raw)["ids"], ids)
    assert srv.stats.flushes_full == 1 and srv.stats.total_batch == 16
    assert len(finish_raised) == 16
    faults = parse_prometheus(app.metrics.render())["service_obs_faults_total"]
    assert faults - before == obs.faults() - before \
        == _FaultyAnnotation.raised + len(finish_raised)


# -- live HTTP against the real index: bit-identical parity -----------------


@pytest.fixture(scope="module")
def live_service():
    from repro import api
    from repro.data.synth import generate_dataset, make_query_workload
    from repro.launch.mesh import make_mesh
    from repro.sketchindex import ShardedIndex

    recs = generate_dataset(m=100, n_elems=3000, alpha_freq=1.1,
                            alpha_size=2.0, seed=0)
    index = api.get_engine("gbkmv").build(
        recs, sum(len(r) for r in recs) // 5)
    sharded = ShardedIndex(index, make_mesh((1, 1), ("data", "model")))
    srv = AsyncSketchServer(sharded, max_batch=4, max_wait=0.002)
    with ServiceHandle(ServiceApp(srv)) as h:
        yield h, sharded, make_query_workload(recs, 8, seed=1)


def test_http_query_parity_with_direct(live_service):
    h, sharded, queries = live_service
    cli = ServiceClient(*h.address)
    direct = sharded.batch_query(queries, 0.5)
    for q, d in zip(queries, direct):
        np.testing.assert_array_equal(cli.query(q, 0.5), d)
    cli.close()


def test_http_topk_parity_with_direct(live_service):
    h, sharded, queries = live_service
    cli = ServiceClient(*h.address)
    for q in queries[:4]:
        ids, scores = cli.topk(q, 5)
        d_ids, d_scores = sharded.topk(q, 5)
        np.testing.assert_array_equal(ids, d_ids)
        np.testing.assert_array_equal(scores, d_scores.astype(np.float32))
    cli.close()


def test_http_ingest_then_query_sees_new_record(live_service):
    h, sharded, _ = live_service
    cli = ServiceClient(*h.address)
    before = cli.healthz()["records"]
    new = np.arange(9000, 9040)
    assert cli.ingest([new]) == {"ingested": 1, "chunks": 1}
    assert cli.healthz()["records"] == before + 1
    # The new record contains itself; with the tight test budget the KMV
    # estimate is well under 1, so probe at a low threshold. The load-
    # bearing assertion is parity: HTTP == direct on the mutated index.
    hits = cli.query(new, 0.2)
    assert before in hits.tolist()      # its id == old record count
    np.testing.assert_array_equal(hits, sharded.batch_query([new], 0.2)[0])
    cli.close()


def test_http_metrics_shape(live_service):
    h, _, _ = live_service
    cli = ServiceClient(*h.address)
    pm = parse_prometheus(cli.metrics_text())
    for key in ("service_flush_total{reason=\"full\"}",
                "service_queue_wait_seconds_count",
                "service_flush_latency_seconds_sum",
                "service_mean_batch_occupancy", "service_inflight",
                "arena_sketch_nbytes"):
        assert key in pm, key
    assert pm["arena_sketch_nbytes"] > 0
    assert pm["service_queue_wait_seconds_count"] >= 1
    cli.close()

