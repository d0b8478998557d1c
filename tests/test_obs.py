"""Observability tests: tracer/span primitives under a fake clock,
Chrome trace export, no-op cost when nothing is attached, stage
profiler + cost drift, histogram quantile edge cases, per-tenant rate
limiting, and — the load-bearing contract — explain-vs-reality parity:
the numbers ``batch_query(..., explain=True)`` reports must equal the
planner's independently recomputed internals, across engines and
backends."""

import json

import numpy as np
import pytest

from repro import api
from repro.data.synth import generate_dataset, make_query_workload
from repro.obs import (
    NULL_TRACER, CostDrift, NullTracer, StageProfiler, Tracer, attach,
    current_trace, stage)
from repro.planner import candidates_for
from repro.planner.plan import probe_hits_per_query
from repro.service import (
    AsyncSketchServer, ServiceApp, ServiceClient, ServiceError,
    ServiceHandle, TenantBuckets, parse_prometheus, tenant_id)
from repro.serving import Histogram


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- tracer / span primitives ------------------------------------------------


def test_trace_span_nesting_and_durations():
    clk = FakeClock()
    tracer = Tracer(capacity=4, clock=clk)
    tr = tracer.begin("query", rid=7)
    clk.t = 1.0
    with tr.span("plan") as outer:
        clk.t = 1.5
        with tr.span("probe", shards=2) as inner:
            clk.t = 2.0
        outer.set(hits=3)
        clk.t = 3.0
    clk.t = 4.0
    tr.end()

    assert tr.root.duration == pytest.approx(4.0)
    names = {s.name: s for s in tr.spans}
    assert names["plan"].duration == pytest.approx(2.0)
    assert names["probe"].duration == pytest.approx(0.5)
    assert names["probe"].parent is names["plan"]
    assert names["plan"].parent is tr.root
    assert names["plan"].attrs["hits"] == 3
    assert names["probe"].attrs["shards"] == 2
    assert tr.root.attrs["rid"] == 7


def test_tracer_ring_buffer_evicts_oldest():
    clk = FakeClock()
    tracer = Tracer(capacity=3, clock=clk)
    for i in range(5):
        tracer.begin(f"t{i}").end()
    recent = tracer.recent()
    assert [t.root.name for t in recent] == ["t2", "t3", "t4"]
    tracer.clear()
    assert tracer.recent() == []


def test_trace_end_is_idempotent():
    clk = FakeClock()
    tracer = Tracer(capacity=4, clock=clk)
    tr = tracer.begin("q")
    tr.end()
    tr.end()
    assert len(tracer.recent()) == 1


def test_chrome_trace_export_shape():
    clk = FakeClock(10.0)
    tracer = Tracer(capacity=4, clock=clk)
    tr = tracer.begin("query", rid=1)
    clk.t = 10.001
    with tr.span("score"):
        clk.t = 10.003
    tr.end()
    doc = tracer.chrome_trace()
    # Must round-trip through JSON (the /debug/traces body).
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"query", "score"}
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float))
        assert e["dur"] >= 0
    score = next(e for e in evs if e["name"] == "score")
    assert score["dur"] == pytest.approx(2000.0)  # 2ms in µs


def test_null_tracer_and_unattached_stage_are_inert():
    tr = NullTracer().begin("anything", rid=1)
    with tr.span("x") as s:
        s.set(a=1)
    tr.end()
    assert NULL_TRACER.chrome_trace() == {"traceEvents": [],
                                          "displayTimeUnit": "ms"}
    assert current_trace() is None
    # No attach → the shared no-op context; sync passes values through.
    with stage("planner.probe", foo=1) as s:
        assert s.sync(42) == 42
        s.set(bar=2)


def test_attach_routes_stages_to_trace_and_profiler():
    clk = FakeClock()
    tracer = Tracer(capacity=4, clock=clk)
    prof = StageProfiler()
    tr = tracer.begin("batch")
    with attach(tr, prof):
        assert current_trace() is tr
        clk.t = 0.5
        with stage("planner.probe", shards=1) as s:
            clk.t = 0.75
            s.set(hits=9)
    tr.end()
    span = next(s for s in tr.spans if s.name == "planner.probe")
    assert span.duration == pytest.approx(0.25)
    assert span.attrs["hits"] == 9
    assert "planner.probe" in prof.stages()
    assert prof.snapshot()["planner.probe"]["count"] == 1
    # Attached region is scoped: gone after the with block.
    assert current_trace() is None


def test_add_span_with_explicit_times():
    clk = FakeClock()
    tracer = Tracer(capacity=2, clock=clk)
    tr = tracer.begin("req")
    tr.add_span("queue_wait", 1.0, 3.5, depth=2)
    tr.end()
    s = next(x for x in tr.spans if x.name == "queue_wait")
    assert s.duration == pytest.approx(2.5)
    assert s.parent is tr.root


# -- stage profiler + cost drift ---------------------------------------------


def test_stage_profiler_histograms_and_snapshot():
    prof = StageProfiler()
    for v in (0.001, 0.002, 0.004):
        prof.observe("serve.score", v)
    prof.observe("serve.topk", 0.01)
    snap = prof.snapshot()
    assert snap["serve.score"]["count"] == 3
    assert snap["serve.score"]["mean_s"] == pytest.approx(0.00233, rel=0.1)
    fams = prof.histograms()
    assert set(fams) == {'stage="serve.score"', 'stage="serve.topk"'}
    assert all(isinstance(h, Histogram) for h in fams.values())


def test_cost_drift_self_fits_and_converges():
    d = CostDrift()
    assert d.drift == 0.0                  # nothing measurable yet
    for _ in range(8):
        d.record(1000.0, 0.01)             # perfectly consistent flushes
    assert d.drift == pytest.approx(1.0, rel=0.05)
    # Garbage inputs never poison the estimate.
    d.record(float("nan"), 0.01)
    d.record(1000.0, 0.0)
    assert np.isfinite(d.drift)


# -- histogram quantile edge cases (satellite fix) ---------------------------


def test_histogram_quantile_empty_is_zero():
    h = Histogram()
    assert h.quantile(0.0) == 0.0
    assert h.quantile(0.5) == 0.0
    assert h.quantile(1.0) == 0.0


def test_histogram_quantile_rejects_out_of_range():
    h = Histogram()
    h.observe(0.01)
    with pytest.raises(ValueError):
        h.quantile(-0.1)
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_quantile_single_observation():
    h = Histogram()
    h.observe(0.0123)
    lo, hi = h.quantile(0.0), h.quantile(1.0)
    # q=0 → lower edge of the occupied bucket, q=1 → its upper edge,
    # and the observation sits between them.
    assert lo <= 0.0123 <= hi
    assert lo > 0.0                        # not the empty underflow bucket
    for q in (0.25, 0.5, 0.9):
        assert lo <= h.quantile(q) <= hi


def test_histogram_quantile_extremes_bracket_observations():
    h = Histogram()
    vals = [0.001, 0.005, 0.02, 0.1, 0.4]
    for v in vals:
        h.observe(v)
    assert h.quantile(0.0) <= min(vals)
    assert h.quantile(1.0) >= max(vals)
    qs = [h.quantile(q) for q in np.linspace(0, 1, 11)]
    assert all(a <= b + 1e-12 for a, b in zip(qs, qs[1:]))  # monotonic


# -- per-tenant rate limiting ------------------------------------------------


def test_tenant_id_header_forms_and_hashing():
    assert tenant_id({}) == "anon"
    a = tenant_id({"X-Auth-Token": "secret-a"})
    b = tenant_id({"Authorization": "Bearer secret-b"})
    assert a != b and a != "anon"
    assert "secret-a" not in a and len(a) == 12      # hashed, never raw
    # Same credential through either header → same tenant.
    assert tenant_id({"Authorization": "Bearer secret-a"}) == a


def test_tenant_buckets_isolate_tenants():
    clk = FakeClock()
    tb = TenantBuckets(rate=1.0, burst=2, clock=clk)
    assert tb.allow("a") and tb.allow("a")
    assert not tb.allow("a")               # a exhausted its burst
    assert tb.allow("b")                   # b unaffected
    assert tb.retry_after("a") > 0.0
    clk.t = 5.0                            # refill
    assert tb.allow("a")


def test_tenant_buckets_disabled_and_eviction():
    assert TenantBuckets(rate=None).allow("anyone")
    clk = FakeClock()
    tb = TenantBuckets(rate=1.0, burst=1, clock=clk, max_tenants=2)
    assert tb.allow("a") and tb.allow("b")
    assert tb.allow("c")                   # evicts a (LRU)
    assert tb.allow("a")                   # a restarts with a full burst


def test_http_tenant_rate_limit_429_and_metric():
    from tests.test_service import StubIndex

    srv = AsyncSketchServer(StubIndex(), max_batch=4, max_wait=0.002)
    app = ServiceApp(srv, tenant_rate_limit=1e-6, tenant_burst=2)
    with ServiceHandle(app) as h:
        a = ServiceClient(*h.address, token="tenant-a")
        b = ServiceClient(*h.address, token="tenant-b")
        a.query(np.arange(3), 0.5)
        a.query(np.arange(3), 0.5)         # a's burst exhausted
        with pytest.raises(ServiceError) as ei:
            a.query(np.arange(3), 0.5)
        assert ei.value.status == 429 and ei.value.retry_after > 0
        b.query(np.arange(3), 0.5)         # b unaffected
        text = a.metrics_text()
        pm = parse_prometheus(text)
        tid = tenant_id({"Authorization": "Bearer tenant-a"})
        assert pm[f'service_ratelimited_total{{tenant="{tid}"}}'] == 1.0
        assert "tenant-a" not in text      # raw credential never exported
        a.close(), b.close()


# -- explain vs planner reality ----------------------------------------------

EXPLAIN_THRESHOLD = 0.8


@pytest.fixture(scope="module", params=["gbkmv", "gkmv", "kmv"])
def explain_setup(request):
    engine = request.param
    recs = generate_dataset(m=150, n_elems=4000, alpha_freq=0.9,
                            alpha_size=1.5, seed=3)
    budget = sum(len(r) for r in recs) // 5
    built = {bk: api.get_engine(engine).build(recs, budget, seed=0,
                                              backend=bk)
             for bk in ("numpy", "jnp")}
    return engine, built, make_query_workload(recs, 6, seed=1)


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_explain_pruned_matches_planner_internals(explain_setup, backend):
    engine, built, queries = explain_setup
    idx = built[backend]
    t = EXPLAIN_THRESHOLD
    hits, ex = idx.batch_query(queries, t, plan="pruned", explain=True)
    plain = idx.batch_query(queries, t, plan="pruned")
    assert len(ex) == len(queries)
    for h, p in zip(hits, plain):          # explain must not change answers
        np.testing.assert_array_equal(h, p)

    # Recompute the planner's internals independently and require the
    # explain numbers to match them exactly.
    _, hash_rows, bit_rows, q_sizes = idx._plan_queries(queries)
    post = idx._sketch_pack().postings()
    probe = probe_hits_per_query(post, hash_rows, bit_rows)
    for g, e in enumerate(ex):
        assert e["plan"] == "pruned"
        assert e["engine"] == engine and e["backend"] == backend
        assert e["threshold"] == pytest.approx(t)
        assert e["hits"] == len(hits[g])
        assert e["probe_hits"] == int(probe[g])
        c = candidates_for(post, hash_rows[g], bit_rows[g], t,
                           int(q_sizes[g]))
        assert e["candidates"] == len(c.rec_ids)
        assert e["pruned"] == c.pruned
        assert e["blocks"] == c.blocks
        assert e["skipped_blocks"] == c.skipped_blocks
        assert e["merge_hits"] == c.hits
        cost = e["cost"]
        assert cost["est_pruned"] > 0
        assert cost["predicted_units"] == pytest.approx(cost["est_pruned"])
        assert cost["measured_seconds"] > 0


@pytest.mark.parametrize("backend", ["numpy", "jnp"])
def test_explain_dense_has_no_planner_fields(explain_setup, backend):
    engine, built, queries = explain_setup
    idx = built[backend]
    hits, ex = idx.batch_query(queries, 0.5, plan="dense", explain=True)
    for g, e in enumerate(ex):
        assert e["plan"] == "dense"
        assert e["hits"] == len(hits[g])
        for key in ("probe_hits", "candidates", "blocks", "skipped_blocks",
                    "tau", "ub_max"):
            assert key not in e
        assert e["cost"]["predicted_units"] == pytest.approx(
            e["cost"]["est_dense"])


def test_explain_single_query_form():
    recs = generate_dataset(m=60, n_elems=2000, alpha_freq=1.0,
                            alpha_size=2.0, seed=4)
    idx = api.get_engine("gbkmv").build(
        recs, sum(len(r) for r in recs) // 5, backend="numpy")
    hits, e = idx.query(recs[0], 0.5, explain=True)
    assert isinstance(e, dict) and e["plan"] in ("dense", "pruned")
    np.testing.assert_array_equal(hits, idx.query(recs[0], 0.5))
    assert idx.last_explain is not None


# -- live HTTP: explain + debug endpoints ------------------------------------


@pytest.fixture(scope="module")
def live_obs_service():
    from repro.launch.mesh import make_mesh
    from repro.sketchindex import ShardedIndex

    recs = generate_dataset(m=100, n_elems=3000, alpha_freq=1.1,
                            alpha_size=2.0, seed=0)
    index = api.get_engine("gbkmv").build(
        recs, sum(len(r) for r in recs) // 5)
    sharded = ShardedIndex(index, make_mesh((1, 1), ("data", "model")))
    srv = AsyncSketchServer(sharded, max_batch=4, max_wait=0.002,
                            tracer=Tracer(capacity=32), slow_threshold=0.0)
    with ServiceHandle(ServiceApp(srv)) as h:
        yield h, sharded, make_query_workload(recs, 4, seed=1)


def test_http_query_explain_round_trip(live_obs_service):
    h, sharded, queries = live_obs_service
    cli = ServiceClient(*h.address)
    hits, e = cli.query_explain(queries[0], EXPLAIN_THRESHOLD)
    np.testing.assert_array_equal(
        hits, sharded.batch_query([queries[0]], EXPLAIN_THRESHOLD)[0])
    assert e["plan"] in ("dense", "pruned")
    assert e["threshold"] == pytest.approx(EXPLAIN_THRESHOLD)
    assert "cost" in e and e["cost"]["measured_seconds"] > 0
    # Plain queries never carry the explain payload.
    status, raw, _ = cli.request(
        "POST", "/query",
        body=json.dumps({"q": queries[0].tolist(), "threshold": 0.5}
                        ).encode())
    assert status == 200 and "explain" not in json.loads(raw)
    # /debug/explain forces it regardless of the body.
    status, raw, _ = cli.request(
        "POST", "/debug/explain",
        body=json.dumps({"q": queries[0].tolist(), "threshold": 0.5}
                        ).encode())
    assert status == 200 and json.loads(raw)["explain"]["plan"] in (
        "dense", "pruned")
    cli.close()


def test_http_debug_traces_chrome_loadable(live_obs_service):
    h, _, queries = live_obs_service
    cli = ServiceClient(*h.address)
    cli.query(queries[0], 0.5)
    doc = cli.debug_traces()
    evs = doc["traceEvents"]
    assert evs and doc["displayTimeUnit"] == "ms"
    for e in evs:                          # chrome trace-event contract
        assert e["ph"] == "X"
        for k in ("name", "ts", "dur", "pid", "tid"):
            assert k in e
    names = {e["name"] for e in evs}
    assert "query" in names and "flush.execute" in names
    assert "queue_wait" in names and "execute" in names
    cli.close()


def test_http_slow_log_and_obs_metrics(live_obs_service):
    h, _, queries = live_obs_service
    cli = ServiceClient(*h.address)
    cli.query(queries[0], 0.5)
    slow = cli.debug_slow()                # threshold 0.0 → everything slow
    assert slow["count"] >= 1 and slow["recent"]
    entry = slow["recent"][0]
    for k in ("rid", "kind", "latency_s", "queue_wait_s", "plan"):
        assert k in entry
    pm = parse_prometheus(cli.metrics_text())
    assert pm["service_slow_queries_total"] >= 1
    assert "service_cost_model_drift" in pm
    stage_counts = [k for k in pm
                    if k.startswith("service_stage_latency_seconds_count")]
    assert any('stage="flush.execute"' in k for k in stage_counts)
    cli.close()


def test_debug_endpoints_require_auth():
    from tests.test_service import StubIndex

    srv = AsyncSketchServer(StubIndex(), max_batch=4, max_wait=0.002,
                            tracer=Tracer(capacity=8))
    with ServiceHandle(ServiceApp(srv, auth_token="hunter2")) as h:
        anon = ServiceClient(*h.address)
        for path in ("/debug/traces", "/debug/slow"):
            status, _, _ = anon.request("GET", path)
            assert status == 401
        status, _, _ = anon.request("POST", "/debug/explain",
                                    body=b"{}")
        assert status == 401
        authed = ServiceClient(*h.address, token="hunter2")
        assert authed.debug_traces()["displayTimeUnit"] == "ms"
        status, _, _ = authed.request("POST", "/debug/traces")
        assert status == 405               # GET-only
        anon.close(), authed.close()
