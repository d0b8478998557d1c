"""Threshold reads over a NETFLIX-law corpus served through ServiceApp on
every route: the dense sweep, the fused device route and the host
pruned route answer alike and as the plain GB-KMV reference does; each
served batch counts its route once, and the planner's probe counts are
the ones ``probe_block_stats`` gives."""

from __future__ import annotations

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

from repro.service import (AsyncSketchServer, ServiceApp, ServiceClient,
                           ServiceHandle)
from repro.service.metrics import parse_prometheus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
THRESHOLDS = (0.5, 0.7, 0.9)
BATCH = 8


def _reference_module():
    path = os.path.join(ROOT, "chipbench", "reference", "gbkmv.py")
    spec = importlib.util.spec_from_file_location("gbkmv_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def corpus():
    """2,000 records of the NETFLIX law (universe 17,770, alpha1 1.14,
    alpha2 4.95), an index at a tenth of the elements, and the reads:
    the first records, at thresholds 0.5, 0.7, 0.9 in turn."""
    from repro import api
    from repro.data.synth import generate_dataset

    recs = generate_dataset(m=2000, n_elems=17770, alpha_freq=1.14,
                            alpha_size=4.95, size_min=150, size_max=2000,
                            seed=11)
    budget = sum(len(r) for r in recs) // 10
    index = api.get_engine("gbkmv").build(recs, budget, r="auto", seed=0,
                                          backend="jnp")
    queries = recs[:BATCH]
    thr = [THRESHOLDS[i % 3] for i in range(BATCH)]
    return recs, index, budget, queries, thr


def _sharded(index, backend="jnp"):
    from repro.launch.mesh import make_mesh
    from repro.sketchindex import ShardedIndex

    return ShardedIndex(index, make_mesh((1, 1), ("data", "model")),
                        backend=backend)


def _serve(sharded, plan, queries, thr):
    """One flush of every read, sent at once over HTTP; returns the
    server, the app and each read's (status, hits)."""
    srv = AsyncSketchServer(sharded, max_batch=len(queries), max_wait=30.0,
                            default_deadline=None, plan=plan)
    app = ServiceApp(srv)
    out = [None] * len(queries)
    with ServiceHandle(app) as h:
        def read(i):
            cli = ServiceClient(*h.address)
            status, raw, _ = cli.request("POST", "/query", body=json.dumps(
                {"q": queries[i].tolist(), "threshold": thr[i]}).encode())
            out[i] = (status, json.loads(raw).get("hits") if status == 200
                      else raw)
            cli.close()

        threads = [threading.Thread(target=read, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    return srv, app, out


ROUTES = [("auto", "jnp"), ("dense", "jnp"), ("pruned", "jnp"),
          ("pruned", "numpy")]


@pytest.fixture(scope="module")
def served(corpus):
    _, index, _, queries, thr = corpus
    return {(plan, backend): _serve(_sharded(index, backend), plan, queries,
                                    thr)
            for plan, backend in ROUTES}


def test_every_route_answers_as_the_reference(corpus, served):
    recs, index, budget, queries, thr = corpus
    ref_mod = _reference_module()
    ids = np.concatenate(recs)
    offsets = np.concatenate([[0], np.cumsum([len(r) for r in recs])])
    ref = ref_mod.GBKMV(ids, offsets, universe=17770, budget=budget,
                        buffer_bits=int(index.core.buffer_bits), seed=0)
    answers = {key: [h for _, h in out] for key, (_, _, out) in
               served.items()}
    for key, (_, _, out) in served.items():
        assert all(status == 200 for status, _ in out), (key, out)
        assert answers[key] == answers[("dense", "jnp")], key
    for q, t, hits in zip(queries, thr, answers[("dense", "jnp")]):
        s = ref.scores(q)
        diff = np.setxor1d(np.asarray(hits, np.int64), ref_mod.hits(s, t))
        # Only records within a few float32 ulps of t may differ.
        assert np.all(np.abs(s[diff] - t) <= 4 * np.spacing(np.float32(t)))
    assert sum(len(h) for h in answers[("dense", "jnp")]) > 0


def test_each_batch_counts_its_route_once(corpus, served):
    from repro.planner import plan as planner_plan

    _, index, _, queries, _ = corpus
    for (plan, backend), (srv, app, _) in served.items():
        assert srv.stats.flushes == 1 and srv.stats.total_batch == BATCH
        tallies = srv.profiler.tallies()
        routes = {k: v for k, v in tallies.items() if k.startswith("route.")}
        path = srv.index.last_plan.path
        want = ("dense" if path == "dense" else
                "fused" if backend == "jnp" else "host_pruned")
        assert routes == {f"route.{want}": (1, float(BATCH))}, (plan, routes)
        metrics = parse_prometheus(app.metrics.render())
        for route in ("dense", "fused", "host_pruned"):
            key = f'service_read_batches_total{{route="{route}"}}'
            assert metrics[key] == float(route == want)
        if plan == "dense":
            assert not any(k.startswith("probe.") for k in tallies)
            continue
        # The probe's counts, recomputed apart from the served flush.
        sharded = srv.index
        _, hash_rows, bit_rows, _ = sharded._plan_queries(queries)
        posts, _ = sharded._shard_postings()
        tb, td, bb = planner_plan.probe_block_stats(posts, hash_rows,
                                                    bit_rows)
        entries = planner_plan.probe_hits(posts, hash_rows, bit_rows)
        assert tallies["probe.tail_blocks"] == (1, float(tb))
        assert tallies["probe.dense_blocks"] == (1, float(td))
        assert tallies["probe.buf_blocks"] == (1, float(bb))
        assert tallies["probe.entries"] == (1, float(entries))
        assert metrics['planner_probe_blocks_total{kind="tail"}'] == tb
        assert metrics["planner_probe_entries_total"] == entries
        assert metrics["planner_routed_batches_total"] == 1
        # The decision has its own span, apart from the probe.
        stages = srv.profiler.stages()
        assert stages["planner.choose"].count == 1
        assert stages["planner.probe"].count == 1
        assert 'stage="route.' not in "".join(srv.profiler.histograms())


def test_auto_route_is_one_of_the_forced_ones(served):
    """``auto`` takes the dense sweep or the fused route, whichever the
    cost model ranks cheaper, and answers as both do."""
    srv, _, _ = served[("auto", "jnp")]
    assert srv.index.last_plan.path in ("dense", "pruned")
    assert srv.index.last_plan.reason.startswith("auto:")


def test_a_raise_in_the_tallies_costs_no_answer(corpus, served,
                                                monkeypatch):
    """Every route and probe tally raises: each read of a pruned flush
    still answers 200 with the same hits, and each raise is counted."""
    from repro import obs
    from repro.obs.profile import StageProfiler

    _, index, _, queries, thr = corpus
    raised = []

    def tally(self, name, amount):
        raised.append(name)
        raise RuntimeError("tally fault")

    monkeypatch.setattr(StageProfiler, "tally", tally)
    before = obs.faults()
    _, _, out = _serve(_sharded(index), "pruned", queries, thr)
    assert [s for s, _ in out] == [200] * BATCH
    assert [h for _, h in out] == [h for _, h in
                                   served[("pruned", "jnp")][2]]
    assert sorted(raised) == sorted(
        ["route.fused", "probe.tail_blocks", "probe.dense_blocks",
         "probe.buf_blocks", "probe.entries"])
    assert obs.faults() - before == len(raised)


def _query_cols(lengths):
    """The batch's widest live query row, rounded up to the compare's
    chunk."""
    from repro.core.estimators import QUERY_CHUNK

    return -(-int(np.max(lengths)) // QUERY_CHUNK) * QUERY_CHUNK


def test_each_dense_sweep_tallies_its_query_columns(corpus, served):
    """A dense flush tallies one sweep of the batch's widest live query
    row, rounded up to the chunk, and /metrics exports both numbers; a
    pruned flush sweeps nothing."""
    from repro.sketchindex.distributed import batch_queries

    _, _, _, queries, _ = corpus
    for (plan, backend), (srv, app, _) in served.items():
        tallies = srv.profiler.tallies()
        metrics = parse_prometheus(app.metrics.render())
        if srv.index.last_plan.path != "dense":
            assert "sweep.query_cols" not in tallies, plan
            assert metrics["dense_sweeps_total"] == 0
            continue
        qp = batch_queries(srv.index.host, queries)
        cols = _query_cols(qp.lengths)
        assert 0 < cols < qp.values.shape[1]
        assert tallies["sweep.query_cols"] == (1, float(cols))
        assert metrics["dense_sweeps_total"] == 1
        assert metrics["dense_sweep_query_cols_total"] == cols


def test_topk_sweeps_tally_their_query_columns(corpus):
    """Top-k batches of one and of three queries through ``serve_batch``:
    two sweeps, each of its own batch's widest live row."""
    from repro import obs
    from repro.obs.profile import StageProfiler
    from repro.sketchindex.distributed import batch_queries

    recs, index, _, _, _ = corpus
    sharded = _sharded(index)
    batches = [[recs[10]], [recs[11], recs[12], recs[13]]]
    prof = StageProfiler()
    with obs.attach(profiler=prof):
        for qs in batches:
            out = sharded.serve_batch(qs, np.inf, k=5)
            assert all(len(o["topk_ids"]) == 5 for o in out)
    cols = [_query_cols(batch_queries(sharded.host, qs).lengths)
            for qs in batches]
    assert cols[0] > 0
    assert prof.tallies()["sweep.query_cols"] == (2, float(sum(cols)))


#: Batch kinds of the hits step: (k, thresholds). ``inf`` is a top-k
#: rider's threshold (``submit_topk``), or a query read's own +inf. Each
#: batch ends with an empty query.
HIT_BATCHES = {
    "topk": (5, [np.inf] * 5),
    "mixed": (5, [np.inf, 0.5, np.inf, 0.7, 0.9]),
    "threshold": (0, [0.5, 0.7, 0.9, 0.5, 0.3]),
    "query_inf": (0, [0.5, np.inf, 0.9, 0.7, np.inf]),
}
HIT_ROUTES = {"dense": ("dense", "jnp"), "fused": ("pruned", "jnp"),
              "host_pruned": ("pruned", "numpy")}


def _hit_batch(recs, kind):
    k, thr = HIT_BATCHES[kind]
    return k, list(recs[20:24]) + [np.zeros(0, np.int64)], thr


@pytest.mark.parametrize("route", list(HIT_ROUTES))
@pytest.mark.parametrize("kind", list(HIT_BATCHES))
def test_hits_skip_columns_whose_threshold_is_inf(corpus, kind, route):
    """Every route answers a +inf column with no hits and every finite
    column as the full mask over the sweep's scores does, bit for bit;
    top-k answers do not depend on the riders' thresholds."""
    from repro.planner import prune

    recs, index, _, _, _ = corpus
    k, queries, thr = _hit_batch(recs, kind)
    plan, backend = HIT_ROUTES[route]
    sharded = _sharded(index, backend)
    out = sharded.serve_batch(queries, thr, k, plan=plan)
    assert sharded.last_plan.path == plan
    # Every dense score is finite, the empty query's too: a column at
    # +inf can have no hits.
    scores = np.asarray(sharded._dense_scores(sharded._sketch(queries)))
    assert np.all(np.isfinite(scores))
    want = prune.threshold_hits_packed(scores[:sharded.num_records], thr)
    for t, w, o in zip(thr, want, out):
        assert o["hits"].dtype == np.int64
        if np.isinf(t):
            assert len(o["hits"]) == 0
        else:
            np.testing.assert_array_equal(o["hits"], w)
    if kind != "topk":
        assert sum(len(o["hits"]) for o in out) > 0
    tops = sharded.serve_batch(queries, None, k, plan=plan)
    for o, w in zip(out, tops):
        np.testing.assert_array_equal(o["topk_ids"], w["topk_ids"])
        np.testing.assert_array_equal(o["topk_scores"], w["topk_scores"])


def test_topk_batch_builds_no_hit_mask(corpus, monkeypatch):
    """A dense batch whose thresholds are all +inf neither compares nor
    packs a mask, yet opens its ``serve.hits`` stage."""
    from repro import obs
    from repro.obs.profile import StageProfiler
    from repro.planner import route

    def no_mask(*_):
        raise AssertionError("hit mask built for a top-k batch")

    monkeypatch.setattr(route, "threshold_hits_packed", no_mask)
    monkeypatch.setattr(route, "threshold_mask", no_mask)
    recs, index, _, _, _ = corpus
    k, queries, thr = _hit_batch(recs, "topk")
    prof = StageProfiler()
    with obs.attach(profiler=prof):
        out = _sharded(index).serve_batch(queries, thr, k)
    assert all(len(o["hits"]) == 0 and len(o["topk_ids"]) == k for o in out)
    assert prof.stages()["serve.hits"].count == 1


def test_dense_batches_tally_their_masked_columns(corpus, served):
    """``serve.hits_cols`` counts each dense batch that reaches the hits
    step and sums the columns whose mask it built: none of a top-k
    batch, the finite ones of a mixed batch, all of a threshold batch.
    /metrics exports the sum; a pruned flush builds no dense mask."""
    from repro import obs
    from repro.obs.profile import StageProfiler

    recs, index, _, _, _ = corpus
    sharded = _sharded(index)
    prof = StageProfiler()
    with obs.attach(profiler=prof):
        for kind in ("topk", "mixed", "threshold"):
            k, queries, thr = _hit_batch(recs, kind)
            sharded.serve_batch(queries, thr, k, plan="dense")
    assert prof.tallies()["serve.hits_cols"] == (3, 0.0 + 3 + 5)
    for (plan, _), (srv, app, _) in served.items():
        metrics = parse_prometheus(app.metrics.render())
        dense = srv.index.last_plan.path == "dense"
        assert metrics["dense_hits_cols_total"] == (BATCH if dense else 0)


@pytest.fixture(scope="module")
def doors(corpus):
    """The three serving doors over the same records: a ShardedIndex on a
    1x1 mesh, the api GB-KMV index itself, and a WindowManager holding
    the records in one epoch."""
    from repro import api

    recs, index, budget, _, _ = corpus
    windowed = api.get_engine("gbkmv").build(recs, budget, r="auto", seed=0,
                                             backend="jnp", windowed=True)
    return {"sharded": _sharded(index), "api": index, "windowed": windowed}


@pytest.mark.parametrize("k", [0, 5])
@pytest.mark.parametrize("plan", ["auto", "dense", "pruned"])
def test_every_door_routes_and_answers_alike(corpus, doors, plan, k):
    """Per-query thresholds mixed over 0.5/0.7/0.9: every door takes the
    same route and gives the same hits, top-k ids and top-k scores."""
    _, _, _, queries, thr = corpus
    served = {name: door.serve_batch(queries, thr, k, plan=plan)
              for name, door in doors.items()}
    paths = {name: door.last_plan.path for name, door in doors.items()}
    assert len(set(paths.values())) == 1, paths
    if plan != "auto":
        assert paths["api"] == plan
    want = served["sharded"]
    assert sum(len(r["hits"]) for r in want) > 0
    assert all(len(r["topk_ids"]) == k for r in want)
    for name, out in served.items():
        for w, g in zip(want, out):
            for key in ("hits", "topk_ids", "topk_scores"):
                np.testing.assert_array_equal(g[key], w[key],
                                              err_msg=f"{name} {key}")
