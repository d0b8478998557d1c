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
        _, hash_rows, bit_rows, _ = planner_plan.gbkmv_plan_queries(
            sharded.host, queries)
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
