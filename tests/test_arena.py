"""Sketch arena: one packed store from engines to planner to serving.

Covers the arena's ownership contract (postings shared across layers,
incremental maintenance through inserts — global and per-shard), the
arena serialization format (round-trips with postings; legacy
postings-less files still load), device residency of the pruned query
path (transfer-guarded), and pruned-vs-dense top-k parity.
"""

import dataclasses
import functools

import jax
import numpy as np
import pytest

from repro import api, planner
from repro.core.arena import SketchArena
from repro.data.synth import generate_dataset, make_query_workload

ENGINES = ("gbkmv", "gkmv", "kmv")
BACKENDS = ("numpy", "jnp", "pallas")


@pytest.fixture(scope="module")
def corpus():
    recs = generate_dataset(m=120, n_elems=3000, alpha_freq=1.0,
                            alpha_size=1.6, seed=10)
    total = sum(len(r) for r in recs)
    queries = make_query_workload(recs, 5, seed=11)
    rng = np.random.default_rng(12)
    queries += [rng.choice(3000, size=s, replace=False) for s in (6, 50)]
    return recs, total, queries


@pytest.fixture(scope="module")
def gb_index(corpus):
    recs, total, _ = corpus
    return api.get_engine("gbkmv").build(recs, int(total * 0.1))


# ---------------------------------------------------------------------------
# arena ownership: every layer views ONE store
# ---------------------------------------------------------------------------


def test_builds_return_arenas(corpus):
    recs, total, _ = corpus
    for engine in ENGINES:
        idx = api.get_engine(engine).build(recs, int(total * 0.1))
        assert isinstance(idx._sketch_pack(), SketchArena)


def test_postings_shared_between_host_and_sharded(gb_index):
    from repro.sketchindex import ShardedIndex

    arena = gb_index._sketch_pack()
    post = gb_index._sketch_pack().postings()
    assert arena._post is post                    # owned by the arena
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = ShardedIndex(gb_index, mesh)
    assert sh.host.sketches is arena              # same store, no copy
    posts, offs = sh._shard_postings()
    assert offs[0] == 0
    # Shard slices live on the arena too (served to any future viewer).
    posts2, _ = arena.shard_postings(mesh.devices.size)
    assert posts2 is posts


def test_device_mirrors_cached(gb_index):
    arena = gb_index._sketch_pack()
    assert arena.device_pack() is arena.device_pack()
    assert arena.device_postings() is arena.device_postings()


def test_dataclasses_replace_resets_caches(gb_index):
    arena = gb_index._sketch_pack()
    arena.postings()
    clone = dataclasses.replace(arena)
    assert isinstance(clone, SketchArena) and clone._post is None


# ---------------------------------------------------------------------------
# incremental maintenance: global + per-shard postings across insert
# ---------------------------------------------------------------------------


def test_sharded_insert_maintains_shard_postings(corpus):
    from repro.sketchindex import ShardedIndex

    recs, total, queries = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.06))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = ShardedIndex(idx, mesh)
    posts_before, offs_before = sh._shard_postings()   # build the cache
    extra = generate_dataset(m=40, n_elems=3000, alpha_freq=1.0,
                             alpha_size=1.6, seed=13)
    sh.insert(extra)
    assert sh.stats.tau_retightens >= 1                # deletion exercised
    arena = sh.host.sketches
    bounds, posts = arena._shard_posts                 # maintained, not None
    assert bounds[-1][1] == arena.num_records
    # Incrementally-maintained slices == fresh rebuilds on the same cuts.
    for (lo, hi), post in zip(bounds, posts):
        fresh = planner.build_postings(arena._column_view(lo, hi))
        assert planner.postings_equal(post, fresh)
    # And the planner still answers identically through them.
    for t in (0.4, 0.8):
        dense = sh.batch_query(queries, t, plan="dense")
        pruned = sh.batch_query(queries, t, plan="pruned")
        for d, p in zip(dense, pruned):
            np.testing.assert_array_equal(d, p)


def test_shard_slices_update_without_retighten(corpus):
    recs, total, _ = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 10))
    arena = idx._sketch_pack()
    arena.shard_postings(3)
    idx.insert([np.asarray([1, 2, 3]), np.asarray([7, 8])])
    assert idx.stats.tau_retightens == 0
    arena = idx._sketch_pack()                         # post-insert arena
    bounds, posts = arena._shard_posts
    for (lo, hi), post in zip(bounds, posts):
        fresh = planner.build_postings(arena._column_view(lo, hi))
        assert planner.postings_equal(post, fresh)


# ---------------------------------------------------------------------------
# serialization: arena round-trip + legacy compatibility
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
def test_arena_save_load_roundtrip_with_postings(corpus, tmp_path, engine):
    recs, total, queries = corpus
    idx = api.get_engine(engine).build(recs, int(total * 0.1))
    idx.batch_query(queries, 0.6, plan="pruned")       # builds postings
    path = str(tmp_path / f"{engine}.npz")
    idx.save(path)
    loaded = api.load_index(path)
    # Postings travel with the arena: no rebuild on first pruned query.
    assert loaded._sketch_pack()._post is not None
    assert planner.postings_equal(loaded._sketch_pack()._post,
                                  idx._sketch_pack()._post)
    for t in (0.4, 0.8):
        for a, b in zip(idx.batch_query(queries, t),
                        loaded.batch_query(queries, t)):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("backend", BACKENDS)
def test_arena_roundtrip_across_backends(corpus, tmp_path, backend):
    recs, total, queries = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.1),
                                        backend=backend)
    idx.batch_query(queries, 0.6, plan="pruned")
    path = str(tmp_path / f"gb_{backend}.npz")
    idx.save(path)
    loaded = api.load_index(path)
    assert loaded.backend == backend
    for t in (0.5, 0.9):
        dense = loaded.batch_query(queries, t, plan="dense")
        pruned = loaded.batch_query(queries, t, plan="pruned")
        want = idx.batch_query(queries, t, plan="dense")
        for d, p, w in zip(dense, pruned, want):
            np.testing.assert_array_equal(d, p)
            np.testing.assert_array_equal(d, w)


def test_v2_flat_postings_npz_still_loads(corpus, tmp_path, gb_index):
    """Files written by the v2 (flat-CSR postings) format re-encode into
    blocks on load and answer identically — with the same blocked
    structure a fresh rebuild produces."""
    recs, total, queries = corpus
    gb_index.batch_query(queries, 0.6, plan="pruned")   # build postings
    core = gb_index.core
    s = core.sketches
    post = gb_index._sketch_pack()._post
    path = str(tmp_path / "v2_flat.npz")
    np.savez_compressed(                    # the exact v2 field set
        path, engine="gbkmv", tau=np.uint32(core.tau),
        top_elems=np.asarray(core.top_elems, np.int64),
        seed=np.int64(core.seed), buffer_bits=np.int64(core.buffer_bits),
        budget=np.int64(-1), arena_version=np.int64(2),
        values=np.asarray(s.values), lengths=np.asarray(s.lengths),
        thresh=np.asarray(s.thresh), buf=np.asarray(s.buf),
        sizes=np.asarray(s.sizes),
        post_keys=post.keys, post_offsets=post.offsets,
        post_rec_ids=post.rec_ids, post_buf_offsets=post.buf_offsets,
        post_buf_rec_ids=post.buf_rec_ids, post_tau=np.uint32(post.tau))
    loaded = api.load_index(path)
    # postings traveled, re-encoded
    assert loaded._sketch_pack()._post is not None
    assert planner.postings_equal(loaded._sketch_pack()._post, post)
    for t in (0.4, 0.8):
        for a, b in zip(gb_index.batch_query(queries, t),
                        loaded.batch_query(queries, t, plan="pruned")):
            np.testing.assert_array_equal(a, b)


def test_legacy_packed_npz_still_loads(corpus, tmp_path, gb_index):
    """Files written by the v1 (postings-less) format keep loading."""
    recs, total, queries = corpus
    path = str(tmp_path / "legacy.npz")
    core = gb_index.core
    s = core.sketches
    np.savez_compressed(                    # the exact pre-arena field set
        path, engine="gbkmv", tau=np.uint32(core.tau),
        top_elems=np.asarray(core.top_elems, np.int64),
        seed=np.int64(core.seed), buffer_bits=np.int64(core.buffer_bits),
        budget=np.int64(-1),
        values=np.asarray(s.values), lengths=np.asarray(s.lengths),
        thresh=np.asarray(s.thresh), buf=np.asarray(s.buf),
        sizes=np.asarray(s.sizes))
    loaded = api.load_index(path)
    assert isinstance(loaded._sketch_pack(), SketchArena)
    # postings lazy, not persisted
    assert loaded._sketch_pack()._post is None
    for a, b in zip(gb_index.batch_query(queries, 0.6),
                    loaded.batch_query(queries, 0.6)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# device residency: candidate-gen → score → packed threshold, no transfers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_pruned_path_device_resident(corpus, backend):
    """The acceptance contract: between staging and the packed result
    there is NO host transfer — probe, block decode, scoring, the packed
    threshold words, AND top-k all run under jax's transfer guard. No
    host header probe feeds the device program: the guard starts right
    after staging."""
    from repro.planner import device as planner_device

    recs, total, queries = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.1),
                                        backend=backend)
    t = 0.7
    want = idx.batch_query(queries, t, plan="pruned")  # warmup: compile
    idx.topk(queries[0], 8, plan="pruned")             # warmup: topk jit
    wt_ids, wt_s = idx.topk(queries[0], 8, plan="dense")
    arena = idx._sketch_pack()
    m = arena.num_records
    qp, _, _, _ = idx._plan_queries(queries)
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp, t)
    with jax.transfer_guard("disallow"):
        words = planner_device.fused_mask_words(
            dpost, dpack, sq, m=m, backend=backend)
        assert not isinstance(words, np.ndarray)       # still on device
    mask = planner_device.unpack_hit_words(words, m)[:, : qp.num_records]
    got = planner.prune.mask_to_hits(mask)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    # top-k: same residency contract on the fused top-k head (fresh
    # staging — the previous call donated the query blob).
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp)
    with jax.transfer_guard("disallow"):
        vals, ids = planner_device.fused_topk_scores(
            dpost, dpack, sq, k=8, m=m, backend=backend)
        assert not isinstance(vals, np.ndarray)
    np.testing.assert_array_equal(np.asarray(ids)[0], wt_ids)
    np.testing.assert_allclose(np.asarray(vals)[0], wt_s, rtol=1e-6)


def test_device_route_is_taken(corpus):
    """batch_query with a device backend actually uses the device path:
    host candidate accounting stays None (nothing was materialized on
    host); the probe breakdown lives on the plan instead."""
    recs, total, queries = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.1),
                                        backend="jnp")
    idx.batch_query(queries, 0.7, plan="pruned")
    assert idx.last_candidate_sizes is None
    per = idx.last_plan.per_query_hits
    assert per is not None and len(per) == len(queries)
    assert int(per.sum()) == idx.last_plan.hits
    # The numpy backend takes the host path and does account candidates.
    idx_np = api.get_engine("gbkmv").build(recs, int(total * 0.1),
                                           backend="numpy")
    idx_np.batch_query(queries, 0.7, plan="pruned")
    assert idx_np.last_candidate_sizes is not None
    assert len(idx_np.last_candidate_sizes) == len(queries)


# ---------------------------------------------------------------------------
# planner-aware top-k: pruned == dense, engines × backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_pruned_topk_matches_dense(corpus, engine, backend):
    recs, total, queries = corpus
    idx = api.get_engine(engine).build(recs, int(total * 0.1),
                                       backend=backend)
    for k in (1, 5, 37, 2 * len(recs)):
        for q in queries[:4]:
            di, ds = idx.topk(q, k, plan="dense")
            pi, ps = idx.topk(q, k, plan="pruned")
            ai, as_ = idx.topk(q, k)                    # auto
            np.testing.assert_array_equal(di, pi)
            np.testing.assert_array_equal(ds, ps)
            np.testing.assert_array_equal(di, ai)
            np.testing.assert_array_equal(ds, as_)


def test_pruned_topk_small_chunks_early_stop(gb_index, corpus):
    """Chunked scoring with the running k-th threshold stops early yet
    stays exact (tiny chunks force multiple rounds + the cutoff)."""
    _, _, queries = corpus
    q = queries[0]
    qp, hash_rows, bit_rows, sizes = gb_index._plan_queries([np.asarray(q)])
    for k in (1, 3, 10):
        want = gb_index.topk(q, k, plan="dense")
        got = planner.pruned_topk(
            gb_index._sketch_pack().postings(), hash_rows[0], bit_rows[0],
            int(sizes[0]), k, functools.partial(gb_index._score_pairs, qp),
            gb_index.num_records, chunk=4)
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])


def test_topk_deterministic_tie_break():
    """Equal scores rank by ascending record id on every path."""
    recs = [np.asarray([1, 2, 3, 4]) for _ in range(12)]   # identical sets
    idx = api.get_engine("gbkmv").build(recs, budget=200)
    q = np.asarray([1, 2, 3, 4])
    for k in (3, 7):
        di, ds = idx.topk(q, k, plan="dense")
        pi, ps = idx.topk(q, k, plan="pruned")
        np.testing.assert_array_equal(di, np.arange(k))
        np.testing.assert_array_equal(di, pi)
        np.testing.assert_array_equal(ds, ps)


def test_pruned_topk_after_insert(corpus):
    recs, total, queries = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.06))
    idx._sketch_pack().postings()
    extra = generate_dataset(m=30, n_elems=3000, alpha_freq=1.0,
                             alpha_size=1.6, seed=14)
    idx.insert(extra)
    for q in queries[:3]:
        di, ds = idx.topk(q, 8, plan="dense")
        pi, ps = idx.topk(q, 8, plan="pruned")
        np.testing.assert_array_equal(di, pi)
        np.testing.assert_array_equal(ds, ps)


def test_sharded_pruned_topk_matches_dense(gb_index, corpus):
    from repro.sketchindex import ShardedIndex

    _, _, queries = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = ShardedIndex(gb_index, mesh)
    for q in queries[:3]:
        di, ds = sh.topk(q, 6, plan="dense")
        pi, ps = sh.topk(q, 6, plan="pruned")
        np.testing.assert_array_equal(di, pi)
        np.testing.assert_allclose(ds, ps, rtol=1e-6)


def test_server_pruned_topk_flush(gb_index, corpus):
    """topk>0 flushes honor plan="pruned" (carve-out removed) and match
    the dense server bit for bit."""
    from repro.serving.batcher import SketchServer

    _, _, queries = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    out = {}
    for plan in ("pruned", "dense"):
        srv = SketchServer(gb_index, mesh, topk=5, plan=plan, max_batch=3)
        rids = [srv.submit(q, 0.5) for q in queries[:3]]
        srv.flush()
        out[plan] = [srv.results[r] for r in rids]
    for a, b in zip(out["pruned"], out["dense"]):
        np.testing.assert_array_equal(a["hits"], b["hits"])
        np.testing.assert_array_equal(a["topk_ids"], b["topk_ids"])
        np.testing.assert_allclose(a["topk_scores"], b["topk_scores"],
                                   rtol=1e-6)


# ---------------------------------------------------------------------------
# cost-model calibration
# ---------------------------------------------------------------------------


def test_calibration_fit_and_plan_usage(tmp_path):
    import json

    from repro.core import cost_model

    # Synthesize rows from known constants; the fit must recover the
    # pruned/dense cost *ratio* (that is all the planner consumes).
    m, cap = 5000, 64
    a = 2e-9                                  # seconds per dense slot
    fixed_s, per_hit_s = 3e-4, 5e-7
    rows = []
    for t, hits in ((0.5, 900.0), (0.7, 400.0), (0.9, 80.0)):
        rows.append({
            "threshold": t,
            "qps_dense": 1.0 / (a * m * cap),
            "qps_pruned": 1.0 / (fixed_s + per_hit_s * hits),
            "mean_probe_hits": hits,
        })
    cal = cost_model.fit_query_constants(rows, m, cap)
    assert cal["dense_cost_per_slot"] == 1.0
    np.testing.assert_allclose(cal["prune_fixed_per_query"], fixed_s / a,
                               rtol=1e-6)
    g_units = (cal["prune_cost_per_hit"]
               + cal["prune_cost_per_cand_slot"] * cap)
    np.testing.assert_allclose(g_units, per_hit_s / a, rtol=1e-6)

    # Round-trip through the artifact format and drive choose_plan.
    path = str(tmp_path / "bench.json")
    with open(path, "w") as f:
        json.dump({"rows": rows, "calibration": cal}, f)
    try:
        cost_model.load_calibration(path)
        assert cost_model.calibration() is not None
        # Fitted units make the model exact: equal costs at the
        # break-even hit count, dense cheaper above it.
        dense_units = cost_model.dense_sweep_cost(m, cap, 1)
        hits_even = (dense_units - cal["prune_fixed_per_query"]) / g_units
        assert cost_model.pruned_path_cost(int(hits_even * 0.5), cap, 1) \
            < dense_units
        assert cost_model.pruned_path_cost(int(hits_even * 2.0), cap, 1) \
            > dense_units
    finally:
        cost_model.set_calibration(None)


def test_calibration_degenerate_hit_spread_keeps_default_fixed():
    """Constant probe hits across rows (the threshold sweep alone) make
    the fixed/per-hit split unidentifiable — the fit must fall back to
    the default fixed cost instead of a minimum-norm artifact."""
    from repro.core import cost_model

    m, cap = 5000, 64
    a = 2e-9
    rows = [{"qps_dense": 1.0 / (a * m * cap), "qps_pruned": 500.0,
             "mean_probe_hits": 1200.0} for _ in range(3)]
    cal = cost_model.fit_query_constants(rows, m, cap)
    np.testing.assert_allclose(cal["prune_fixed_per_query"],
                               cost_model.PRUNE_FIXED_PER_QUERY)
    assert cal["prune_cost_per_hit"] > 0


def test_calibration_validates_keys():
    from repro.core import cost_model

    with pytest.raises(ValueError):
        cost_model.set_calibration({"dense_cost_per_slot": 1.0})
    assert cost_model.calibration() is None
