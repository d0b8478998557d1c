"""Candidate-pruning query planner: postings maintenance, pruned-vs-dense
exact parity (all engines × all backends, before and after inserts),
plan selection, and the ragged gather-score kernel."""

import jax
import numpy as np
import pytest

from repro import api, planner
from repro.core.hashing import hash_u32_np
from repro.data.synth import generate_dataset, make_query_workload
from repro.planner import prune

ENGINES = ("gbkmv", "gkmv", "kmv")
BACKENDS = ("numpy", "jnp", "pallas")


@pytest.fixture(scope="module")
def corpus():
    recs = generate_dataset(m=130, n_elems=4000, alpha_freq=1.0,
                            alpha_size=1.6, seed=0)
    total = sum(len(r) for r in recs)
    queries = make_query_workload(recs, 6, seed=1)
    # Off-corpus queries too: partial overlaps, not guaranteed self-hits.
    rng = np.random.default_rng(3)
    queries += [rng.choice(4000, size=s, replace=False)
                for s in (5, 40, 160)]
    return recs, total, queries


@pytest.fixture(scope="module")
def gb_index(corpus):
    recs, total, _ = corpus
    return api.get_engine("gbkmv").build(recs, int(total * 0.1))


# ---------------------------------------------------------------------------
# postings: CSR structure + incremental maintenance
# ---------------------------------------------------------------------------


def test_postings_csr_structure(gb_index):
    s = gb_index.core.sketches
    post = planner.build_postings(s)
    assert post.num_records == s.num_records
    assert np.all(np.diff(post.keys.astype(np.int64)) > 0)       # strict asc
    assert post.offsets[0] == 0 and post.offsets[-1] == post.nnz
    assert np.all(np.diff(post.offsets) >= 1)     # no empty hash rows
    assert post.nnz == int(np.asarray(s.lengths).sum())
    # Every (hash, record) pair is findable, rec lists ascending per key.
    vals, lens = np.asarray(s.values), np.asarray(s.lengths)
    for i in (0, s.num_records // 2, s.num_records - 1):
        for h in vals[i, : lens[i]][:20]:
            j = int(np.searchsorted(post.keys, h))
            seg = post.rec_ids[post.offsets[j] : post.offsets[j + 1]]
            assert post.keys[j] == h and i in seg
            assert np.all(np.diff(seg) > 0)
    assert post.nbytes() > 0


def test_postings_buffer_rows(gb_index):
    s = gb_index.core.sketches
    post = planner.build_postings(s)
    if s.buf_words == 0:
        pytest.skip("cost model chose r=0 for this corpus")
    bits = ((np.asarray(s.buf)[:, :, None]
             >> np.arange(32, dtype=np.uint32)) & 1).reshape(s.num_records, -1)
    for j in range(min(bits.shape[1], 48)):
        seg = post.buf_rec_ids[post.buf_offsets[j] : post.buf_offsets[j + 1]]
        np.testing.assert_array_equal(seg, np.nonzero(bits[:, j])[0])


def test_incremental_update_equals_rebuild(corpus, gb_index):
    recs, total, _ = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.06))
    idx._sketch_pack().postings()            # build before the insert
    extra = generate_dataset(m=50, n_elems=4000, alpha_freq=1.0,
                             alpha_size=1.6, seed=7)
    idx.insert(extra)
    assert idx.stats.tau_retightens >= 1     # deletion path exercised
    fresh = planner.build_postings(idx.core.sketches)
    assert planner.postings_equal(idx._sketch_pack()._post, fresh)


def test_incremental_update_without_retighten(corpus):
    recs, total, _ = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 10))  # roomy budget
    idx._sketch_pack().postings()
    idx.insert([np.asarray([1, 2, 3]), np.asarray([4, 5])])
    assert idx.stats.tau_retightens == 0     # append-only path
    assert planner.postings_equal(
        idx._sketch_pack()._post, planner.build_postings(idx.core.sketches))


# ---------------------------------------------------------------------------
# parity: pruned == dense, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_pruned_matches_dense(corpus, engine, backend):
    recs, total, queries = corpus
    idx = api.get_engine(engine).build(recs, int(total * 0.1), backend=backend)
    for t in (0.3, 0.6, 0.9):
        dense = idx.batch_query(queries, t, plan="dense")
        pruned = idx.batch_query(queries, t, plan="pruned")
        auto = idx.batch_query(queries, t)
        for d, p, a in zip(dense, pruned, auto):
            np.testing.assert_array_equal(d, p)
            np.testing.assert_array_equal(d, a)


@pytest.mark.parametrize("engine", ENGINES)
def test_single_query_plan_kw(corpus, engine):
    recs, total, queries = corpus
    idx = api.get_engine(engine).build(recs, int(total * 0.1))
    q = queries[0]
    np.testing.assert_array_equal(idx.query(q, 0.5, plan="pruned"),
                                  idx.query(q, 0.5, plan="dense"))
    np.testing.assert_array_equal(idx.query(q, 0.5), idx.query(q, 0.5,
                                                               plan="dense"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_parity_after_insert_retighten(corpus, backend):
    recs, total, queries = corpus
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.06),
                                        backend=backend)
    idx._sketch_pack().postings()
    extra = generate_dataset(m=40, n_elems=4000, alpha_freq=1.0,
                             alpha_size=1.6, seed=9)
    idx.insert(extra)
    assert idx.stats.tau_retightens >= 1
    for t in (0.4, 0.8):
        dense = idx.batch_query(queries, t, plan="dense")
        pruned = idx.batch_query(queries, t, plan="pruned")
        for d, p in zip(dense, pruned):
            np.testing.assert_array_equal(d, p)


def test_candidates_never_drop_a_hit(corpus, gb_index):
    """The filter step alone (before verify) is a superset of the dense
    hits — pruning never drops a record with estimated containment ≥ t."""
    recs, total, queries = corpus
    post = gb_index._sketch_pack().postings()
    for t in (0.2, 0.5, 0.8):
        _, hash_rows, bit_rows, sizes = gb_index._plan_queries(
            [np.asarray(q) for q in queries])
        dense = gb_index.batch_query(queries, t, plan="dense")
        for qh, qb, qs, hits in zip(hash_rows, bit_rows, sizes, dense):
            cand = prune.candidates_for(post, qh, qb, t, int(qs))
            assert set(hits.tolist()) <= set(cand.rec_ids.tolist())


def test_bound_survives_f32_rounding_of_buffer_scores():
    """A buffer-only score like o1/|Q| = 1/3 rounds UP in float32
    (fl32(1/3) > 1/3), so for thresholds inside (1/3, fl32(1/3)] the
    dense sweep returns the record while the exact real-valued bound
    sits below t — the bound's slack must absorb that, or pruning drops
    a dense hit."""
    # Element 0 is ubiquitous -> buffered; records share ONLY it with Q.
    recs = [np.asarray([0, 100 + i, 200 + i, 300 + i]) for i in range(20)]
    idx = api.get_engine("gbkmv").build(recs, budget=400, r=32)
    assert 0 in idx.core.top_elems
    q = np.asarray([0, 9001, 9002])          # |Q|=3, only elem 0 shared
    s = idx.scores(q)
    t = float(np.float32(1 / 3))             # == fl32(1/3) > 1/3
    assert s.max() == np.float32(1 / 3)      # buffer-only score, rounded up
    dense = idx.batch_query([q], t, plan="dense")[0]
    pruned = idx.batch_query([q], t, plan="pruned")[0]
    assert len(dense) > 0                    # the edge actually triggers
    np.testing.assert_array_equal(dense, pruned)


# ---------------------------------------------------------------------------
# plan selection
# ---------------------------------------------------------------------------


def test_plan_guards_and_forcing(corpus, gb_index):
    recs, total, queries = corpus
    _, hash_rows, bit_rows, _ = gb_index._plan_queries(
        [np.asarray(q) for q in queries[:2]])
    post = gb_index._sketch_pack().postings()
    s = gb_index.core.sketches
    # t <= 0: pruning is unsound, always dense (even when forced).
    d = planner.choose_plan(post, hash_rows, bit_rows, 0.0,
                            s.num_records, s.capacity, plan="pruned")
    assert d.path == "dense"
    for mode in ("dense", "pruned"):
        d = planner.choose_plan(post, hash_rows, bit_rows, 0.9,
                                s.num_records, s.capacity, plan=mode)
        assert d.path == mode and d.reason == "forced"
    with pytest.raises(ValueError):
        planner.normalize_plan("fastest")
    # Auto obeys the cost ordering on both extremes of index size.
    auto = planner.choose_plan(post, hash_rows, bit_rows, 0.9,
                               s.num_records, s.capacity)
    assert auto.path in ("dense", "pruned") and auto.hits > 0
    big_m = planner.choose_plan(post, hash_rows, bit_rows, 0.9,
                                10_000_000, s.capacity)
    assert big_m.path == "pruned"    # selective probe vs huge sweep


def test_topk_scores_match_dense_ranking(corpus, gb_index):
    _, _, queries = corpus
    ids, scores = gb_index.topk(queries[0], 5)   # auto plan routing
    s = gb_index.scores(queries[0])
    np.testing.assert_allclose(scores, np.sort(s)[::-1][:5], rtol=1e-6)


# ---------------------------------------------------------------------------
# packed thresholding + float32 threshold exactness
# ---------------------------------------------------------------------------


def test_threshold_hits_packed_matches_nonzero():
    rng = np.random.default_rng(0)
    s = rng.random((50, 7)).astype(np.float32)
    for t in (0.3, 0.7, float(s[3, 2])):
        want = [np.nonzero(s[:, j] >= t)[0] for j in range(7)]
        got = prune.threshold_hits_packed(s, t)
        got_dev = prune.threshold_hits_packed(jax.numpy.asarray(s), t)
        for w, g, gd in zip(want, got, got_dev):
            np.testing.assert_array_equal(w, g)
            np.testing.assert_array_equal(w, gd)
    thr = rng.random(7)
    want = [np.nonzero(s[:, j] >= thr[j])[0] for j in range(7)]
    for w, g in zip(want, prune.threshold_hits_packed(s, thr)):
        np.testing.assert_array_equal(w, g)


def test_f32_threshold_is_exact():
    for t in (0.7, 0.1, 1 / 3, 0.5, 0.9999999):
        up = prune.f32_threshold(t)
        grid = np.nextafter(np.float32(t),
                            np.float32([-np.inf, np.inf])).tolist()
        for s in [np.float32(t)] + [np.float32(g) for g in grid]:
            assert (s >= up) == (float(s) >= t)


# ---------------------------------------------------------------------------
# ragged gather-score kernel
# ---------------------------------------------------------------------------


def test_gather_kernel_backends_agree(corpus, gb_index):
    from repro.kernels import gather_score
    from repro.sketchindex.distributed import batch_queries

    recs, total, queries = corpus
    qp = batch_queries(gb_index.core, [np.asarray(q) for q in queries])
    m = gb_index.num_records
    rng = np.random.default_rng(1)
    cand_rec = rng.integers(0, m, size=37).astype(np.int32)
    cand_q = rng.integers(0, len(queries), size=37).astype(np.int32)
    x = gb_index.core.sketches
    s_np = gather_score.score_pairs(x, qp, cand_rec, cand_q, backend="numpy")
    s_jnp = gather_score.score_pairs(x, qp, cand_rec, cand_q, backend="jnp")
    s_pl = gather_score.score_pairs(x, qp, cand_rec, cand_q, backend="pallas")
    np.testing.assert_allclose(s_np, s_jnp, rtol=1e-6)
    np.testing.assert_allclose(s_jnp, s_pl, rtol=1e-6)
    # ... and each pair equals the dense matrix entry it addresses.
    dense = gb_index.batch_scores(queries)
    np.testing.assert_allclose(s_jnp, dense[cand_rec, cand_q], rtol=1e-6)


# ---------------------------------------------------------------------------
# distributed + serving wiring
# ---------------------------------------------------------------------------


def test_sharded_planner_matches_dense(corpus, gb_index):
    from repro.sketchindex import ShardedIndex

    _, _, queries = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = ShardedIndex(gb_index, mesh)
    posts, offs = sh._shard_postings()
    assert len(posts) == 1 and offs == [0]
    for t in (0.4, 0.8):
        dense = sh.batch_query(queries, t, plan="dense")
        pruned = sh.batch_query(queries, t, plan="pruned")
        host = gb_index.batch_query(queries, t, plan="dense")
        for d, p, h in zip(dense, pruned, host):
            np.testing.assert_array_equal(d, p)
            np.testing.assert_array_equal(d, h)


def test_shard_union_equals_global(gb_index):
    """Cross-shard candidate union == single global postings' candidates."""
    post_global = gb_index._sketch_pack().postings()
    s = gb_index.core.sketches
    qp, hash_rows, bit_rows, sizes = gb_index._plan_queries(
        [np.arange(10), np.arange(50, 90)])
    # Split the records into 3 artificial shards.
    import dataclasses

    cuts = [0, 40, 90, s.num_records]
    posts, offs = [], []
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        sub = dataclasses.replace(
            s, values=np.asarray(s.values)[lo:hi],
            lengths=np.asarray(s.lengths)[lo:hi],
            thresh=np.asarray(s.thresh)[lo:hi],
            buf=np.asarray(s.buf)[lo:hi], sizes=np.asarray(s.sizes)[lo:hi])
        posts.append(planner.build_postings(sub))
        offs.append(lo)
    gen = planner.plan.merged_candidates(posts, offs)
    for qh, qb, qs in zip(hash_rows, bit_rows, sizes):
        want = prune.candidates_for(post_global, qh, qb, 0.5, int(qs))
        got = gen(qh, qb, 0.5, int(qs))
        np.testing.assert_array_equal(want.rec_ids, got.rec_ids)
        np.testing.assert_array_equal(want.counts, got.counts)
        np.testing.assert_array_equal(want.o1, got.o1)


def test_server_plan_hint_threshold_only(corpus, gb_index):
    from repro.serving.batcher import SketchServer

    _, _, queries = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    out = {}
    for plan in ("pruned", "dense"):
        srv = SketchServer(gb_index, mesh, topk=0, plan=plan, max_batch=4,
                           clock=clock)
        rids = [srv.submit(q, 0.6) for q in queries[:4]]
        srv.flush()
        out[plan] = [srv.results[r] for r in rids]
    for a, b in zip(out["pruned"], out["dense"]):
        np.testing.assert_array_equal(a["hits"], b["hits"])
        assert len(a["topk_ids"]) == 0 and len(a["topk_scores"]) == 0


def test_server_topk_unchanged(corpus, gb_index):
    from repro.serving.batcher import SketchServer

    _, _, queries = corpus
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    srv = SketchServer(gb_index, mesh, topk=5, plan="pruned", max_batch=2)
    r0 = srv.submit(queries[0], 0.5)
    r1 = srv.submit(queries[1], 0.5)
    assert len(srv.results[r0]["topk_ids"]) == 5
    np.testing.assert_array_equal(
        srv.results[r0]["hits"], gb_index.query(queries[0], 0.5, plan="dense"))
    assert r1 in srv.results


# ---------------------------------------------------------------------------
# deterministic fuzz: pruning soundness on adversarial small sets
# ---------------------------------------------------------------------------


def test_pruning_sound_on_random_small_sets():
    rng = np.random.default_rng(42)
    for trial in range(8):
        m = int(rng.integers(10, 60))
        recs = [np.unique(rng.integers(0, 300, size=rng.integers(1, 30)))
                for _ in range(m)]
        total = sum(len(r) for r in recs)
        idx = api.get_engine("gbkmv").build(
            recs, max(int(total * float(rng.uniform(0.05, 0.6))), m))
        qs = [np.unique(rng.integers(0, 300, size=rng.integers(1, 25)))
              for _ in range(4)]
        for t in (0.101, 0.499, 0.93):
            dense = idx.batch_query(qs, t, plan="dense")
            pruned = idx.batch_query(qs, t, plan="pruned")
            for d, p in zip(dense, pruned):
                np.testing.assert_array_equal(d, p)
