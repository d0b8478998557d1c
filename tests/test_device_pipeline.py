"""The all-device query pipeline (planner/device.py + fused kernels).

The contract under test: with a device backend, one staged query batch
runs probe → block decode → K∩ scatter → estimator → output head (packed
threshold words or top-k) as ONE device program — no host transfer
between staging and the packed fetch — while bit-matching the dense
sweep. Plus the machinery that keeps steady-state serving on one
compiled program: Gq/k shape bucketing with inert padding, the pooled
staging buffers, the compile/staging counters, and the fused device
*build* (postings encoded on device, bit-identical to the host encoder).
"""

import functools
import logging

import jax
import numpy as np
import pytest

from repro import api, planner
from repro.core.arena import SketchArena
from repro.data.synth import generate_dataset, make_query_workload
from repro.planner import device as planner_device
from repro.planner import postings as postings_mod
from repro.planner import route
from repro.planner.prune import f32_threshold

DEVICE_BACKENDS = ("jnp", "pallas")


@pytest.fixture(scope="module")
def corpus():
    recs = generate_dataset(m=120, n_elems=3000, alpha_freq=1.0,
                            alpha_size=1.6, seed=20)
    total = sum(len(r) for r in recs)
    queries = make_query_workload(recs, 4, seed=21)
    rng = np.random.default_rng(22)
    queries += [rng.choice(3000, size=s, replace=False) for s in (6, 40)]
    return recs, total, queries


def dense_corpus():
    """Records sharing near-ubiquitous small elements kept in the TAIL
    (tiny records + generous budget -> τ retains everything; r=2 keeps
    the buffer from swallowing them) so their posting lists span long
    runs of consecutive record ids -> dense bitmap blocks."""
    rng = np.random.default_rng(7)
    recs = []
    for _ in range(600):
        base = rng.choice(3000, size=rng.integers(2, 5), replace=False) + 100
        common = [c for c in range(10) if rng.random() < 0.85]
        recs.append(np.unique(np.concatenate([common, base]).astype(np.int64)))
    return recs


def build(engine, recs, budget, **kw):
    return api.get_engine(engine).build(recs, budget, **kw)


# ---------------------------------------------------------------------------
# transfer-guard residency: probe, decode, score, threshold pack, top-k
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_score_matrix_device_resident(corpus, backend):
    """``pruned_scores`` (probe + decode + estimator, no output head)
    stays on device under the transfer guard and equals the dense
    scores exactly."""
    recs, total, queries = corpus
    idx = build("gbkmv", recs, int(total * 0.1), backend=backend)
    dense = idx.batch_scores(queries)
    arena = idx._sketch_pack()
    m = arena.num_records
    qp, _, _, _ = idx._plan_queries(queries)
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp)
    planner_device.pruned_scores(dpost, dpack, sq, m=m,
                                 backend=backend)  # warmup: compile
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp)
    with jax.transfer_guard("disallow"):
        s = planner_device.pruned_scores(dpost, dpack, sq, m=m,
                                         backend=backend)
        assert not isinstance(s, np.ndarray)
    np.testing.assert_array_equal(np.asarray(s)[:, : len(queries)], dense)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_vector_thresholds_device_resident(corpus, backend):
    """Per-query threshold vectors ride the same staged f32-exact cut:
    no transfer inside the guard, hits equal per-query dense calls."""
    recs, total, queries = corpus
    idx = build("gbkmv", recs, int(total * 0.1), backend=backend)
    thr = np.linspace(0.2, 0.9, len(queries))
    want = [idx.batch_query([q], float(t), plan="dense")[0]
            for q, t in zip(queries, thr)]
    arena = idx._sketch_pack()
    m = arena.num_records
    qp, _, _, _ = idx._plan_queries(queries)
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp, thr)
    planner_device.fused_mask_words(dpost, dpack, sq, m=m,
                                    backend=backend)  # warmup: compile
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp, thr)
    with jax.transfer_guard("disallow"):
        words = planner_device.fused_mask_words(
            dpost, dpack, sq, m=m, backend=backend)
        assert not isinstance(words, np.ndarray)
    mask = planner_device.unpack_hit_words(words, m)[:, : len(queries)]
    got = planner.prune.mask_to_hits(mask)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_packed_words_encode_scores_exactly(corpus, backend):
    """The packed hit words are literally ``score >= fl32(t)`` — decode
    them against the device score matrix bit for bit."""
    recs, total, queries = corpus
    idx = build("gbkmv", recs, int(total * 0.1), backend=backend)
    arena = idx._sketch_pack()
    m = arena.num_records
    qp, _, _, _ = idx._plan_queries(queries)
    t = 0.4
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp, t)
    words = planner_device.fused_mask_words(dpost, dpack, sq,
                                            m=m, backend=backend)
    mask = planner_device.unpack_hit_words(words, m)
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp)
    s = np.asarray(planner_device.pruned_scores(dpost, dpack, sq, m=m,
                                                backend=backend))
    np.testing.assert_array_equal(mask, s >= f32_threshold(t))


# ---------------------------------------------------------------------------
# shape bucketing + staging pool: one compiled program in steady state
# ---------------------------------------------------------------------------


def test_compile_cache_and_staging_reuse(corpus, caplog):
    """Batches of 2/5/8 queries share one Gq bucket: one compile
    signature, one staging allocation, the rest cache hits + pool reuse.
    A 9-query batch crosses the bucket and logs the slow-path line."""
    recs, total, queries = corpus
    idx = build("gbkmv", recs, int(total * 0.1), backend="jnp")
    qs = (queries * 2)[:9]
    planner_device.reset_pipeline_stats()
    for n in (2, 5, 8):
        idx.batch_query(qs[:n], 0.5, plan="pruned")
    st = planner_device.pipeline_stats()
    assert st["calls"] == 3
    assert st["compiles"] == 1 and st["cache_hits"] == 2
    assert st["staging_alloc"] == 1 and st["staging_reuse"] == 2
    assert st["signatures"] == 1 and st["staging_buffers"] == 1
    with caplog.at_level(logging.INFO, logger="repro.planner.device"):
        idx.batch_query(qs[:9], 0.5, plan="pruned")   # new Gq bucket (16)
    st = planner_device.pipeline_stats()
    assert st["compiles"] == 2 and st["staging_buffers"] == 2
    assert any("slow path" in r.message for r in caplog.records)


def test_gq_bucket_padding_is_inert(corpus):
    """Every batch size across a bucket (1..9 queries) returns exactly
    the per-query dense answers — the PAD-query padding never leaks into
    real columns, for threshold hits and for top-k."""
    recs, total, queries = corpus
    idx = build("gbkmv", recs, int(total * 0.1), backend="jnp")
    dense = build("gbkmv", recs, int(total * 0.1), backend="numpy")
    qs = (queries * 2)[:9]
    want = [dense.batch_query([q], 0.5, plan="dense")[0] for q in qs]
    wtop = [dense.topk(q, 7, plan="dense") for q in qs]
    for n in range(1, 10):
        got = idx.batch_query(qs[:n], 0.5, plan="pruned")
        assert len(got) == n
        for w, g in zip(want[:n], got):
            np.testing.assert_array_equal(w, g)
    for q, (wi, ws) in zip(qs, wtop):
        gi, gs = idx.topk(q, 7, plan="pruned")
        np.testing.assert_array_equal(gi, wi)
        np.testing.assert_array_equal(gs, ws)


# ---------------------------------------------------------------------------
# device top-k: host pruned_topk contract, engines × backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("engine", ("gbkmv", "gkmv"))
def test_topk_matches_host_pruned_topk(corpus, engine, backend):
    """``pruned_topk_device`` == host ``planner.pruned_topk`` (same
    (score desc, id asc) order, same shortfall fill) including k > m."""
    recs, total, queries = corpus
    idx = build(engine, recs, int(total * 0.1), backend=backend)
    arena = idx._sketch_pack()
    qp, _, _, _ = idx._plan_queries(queries)
    for k in (1, 9, 2 * len(recs)):
        got = planner_device.pruned_topk_device(
            arena, qp, k, backend=backend)
        for g, (ids, vals) in enumerate(got):
            # single-query pack: pruned_topk's score_fn addresses query 0
            qp_g, hr, br, sz = idx._plan_queries([queries[g]])
            want_ids, want_vals = planner.pruned_topk(
                idx._sketch_pack().postings(), hr[0], br[0], int(sz[0]),
                k, functools.partial(idx._score_pairs, qp_g),
                arena.num_records)
            np.testing.assert_array_equal(ids, want_ids)
            np.testing.assert_array_equal(vals, want_vals)


def test_topk_kmv_host_route_still_matches(corpus):
    """kmv has no device twin — plan="pruned" takes the host route and
    must still match the dense ordering."""
    recs, total, queries = corpus
    idx = build("kmv", recs, int(total * 0.1), backend="jnp")
    for k in (3, 17):
        pi, ps = idx.topk(queries[0], k, plan="pruned")
        di, ds = idx.topk(queries[0], k, plan="dense")
        np.testing.assert_array_equal(pi, di)
        np.testing.assert_array_equal(ps, ds)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_topk_tie_break_and_shortfall(backend):
    """12 identical records tie at the top: ids come back ascending.
    With k past the candidates, zero-score records fill in ascending-id
    order — the dense (-score, id) rule end to end."""
    recs = [np.arange(50)] * 12 + \
        [np.arange(1000 + 10 * i, 1000 + 10 * i + 5) for i in range(8)]
    idx = build("gbkmv", recs, 600, backend=backend)
    q = np.arange(25)
    ids, vals = idx.topk(q, 12, plan="pruned")
    np.testing.assert_array_equal(ids, np.arange(12))
    assert len(set(vals.tolist())) == 1
    ids, vals = idx.topk(q, 18, plan="pruned")
    di, dv = idx.topk(q, 18, plan="dense")
    np.testing.assert_array_equal(ids, di)
    np.testing.assert_array_equal(vals, dv)
    # shortfall tail is the ascending zero-score ids
    np.testing.assert_array_equal(ids[12:], np.sort(ids[12:]))


def test_f32_slack_bound_on_device():
    """The float32-rounding edge (buffer-only score fl32(1/3) > 1/3)
    that motivated the host bound slack: the device path thresholds in
    float32 exactly, so the dense hit survives."""
    recs = [np.asarray([0, 100 + i, 200 + i, 300 + i]) for i in range(20)]
    q = np.asarray([0, 9001, 9002])
    t = float(np.float32(1 / 3))
    dense = build("gbkmv", recs, 400, r=32, backend="numpy")
    want = dense.batch_query([q], t, plan="dense")[0]
    assert len(want) > 0                     # the edge actually triggers
    for backend in DEVICE_BACKENDS:
        idx = build("gbkmv", recs, 400, r=32, backend=backend)
        got = idx.batch_query([q], t, plan="pruned")[0]
        np.testing.assert_array_equal(want, got)


# ---------------------------------------------------------------------------
# fused device build: postings encoded on device, bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("engine", ("gbkmv", "gkmv"))
def test_device_encode_bit_identity(corpus, engine, backend):
    """``build_backend=<device>`` encodes the tail postings on device;
    the installed store is bit-identical to the host encoder's (and the
    adopted mirror's has_dense flag agrees with the host meta bits) and
    queries match a host-built numpy twin."""
    recs, total, queries = corpus
    idx = build(engine, recs, int(total * 0.15), backend=backend,
                build_backend=backend, postings="eager")
    arena = idx._sketch_pack()
    assert arena._dev_post is not None       # adopted, not re-mirrored
    host_post = postings_mod.build_postings(arena)
    assert postings_mod.postings_equal(host_post, arena._post)
    assert arena._dev_post.has_dense == bool(
        np.any((host_post.tail.meta >> 13) & 1))
    dense = build(engine, recs, int(total * 0.15), backend="numpy")
    for w, g in zip(dense.batch_query(queries, 0.5, plan="dense"),
                    idx.batch_query(queries, 0.5, plan="pruned")):
        np.testing.assert_array_equal(w, g)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_no_dense_blocks_compiles_dense_loop_out(backend):
    """Disjoint records -> every posting list is a single entry -> only
    sparse blocks. has_dense=False drops the dense decode loop from the
    compiled program; queries still match the dense sweep."""
    recs = [np.arange(20 * i, 20 * i + 15) for i in range(80)]
    idx = build("gbkmv", recs, 700, backend=backend,
                build_backend=backend, postings="eager")
    arena = idx._sketch_pack()
    assert not arena._dev_post.has_dense
    assert not np.any((arena._post.tail.meta >> 13) & 1)
    dense = build("gbkmv", recs, 700, backend="numpy")
    qs = [recs[3][:8], recs[40][:4], np.arange(5000, 5006)]
    for w, g in zip(dense.batch_query(qs, 0.5, plan="dense"),
                    idx.batch_query(qs, 0.5, plan="pruned")):
        np.testing.assert_array_equal(w, g)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_device_encode_bit_identity_dense_blocks(backend):
    """Same bit-identity through the dense-bitmap encode path (mirror
    fields compared raw: keys/first/last/meta/off/payload)."""
    recs = dense_corpus()
    queries = [r[: max(2, len(r) // 2)] for r in recs[:4]]
    idx = build("gbkmv", recs, 20_000, r=2, backend=backend,
                build_backend=backend, postings="eager")
    arena = idx._sketch_pack()
    host_post = postings_mod.build_postings(arena)
    assert postings_mod.postings_equal(host_post, arena._post)
    dp, t = arena._dev_post, host_post.tail
    assert dp.has_dense and np.any((t.meta >> 13) & 1)
    np.testing.assert_array_equal(np.asarray(dp.keys), host_post.keys)
    np.testing.assert_array_equal(np.asarray(dp.first), t.first)
    np.testing.assert_array_equal(np.asarray(dp.last), t.last)
    np.testing.assert_array_equal(np.asarray(dp.meta), t.meta)
    np.testing.assert_array_equal(np.asarray(dp.off), t.off.astype(np.int32))
    np.testing.assert_array_equal(np.asarray(dp.payload), t.payload)
    dense = build("gbkmv", recs, 20_000, r=2, backend="numpy")
    for w, g in zip(dense.batch_query(queries, 0.5, plan="dense"),
                    idx.batch_query(queries, 0.5, plan="pruned")):
        np.testing.assert_array_equal(w, g)
    wi, ws = dense.topk(queries[0], 9, plan="dense")
    gi, gs = idx.topk(queries[0], 9, plan="pruned")
    np.testing.assert_array_equal(gi, wi)
    np.testing.assert_array_equal(gs, ws)


# ---------------------------------------------------------------------------
# sharded single-device route
# ---------------------------------------------------------------------------


def test_sharded_single_device_takes_fused_route(corpus):
    """A 1-device ShardedIndex serves pruned batches and top-k through
    the fused pipeline (no host candidate sets) with dense parity."""
    from jax.sharding import Mesh

    from repro.sketchindex.distributed import ShardedIndex

    recs, total, queries = corpus
    host = build("gbkmv", recs, int(total * 0.1), backend="jnp")
    dense = build("gbkmv", recs, int(total * 0.1), backend="numpy")
    mesh = Mesh(np.array(jax.devices()[:1]), ("records",))
    sh = ShardedIndex(host, mesh, backend="jnp")
    assert route.fused(sh)
    got = sh.batch_query(queries, 0.6, plan="pruned")
    want = dense.batch_query(queries, 0.6, plan="dense")
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)
    assert sh.last_plan.path == "pruned"
    assert sh.last_candidates is None        # nothing materialized on host
    out = sh.serve_batch(queries, 0.6, k=7, plan="pruned", explain=True)
    for q, res in zip(queries, out):
        wi, ws = dense.topk(q, 7, plan="dense")
        np.testing.assert_array_equal(res["topk_ids"], wi)
        np.testing.assert_array_equal(res["topk_scores"], ws)
        assert res["explain"]["plan"] == "pruned"
