"""Distributed sketch-index tests: device scoring vs host oracle, global
top-k vs numpy, histogram τ vs exact quantile, query batching.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gbkmv as gbkmv_mod
from repro.core.gbkmv import build_gbkmv, sketch_query
from repro.core.hashing import hash_u32_np
from repro.data.synth import generate_dataset, make_query_workload
from repro.sketchindex import (
    batch_queries,
    distributed_tau,
    distributed_topk,
    score_batch,
    to_device_index,
)
from repro.sketchindex.build import histogram_tau


def _setup(m=150, budget=3000, r=64, seed=0):
    recs = generate_dataset(m=m, n_elems=4000, alpha_freq=1.1,
                            alpha_size=2.0, seed=seed)
    idx = build_gbkmv(recs, budget=budget, r=r, seed=seed)
    return recs, idx


def test_device_scores_match_host():
    recs, idx = _setup()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    didx = to_device_index(idx, mesh)
    queries = make_query_workload(recs, 6)
    qp = batch_queries(idx, queries)
    scores = np.asarray(score_batch(didx, qp))
    for j, q in enumerate(queries):
        host = np.asarray(gbkmv_mod.containment_scores(idx, sketch_query(idx, q)))
        np.testing.assert_allclose(scores[: idx.num_records, j], host,
                                   rtol=1e-5, atol=1e-5)


def test_kernel_impl_matches_jnp():
    recs, idx = _setup(m=64)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    didx = to_device_index(idx, mesh)
    qp = batch_queries(idx, make_query_workload(recs, 3))
    s_jnp = np.asarray(score_batch(didx, qp, backend="jnp"))
    s_krn = np.asarray(score_batch(didx, qp, backend="pallas"))
    s_np = np.asarray(score_batch(didx, qp, backend="numpy"))
    np.testing.assert_allclose(s_krn, s_jnp, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_np, s_jnp, rtol=1e-5, atol=1e-5)


def test_distributed_topk_matches_numpy():
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.normal(size=(128, 5)), jnp.float32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    v, i = distributed_topk(scores, 7, mesh)
    ref = np.sort(np.asarray(scores), axis=0)[::-1][:7]       # [7, 5]
    np.testing.assert_allclose(np.asarray(v), ref.T, rtol=1e-6)
    # ids point at the right values
    picked = np.take_along_axis(np.asarray(scores), np.asarray(i).T, axis=0)
    np.testing.assert_allclose(picked.T, np.asarray(v), rtol=1e-6)


def test_histogram_tau_near_exact():
    rng = np.random.default_rng(1)
    h = rng.integers(0, 2**32, size=20000).astype(np.uint32)
    budget = 1500
    exact = np.partition(h, budget - 1)[budget - 1]
    t = int(histogram_tau(jnp.asarray(h), budget))
    assert abs(int(exact) - t) <= (1 << 8)
    kept = int((h <= t).sum())
    assert budget <= kept <= budget + 16    # never under-covers the budget


def test_distributed_tau_matches_single_device():
    rng = np.random.default_rng(2)
    h = rng.integers(0, 2**32, size=8192).astype(np.uint32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    t1 = int(histogram_tau(jnp.asarray(h), 600))
    t2 = int(distributed_tau(jnp.asarray(h), 600, mesh, ("data",)))
    assert t1 == t2


def test_search_threshold_agrees_with_host_search():
    recs, idx = _setup(m=100, budget=6000, r=32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    didx = to_device_index(idx, mesh)
    q = recs[7]
    qp = batch_queries(idx, [q])
    from repro.sketchindex import distributed_search
    mask, scores = distributed_search(didx, qp, threshold=0.5)
    got = set(np.nonzero(np.asarray(mask)[: idx.num_records, 0])[0].tolist())
    host = set(gbkmv_mod.search(idx, q, 0.5).tolist())
    assert got == host


def test_padding_rows_never_match():
    recs, idx = _setup(m=37)          # odd size → padding on any mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    didx = to_device_index(idx, mesh)
    qp = batch_queries(idx, [recs[0]])
    scores = np.asarray(score_batch(didx, qp))
    assert (scores[idx.num_records:] == 0).all()
