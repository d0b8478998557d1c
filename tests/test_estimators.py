import zlib
from types import SimpleNamespace

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.estimators import (
    gkmv_pair_estimate, gkmv_pair_oracle_np,
    kmv_pair_estimate, kmv_pair_oracle_np,
    buffer_intersection, gbkmv_containment_np,
)
from repro.core.hashing import hash_u32_np, PAD, TWO32


def _pack(rows, cap):
    m = len(rows)
    v = np.full((m, cap), PAD, np.uint32)
    n = np.zeros(m, np.int32)
    for i, r in enumerate(rows):
        v[i, : len(r)] = r
        n[i] = len(r)
    return jnp.asarray(v), jnp.asarray(n)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gkmv_matches_set_oracle(seed):
    rng = np.random.default_rng(seed)
    tau = np.uint32(0.35 * 2**32)
    q_ids = rng.choice(5000, size=300, replace=False)
    qh = np.sort(hash_u32_np(q_ids))
    qk = qh[qh <= tau]

    rows, taus, oracle = [], [], []
    for _ in range(50):
        x_ids = rng.choice(5000, size=rng.integers(20, 400), replace=False)
        xh = np.sort(hash_u32_np(x_ids))
        t = np.uint32(rng.uniform(0.05, 0.35) * 2**32)  # per-record thresholds
        rows.append(xh[xh <= t])
        taus.append(t)
        oracle.append(gkmv_pair_oracle_np(qk, tau, rows[-1], t))

    cap = max(len(r) for r in rows + [qk]) + 3
    xv, xn = _pack(rows, cap)
    qv, qn = _pack([qk], cap)
    d, k, kc = gkmv_pair_estimate(qv[0], qn[0], jnp.uint32(tau), xv, xn,
                                  jnp.asarray(np.asarray(taus, np.uint32)))
    for i, (od, ok, okc) in enumerate(oracle):
        assert int(k[i]) == ok
        assert int(kc[i]) == okc
        np.testing.assert_allclose(float(d[i]), od, rtol=2e-5)


def test_gkmv_pair_identical_records():
    ids = np.arange(100)
    h = np.sort(hash_u32_np(ids))
    tau = np.uint32(PAD - 1)
    cap = 104
    xv, xn = _pack([h], cap)
    d, k, kc = gkmv_pair_estimate(xv[0], xn[0], tau, xv, xn,
                                  jnp.asarray([tau]))
    assert int(kc[0]) == 100 and int(k[0]) == 100
    # (k-1)/U estimates the distinct count of the union (=100) unbiasedly.
    assert 40 < float(d[0]) < 300


@pytest.mark.parametrize("seed", [0, 5])
def test_kmv_matches_set_oracle(seed):
    rng = np.random.default_rng(seed)
    kq, kx = 40, 25
    q_ids = rng.choice(3000, size=500, replace=False)
    qh = np.sort(hash_u32_np(q_ids))[:kq]
    rows, oracle = [], []
    for _ in range(30):
        x_ids = rng.choice(3000, size=rng.integers(30, 600), replace=False)
        xh = np.sort(hash_u32_np(x_ids))[:kx]
        rows.append(xh)
        oracle.append(kmv_pair_oracle_np(qh, xh))
    cap = kq
    xv, xn = _pack(rows, cap)
    qv, qn = _pack([qh], cap)
    d, k, kc = kmv_pair_estimate(qv[0], qn[0], xv, xn)
    for i, (od, ok, okc) in enumerate(oracle):
        assert int(k[i]) == ok, i
        assert int(kc[i]) == okc, i
        np.testing.assert_allclose(float(d[i]), od, rtol=2e-5)


def test_buffer_intersection_popcount():
    rng = np.random.default_rng(0)
    q = rng.integers(0, 2**32, size=4, dtype=np.uint32)
    x = rng.integers(0, 2**32, size=(7, 4), dtype=np.uint32)
    got = np.asarray(buffer_intersection(jnp.asarray(q), jnp.asarray(x)))
    want = [bin(int(q[w]) & int(x[i, w])).count("1") for i in range(7) for w in range(4)]
    want = np.asarray(want).reshape(7, 4).sum(1)
    np.testing.assert_array_equal(got, want)


def _pair_estimate_by_index(q_values, q_thresh, x_values, x_thresh):
    """The former ``gkmv_pair_estimate``: U_(k) read at index n - 1 of each
    row with ``take_along_axis``. The oracle of the masked-max form."""
    q_values = jnp.asarray(q_values, dtype=jnp.uint32)
    x_values = jnp.asarray(x_values, dtype=jnp.uint32)
    tau_pair = jnp.minimum(jnp.asarray(x_thresh, jnp.uint32),
                           jnp.asarray(q_thresh, jnp.uint32))
    nq = jnp.sum(q_values[None, :] <= tau_pair[:, None], axis=-1).astype(jnp.int32)
    nx = jnp.sum(x_values <= tau_pair[:, None], axis=-1).astype(jnp.int32)
    live = x_values <= tau_pair[:, None]
    member = jnp.any(x_values[:, :, None] == q_values[None, None, :], axis=-1)
    k_cap = jnp.sum(live & member, axis=-1).astype(jnp.int32)
    k = nq + nx - k_cap

    def last_live(vals, n):
        idx = jnp.maximum(n - 1, 0)
        v = jnp.take_along_axis(vals, idx[:, None].astype(jnp.int32), axis=-1)[:, 0]
        return jnp.where(n > 0, v, jnp.uint32(0))

    uq = last_live(jnp.broadcast_to(q_values[None, :], (x_values.shape[0],) + q_values.shape), nq)
    ux = last_live(x_values, nx)
    u = jnp.maximum(uq, ux)
    u_unit = (u.astype(jnp.float32) + 1.0) / TWO32
    valid = (k >= 2) & (k_cap >= 1)
    d_hat = jnp.where(
        valid,
        (k_cap.astype(jnp.float32) / jnp.maximum(k, 1).astype(jnp.float32))
        * ((k.astype(jnp.float32) - 1.0) / jnp.maximum(u_unit, 1e-30)),
        jnp.where(k_cap >= 1, k_cap.astype(jnp.float32), 0.0),
    )
    return d_hat, k, k_cap


def _sorted_rows(rng, m, cap, lo, hi, pool):
    """m ascending duplicate-free rows of lo..hi values drawn from ``pool``,
    PAD-filled to ``cap``."""
    v = np.full((m, cap), PAD, np.uint32)
    for i in range(m):
        n = int(rng.integers(lo, hi + 1))
        v[i, :n] = np.sort(rng.choice(pool, size=n, replace=False))
    return v


def _pair_case(name):
    """(q_values u32[Cq], q_thresh, x_values u32[m, C], x_thresh u32[m])."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    pool = np.unique(hash_u32_np(np.arange(20_000)))
    if name == "nothing_live":
        # Thresholds below every hash: nq = nx = 0, and rows where only
        # one side has live values.
        q = _sorted_rows(rng, 1, 24, 24, 24, pool[pool > 1000])[0]
        x = _sorted_rows(rng, 6, 24, 1, 24, pool[pool > 1000])
        x[4, 0], x[5, 0] = 5, 7
        q_t = np.uint32(q[0] - 1)
        x_t = np.array([0, 10, 999, q[3], 5, 7], np.uint32)
        return q, q_t, x, x_t
    if name == "all_live":
        v = _sorted_rows(rng, 9, 32, 32, 32, pool)
        return v[0], np.uint32(PAD - 1), v, np.full(9, PAD - 1, np.uint32)
    if name == "padded_rows":
        # Rows added to fill a shard: all PAD, thresh 0, beside real rows.
        q = _sorted_rows(rng, 1, 16, 10, 16, pool)[0]
        x = _sorted_rows(rng, 8, 16, 2, 16, pool)
        x[5:] = PAD
        x_t = np.where(np.arange(8) < 5, np.uint32(PAD - 1), np.uint32(0))
        return q, np.uint32(PAD - 1), x, x_t.astype(np.uint32)
    if name == "pad_tails":
        x = _sorted_rows(rng, 40, 48, 0, 30, pool)
        q = np.concatenate([x[3, :20], np.full(28, PAD, np.uint32)])
        # The query's threshold is one of its own hashes, and row 4 shares
        # the query's ten smallest: there U_(k) is the query's, at tau_pair.
        x[4] = PAD
        x[4, :10] = q[:10]
        x_t = rng.integers(0, 2**32 - 1, size=40, dtype=np.uint32)
        x_t[3:5] = PAD - 1
        return q, q[15], x, x_t
    if name == "zero_hash":
        # Hash value 0 live in a row: U_(k) = 0 where it is the only one.
        x = _sorted_rows(rng, 6, 12, 3, 12, pool[pool > 0])
        x[:, 0] = 0
        q = x[1].copy()
        x_t = np.array([0, 0, PAD - 1, x[3, 2], 1, 2**31], np.uint32)
        return q, np.uint32(PAD - 1), x, x_t
    m = int(name.split("_m")[1])
    x = _sorted_rows(rng, m, 40, 0, 40, pool)
    shared = x[0][x[0] != PAD][:10]
    qv = np.unique(np.concatenate([shared, rng.choice(pool, 30)]))
    q = np.full(40, PAD, np.uint32)
    q[:len(qv)] = qv
    x_t = rng.integers(0, 2**32 - 1, size=m, dtype=np.uint32)
    return q, np.uint32(rng.integers(0, 2**32 - 1)), x, x_t


@pytest.mark.parametrize("case", ["nothing_live", "all_live", "padded_rows",
                                  "pad_tails", "zero_hash", "random_m1",
                                  "random_m37", "random_m1000"])
def test_masked_max_matches_index_form(case):
    """U_(k) as each row's masked max gives the former per-row index read's
    d_hat, k and k_cap bit for bit."""
    q, q_t, x, x_t = _pair_case(case)
    xj, x_tj = jnp.asarray(x), jnp.asarray(x_t)
    got = gkmv_pair_estimate(jnp.asarray(q), None, q_t, xj, None, x_tj)
    want = _pair_estimate_by_index(jnp.asarray(q), q_t, xj, x_tj)
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.uint32),
                                  np.asarray(want[0]).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


@pytest.mark.parametrize("seed", [0, 1])
def test_dense_sweep_scores_match_numpy_twin(seed):
    """``_scores_jnp`` (the dense sweep) against ``gbkmv_containment_np``,
    bit for bit."""
    from repro.sketchindex.distributed import _scores_jnp

    rng = np.random.default_rng(seed)
    pool = np.unique(hash_u32_np(np.arange(20_000)))
    m, cap, gq, w = 300, 40, 5, 2
    x = SimpleNamespace(
        values=_sorted_rows(rng, m, cap, 0, cap, pool),
        thresh=rng.integers(2**30, 2**32 - 1, size=m, dtype=np.uint32),
        buf=rng.integers(0, 2**32, size=(m, w), dtype=np.uint32))
    x.lengths = (x.values != PAD).sum(-1).astype(np.int32)
    qv = x.values[rng.choice(m, size=gq, replace=False)]
    qt = rng.integers(2**30, 2**32 - 1, size=gq, dtype=np.uint32)
    qb = rng.integers(0, 2**32, size=(gq, w), dtype=np.uint32)
    qs = ((qv != PAD).sum(-1) + 7).astype(np.int32)
    got = np.asarray(_scores_jnp(
        jnp.asarray(x.values), jnp.asarray(x.lengths), jnp.asarray(x.thresh),
        jnp.asarray(x.buf), jnp.asarray(qv), jnp.asarray(qt),
        jnp.asarray(qb), jnp.asarray(qs)))
    want = np.stack([gbkmv_containment_np(qv[g], qt[g], qb[g], qs[g], x)
                     for g in range(gq)], axis=-1)
    assert want.max() > 0
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def _pair_estimate_full_width(q_values, q_thresh, x_values, x_thresh):
    """The former ``gkmv_pair_estimate``: nq, U_(k) and K∩ over every column
    of the PAD-padded query row, K∩ by one broadcast compare. The oracle of
    the form bounded by the query's live width."""
    q_values = jnp.asarray(q_values, dtype=jnp.uint32)
    x_values = jnp.asarray(x_values, dtype=jnp.uint32)
    tau_pair = jnp.minimum(jnp.asarray(x_thresh, jnp.uint32),
                           jnp.asarray(q_thresh, jnp.uint32))
    nq = jnp.sum(q_values[None, :] <= tau_pair[:, None], axis=-1).astype(jnp.int32)
    nx = jnp.sum(x_values <= tau_pair[:, None], axis=-1).astype(jnp.int32)
    live = x_values <= tau_pair[:, None]
    member = jnp.any(x_values[:, :, None] == q_values[None, None, :], axis=-1)
    k_cap = jnp.sum(live & member, axis=-1).astype(jnp.int32)
    k = nq + nx - k_cap
    zero = jnp.uint32(0)
    uq = jnp.max(jnp.where(q_values[None, :] <= tau_pair[:, None],
                           q_values[None, :], zero), axis=-1)
    ux = jnp.max(jnp.where(live, x_values, zero), axis=-1)
    u = jnp.maximum(uq, ux)
    u_unit = (u.astype(jnp.float32) + 1.0) / TWO32
    valid = (k >= 2) & (k_cap >= 1)
    d_hat = jnp.where(
        valid,
        (k_cap.astype(jnp.float32) / jnp.maximum(k, 1).astype(jnp.float32))
        * ((k.astype(jnp.float32) - 1.0) / jnp.maximum(u_unit, 1e-30)),
        jnp.where(k_cap >= 1, k_cap.astype(jnp.float32), 0.0),
    )
    return d_hat, k, k_cap


#: Capacity of the bounded-width cases: three compare chunks.
CAP = 24


def _overflow_case(rng, m, widths):
    """Records and queries packed by ``pack_csr`` at capacity ``CAP``.

    About a third of the m records hold more hashes than fit: they keep
    their smallest and lower their threshold to the largest kept. Query g
    holds ``widths[g]`` live hashes, half of them drawn from a record it
    overlaps. Returns (x PackedSketches, q values, q thresholds)."""
    from repro.core.sketches import pack_csr

    pool = np.unique(hash_u32_np(np.arange(4_000)))
    rows = [np.sort(rng.choice(pool, size=int(rng.integers(0, 2 * CAP)),
                               replace=False)) for _ in range(m)]
    tau = np.uint32(pool.max())
    x = pack_csr(np.concatenate(rows), np.repeat(np.arange(m), [len(r) for r in rows]),
                 m, np.full(m, tau, np.uint32), np.array([len(r) for r in rows]),
                 capacity=CAP)
    assert (x.thresh < tau).any() and (x.thresh == tau).any()
    qv = np.full((len(widths), CAP), PAD, np.uint32)
    for g, w in enumerate(widths):
        near = rows[g % m][: w // 2]
        rest = rng.choice(np.setdiff1d(pool, near), size=w - len(near),
                          replace=False)
        qv[g, :w] = np.sort(np.concatenate([near, rest]))
    qt = rng.integers(2**31, 2**32 - 1, size=len(widths), dtype=np.uint32)
    qt[::2] = tau
    return x, qv, qt


@pytest.mark.parametrize("width", [0, 1, 7, 8, 9, 16, CAP - 1, CAP])
def test_bounded_query_width_matches_full_width(width):
    """K∩, nq and U_(k) over the query's live columns only give the full
    broadcast form's d_hat, k and k_cap bit for bit, beside records whose
    threshold capacity overflow lowered."""
    rng = np.random.default_rng(100 + width)
    x, qv, qt = _overflow_case(rng, 64, [width])
    args = (jnp.asarray(qv[0]), qt[0], jnp.asarray(x.values),
            jnp.asarray(x.thresh))
    got = gkmv_pair_estimate(args[0], None, args[1], args[2], None, args[3])
    want = _pair_estimate_full_width(*args)
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.uint32),
                                  np.asarray(want[0]).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    assert width == 0 or np.asarray(want[2]).max() > 0


@pytest.mark.parametrize("widths", [
    [5], [0, CAP], [0, 1, 7, 8, 9, 16, CAP - 1, CAP, 3, 2, 12, 17, 4, 0, 6, 1],
], ids=["gq1", "gq2", "gq16"])
def test_dense_sweep_bounded_width_matches_full_width(widths):
    """``_scores_jnp`` over a batch of mixed live widths (the batch's
    widest row bounds every query's compare) scores as the full broadcast
    form does, bit for bit."""
    import jax

    from repro.sketchindex.distributed import _scores_jnp

    rng = np.random.default_rng(len(widths))
    x, qv, qt = _overflow_case(rng, 200, widths)
    w = 2
    xb = rng.integers(0, 2**32, size=(x.num_records, w), dtype=np.uint32)
    qb = rng.integers(0, 2**32, size=(len(widths), w), dtype=np.uint32)
    qs = (np.asarray(widths) + 5).astype(np.int32)
    xs = [jnp.asarray(a) for a in (x.values, x.lengths, x.thresh, xb)]
    qs_ = [jnp.asarray(a) for a in (qv, qt, qb, qs)]
    got = np.asarray(jax.jit(_scores_jnp)(*xs, *qs_))

    def one_query(v, t, b, s):
        d_hat, _, _ = _pair_estimate_full_width(v, t, xs[0], xs[2])
        o1 = buffer_intersection(b, xs[3])
        return (o1.astype(jnp.float32) + d_hat) / jnp.maximum(
            s.astype(jnp.float32), 1.0)

    want = np.asarray(jax.jit(jax.vmap(one_query))(*qs_).T)
    assert (want > 0).any()
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
