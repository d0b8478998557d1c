"""Block-compressed postings: encode/decode identity (deterministic fuzz
+ hypothesis property), sparse/dense block choice, incremental
maintenance on blocks, header-bound block skipping (with exact parity),
the device block-decode paths (jnp twin vs Pallas kernel vs host), and
honest arena space accounting."""

import numpy as np
import pytest

from repro import api, planner
from repro.planner import postings as P
from repro.planner import prune


def _random_csr(rng, nrows_max=14, len_max=350):
    """Random flat CSR: per-row sorted ids, duplicates allowed, mixed
    dense/sparse/empty rows — every shape the encoder must survive."""
    rows = []
    for _ in range(int(rng.integers(0, nrows_max))):
        n = int(rng.integers(0, len_max))
        style = int(rng.integers(0, 4))
        if style == 0:
            ids = np.sort(rng.integers(0, 8000, size=n))          # dups ok
        elif style == 1:
            ids = np.arange(n) + int(rng.integers(0, 64))         # dense run
        elif style == 2 and n:
            ids = np.sort(rng.choice(2**30, size=n, replace=False))
        else:
            ids = np.sort(rng.integers(0, 40, size=n))            # heavy dups
        rows.append(ids.astype(np.int64))
    offsets = np.concatenate(
        [[0], np.cumsum([len(r) for r in rows])]).astype(np.int64)
    rec = (np.concatenate(rows).astype(np.int32)
           if rows and offsets[-1] else np.zeros(0, np.int32))
    return offsets, rec


def test_encode_decode_identity_fuzz():
    rng = np.random.default_rng(0)
    for trial in range(120):
        offsets, rec = _random_csr(rng)
        st = P.encode_store(offsets, rec)
        off2, rec2 = P.decode_store(st)
        np.testing.assert_array_equal(off2, offsets, err_msg=str(trial))
        np.testing.assert_array_equal(rec2, rec, err_msg=str(trial))
        np.testing.assert_array_equal(st.row_lengths(), np.diff(offsets))
        # header invariants: first/last bracket every decoded block
        ids, cnts = P.decode_blocks(st, np.arange(st.num_blocks))
        pos = np.concatenate([[0], np.cumsum(cnts)])
        for b in range(st.num_blocks):
            seg = ids[pos[b]: pos[b + 1]]
            assert seg[0] == st.first[b] and seg[-1] == st.last[b]
            assert len(seg) <= P.BLOCK


def test_block_choice_dense_vs_sparse():
    rng = np.random.default_rng(2)
    # ~50% density, jittered: bitmap beats bitpacked deltas
    ids = np.sort(rng.choice(6000, size=3000, replace=False))
    st = P.encode_store(np.asarray([0, 3000]), ids)
    kind = (st.meta >> np.uint32(13)) & 1
    assert kind.all()
    # wide-spread ids: bitpacked deltas win and still beat flat int32
    # (≈22-bit deltas vs 32-bit ids on a 2^30 universe)
    ids2 = np.sort(rng.choice(2**30, size=3000, replace=False))
    st2 = P.encode_store(np.asarray([0, 3000]), ids2)
    assert not ((st2.meta >> np.uint32(13)) & 1).any()
    assert st2.nbytes() < 3000 * 4
    # duplicate ids (32-bit collisions) can never sit in a dense bitmap
    ids3 = np.repeat(np.arange(200), 2)
    st3 = P.encode_store(np.asarray([0, 400]), ids3)
    assert not ((st3.meta >> np.uint32(13)) & 1).any()
    _, rec3 = P.decode_store(st3)
    np.testing.assert_array_equal(rec3, ids3)


def test_blocked_truncate_append_equal_rebuild_across_boundaries():
    """Lists longer than one block keep full-block prefixes byte-stable
    through append; truncation slices keys, headers, and payload."""
    from repro.core.sketches import pack_rows

    rng = np.random.default_rng(3)

    def mkpack(rows):
        thr = np.full(len(rows), 2**32 - 2, np.uint32)
        sizes = np.full(len(rows), 5, np.int32)
        return pack_rows([np.sort(np.asarray(r, np.uint32)) for r in rows],
                         thr, sizes)

    # A shared element set forces >128-entry posting lists.
    common = rng.choice(2**31, size=7, replace=False)
    rows = [np.concatenate([common, rng.choice(2**31, size=20)])
            for _ in range(300)]
    post = P.build_postings(mkpack(rows))
    assert (post.tail.row_lengths().max()) > P.BLOCK   # multi-block lists

    rows2 = rows + [np.concatenate([common, rng.choice(2**31, size=20)])
                    for _ in range(40)]
    inc = P.append_rows(post, mkpack(rows2), 300, 340)
    fresh = P.build_postings(mkpack(rows2))
    assert planner.postings_equal(inc, fresh)

    tau = np.uint32(2**30)
    tr = P.truncate_postings(fresh, tau)
    fresh_cut = P.build_postings(mkpack(
        [np.asarray(r)[np.asarray(r) <= tau] for r in rows2]))
    assert np.array_equal(tr.keys, fresh_cut.keys)
    assert P._stores_equal(tr.tail, fresh_cut.tail)


def test_posting_lengths_from_headers():
    rng = np.random.default_rng(4)
    offsets, rec = _random_csr(rng, nrows_max=10)
    keys = np.sort(rng.choice(2**31, size=len(offsets) - 1,
                              replace=False)).astype(np.uint32)
    post = P.from_flat(keys, offsets, rec, np.zeros(1, np.int64),
                       np.zeros(0, np.int32), 8000, 2**31)
    probe = np.concatenate([keys, np.asarray([1, 2**31 - 5], np.uint32)])
    want = np.concatenate([np.diff(offsets), [0, 0]])
    np.testing.assert_array_equal(post.posting_lengths(probe), want)


# ---------------------------------------------------------------------------
# header-bound block skipping
# ---------------------------------------------------------------------------


def test_block_skipping_header_bound_exact():
    """Synthetic postings with controlled hash values: near-2³² hashes
    make unit ≈ 1, so bound_tail(c) ≈ c and the per-block keep/skip
    decision is computable by hand. Lists A/B/C overlap on one id range
    (c_max = 3 survives t = 0.6 at |Q| = 4), list D sits alone in a
    far range (c_max = 1 → ub = 0.25 < t: its block must skip and its
    records must not surface)."""
    top = np.uint32(2**32 - 10)
    keys = np.asarray([top - 3, top - 2, top - 1, top], np.uint32)
    shared = np.arange(128, dtype=np.int32)          # lists A, B, C
    alone = np.arange(5000, 5128, dtype=np.int32)    # list D
    offsets = np.asarray([0, 128, 256, 384, 512], np.int64)
    rec = np.concatenate([shared, shared, shared, alone])
    post = P.from_flat(keys, offsets, rec, np.zeros(1, np.int64),
                       np.zeros(0, np.int32), 6000, top)
    cand = prune.candidates_for(post, keys, np.zeros(0, np.int64),
                                0.6, 4)
    assert cand.skipped_blocks == 1
    assert cand.blocks == 3
    np.testing.assert_array_equal(cand.rec_ids, shared)   # D never decoded
    np.testing.assert_array_equal(cand.counts, np.full(128, 3))
    # threshold 0 decodes everything, D's records included
    cand0 = prune.candidates_for(post, keys, np.zeros(0, np.int64), 0.0, 4)
    assert cand0.skipped_blocks == 0
    np.testing.assert_array_equal(cand0.rec_ids,
                                  np.concatenate([shared, alone]))


def test_block_skipping_end_to_end_parity():
    """Two disjoint record-id clusters: whatever the header bounds skip,
    pruned results stay bit-identical to the dense sweep."""
    rng = np.random.default_rng(7)
    lo = [rng.choice(3000, size=12, replace=False) for _ in range(160)]
    hi = [3000 + rng.choice(3000, size=12, replace=False)
          for _ in range(160)]
    recs = [np.asarray(r) for r in lo + hi]
    total = sum(len(r) for r in recs)
    idx = api.get_engine("gbkmv").build(recs, int(total * 0.4),
                                        backend="numpy")
    queries = [recs[3], recs[170], np.asarray([1, 2, 3, 9, 11])]
    for t in (0.3, 0.6, 0.9):
        dense = idx.batch_query(queries, t, plan="dense")
        pruned = idx.batch_query(queries, t, plan="pruned")
        for d, p in zip(dense, pruned):
            np.testing.assert_array_equal(d, p)


# ---------------------------------------------------------------------------
# device block decode: jnp twin, Pallas kernel, dense overlay
# ---------------------------------------------------------------------------


def _device_decode_case(rng):
    offsets, rec = _random_csr(rng, nrows_max=8, len_max=300)
    st = P.encode_store(offsets, rec)
    if st.num_blocks == 0:
        return None
    kind = ((st.meta >> np.uint32(13)) & 1).astype(np.int64)
    sparse = np.nonzero(kind == 0)[0]
    if len(sparse) == 0:
        return None
    return st, sparse


def test_block_decode_jnp_matches_host():
    import jax.numpy as jnp
    from repro.kernels import postings_merge as pm

    rng = np.random.default_rng(11)
    checked = 0
    while checked < 6:
        case = _device_decode_case(rng)
        if case is None:
            continue
        st, sparse = case
        cnt = st.counts()[sparse].astype(np.int32)
        bw = ((st.meta[sparse] >> np.uint32(8)) & np.uint32(0x1F)
              ).astype(np.int32)
        pay = jnp.asarray(np.concatenate(
            [st.payload, np.zeros(pm.DECODE_WINDOW, np.uint32)]))
        got = np.asarray(pm._decode_sparse_jnp(
            jnp.asarray(st.first[sparse]),
            jnp.asarray(st.off[sparse], jnp.int32),
            jnp.asarray(bw), jnp.asarray(cnt), pay))
        want_ids, want_cnt = P.decode_blocks(st, sparse)
        pos = np.concatenate([[0], np.cumsum(want_cnt)])
        for j in range(len(sparse)):
            np.testing.assert_array_equal(
                got[j, : int(want_cnt[j])], want_ids[pos[j]: pos[j + 1]])
        checked += 1


def test_block_decode_pallas_kernel_matches_jnp():
    """The Pallas block-decode kernel (interpret mode) is lane-for-lane
    identical to the jnp twin on real encoded stores."""
    import jax.numpy as jnp
    from repro.kernels import postings_merge as pm

    rng = np.random.default_rng(13)
    case = None
    while case is None:
        case = _device_decode_case(rng)
    st, sparse = case
    cnt = st.counts()[sparse].astype(np.int32)
    bw = ((st.meta[sparse] >> np.uint32(8)) & np.uint32(0x1F)
          ).astype(np.int32)
    pay = jnp.asarray(np.concatenate(
        [st.payload, np.zeros(pm.DECODE_WINDOW, np.uint32)]))
    first = jnp.asarray(st.first[sparse])
    off = jnp.asarray(st.off[sparse], jnp.int32)
    a = np.asarray(pm._decode_sparse_jnp(first, off, jnp.asarray(bw),
                                         jnp.asarray(cnt), pay))
    b = np.asarray(pm._decode_sparse_pallas(
        first, off, jnp.asarray(bw), jnp.asarray(cnt),
        pm._decode_payload_rows(jnp.asarray(st.payload)), interpret=True))
    lanes = np.arange(P.BLOCK)[None, :]
    valid = lanes < cnt[:, None]
    np.testing.assert_array_equal(a[valid], b[valid])


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_device_dense_block_overlay_parity(backend):
    """Indexes whose postings contain dense-bitmap blocks answer
    device-pruned queries bit-identically to the dense sweep (the tbd
    overlay path)."""
    rng = np.random.default_rng(17)
    share = set(np.sort(rng.choice(1200, size=660, replace=False)).tolist())
    recs = [np.asarray([3000 + i] + ([7] if i in share else []))
            for i in range(1200)]
    idx = api.get_engine("gbkmv").build(recs, budget=5000, backend=backend,
                                        r=0)
    kind = (idx._sketch_pack().postings().tail.meta >> np.uint32(13)) & 1
    assert int(kind.sum()) > 0                 # dense blocks really exist
    q = np.asarray([7, 99991, 99992])
    dense = idx.batch_query([q], 0.2, plan="dense")[0]
    pruned = idx.batch_query([q], 0.2, plan="pruned")[0]
    assert idx.last_plan.tail_dense_blocks > 0
    np.testing.assert_array_equal(dense, pruned)


# ---------------------------------------------------------------------------
# honest space accounting
# ---------------------------------------------------------------------------


def test_arena_nbytes_counts_postings_and_mirrors():
    rng = np.random.default_rng(19)
    recs = [rng.choice(5000, size=30, replace=False) for _ in range(150)]
    idx = api.get_engine("gbkmv").build(recs, budget=2000, backend="jnp")
    arena = idx._sketch_pack()
    base = arena.sketch_nbytes()
    assert idx.nbytes() == base                 # nothing derived yet
    post_b = arena.postings_nbytes()            # builds the postings
    assert post_b > 0
    assert idx.nbytes() == base + post_b
    idx.batch_query([recs[0]], 0.5, plan="pruned")  # device mirrors placed
    total = idx.nbytes()
    assert total > base + post_b
    dev = arena.device_postings().nbytes() + arena.device_pack().nbytes()
    assert total == base + post_b + dev
    # The device mirror ships only the tail store — strictly less than
    # the at-rest postings (buffer lists never cross to the device).
    assert arena.device_postings().nbytes() < post_b


