"""Hypothesis property: the planner's candidate filter never drops a
record whose estimated containment clears the threshold (pruning bound
soundness), and the pruned path stays bit-identical to the dense sweep."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import api, planner  # noqa: E402
from repro.planner import prune  # noqa: E402

settings.register_profile("ci", max_examples=20, deadline=None)
settings.load_profile("ci")

id_set = st.sets(st.integers(min_value=0, max_value=500),
                 min_size=1, max_size=40)
dataset = st.lists(id_set, min_size=4, max_size=25)


@given(recs=dataset, q=id_set,
       frac=st.floats(0.05, 0.8), t=st.floats(0.05, 1.0))
def test_pruning_never_drops_a_qualifying_record(recs, q, frac, t):
    recs = [np.asarray(sorted(r)) for r in recs]
    total = sum(len(r) for r in recs)
    idx = api.get_engine("gbkmv").build(
        recs, max(int(total * frac), len(recs)))
    q = np.asarray(sorted(q))

    scores = idx.scores(q)                       # dense estimator, f32[m]
    qualifying = np.nonzero(scores >= t)[0]

    post = idx._sketch_pack().postings()
    _, hash_rows, bit_rows, sizes = idx._plan_queries([q])
    cand = prune.candidates_for(post, hash_rows[0], bit_rows[0], float(t),
                                int(sizes[0]))
    assert set(qualifying.tolist()) <= set(cand.rec_ids.tolist())

    # End to end: verify step returns exactly the dense hit set.
    np.testing.assert_array_equal(
        idx.query(q, float(t), plan="pruned"),
        idx.query(q, float(t), plan="dense"))


@given(recs=dataset, extra=dataset, q=id_set, t=st.floats(0.1, 1.0))
def test_postings_maintenance_preserves_parity(recs, extra, q, t):
    recs = [np.asarray(sorted(r)) for r in recs]
    extra = [np.asarray(sorted(r)) for r in extra]
    total = sum(len(r) for r in recs)
    idx = api.get_engine("gbkmv").build(recs, max(int(total * 0.3), len(recs)))
    idx._sketch_pack().postings()                # force incremental path
    idx.insert(extra)
    assert planner.postings_equal(
        idx._sketch_pack()._post, planner.build_postings(idx.core.sketches))
    q = np.asarray(sorted(q))
    np.testing.assert_array_equal(
        idx.query(q, float(t), plan="pruned"),
        idx.query(q, float(t), plan="dense"))
