"""The readers of the threshold route (``route_fused``,
``fused_roofline``) on hand-made snapshots and traces whose answers are
counted by hand; the fused route's byte count; and the device program
name ``fused_roofline`` matches on."""

from __future__ import annotations

import re
import types

import numpy as np
import pytest

import chipbench_testlib  # noqa: F401
from bench import spec
from bench import trace as tr

DEV = "/device:TPU:0"
MS = 1e6


def _snap(stages, flushes=0, batch_total=0):
    return {"stages": stages, "flushes": flushes, "batch_total": batch_total}


def test_route_fused_share_of_served_batches():
    """3 dense and 1 fused batch in the window (2 dense before it)."""
    before = _snap({"route.dense": (2, 20.0)})
    after = _snap({"route.dense": (5, 50.0), "route.fused": (1, 16.0)})
    read = spec.reader("route_fused.threshold")
    assert read({"before": before, "after": after}) == pytest.approx(25.0)
    only_dense = _snap({"route.dense": (5, 50.0)})
    assert read({"before": before, "after": only_dense}) == 0.0


def test_route_fused_reads_nothing_without_route_counts():
    """A program without the route tallies (or a window that served no
    read) reads nothing, and does not raise."""
    read = spec.reader("route_fused.threshold")
    plain = _snap({"serve.sketch": (4, 0.1)})
    assert read({"before": _snap({}), "after": plain}) is None
    same = _snap({"route.dense": (2, 20.0)})
    assert read({"before": same, "after": same}) is None


def test_bytes_of_a_hand_counted_fused_batch():
    w = spec.work("fused_prune")
    # 200 tail blocks at the store's mean body (5,000 words over 50
    # blocks: 400 B) plus a 12 B header each; 1,000 records with 2
    # bitmap words and 3 scalars; 4 queries of width 16.
    postings = 200 * (400 + 12)
    index = 1000 * (4 * 2 + 12)
    blob = 4 * (4 * 16 + 4 * 2 + 12)
    assert w.bytes_read(tail_blocks=200, store_blocks=50,
                        payload_words=5000, records=1000, buf_words=2,
                        queries=4, capacity=16) == postings + index + blob


def _stub_system():
    tail = types.SimpleNamespace(num_blocks=50,
                                 payload=np.zeros(5000, np.uint32))
    sketches = types.SimpleNamespace(
        postings=lambda: types.SimpleNamespace(tail=tail))
    didx = types.SimpleNamespace(num_records=1000,
                                 buf=np.zeros((1000, 2), np.uint32),
                                 values=np.zeros((1000, 16), np.uint32))
    sharded = types.SimpleNamespace(didx=didx, host=types.SimpleNamespace(
        sketches=sketches))
    return types.SimpleNamespace(sharded=sharded)


def _fused_ctx(events):
    """Two fused calls of 1 ms each in a 100 ms window; the probe tallied
    400 tail blocks over 2 planner-routed batches in it; 2 flushes of 4."""
    return {
        "trace": tr.reduce(events),
        "peaks": spec.peaks("TPU v5 lite"),
        "work": spec.work,
        "system": _stub_system(),
        "before": _snap({"probe.tail_blocks": (1, 10.0)}),
        "after": _snap({"probe.tail_blocks": (3, 410.0)}, flushes=2,
                       batch_total=8),
    }


def _events(program="jit_fused_hit_words"):
    def mod(s, e):
        return (DEV, tr.MODULES_LINE, f"{program}(42)", s * MS, (e - s) * MS)

    return [("/host:CPU", "python", tr.WINDOW_SPAN, 0.0, 100 * MS),
            mod(10, 11), mod(50, 51), mod(-5, -4),
            (DEV, tr.OPS_LINE, "fusion.3", 10 * MS, 1 * MS)]


def test_fused_roofline_on_hand_trace():
    per_call = 200 * 412 + 1000 * 20 + 4 * (4 * 16 + 4 * 2 + 12)
    want = 100.0 * 2 * per_call / 819e9 / 2e-3
    read = spec.reader("fused_roofline.threshold")
    assert read(_fused_ctx(_events())) == pytest.approx(want)


def test_fused_roofline_reads_nothing_without_program_or_tallies():
    read = spec.reader("fused_roofline.threshold")
    assert read(_fused_ctx(_events("jit__score_batch_jit"))) is None
    ctx = _fused_ctx(_events())
    ctx["after"]["stages"] = {}
    assert read(ctx) is None
    ctx["trace"] = None
    assert read(ctx) is None


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_fused_program_name_the_benchmark_matches():
    """The fused hit-words program of a pruned threshold batch keeps the
    name ``fused_roofline`` finds it by."""
    from repro import api
    from repro.data.synth import generate_dataset
    from repro.kernels import postings_merge
    from repro.planner import device as planner_device
    from repro.sketchindex.distributed import batch_queries

    recs = generate_dataset(m=100, n_elems=3000, alpha_freq=1.1,
                            alpha_size=2.0, seed=0)
    index = api.get_engine("gbkmv").build(
        recs, sum(len(r) for r in recs) // 5)
    arena = index.core.sketches
    qp = batch_queries(index.core, recs[:4])
    dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp, 0.5)
    lowered = postings_merge.fused_hit_words.lower(
        dpost.keys, dpost.row_blocks, dpost.first, dpost.meta, dpost.off,
        dpost.payload, dpack.values, dpack.thresh, dpack.buf, sq.blob,
        gq=sq.gq, cq=sq.cq, w=sq.w, m=arena.num_records, backend="jnp",
        interpret=True, has_dense=dpost.has_dense)
    name = re.match(r"HloModule (\S+?),",
                    lowered.compile().as_text()).group(1)
    roofline = spec._load_module(
        spec.reader_path("fused_roofline.threshold"), "fused_reader")
    assert roofline.PROGRAM in name
