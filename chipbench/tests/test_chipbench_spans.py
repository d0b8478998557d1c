"""The readers of the program's own spans (``http_ms``, ``answer_ms``,
``gather_ms``) on hand-made traces whose answers are counted by hand;
the spans themselves in a trace recorded by jax.profiler around a small
served index; and the device program names the benchmark matches on."""

from __future__ import annotations

import re
import threading

import numpy as np
import pytest

import chipbench_testlib  # noqa: F401
from bench import spec
from bench import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def _hand_trace():
    """A 100 ms window. Serve flushes start at 10, 40 and 95 ms (a fourth
    at -10 lies before the window). ``serve.hits`` takes 1 and 2 ms and
    ``flush.answer`` 2 and 1 ms in the window (one more starts after it);
    ``flush.gather`` takes 10 and 18 ms in it (one more starts before
    it). Every Python thread writes to a line called ``python``:

    * request A [5, 60) holds wait [7, 58): self 4 ms;
    * request B [6, 30) holds wait [8, 28), which A holds too: B is the
      latest-starting holder, so B's self time is 4 ms and A keeps 4;
    * request C [-3, 12) with wait [1, 11) starts before the window;
    * request D [98, 130) with wait [101, 125): self 8 ms, though its
      wait starts after the window;
    * request E [50, 52) was refused before it waited: self 2 ms.

    So 2.0 ms of answering and 28/3 ms of gathering per flush, and a
    mean HTTP self time of (4 + 4 + 8 + 2) / 4 = 4.5 ms per read."""
    def ev(name, s, e, plane=HOST):
        return (plane, "python", name, s * MS, (e - s) * MS)

    return [
        ev(tr.WINDOW_SPAN, 0, 100),
        ev("flush.execute", -10, -5), ev("flush.execute", 10, 20),
        ev("flush.execute", 40, 50), ev("flush.execute", 95, 105),
        ev("serve.hits", 18, 19), ev("serve.hits", 48, 50),
        ev("flush.answer", 20, 22), ev("flush.answer", 50, 51),
        ev("flush.answer", 105, 106),
        ev("flush.gather", -5, 0), ev("flush.gather", 0, 10),
        ev("flush.gather", 22, 40),
        ev("http.request", 5, 60), ev("http.wait", 7, 58),
        ev("http.request", 6, 30), ev("http.wait", 8, 28),
        ev("http.request", -3, 12), ev("http.wait", 1, 11),
        ev("http.request", 98, 130), ev("http.wait", 101, 125),
        ev("http.request", 50, 52),
        ev("fusion.1", 12, 18, plane=DEV),
    ]


def _read(metric, events):
    return spec.reader(metric)({"trace": tr.reduce(events)})


@pytest.mark.parametrize("metric,want", [
    ("answer_ms.topk", 2.0),
    ("gather_ms.topk", 28 / 3),
    ("http_ms.topk", 4.5),
])
def test_span_reader_on_hand_trace(metric, want):
    assert _read(metric, _hand_trace()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["answer_ms.topk", "gather_ms.topk",
                                    "http_ms.topk"])
def test_span_reader_reads_nothing_without_spans(metric):
    """A program without the spans (or a run without a trace) reads
    nothing, and does not raise."""
    bare = [e for e in _hand_trace()
            if e[2] in (tr.WINDOW_SPAN, "fusion.1", "client.topk")]
    assert _read(metric, bare) is None
    assert spec.reader(metric)({"trace": None}) is None


def _small_index():
    from repro import api
    from repro.data.synth import generate_dataset
    from repro.launch.mesh import make_mesh
    from repro.sketchindex import ShardedIndex

    recs = generate_dataset(m=100, n_elems=3000, alpha_freq=1.1,
                            alpha_size=2.0, seed=0)
    index = api.get_engine("gbkmv").build(
        recs, sum(len(r) for r in recs) // 5)
    return ShardedIndex(index, make_mesh((1, 1), ("data", "model"))), recs


def _inside(inner, outers):
    return any(s <= inner[0] and inner[1] <= e for s, e in outers)


def test_served_reads_write_their_spans_to_the_profiler(tmp_path):
    """Top-k reads served over HTTP under a jax.profiler session: every
    span of the served path is in the trace, nested as the program
    opens them, and the three readers read it."""
    import jax

    from bench import spans
    from repro.service import (AsyncSketchServer, ServiceApp,
                               ServiceClient, ServiceHandle)

    sharded, recs = _small_index()
    srv = AsyncSketchServer(sharded, max_batch=4, max_wait=0.002)
    with ServiceHandle(ServiceApp(srv)) as h:
        ServiceClient(*h.address).topk(recs[0], 5)     # compile outside

        def client(i):
            cli = ServiceClient(*h.address)
            for j in range(3):
                cli.topk(recs[4 * i + j], 5)
            cli.close()

        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        jax.profiler.stop_trace()
        assert not any(t.is_alive() for t in threads)
    ev = tr.load_xplane(str(tmp_path))
    got = {n: spans.named(ev, n) for n in (
        "flush.gather", "flush.execute", "serve.sketch", "serve.topk",
        "serve.hits", "flush.answer", "http.request", "http.wait")}
    assert all(got.values()), {n: len(v) for n, v in got.items()}
    for n in ("serve.sketch", "serve.topk", "serve.hits"):
        assert all(_inside(s, got["flush.execute"]) for s in got[n]), n
    assert all(_inside(s, got["http.request"]) for s in got["http.wait"])
    for n in ("flush.answer", "flush.gather"):     # the flush's siblings
        assert not any(_inside(s, got["flush.execute"]) for s in got[n]), n
    assert len(got["http.request"]) >= 12
    ctx = {"trace": tr.reduce(ev)}
    assert spec.reader("http_ms.topk")(ctx) > 0
    assert spec.reader("answer_ms.topk")(ctx) > 0
    assert spec.reader("gather_ms.topk")(ctx) >= 0


def _module_name(lowered) -> str:
    return re.match(r"HloModule (\S+?),",
                    lowered.compile().as_text()).group(1)


def test_program_names_the_benchmark_matches():
    """The device programs of a top-k flush keep the names the trace
    reduction finds them by: ``dense_sweep_roofline`` matches the sweep's
    module by its jit name."""
    from repro.sketchindex import distributed

    sharded, recs = _small_index()
    qp = distributed.batch_queries(sharded.host, recs[:4])
    sweep = distributed._score_batch_jit.lower(
        sharded.didx, qp, sharded.backend, sharded.mesh)
    roofline = spec._load_module(
        spec.reader_path("dense_sweep_roofline.topk"), "roofline_reader")
    assert roofline.PROGRAM in _module_name(sweep)
    scores = np.zeros((sharded.didx.values.shape[0], 4), np.float32)
    topk = distributed.distributed_topk.lower(scores, 5, sharded.mesh)
    assert "distributed_topk" in _module_name(topk)
