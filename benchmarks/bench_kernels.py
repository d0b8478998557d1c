"""Pallas kernel microbench. On the CPU the kernel runs in interpret
mode, so its times there gate correctness only; each row names the
platform it ran on.

Sweeps the GB-KMV scoring kernel vs the pure-jnp oracle over index sizes
and query-batch sizes Gq; the Gq sweep is the query-batching §Perf knob
(one sweep of the sketch matrix amortized over Gq queries)."""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import write_csv
from repro import api
from repro.data.synth import generate_dataset, make_query_workload
from repro.kernels.ops import score_index
from repro.kernels.ref import gbkmv_score_ref
from repro.sketchindex import batch_queries


def run(quick: bool = True):
    rows = []
    m = 256 if quick else 2048
    recs = generate_dataset(m=m, n_elems=20_000, alpha_freq=1.1,
                            alpha_size=2.0, seed=0)
    total = sum(len(r) for r in recs)
    index = api.get_engine("gbkmv").build(recs, int(total * 0.1), r=64).core
    s = index.sketches
    for gq in (1, 4, 16):
        qp = batch_queries(index, make_query_workload(recs, gq))
        args = (s.values, s.thresh,
                s.buf if s.buf.shape[1] else np.zeros((m, 1), np.uint32),
                qp.values, qp.thresh,
                qp.buf if qp.buf.shape[1] else np.zeros((gq, 1), np.uint32),
                qp.sizes)
        out_k = np.asarray(score_index(*args))
        out_r = np.asarray(gbkmv_score_ref(
            args[0], args[1].reshape(-1), args[2],
            args[3], args[4].reshape(-1), args[5], args[6].reshape(-1)))
        err = float(np.abs(out_k[:m] - out_r).max())

        t0 = time.time()
        jax.block_until_ready(score_index(*args))
        t_k = time.time() - t0
        t0 = time.time()
        jax.block_until_ready(gbkmv_score_ref(
            args[0], args[1].reshape(-1), args[2],
            args[3], args[4].reshape(-1), args[5], args[6].reshape(-1)))
        t_r = time.time() - t0
        rows.append({"records": m, "gq": gq, "max_abs_err": f"{err:.2e}",
                     "kernel_ms": round(t_k * 1e3, 1),
                     "jnp_ref_ms": round(t_r * 1e3, 1),
                     "platform": jax.default_backend()})
    write_csv("kernel_microbench.csv", rows)
    return rows
