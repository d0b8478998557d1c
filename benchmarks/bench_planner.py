"""Planner bench: dense index sweep vs postings-pruned filter-and-verify.

QPS and candidate-set sizes at thresholds {0.5, 0.7, 0.9} on the Zipf
workload (the Fig. 16 generator) — the perf trajectory for the
candidate-pruning query planner — plus top-k rows at k ∈ {10, 100}
(pruned fused-device/upper-bound path vs the dense full-sweep ranking,
with per-stage splits and their own same-backend regression gate under
``topk_rows_by_backend``). Parity between the two paths is asserted on
every batch and every top-k query: a mismatch raises (and fails the CI
smoke step), because the planner's whole contract is bit-identical
results.

``run(quick, json_out=..., backend=..., baseline=..., calibrate=...)``:

* ``backend`` picks the scoring implementation ("jnp" default; CI also
  smokes "numpy" — with jnp/pallas the pruned path runs device-resident
  over the sketch arena).
* ``baseline`` points at a committed BENCH_PLANNER.json; the run FAILS
  if pruned-path QPS regresses below it — >10% for same-backend runs
  (dense-QPS-ratio normalized, so machine speed cancels out and the
  gate is effectively "block compression may cost at most 10% pruned
  QPS"), >20% for cross-backend runs (raw QPS, inherently noisier).
  Independently of any baseline, the run FAILS if the block-compressed
  postings exceed ``MAX_POSTINGS_RATIO`` × the packed sketch bytes —
  the space claim the compressed format exists to hold.
* ``calibrate`` fits the core/cost_model.py query-path constants from
  the measured QPS (mean_probe_hits feeds the pruned-path model) and
  embeds them under the artifact's "calibration" key —
  ``cost_model.load_calibration`` / $REPRO_COST_CALIBRATION installs
  them so ``plan="auto"`` uses measured instead of hand-set constants.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import write_csv
from repro import api
from repro.data.synth import generate_dataset, make_query_workload
from repro.obs import StageProfiler, attach
from repro.planner import candidates_for
from repro.planner.plan import probe_hits_per_query

THRESHOLDS = (0.5, 0.7, 0.9)
TOPK_KS = (10, 100)
BATCH = 16
REGRESSION_TOLERANCE = 0.8        # cross-backend: ≥ 0.8 × baseline (raw)
COMPRESSION_QPS_TOLERANCE = 0.9   # same-backend: ≥ 0.9 × baseline (scaled)
MAX_POSTINGS_RATIO = 0.6          # compressed postings ≤ 0.6 × sketch bytes


def _batches(queries):
    return [queries[i : i + BATCH] for i in range(0, len(queries), BATCH)]


def _time_path(index, batches, threshold, plan, repeats: int = 3) -> float:
    """Best-of-``repeats`` seconds for one pass over the workload (after
    a warmup pass). Best-of, not mean-of: scheduler noise only ever adds
    time, so the minimum is the stable estimate the QPS gate needs to
    stay reproducible across loaded CI machines."""
    for b in batches:                      # warmup: jit caches, postings
        index.batch_query(b, threshold, plan=plan)
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        for b in batches:
            index.batch_query(b, threshold, plan=plan)
        best = min(best, time.perf_counter() - t0)
    return best


def _stage_splits(index, batches, threshold, plan) -> dict:
    """Mean per-stage latency (ms) for one pass over the workload, from
    the obs stage profiler. Untimed and separate from ``_time_path`` on
    purpose: observing adds device syncs at stage seams, so the QPS
    gates keep measuring the production (unobserved) path."""
    prof = StageProfiler()
    with attach(None, prof):
        for b in batches:
            index.batch_query(b, threshold, plan=plan)
    return {name: round(s["mean_s"] * 1e3, 4)
            for name, s in sorted(prof.snapshot().items())}


def _time_topk(index, queries, k, plan, repeats: int = 3) -> float:
    """Best-of-``repeats`` seconds for one top-k pass over the workload
    (per-query calls — the api surface is single-query), after a warmup
    pass for jit caches."""
    for q in queries:
        index.topk(q, k, plan=plan)
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        for q in queries:
            index.topk(q, k, plan=plan)
        best = min(best, time.perf_counter() - t0)
    return best


def _topk_stage_splits(index, queries, k) -> dict:
    """Mean per-stage latency (ms) of the pruned top-k pass (untimed,
    separate from the QPS measurement — same rationale as
    :func:`_stage_splits`)."""
    prof = StageProfiler()
    with attach(None, prof):
        for q in queries:
            index.topk(q, k, plan="pruned")
    return {name: round(s["mean_s"] * 1e3, 4)
            for name, s in sorted(prof.snapshot().items())}


def check_baseline(rows, baseline_path: str, backend: str) -> list[str]:
    """Compare pruned QPS per threshold against a committed artifact.

    Returns human-readable failure strings (empty = pass). The artifact
    carries per-backend baseline rows (``rows_by_backend``) so every CI
    matrix cell gates against ITS OWN backend's committed trajectory,
    scaled by the dense-QPS ratio so a slower/faster CI machine doesn't
    trip the gate (dense is the stable denominator on one backend) —
    at ``COMPRESSION_QPS_TOLERANCE``, the "compression may cost at most
    10% pruned QPS" gate. Backends the artifact has never measured fall
    back to a raw comparison against the primary rows at the looser
    ``REGRESSION_TOLERANCE`` (cross-backend cost structures differ).
    """
    with open(baseline_path) as f:
        base = json.load(f)
    by_backend = base.get("rows_by_backend", {})
    if backend in by_backend:
        base_rows = {r["threshold"]: r for r in by_backend[backend]}
        same = True
    else:
        base_rows = {r["threshold"]: r for r in base.get("rows", [])}
        same = backend == base.get("workload", {}).get("backend", "jnp")
    failures = []
    for r in rows:
        b = base_rows.get(r["threshold"])
        if b is None:
            continue
        scale = r["qps_dense"] / max(b["qps_dense"], 1e-9) if same else 1.0
        tol = COMPRESSION_QPS_TOLERANCE if same else REGRESSION_TOLERANCE
        floor = tol * b["qps_pruned"] * scale
        if r["qps_pruned"] < floor:
            failures.append(
                f"t={r['threshold']}: pruned QPS {r['qps_pruned']:.1f} < "
                f"floor {floor:.1f} (baseline {b['qps_pruned']:.1f} × "
                f"scale {scale:.2f} × {tol})")
    return failures


def check_topk_baseline(topk_rows, baseline_path: str,
                        backend: str) -> list[str]:
    """Same-backend regression gate for the top-k rows, mirroring
    :func:`check_baseline`: pruned top-k QPS per k vs the committed
    ``topk_rows_by_backend``, dense-top-k-ratio scaled. Artifacts
    written before the top-k rows existed simply have no baseline —
    empty result, never a failure."""
    with open(baseline_path) as f:
        base = json.load(f)
    by_backend = base.get("topk_rows_by_backend", {})
    if backend in by_backend:
        base_rows = {r["k"]: r for r in by_backend[backend]}
        same = True
    else:
        base_rows = {r["k"]: r for r in base.get("topk_rows", [])}
        same = backend == base.get("workload", {}).get("backend", "jnp")
    failures = []
    for r in topk_rows:
        b = base_rows.get(r["k"])
        if b is None:
            continue
        scale = (r["qps_dense_topk"] / max(b["qps_dense_topk"], 1e-9)
                 if same else 1.0)
        tol = COMPRESSION_QPS_TOLERANCE if same else REGRESSION_TOLERANCE
        floor = tol * b["qps_pruned_topk"] * scale
        if r["qps_pruned_topk"] < floor:
            failures.append(
                f"k={r['k']}: pruned top-k QPS {r['qps_pruned_topk']:.1f} "
                f"< floor {floor:.1f} (baseline {b['qps_pruned_topk']:.1f} "
                f"× scale {scale:.2f} × {tol})")
    return failures


def run(quick: bool = True, json_out: str | None = None,
        backend: str = "jnp", baseline: str | None = None,
        calibrate: bool = False):
    m = 4000 if quick else 20_000
    n_elems = 20_000 if quick else 100_000
    nq = 64 if quick else 256
    recs = generate_dataset(m, n_elems, alpha_freq=0.8, alpha_size=1.0,
                            size_min=10, size_max=400, seed=5)
    total = sum(len(r) for r in recs)
    budget = int(total * 0.1)
    index = api.get_engine("gbkmv").build(recs, budget, backend=backend)
    queries = make_query_workload(recs, nq, seed=2)
    batches = _batches(queries)

    # Untimed candidate accounting, identical for every backend: the
    # host filter's candidate-set sizes and the probe's posting-entry
    # counts (the device path never materializes candidates on host).
    _, hash_rows, bit_rows, q_sizes = index._plan_queries(queries)
    post = index._sketch_pack().postings()
    probe = probe_hits_per_query(post, hash_rows, bit_rows)

    # Space accounting for the block-compressed postings: at-rest bytes
    # vs the packed sketch columns, plus the flat-CSR bytes the same
    # lists would cost (keys + int64 row pointers + int32 entries).
    arena = index._sketch_pack()
    sketch_b = arena.sketch_nbytes()
    post_b = post.nbytes()
    flat_b = (int(post.keys.nbytes) + 8 * (len(post.keys) + 1)
              + 4 * post.nnz + 8 * (post.buf.num_rows + 1)
              + 4 * post.buf.nnz)
    postings_info = {
        "postings_nbytes": int(post_b),
        "sketch_nbytes": int(sketch_b),
        "postings_ratio": round(post_b / max(sketch_b, 1), 4),
        "flat_equiv_nbytes": int(flat_b),
        "compression_vs_flat": round(flat_b / max(post_b, 1), 2),
    }

    rows = []
    for t in THRESHOLDS:
        dense = index.batch_query(queries, t, plan="dense")
        pruned = index.batch_query(queries, t, plan="pruned")
        for j, (d, p) in enumerate(zip(dense, pruned)):
            if not np.array_equal(d, p):
                raise RuntimeError(
                    f"planner parity broken at t={t}, query {j}: "
                    f"dense={d.tolist()} pruned={p.tolist()}")
        cands = [candidates_for(post, qh, qb, t, int(qs))
                 for qh, qb, qs in zip(hash_rows, bit_rows, q_sizes)]
        cand_sizes = [len(c.rec_ids) for c in cands]
        dt_dense = _time_path(index, batches, t, "dense")
        dt_pruned = _time_path(index, batches, t, "pruned")
        stages = _stage_splits(index, batches, t, "pruned")
        rows.append({
            "threshold": t,
            "qps_dense": round(nq / dt_dense, 2),
            "qps_pruned": round(nq / dt_pruned, 2),
            "speedup": round(dt_dense / dt_pruned, 3),
            "mean_candidates": round(float(np.mean(cand_sizes)), 2),
            "candidate_frac": round(float(np.mean(cand_sizes)) / m, 5),
            "mean_probe_hits": round(float(probe.mean()), 2),
            "mean_blocks": round(float(np.mean([c.blocks for c in cands])), 2),
            "mean_skipped_blocks": round(
                float(np.mean([c.skipped_blocks for c in cands])), 2),
            "mean_hits": float(np.mean([len(d) for d in dense])),
            "stages_ms": stages,
            "parity": True,
        })

    # Top-k trajectory: fused device lax.top_k (jnp/pallas) or the
    # host upper-bound-pruned walk, vs the dense full-sweep ranking.
    # Parity is exact — same (-score, id) order entry for entry.
    topk_rows = []
    for k in TOPK_KS:
        for j, q in enumerate(queries):
            di, ds = index.topk(q, k, plan="dense")
            pi, ps = index.topk(q, k, plan="pruned")
            if not (np.array_equal(di, pi) and np.array_equal(ds, ps)):
                raise RuntimeError(
                    f"top-k parity broken at k={k}, query {j}: "
                    f"dense={list(zip(di.tolist(), ds.tolist()))} "
                    f"pruned={list(zip(pi.tolist(), ps.tolist()))}")
        dt_dense = _time_topk(index, queries, k, "dense")
        dt_pruned = _time_topk(index, queries, k, "pruned")
        topk_rows.append({
            "k": k,
            "qps_dense_topk": round(nq / dt_dense, 2),
            "qps_pruned_topk": round(nq / dt_pruned, 2),
            "speedup": round(dt_dense / dt_pruned, 3),
            "stages_ms": _topk_stage_splits(index, queries, k),
            "parity": True,
        })

    write_csv("planner.csv", rows)
    write_csv("planner_topk.csv", topk_rows)
    print(f"  postings: {post_b} B compressed vs {flat_b} B flat "
          f"({postings_info['compression_vs_flat']}×), "
          f"{postings_info['postings_ratio']}× sketch bytes")

    failures = []
    if postings_info["postings_ratio"] > MAX_POSTINGS_RATIO:
        failures.append(
            f"compressed postings are {postings_info['postings_ratio']}× "
            f"the packed sketch bytes (cap {MAX_POSTINGS_RATIO}): "
            f"{post_b} B vs {sketch_b} B")
    if baseline and os.path.exists(baseline):
        failures += check_baseline(rows, baseline, backend)
        failures += check_topk_baseline(topk_rows, baseline, backend)

    if json_out:
        # Carry other backends' committed rows forward so the artifact
        # keeps one same-backend baseline per CI matrix cell.
        by_backend, topk_by_backend = {}, {}
        if os.path.exists(json_out):
            try:
                with open(json_out) as f:
                    prev = json.load(f)
                by_backend = dict(prev.get("rows_by_backend", {}))
                topk_by_backend = dict(prev.get("topk_rows_by_backend", {}))
            except (json.JSONDecodeError, OSError):
                by_backend, topk_by_backend = {}, {}
        by_backend[backend] = rows
        topk_by_backend[backend] = topk_rows
        payload = {
            "suite": "planner",
            "profile": "quick" if quick else "full",
            "workload": {
                "generator": "zipf", "m": m, "n_elems": n_elems,
                "alpha_freq": 0.8, "alpha_size": 1.0, "budget": budget,
                "n_queries": nq, "batch": BATCH, "engine": "gbkmv",
                "backend": backend,
            },
            "postings": postings_info,
            "rows": rows,
            "rows_by_backend": by_backend,
            "topk_rows": topk_rows,
            "topk_rows_by_backend": topk_by_backend,
        }
        if calibrate:
            from repro.core import cost_model

            # Probe hits do not vary with threshold, so the main rows
            # alone cannot separate fixed from per-hit cost. Add
            # calibration-only measurements at truncated query sizes
            # (fewer retained hashes → genuinely different hit counts).
            cal_rows = list(rows)
            for frac in (0.25, 0.5):
                qsub = [np.asarray(q)[: max(2, int(len(q) * frac))]
                        for q in queries]
                bsub = _batches(qsub)
                dt = _time_path(index, bsub, 0.7, "pruned")
                _, h_sub, b_sub, _ = index._plan_queries(qsub)
                per = probe_hits_per_query(post, h_sub, b_sub)
                cal_rows.append({
                    "qps_pruned": nq / dt,
                    "mean_probe_hits": float(per.mean()),
                })
            payload["calibration"] = cost_model.fit_query_constants(
                cal_rows, m, index._sketch_pack().capacity)
        with open(json_out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")

    if failures:
        raise RuntimeError(
            "planner gates failed (QPS baseline / postings-bytes cap):\n  "
            + "\n  ".join(failures))
    return rows
