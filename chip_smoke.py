"""Bring-up run of the served GB-KMV index on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the same service on a 1x4 mesh

Builds the service the way ``python -m repro.service.launch`` does, at the
NETFLIX deployment's record count (``--dataset NETFLIX --scale 120``:
480,000 records, budget 10%, WAL and snapshots on), serves it over HTTP
on an ephemeral port and drives it through ``ServiceClient``: threshold
queries at t = 0.5, 0.7 and 0.9 in batches of 16, top-10 queries, and one
streamed ``/ingest`` followed by queries that must see it. It does so
with ``--backend jnp`` and then ``--backend pallas`` over the same
records, and compares every answer with the host reference of the same
estimator (the numpy GB-KMV index, ``plan="dense"``). The only difference
allowed is a record whose reference score lies within a few float32 ulps
of the threshold or of a top-k neighbour; such cases are counted.

This is a check that the system runs on the chip and answers correctly,
not a benchmark: the latencies it prints are host-clock readings of one
short run.

Exits non-zero, printing no result, when JAX finds no TPU, on any wrong
answer, any non-200 response or any compile after warm-up. On success
the last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DATASET, SCALE, BUDGET_FRAC = "NETFLIX", 120, 0.1
BATCH = 16
THRESHOLDS = (0.5, 0.7, 0.9)
TOPK = 10
TIMED_ROUNDS = 2
INGEST = 300
ULPS = 4            # float32 slack, in units in the last place
CLIENT_TIMEOUT_S = 900.0


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileCounter:
    """Executables built (in-memory jit cache misses, whether or not the
    persistent cache held them) and persistent-cache hits, process-wide."""

    def __init__(self):
        import jax

        self.built = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1
            self.seconds += secs
            self.names[fun_name] += 1

    def _count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# traffic and the reference
# ---------------------------------------------------------------------------


class Workload:
    """The records, the query sets and the ingest batch, with the host
    reference scores of every query set (computed once, shared by both
    backends)."""

    def __init__(self, recs, pool):
        from repro import api
        from repro.data.synth import make_query_workload

        self.m = len(recs)
        self.parity_failures = []
        total = sum(len(r) for r in recs)
        self.budget = int(total * BUDGET_FRAC)
        self.sets = {t: make_query_workload(recs, BATCH, seed=i)
                     for i, t in enumerate(THRESHOLDS)}
        self.sets["topk"] = make_query_workload(recs, BATCH, seed=10)
        rng = np.random.default_rng(20)
        self.ingest = [recs[i] for i in rng.integers(0, self.m, INGEST)]
        self.after = self.ingest[:BATCH]

        t0 = time.time()
        ref = api.get_engine("gbkmv").build(recs, self.budget, seed=0,
                                            backend="numpy")
        self.scores = self._score_all(ref, self.sets, pool)
        ref.insert(self.ingest)
        self.scores_after = self._score_all(ref, {"after": self.after},
                                            pool)["after"]
        log(f"host reference (numpy GB-KMV, plan=dense) scored "
            f"{BATCH * (len(self.sets) + 1)} queries in "
            f"{time.time() - t0:.1f}s")

    @staticmethod
    def _score_all(ref, sets, pool):
        """f32[m, Gq] reference score matrix per query set, one query per
        worker (``batch_scores`` is the dense numpy sweep)."""
        out = {}
        for key, qs in sets.items():
            cols = list(pool.map(lambda q: ref.batch_scores([q])[:, 0], qs))
            out[key] = np.stack(cols, axis=1)
        return out


def _tol(x) -> float:
    return ULPS * float(np.spacing(np.float32(max(abs(float(x)), 1e-30))))


def check_hits(served, scores, t, what):
    """Served hit ids vs the reference; returns the slack-case count."""
    from repro.planner.prune import f32_threshold, threshold_hits_packed

    want = threshold_hits_packed(scores, t)
    thr = float(f32_threshold(t))
    slack = 0
    for g, (got, ref) in enumerate(zip(served, want)):
        for rid in np.setxor1d(got, ref):
            s = float(scores[rid, g])
            if abs(s - thr) > _tol(thr):
                raise SmokeFailure(
                    f"{what}: query {g} at t={t}: record {rid} "
                    f"{'served' if rid in got else 'missed'} with "
                    f"reference score {s!r} (threshold {thr!r})")
            slack += 1
    return slack


def check_topk(served, scores, what):
    from repro.planner import topk_select

    m = scores.shape[0]
    slack = 0
    for g, (ids, vals) in enumerate(served):
        ref_ids, ref_vals = topk_select(np.arange(m, dtype=np.int64),
                                        scores[:, g], TOPK, m)
        if np.array_equal(ids, ref_ids) and np.array_equal(vals, ref_vals):
            continue
        if len(ids) != len(ref_ids):
            raise SmokeFailure(f"{what}: query {g}: {len(ids)} results, "
                               f"reference has {len(ref_ids)}")
        for rid, v, rrid, rv in zip(ids, vals, ref_ids, ref_vals):
            if abs(float(v) - float(scores[rid, g])) > _tol(v) or \
                    abs(float(scores[rid, g]) - float(rv)) > _tol(rv):
                raise SmokeFailure(
                    f"{what}: query {g}: served ({rid}, {v!r}) where the "
                    f"reference ranks ({rrid}, {rv!r})")
            slack += int(rid != rrid or v != rv)
    return slack


# ---------------------------------------------------------------------------
# one backend through the served path
# ---------------------------------------------------------------------------


class Clients:
    """BATCH concurrent HTTP clients: each batch of requests is sent at
    once, so the server flushes it as one full batch."""

    def __init__(self, host, port):
        from repro.service import ServiceClient

        self._local = threading.local()
        self._make = lambda: ServiceClient(host, port,
                                           timeout=CLIENT_TIMEOUT_S)
        self._all = []
        self._lock = threading.Lock()
        self.pool = ThreadPoolExecutor(BATCH)

    def _client(self):
        c = getattr(self._local, "c", None)
        if c is None:
            c = self._local.c = self._make()
            with self._lock:
                self._all.append(c)
        return c

    def send(self, call, qs):
        """(answers, seconds) of ``call(client, q)`` for every query, all
        in flight at once."""
        from repro.service import ServiceError

        def timed(q):
            t0 = time.perf_counter()
            try:
                out = call(self._client(), q)
            except ServiceError as e:
                raise SmokeFailure(f"HTTP {e.status}: {e.body}") from None
            return out, time.perf_counter() - t0

        res = list(self.pool.map(timed, qs))
        return [r for r, _ in res], [s for _, s in res]

    def query(self, qs, t):
        return self.send(lambda c, q: c.query(q, t), qs)

    def topk(self, qs):
        return self.send(lambda c, q: c.topk(q, TOPK), qs)

    def close(self):
        self.pool.shutdown()
        for c in self._all:
            c.close()


def _digest(h, arrays):
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())


ROUTES = ("auto", "pruned")


def run_backend(work: Workload, backend: str, mesh: str, counter,
                digest) -> dict:
    """One service over the whole served path. Traffic runs under the
    launcher's default ``--plan auto`` (the planner's choice: the dense
    sweep on this corpus) and under ``--plan pruned`` (the fused device
    program on one chip), switched between rounds on the running
    server."""
    from repro import obs
    from repro.service import ServiceHandle
    from repro.service.launch import build_service, parse_args

    with tempfile.TemporaryDirectory() as data_dir:
        args = parse_args([
            "--dataset", DATASET, "--scale", str(SCALE),
            "--budget-frac", str(BUDGET_FRAC), "--backend", backend,
            "--mesh", mesh, "--data-dir", data_dir,
            "--max-batch", str(BATCH), "--max-wait-ms", "50",
            "--deadline-ms", str(CLIENT_TIMEOUT_S * 1e3)])
        t0 = time.time()
        app = build_service(args)
        sharded = app.server.index
        log(f"[{backend}] service built in {time.time() - t0:.1f}s: "
            f"{sharded.num_records} records, sketch columns "
            f"{sharded.host.sketches.sketch_nbytes()} B")
        if sharded.num_records != work.m:
            raise SmokeFailure(f"served {sharded.num_records} records, "
                               f"expected {work.m}")

        slack = {"threshold": 0, "topk": 0}
        with ServiceHandle(app, host="127.0.0.1", port=0) as handle:
            clients = Clients(*handle.address)
            try:
                def one_round(record):
                    lat = []
                    for route in ROUTES:
                        app.server.plan = route
                        what = f"[{backend}, plan={route}]"
                        for t in THRESHOLDS:
                            hits, s = clients.query(work.sets[t], t)
                            lat += s
                            if record:
                                slack["threshold"] += check_hits(
                                    hits, work.scores[t], t, what)
                                _digest(digest, hits)
                        tops, s = clients.topk(work.sets["topk"])
                        lat += s
                        if record:
                            slack["topk"] += check_topk(
                                tops, work.scores["topk"], what)
                            _digest(digest,
                                    [a for pair in tops for a in pair])
                    app.server.plan = ROUTES[0]
                    return lat

                # Warm-up and ingest compile programs cold inside a flush;
                # the timed rounds are held to the service's own limit.
                limit = app.result_timeout
                app.result_timeout = CLIENT_TIMEOUT_S
                t0 = time.time()
                built0 = counter.built
                one_round(record=True)
                warm = obs.device_pipeline_stats()
                app.result_timeout = limit
                log(f"[{backend}] warm-up round, both routes, answers "
                    f"checked, in {time.time() - t0:.1f}s "
                    f"({counter.built - built0} executables built, "
                    f"{counter.seconds:.1f}s compiling so far, "
                    f"{counter.cache_hits} persistent-cache hits so far)")

                built1 = counter.built
                names1 = collections.Counter(counter.names)
                t0 = time.time()
                lat = []
                for _ in range(TIMED_ROUNDS):
                    lat += one_round(record=False)
                after = obs.device_pipeline_stats()
                ms = np.asarray(lat) * 1e3
                log(f"[{backend}] {TIMED_ROUNDS} timed rounds "
                    f"({len(lat)} requests) in {time.time() - t0:.1f}s; "
                    f"host-clock latency p50 {np.percentile(ms, 50):.1f}ms "
                    f"p99 {np.percentile(ms, 99):.1f}ms (not a benchmark)")
                log(f"[{backend}] device-pipeline compiles after warm-up "
                    f"{warm['compiles']}, after timed rounds "
                    f"{after['compiles']}; fused-program calls in timed "
                    f"rounds {after['calls'] - warm['calls']}; executables "
                    f"built in timed rounds {counter.built - built1} "
                    f"{dict(counter.names - names1)}")
                st = app.server.stats
                log(f"[{backend}] flushes: {st.flushes_full} full, "
                    f"{st.flushes_deadline} by deadline, "
                    f"{st.flushes_expired} expired; mean batch "
                    f"{st.mean_batch:.2f}")
                if after["compiles"] != warm["compiles"]:
                    raise SmokeFailure(f"[{backend}] the device pipeline "
                                       "compiled after warm-up")
                if after["calls"] == warm["calls"] and mesh == "1x1":
                    raise SmokeFailure(
                        f"[{backend}] no batch took the fused device route")
                if backend == "pallas" and mesh == "1x1":
                    for name, has in pallas_programs(sharded).items():
                        log(f"[pallas] compiled {name} contains "
                            f"tpu_custom_call: {has}")
                        if not has:
                            raise SmokeFailure(f"{name} runs no Pallas "
                                               "kernel")
                    work.parity_failures += kernel_parity(sharded, work)
                log(f"[{backend}] arena bytes (columns, postings, device "
                    f"mirrors): {sharded.host.sketches.nbytes()}")
                log_memory(backend, sharded)

                app.result_timeout = CLIENT_TIMEOUT_S
                t0 = time.time()
                ack = clients._client().ingest(work.ingest)
                if ack.get("ingested") != INGEST:
                    raise SmokeFailure(f"ingest acknowledged {ack}")
                new = 0
                for route in ROUTES:
                    app.server.plan = route
                    hits, _ = clients.query(work.after, THRESHOLDS[0])
                    slack["threshold"] += check_hits(
                        hits, work.scores_after, THRESHOLDS[0],
                        f"[{backend}, plan={route}] after ingest")
                    _digest(digest, hits)
                    new += sum(int((h >= work.m).sum()) for h in hits)
                log(f"[{backend}] ingested {INGEST} records over HTTP (WAL "
                    f"on); the next {BATCH} queries on each route match the "
                    f"reference with the ingest applied and return {new} "
                    f"ingested ids ({time.time() - t0:.1f}s, compiles "
                    f"included)")
                if not new or sharded.num_records != work.m + INGEST:
                    raise SmokeFailure(f"[{backend}] the ingest is not "
                                       "visible to the next query")
            finally:
                clients.close()
        log(f"[{backend}] answers differing from the reference within "
            f"{ULPS} ulp of the cut: threshold {slack['threshold']}, "
            f"top-k {slack['topk']}")
        return slack


def log_memory(backend: str, sharded) -> None:
    """Per device: what it holds of the resident index, and its
    allocator's bytes in use and peak (read after every path ran). Fails
    unless every device holds an equal share of the index."""
    import jax

    held = collections.Counter()
    arrays = jax.tree_util.tree_leaves(sharded.didx)
    if sharded._lane_rows is not None:
        arrays.append(sharded._lane_rows)
    for a in arrays:
        for shard in a.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for i, d in enumerate(jax.devices()):
        stats = d.memory_stats() or {}
        log(f"[{backend}] device {i} ({d.device_kind}) holds index "
            f"{held[d]} B; bytes_in_use {stats.get('bytes_in_use')} "
            f"peak {stats.get('peak_bytes_in_use')}")
    shares = [held[d] for d in sharded.mesh.devices.flat]
    if min(shares) != max(shares) or sum(held.values()) != sum(shares):
        raise SmokeFailure(f"[{backend}] the index is not spread evenly "
                           f"over the mesh: {dict(held)}")


def pallas_programs(sharded) -> dict:
    """Whether each compiled program the pallas backend serves with holds
    a Pallas kernel (``tpu_custom_call``) rather than the interpreter."""
    from repro.kernels import interpret_mode, postings_merge
    from repro.planner import device as planner_device
    from repro.sketchindex import batch_queries
    from repro.sketchindex.distributed import _score_batch_jit

    qp = batch_queries(sharded.host, [np.arange(1, 40)] * BATCH)
    out = {}
    dense = _score_batch_jit.lower(sharded.didx, qp, "pallas",
                                   sharded.mesh).compile()
    out["dense scorer"] = "tpu_custom_call" in dense.as_text()
    if sharded._device_route():
        arena = sharded.host.sketches
        dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp, 0.5)
        fused = postings_merge.fused_hit_words.lower(
            dpost.keys, dpost.row_blocks, dpost.first, dpost.meta,
            dpost.off, dpost.payload, dpack.values, dpack.thresh,
            dpack.buf, sq.blob, gq=sq.gq, cq=sq.cq, w=sq.w,
            m=arena.num_records, backend="pallas",
            interpret=interpret_mode(), has_dense=dpost.has_dense).compile()
        out["fused query program"] = "tpu_custom_call" in fused.as_text()
    return out


def _mismatch(a, b) -> str | None:
    """None when ``a`` and ``b`` are bitwise equal, else a description."""
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return f"shapes {a.shape} vs {b.shape}"
    ua = a.view(np.uint32) if a.dtype == np.float32 else a
    ub = b.view(np.uint32) if b.dtype == np.float32 else b
    bad = ua != ub
    if not bad.any():
        return None
    desc = f"{int(bad.sum())} of {bad.size} differ"
    if a.dtype == np.float32:
        ulps = np.abs(ua.astype(np.int64) - ub.astype(np.int64))
        desc += f", at most {int(ulps.max())} ulp apart"
    return desc


def kernel_parity(sharded, work: Workload) -> list:
    """Every Pallas kernel against its jnp twin on the served index, on
    the chip, bit for bit; returns the names of those that differ."""
    import jax.numpy as jnp

    from repro.kernels import gather_score, interpret_mode
    from repro.kernels import postings_merge as pm
    from repro.planner import device as planner_device
    from repro.sketchindex import batch_queries
    from repro.sketchindex.distributed import score_batch

    arena = sharded.host.sketches
    m = arena.num_records
    qp = batch_queries(sharded.host, work.sets[THRESHOLDS[0]])
    interp = interpret_mode()
    checks = {}

    checks["dense scorer"] = _mismatch(
        score_batch(sharded.didx, qp, backend="jnp"),
        score_batch(sharded.didx, qp, backend="pallas"))

    fused = []
    for backend in ("jnp", "pallas"):
        dpost, dpack, sq = planner_device.stage_query_inputs(arena, qp)
        fused.append(planner_device.pruned_scores(dpost, dpack, sq, m=m,
                                                  backend=backend))
    checks["fused program (probe, decode)"] = _mismatch(*fused)

    q_flat = jnp.asarray(np.asarray(qp.values).reshape(-1))
    pos_j, hit_j = pm._probe_jnp(dpost.keys, q_flat)
    pos_p, hit_p = pm._probe_pallas(dpost.keys, q_flat, interpret=interp)
    checks["postings probe"] = _mismatch(
        np.stack([np.asarray(pos_j), np.asarray(hit_j, np.int32)]),
        np.stack([np.asarray(pos_p), np.asarray(hit_p, np.int32)]))

    tail = arena.postings().tail
    meta = np.asarray(tail.meta)
    sparse = np.nonzero(((meta >> 13) & 1) == 0)[0][:4096]
    first = jnp.asarray(np.asarray(tail.first)[sparse], jnp.int32)
    off = jnp.asarray(np.asarray(tail.off)[sparse], jnp.int32)
    bw = jnp.asarray(((meta[sparse] >> 8) & 0x1F).astype(np.int32))
    cnt = (meta[sparse] & 0x7F).astype(np.int32) + 1
    payload = jnp.asarray(np.asarray(tail.payload), jnp.uint32)
    dec_j = np.asarray(pm._decode_sparse_jnp(
        first, off, bw, jnp.asarray(cnt),
        jnp.pad(payload, (0, pm.DECODE_WINDOW))))
    dec_p = np.asarray(pm._decode_sparse_pallas(
        first, off, bw, jnp.asarray(cnt), pm._decode_payload_rows(payload),
        interpret=interp))
    valid = np.arange(pm.BLOCK)[None, :] < cnt[:, None]
    checks[f"block decode ({len(sparse)} blocks)"] = _mismatch(
        dec_j[valid], dec_p[valid])

    rng = np.random.default_rng(30)
    rec = rng.integers(0, m, 4096).astype(np.int32)
    qi = rng.integers(0, qp.num_records, 4096).astype(np.int32)
    checks["pair scorer"] = _mismatch(
        gather_score.score_pairs(arena, qp, rec, qi, backend="jnp"),
        gather_score.score_pairs(arena, qp, rec, qi, backend="pallas"))

    for name, bad in checks.items():
        log(f"[pallas] kernel parity on the chip, {name} vs its jnp twin: "
            f"{'bit-identical' if bad is None else bad}")
    return [n for n, bad in checks.items() if bad is not None]


def run(chips: int) -> None:
    from repro.data import datasets
    from repro import runtime

    counter = CompileCounter()
    cache = runtime.use_compile_cache()
    log(f"compile cache: {cache}")
    t0 = time.time()
    recs = datasets.load(DATASET, scale=SCALE)
    log(f"{DATASET} x{SCALE}: {len(recs)} records, "
        f"{sum(len(r) for r in recs)} elements, generated in "
        f"{time.time() - t0:.1f}s")
    with ThreadPoolExecutor(8) as pool:
        work = Workload(recs, pool)
    mesh = "1x4" if chips == 4 else "1x1"
    digest = hashlib.sha256()
    t0 = time.time()
    for backend in ("jnp", "pallas"):
        run_backend(work, backend, mesh, counter, digest)
    if work.parity_failures:
        raise SmokeFailure(f"Pallas kernels differ from their jnp twins: "
                           f"{work.parity_failures}")
    log(f"mesh {mesh}: answers digest {digest.hexdigest()[:16]} "
        f"(jnp then pallas); {counter.built} executables built, "
        f"{counter.seconds:.1f}s compiling, {counter.cache_hits} "
        f"persistent-cache hits; served phases took "
        f"{time.time() - t0:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 1x4 mesh of four chips (jnp and "
                         "pallas, compared with the host reference)")
    args = ap.parse_args(argv)

    from repro import runtime

    device = runtime.device_summary()
    chips = 4 if args.four_chips else 1
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX sees no TPU (platform "
              f"{device['platform']!r})", file=sys.stderr)
        return 2
    if device["count"] < chips:
        print(f"chip_smoke: {chips} chips needed, JAX sees "
              f"{device['count']}", file=sys.stderr)
        return 2
    from repro.service import ServiceError

    t0 = time.time()
    try:
        run(chips)
    except (SmokeFailure, ServiceError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"done in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
