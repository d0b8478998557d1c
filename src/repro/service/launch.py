"""Service CLI: build (or load) a GB-KMV index and serve it over HTTP.

    PYTHONPATH=src python -m repro.service.launch \
        --dataset NETFLIX --scale 0.25 --port 8080 \
        --max-inflight 256 --rate-limit 500 --auth-token s3cret

``--rounds N`` runs a self-driven smoke instead of serving forever: N
batched rounds through the real HTTP stack on an ephemeral port, then
exits printing p50/p99 — the behavior the deprecated
``repro.launch.serve --mode sketch`` shim maps onto.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro import api, runtime
from repro.launch.mesh import make_mesh
from repro.data import datasets
from repro.data.synth import make_query_workload
from repro.obs import Tracer
from repro.sketchindex import ShardedIndex
from repro.service import (
    AsyncSketchServer, Durability, ServiceApp, ServiceClient, ServiceHandle)


def add_service_args(ap: argparse.ArgumentParser):
    ap.add_argument("--port", type=int, default=8080,
                    help="listen port (0 = ephemeral)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--max-inflight", type=int, default=256,
                    help="admission queue bound; beyond it requests shed "
                         "with 429 + Retry-After")
    ap.add_argument("--rate-limit", type=float, default=None,
                    help="token-bucket rate limit, requests/s (default: off)")
    ap.add_argument("--burst", type=int, default=None,
                    help="token-bucket burst size (default: ~1s of rate)")
    ap.add_argument("--tenant-rate-limit", type=float, default=None,
                    help="per-tenant (per-auth-token) bucket rate, "
                         "requests/s (default: off)")
    ap.add_argument("--tenant-burst", type=int, default=None,
                    help="per-tenant bucket burst size")
    ap.add_argument("--auth-token", default=None,
                    help="require this bearer token on query/topk/ingest")
    ap.add_argument("--trace-capacity", type=int, default=0,
                    help="keep the last N request traces for /debug/traces "
                         "(0 = tracing off)")
    ap.add_argument("--slow-query-ms", type=float, default=1000.0,
                    help="slow-query log threshold; <= 0 disables the log")
    ap.add_argument("--no-profile", action="store_true",
                    help="disable the per-stage latency profiler")
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="micro-batch deadline (flush age bound)")
    ap.add_argument("--deadline-ms", type=float, default=500.0,
                    help="default per-request SLO; expired requests take "
                         "the dense fallback path")
    ap.add_argument("--ingest-chunk", type=int, default=256,
                    help="records per streamed /ingest flush chunk")
    ap.add_argument("--plan", default="auto",
                    choices=("auto", "dense", "pruned"))
    ap.add_argument("--windowed", action="store_true",
                    help="serve a time-windowed index (WindowManager): "
                         "/ingest accepts ?epoch=N and /admin/retire "
                         "drops expired epochs")
    ap.add_argument("--data-dir", default=None,
                    help="durable state directory: ingest goes through a "
                         "WAL, snapshots land here, and on boot the newest "
                         "valid snapshot + WAL tail is recovered instead "
                         "of rebuilding from the dataset")
    ap.add_argument("--fsync", default="batch",
                    choices=("always", "batch", "off"),
                    help="WAL fsync policy: 'always' = one fsync per "
                         "append, 'batch' = one per mutation batch (group "
                         "commit, the default), 'off' = OS page cache only")
    ap.add_argument("--snapshot-interval-s", type=float, default=0.0,
                    help="background snapshot period in seconds "
                         "(0 = only on POST /admin/snapshot)")
    ap.add_argument("--snapshot-keep", type=int, default=2,
                    help="completed snapshots retained (older pruned)")
    ap.add_argument("--idem-window", type=int, default=1024,
                    help="idempotency-key dedupe window (entries)")


def _build_index(args):
    """Fresh build from the dataset (no durable state to recover)."""
    recs = datasets.load(args.dataset, scale=args.scale)
    total = sum(len(r) for r in recs)
    t0 = time.time()
    if getattr(args, "windowed", False):
        # Time-windowed serving: the WindowManager speaks the same
        # serve_batch protocol; /ingest?epoch=N opens epochs and
        # /admin/retire drops expired ones. No sharding layer — windows
        # merge lazily on the host before device queries.
        sharded = api.get_engine("gbkmv").build(
            recs, int(total * args.budget_frac), seed=0,
            backend=args.backend, windowed=True, epoch=0)
        desc = f"windowed index={sharded.nbytes()/1e6:.1f}MB"
    else:
        mesh = make_mesh(tuple(int(x) for x in args.mesh.split("x")),
                         ("data", "model"))
        index = api.get_engine("gbkmv").build(
            recs, int(total * args.budget_frac), seed=0,
            backend=args.backend)
        sharded = ShardedIndex(index, mesh, backend=args.backend)
        desc = f"index={index.nbytes()/1e6:.1f}MB"
    print(f"[service] {args.dataset}: m={len(recs)} "
          f"{desc} built in {time.time()-t0:.2f}s")
    return sharded


def build_service(args) -> ServiceApp:
    durability = None
    sharded = None
    if getattr(args, "data_dir", None):
        durability = Durability(
            args.data_dir, fsync=getattr(args, "fsync", "batch"),
            snapshot_keep=getattr(args, "snapshot_keep", 2),
            idem_window=getattr(args, "idem_window", 1024),
            snapshot_interval=getattr(args, "snapshot_interval_s", 0.0))
        t0 = time.time()
        loaded, manifest = durability.load_latest_index()
        if loaded is not None:
            if manifest.get("windowed"):
                sharded = loaded
            else:
                mesh = make_mesh(
                    tuple(int(x) for x in args.mesh.split("x")),
                    ("data", "model"))
                sharded = ShardedIndex(loaded, mesh, backend=args.backend)
            stats = durability.replay_into(sharded)
            print(f"[service] recovered from {args.data_dir}: snapshot "
                  f"wal_seq={durability.snap_seq}, replayed "
                  f"{stats['replayed_entries']} WAL entries "
                  f"({stats['replayed_records']} records, "
                  f"{stats['torn_tail_bytes']}B torn tail) "
                  f"in {time.time()-t0:.2f}s")
    if sharded is None:
        sharded = _build_index(args)
        if durability is not None:
            # A WAL without a snapshot (crash before the first one):
            # the dataset build is deterministic, so re-applying the
            # tail on top reproduces the pre-crash state.
            stats = durability.replay_into(sharded)
            if stats["replayed_entries"]:
                print(f"[service] replayed {stats['replayed_entries']} "
                      f"WAL entries onto the fresh build")
            # Baseline snapshot: the next boot recovers from disk
            # instead of rebuilding from the dataset.
            durability.snapshot(sharded)
    tracer = (Tracer(capacity=args.trace_capacity)
              if args.trace_capacity > 0 else None)
    server = AsyncSketchServer(
        sharded, max_batch=args.max_batch,
        max_wait=args.max_wait_ms / 1e3,
        max_inflight=args.max_inflight,
        default_deadline=args.deadline_ms / 1e3, plan=args.plan,
        tracer=tracer, profile=not args.no_profile,
        slow_threshold=(args.slow_query_ms / 1e3
                        if args.slow_query_ms > 0 else None),
        durability=durability)
    return ServiceApp(server, auth_token=args.auth_token,
                      rate_limit=args.rate_limit, burst=args.burst,
                      tenant_rate_limit=args.tenant_rate_limit,
                      tenant_burst=args.tenant_burst,
                      ingest_chunk=args.ingest_chunk)


def smoke_rounds(app: ServiceApp, args) -> None:
    """Self-driven rounds through the real HTTP stack (shim behavior)."""
    recs = datasets.load(args.dataset, scale=args.scale)
    queries = make_query_workload(recs, args.batch * args.rounds)
    with ServiceHandle(app, host=args.host, port=0) as handle:
        host, port = handle.address
        cli = ServiceClient(host, port, token=args.auth_token)
        lat = []
        for r in range(args.rounds):
            qs = queries[r * args.batch:(r + 1) * args.batch]
            t0 = time.time()
            hits = [cli.query(q, 0.5) for q in qs]
            lat.append(time.time() - t0)
            if r == 0:
                ids, scores = cli.topk(qs[0], args.topk)
                print(f"[service] round0 top1 score: "
                      f"{float(scores[0]):.3f} (id {int(ids[0])}), "
                      f"{len(hits[0])} hits at t=0.5")
        cli.close()
        lat = np.asarray(lat) * 1e3
        stats = app.server.stats
        print(f"[service] {args.rounds} rounds × {args.batch} queries over "
              f"HTTP: p50={np.percentile(lat, 50):.1f}ms "
              f"p99={np.percentile(lat, 99):.1f}ms "
              f"({args.batch / (np.mean(lat) / 1e3):.0f} q/s, "
              f"mean batch {stats.mean_batch:.1f})")


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's options (``argv=None`` reads the command line)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--dataset", default="NETFLIX")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--budget-frac", type=float, default=0.1)
    ap.add_argument("--backend", default="jnp",
                    choices=("numpy", "jnp", "pallas"))
    ap.add_argument("--batch", type=int, default=16,
                    help="queries per round in --rounds smoke mode")
    ap.add_argument("--rounds", type=int, default=0,
                    help="run N smoke rounds and exit (0 = serve forever)")
    ap.add_argument("--topk", type=int, default=10)
    add_service_args(ap)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    cache = runtime.use_compile_cache()
    dev = runtime.device_summary()
    print(f"[service] devices: platform={dev['platform']} "
          f"kind={dev['kind']} count={dev['count']} (compile cache {cache})")
    app = build_service(args)
    if args.rounds > 0:
        smoke_rounds(app, args)
        return
    with ServiceHandle(app, host=args.host, port=args.port) as handle:
        host, port = handle.address
        print(f"[service] listening on http://{host}:{port} "
              f"(auth={'on' if args.auth_token else 'off'}, "
              f"rate_limit={args.rate_limit or 'off'}, "
              f"max_inflight={args.max_inflight})")
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            print("[service] shutting down")


if __name__ == "__main__":
    main()
