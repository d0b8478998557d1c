"""Multi-device shard_map correctness: runs an 8-host-device subprocess
(the XLA device-count flag must precede jax import, so these cannot run
in the main pytest process, which pins 1 CPU device)."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp

from repro.data.synth import generate_dataset, make_query_workload
from repro.core.gbkmv import build_gbkmv, sketch_query
from repro.core import gbkmv as G
from repro.sketchindex import (batch_queries, distributed_tau,
                               distributed_topk, score_batch, to_device_index)
from repro.sketchindex.build import histogram_tau
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))

# --- distributed_topk vs numpy on an 8-way sharded score matrix ---
rng = np.random.default_rng(0)
scores = jnp.asarray(rng.normal(size=(160, 5)), jnp.float32)
v, i = distributed_topk(scores, 7, mesh)
ref = np.sort(np.asarray(scores), axis=0)[::-1][:7].T
np.testing.assert_allclose(np.asarray(v), ref, rtol=1e-6)
picked = np.take_along_axis(np.asarray(scores), np.asarray(i).T, axis=0).T
np.testing.assert_allclose(picked, np.asarray(v), rtol=1e-6)
print("topk-ok")

# --- sharded scoring == host oracle ---
recs = generate_dataset(m=96, n_elems=4000, alpha_freq=1.1, alpha_size=2.0,
                        seed=0)
idx = build_gbkmv(recs, budget=2000, r=32)
didx = to_device_index(idx, mesh)
queries = make_query_workload(recs, 3)
qp = batch_queries(idx, queries)
sc = np.asarray(score_batch(didx, qp))
for j, q in enumerate(queries):
    host = np.asarray(G.containment_scores(idx, sketch_query(idx, q)))
    np.testing.assert_allclose(sc[: idx.num_records, j], host,
                               rtol=1e-5, atol=1e-5)
print("score-ok")

# --- pair scorer over the sharded columns == over the host pack ---
from repro.kernels.gather_score import score_pairs
from repro.sketchindex import ShardedIndex
# Skewed owners: most pairs on the first shard, none on some.
rec = np.concatenate([rng.integers(0, 12, 250),
                      rng.integers(0, idx.num_records, 50)]).astype(np.int32)
qi = rng.integers(0, len(queries), 300).astype(np.int32)
for backend in ("jnp", "pallas"):
    sh = ShardedIndex(idx, mesh, backend=backend)
    np.testing.assert_array_equal(
        sh._score_pairs(qp, rec, qi),
        score_pairs(idx.sketches, qp, rec, qi, backend=backend))
    np.testing.assert_array_equal(
        sh._score_pairs(qp, rec[:5], qi[:5]),
        score_pairs(idx.sketches, qp, rec[:5], qi[:5], backend=backend))
print("pairs-ok")

# --- distributed τ (psum histogram) == single-device histogram ---
h = rng.integers(0, 2**32, size=16384).astype(np.uint32)
t1 = int(histogram_tau(jnp.asarray(h), 900))
t2 = int(distributed_tau(jnp.asarray(h), 900, mesh, ("data", "model")))
assert t1 == t2, (hex(t1), hex(t2))
print("tau-ok")
"""


def test_shard_map_paths_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    for marker in ("topk-ok", "score-ok", "pairs-ok", "tau-ok"):
        assert marker in r.stdout, (marker, r.stdout[-500:])
