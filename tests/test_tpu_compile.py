"""Compile the main path's kernels for a described TPU v5e at real widths.

Interpret mode on the CPU checks what a kernel computes, not whether the
chip's compiler accepts it: a slice off the (8, 128) tiling, a block that
does not fit VMEM or a primitive Mosaic cannot lower passes every
interpret-mode test. These tests compile each kernel, and the fused query
programs, for a v5e chip that is described but not attached, at the
sizes of the NETFLIX deployment (480,000 records, sketch capacity 48,
16 queries per batch), the dense sweep at those of REUTERS, and both
threshold routes at the published NETFLIX row (480,189 records, sketch
capacity 488). Nothing runs.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import AxisType, SingleDeviceSharding

# NETFLIX x120 as built: 480,000 records, capacity 48; its tail postings
# hold about 68k keys, 70k blocks and 176k payload words. W = 2 covers a
# wider bitmap than the deployment's one word.
M, C, GQ, W = 480_000, 48, 16, 2
KEYS = 70_000
BLOCKS = 72_000
PAYLOAD = 180_000

# The NETFLIX row of Table II as the chip benchmark builds it
# (chipbench/configs/netflix-480189.json: 480,189 records, 10 % budget,
# r = 320): capacity 488, 10 bitmap words; its tail postings hold 1,772
# keys, 41,926 blocks (none dense) and 1,485,610 payload words.
NF_M, NF_C, NF_W = 480_189, 488, 10
NF_KEYS, NF_BLOCKS, NF_PAYLOAD = 1_772, 41_926, 1_485_610
#: One chip's HBM (chipbench/peaks.json).
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    """A described v5e:2x2. Only an installation without the TPU
    compiler (no ``libtpu``) skips; any other failure fails."""
    from jax.experimental import topologies

    pytest.importorskip("libtpu", reason="no TPU compiler installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    one = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _index_args(spec):
    return (spec((M, C), jnp.uint32), spec((M,), jnp.uint32),
            spec((M, W), jnp.uint32), spec((GQ, C), jnp.uint32),
            spec((GQ,), jnp.uint32), spec((GQ, W), jnp.uint32),
            spec((GQ,), jnp.int32))


def test_dense_scorer_compiles(spec):
    from repro.kernels.gbkmv_score import gbkmv_score

    _, text = _compile(lambda *a: gbkmv_score(*a, interpret=False),
                       *_index_args(spec))
    assert "tpu_custom_call" in text


def test_postings_probe_compiles(spec):
    from repro.kernels import postings_merge as pm

    _, text = _compile(lambda k, q: pm._probe_pallas(k, q, interpret=False),
                       spec((KEYS,), jnp.uint32), spec((GQ * C,), jnp.uint32))
    assert "tpu_custom_call" in text


def test_block_decode_compiles(spec):
    from repro.kernels import postings_merge as pm

    tasks = spec((pm.TASK_CHUNK,), jnp.int32)
    rows = spec((PAYLOAD // 128 + 2, 128), jnp.uint32)
    _, text = _compile(
        lambda f, o, b, c, p: pm._decode_sparse_pallas(f, o, b, c, p,
                                                       interpret=False),
        tasks, tasks, tasks, tasks, rows)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pairs", [3000, 4096])
def test_pair_scorer_compiles(spec, pairs):
    from repro.kernels.gather_score import _gather_score_pallas

    cand = spec((pairs,), jnp.int32)
    rows = spec((M, 128), jnp.uint32)
    _, text = _compile(
        lambda *a: _gather_score_pallas(*a, interpret=False),
        rows, *_index_args(spec)[1:], cand, cand)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("head", ["hit_words", "topk"])
def test_fused_query_program_compiles(spec, backend, head):
    from repro.kernels import postings_merge as pm

    args = (spec((KEYS,), jnp.uint32), spec((KEYS + 1,), jnp.int32),
            spec((BLOCKS,), jnp.int32), spec((BLOCKS,), jnp.uint32),
            spec((BLOCKS + 1,), jnp.int32), spec((PAYLOAD,), jnp.uint32),
            spec((M, C), jnp.uint32), spec((M,), jnp.uint32),
            spec((M, W), jnp.uint32),
            spec((GQ * (C + W + 3),), jnp.uint32))
    kw = dict(gq=GQ, cq=C, w=W, m=M, backend=backend, interpret=False,
              has_dense=True)
    if head == "topk":
        fn = lambda *a: pm.fused_topk(*a, k=16, **kw)  # noqa: E731
    else:
        fn = lambda *a: pm.fused_hit_words(*a, **kw)  # noqa: E731
    compiled, text = _compile(fn, *args)
    assert ("tpu_custom_call" in text) == (backend == "pallas")
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


#: The REUTERS row of Table II as the chip benchmark builds it
#: (chipbench/configs/reuters-833k.json): 833,081 records, capacity 72.
RT_M, RT_C = 833_081, 72


@pytest.fixture(scope="module")
def reuters_sweep(spec):
    """HLO of the served dense sweep at the REUTERS widths, 16 queries."""
    from repro.sketchindex.distributed import _scores_jnp

    m, c, w = RT_M, RT_C, 1
    _, text = _compile(_scores_jnp, spec((m, c), jnp.uint32),
                       spec((m,), jnp.int32), spec((m,), jnp.uint32),
                       spec((m, w), jnp.uint32), spec((GQ, c), jnp.uint32),
                       spec((GQ,), jnp.uint32), spec((GQ, w), jnp.uint32),
                       spec((GQ,), jnp.int32))
    return text


def test_dense_sweep_compiles_without_gathers(reuters_sweep):
    """The served dense sweep at the REUTERS widths (833,081 records,
    capacity 72, 16 queries): a per-row index read of U_(k) would lower
    to a gather over every (query, record) pair."""
    assert "fusion" in reuters_sweep
    assert "gather(" not in reuters_sweep


def test_dense_sweep_compares_no_padded_query_axis(reuters_sweep):
    """The K∩ compare walks the query row in chunks, in a loop whose trip
    count is the batch's widest live row: no array of the sweep carries
    a query axis beside the record's 72 columns, as the broadcast of
    every record column against every padded query column did."""
    assert re.search(rf"\[{GQ},{RT_M},{RT_C},\d+\]", reuters_sweep) is None
    assert re.search(rf"\[{GQ},{RT_M},{RT_C}\]", reuters_sweep)
    assert " while(" in reuters_sweep


def _fits_one_chip(compiled) -> bool:
    mem = compiled.memory_analysis()
    return mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_dense_sweep_fits_one_chip_at_the_published_netflix_count(spec):
    """The threshold route's dense sweep over all 480,189 NETFLIX records
    at capacity 488 for 16 queries: without gathers it fits one chip,
    so the record count needs no cut."""
    from repro.sketchindex.distributed import _scores_jnp

    m, c, w = NF_M, NF_C, NF_W
    compiled, text = _compile(
        _scores_jnp, spec((m, c), jnp.uint32), spec((m,), jnp.int32),
        spec((m,), jnp.uint32), spec((m, w), jnp.uint32),
        spec((GQ, c), jnp.uint32), spec((GQ,), jnp.uint32),
        spec((GQ, w), jnp.uint32), spec((GQ,), jnp.int32))
    assert "gather(" not in text
    assert _fits_one_chip(compiled)


def test_fused_route_fits_one_chip_at_the_published_netflix_count(spec):
    """The fused hit-words program of the pruned threshold route over the
    same index and its tail postings, at the Gq 16 bucket."""
    from repro.kernels import postings_merge as pm

    m, c, w = NF_M, NF_C, NF_W
    args = (spec((NF_KEYS,), jnp.uint32), spec((NF_KEYS + 1,), jnp.int32),
            spec((NF_BLOCKS,), jnp.int32), spec((NF_BLOCKS,), jnp.uint32),
            spec((NF_BLOCKS + 1,), jnp.int32),
            spec((NF_PAYLOAD,), jnp.uint32),
            spec((m, c), jnp.uint32), spec((m,), jnp.uint32),
            spec((m, w), jnp.uint32), spec((GQ * (c + w + 3),), jnp.uint32))
    compiled, _ = _compile(
        lambda *a: pm.fused_hit_words(*a, gq=GQ, cq=c, w=w, m=m,
                                      backend="jnp", interpret=False,
                                      has_dense=False), *args)
    assert _fits_one_chip(compiled)


@pytest.fixture(scope="module")
def mesh4(topo):
    """``--mesh 1x4`` over the described chips, with a maker of abstract
    arrays sharded by a PartitionSpec."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)

    def sharded(shape, dtype, spec_):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec_))
    return mesh, sharded


def test_dense_pallas_scorer_compiles_sharded_over_four_chips(mesh4):
    """The mesh path of ``--mesh 1x4``: each chip runs the kernel on its
    quarter of the records."""
    from repro.core.sketches import PackedSketches
    from repro.sketchindex.distributed import DeviceIndex, _score_batch_jit

    mesh, sharded = mesh4
    rows = P(("data", "model"))

    def replicated(shape, dtype):
        return sharded(shape, dtype, P())

    didx = DeviceIndex(
        values=sharded((M, C), jnp.uint32, P(rows[0], None)),
        lengths=sharded((M,), jnp.int32, rows),
        thresh=sharded((M,), jnp.uint32, rows),
        buf=sharded((M, W), jnp.uint32, P(rows[0], None)),
        sizes=sharded((M,), jnp.int32, rows),
        num_records=M, tau=0, top_elems=(), seed=0)
    q = PackedSketches(values=replicated((GQ, C), jnp.uint32),
                       lengths=replicated((GQ,), jnp.int32),
                       thresh=replicated((GQ,), jnp.uint32),
                       buf=replicated((GQ, W), jnp.uint32),
                       sizes=replicated((GQ,), jnp.int32))
    compiled = _score_batch_jit.lower(didx, q, "pallas", mesh).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # Each chip holds its quarter of the index, not all of it.
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip < M * (C + W + 3) * 4 / 2


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_pair_scorer_compiles_over_four_chip_columns(mesh4, backend):
    """The pruned route of ``--mesh 1x4``: each chip scores the candidate
    pairs whose record it holds, against its resident rows."""
    from repro.core.sketches import PackedSketches
    from repro.sketchindex.distributed import _score_pairs_jit

    mesh, sharded = mesh4
    rows = P(("data", "model"))
    width = 128 if backend == "pallas" else C
    x = (sharded((M, width), jnp.uint32, P(rows[0], None)),
         sharded((M,), jnp.uint32, rows),
         sharded((M, W), jnp.uint32, P(rows[0], None)))
    rep = lambda shape, dtype: sharded(shape, dtype, P())  # noqa: E731
    q = PackedSketches(values=rep((GQ, C), jnp.uint32),
                       lengths=rep((GQ,), jnp.int32),
                       thresh=rep((GQ,), jnp.uint32),
                       buf=rep((GQ, W), jnp.uint32),
                       sizes=rep((GQ,), jnp.int32))
    cand = sharded((4 * 1024,), jnp.int32, rows)
    compiled = _score_pairs_jit.lower(*x, q, cand, cand, backend,
                                      mesh).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")
    # Each chip holds its quarter of the rows, not all of them.
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip < M * (width + W + 1) * 4 / 2
