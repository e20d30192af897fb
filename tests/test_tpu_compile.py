"""Compile the advisor's Pallas kernels for a described TPU v5e (tier 1).

The TPU compiler compiles for a chip that is described, not attached, so
these tests need no accelerator.  They catch what interpret mode cannot: a
primitive Mosaic has no lowering for, an unaligned shape cast, a block
that overflows scoped VMEM.  Shapes are the TPC-H SF1 main path's:
SampleCF samples of 60,000 rows, launches of `CHUNK` of a codec call's
targets (up to 282 of them), rows per page that are not lane multiples,
the planner's largest (candidates, children) record, and the largest
launch of the planner's batches of records.

The topology is described inside a module fixture, never at import time,
and the persistent compilation cache is off around these compiles (a
compile for a described chip cannot be read back without one).
"""
import functools
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels import codec_bytes as ck  # noqa: E402
from repro.kernels import planner_score as ps  # noqa: E402

SAMPLE_ROWS = 60_000   # 1% SampleCF sample of SF1 lineitem (6.0M rows)
TARGETS = 282          # largest target stack of one SF1 codec call
MAX_CANDIDATES = 14    # largest planner record at SF1: candidates ...
MAX_CHILDREN = 11      # ... and children per candidate
N_FRACTIONS = 5        # len(estimation_graph.F_GRID)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def compile_for(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel
    return compiled


@pytest.mark.parametrize("method,rpp", [
    ("NS", 0), ("LDICT", 73), ("LDICT", 682), ("LDICT", 1638),
    ("PREFIX", 682), ("PREFIX", 1638), ("RLE", 682), ("RLE", 273)])
def test_codec_call_compiles(one_chip, method, rpp):
    """The whole codec launch at an SF1 sample: plane split, page
    gather, page sort, kernel.  The stack arrives as its int32 words,
    (c, 2n), in one launch of `CHUNK` targets, with the page length a
    traced int32 and its bucket static."""
    assert TARGETS > ck.CHUNK
    bucket = ck.page_bucket(rpp) if method in ck.ORD_DEP_METHODS else 0
    compile_for(one_chip,
                functools.partial(ck._codec_call, method=method,
                                  bucket=bucket, interpret=False),
                ((ck.CHUNK, 2 * SAMPLE_ROWS), jnp.int32),
                ((ck.CHUNK,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("method,rows,seg", [
    ("GDICT", 128, SAMPLE_ROWS),            # whole sorted sample rows
    ("NS", 128, SAMPLE_ROWS),
    ("LDICT", 128 * 88, 682),               # one row per page
    ("PREFIX", 128 * 37, 1638),
    ("RLE", 128 * 220, 273)])
def test_segment_kernel_compiles(one_chip, method, rows, seg):
    """The Pallas segment kernel alone for every codec, GDICT included
    (its full call adds only XLA's row sort, slow to compile at 60k)."""
    tile_r, tile_c, r_pad, c_pad = ck.segment_tiles(rows, seg)
    plane = ((r_pad, c_pad), jnp.int32)
    row = ((r_pad, 1), jnp.int32)
    compile_for(one_chip,
                functools.partial(ck.segment_call, method=method,
                                  tile_r=tile_r, tile_c=tile_c,
                                  interpret=False),
                plane, plane, row, row)


def test_prob_within_compiles(one_chip):
    compile_for(one_chip,
                functools.partial(ps._prob_call, e=0.5, interpret=False),
                ((1, 1024), jnp.float32), ((1, 1024), jnp.float32))


def test_fused_score_compiles(one_chip):
    nc = -(-MAX_CANDIDATES // 8) * 8
    nf = 128                     # fused_score pads fractions to a lane
    assert N_FRACTIONS <= nf
    f32, i32 = jnp.float32, jnp.int32
    stack = ((nc, MAX_CHILDREN * nf), f32)
    col = ((nc, 1), f32)
    compile_for(one_chip,
                functools.partial(ps._fused_call, k=MAX_CHILDREN, e=0.5,
                                  q=0.9, interpret=False),
                stack, stack, col, col, col, ((nc, nf), i32),
                ((nc, nf), i32), ((nc, nf), f32))


@pytest.mark.parametrize("nc,k", [
    (ps.MAX_CANDIDATES, ps.MAX_CELLS // ps.MAX_CANDIDATES),
    (ps.MAX_CELLS // ps.MAX_CHILDREN, ps.MAX_CHILDREN)])
def test_fused_score_batch_cap_compiles(one_chip, nc, k):
    """The largest launches of the walk's batches: the candidate cap at
    the children it allows, and the child cap at its candidates."""
    assert (nc, k) in ps.programs()
    f32, i32 = jnp.float32, jnp.int32
    stack = ((nc, k * 128), f32)
    col = ((nc, 1), f32)
    compile_for(one_chip,
                functools.partial(ps._fused_call, k=k, e=0.5, q=0.9,
                                  interpret=False),
                stack, stack, col, col, col, ((nc, 128), i32),
                ((nc, 128), i32), ((nc, 128), f32))


@pytest.mark.parametrize("kernel,fn,shapes", [
    ("codec_ldict",
     functools.partial(ck._codec_call, method="LDICT", bucket=1024,
                       interpret=False),
     [((ck.CHUNK, 12000), jnp.int32), ((ck.CHUNK,), jnp.int32),
      ((), jnp.int32)]),
    ("codec_ns",
     functools.partial(ck._codec_call, method="NS", bucket=0,
                       interpret=False),
     [((ck.CHUNK, 12000), jnp.int32), ((ck.CHUNK,), jnp.int32),
      ((), jnp.int32)]),
    ("planner_prob",
     functools.partial(ps._prob_call, e=0.5, interpret=False),
     [((1, 128), jnp.float32)] * 2),
    ("planner_fused_score",
     functools.partial(ps._fused_call, k=2, e=0.5, q=0.9, interpret=False),
     [((8, 256), jnp.float32)] * 2 + [((8, 1), jnp.float32)] * 3
     + [((8, 128), jnp.int32)] * 2 + [((8, 128), jnp.float32)])])
def test_kernels_carry_their_names(one_chip, kernel, fn, shapes):
    """Each Pallas call is named, so that a trace's device operations say
    which kernel ran."""
    text = compile_for(one_chip, fn, *shapes).as_text()
    assert re.search(rf"%{kernel}(\.\d+)? = [^\n]* custom-call\(", text)
