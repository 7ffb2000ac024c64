"""Compile rehearsal for TPU v5e: the join's Pallas kernels at real widths
and the mesh APRIL filter step at a real batch, compiled by the TPU
compiler for a described (not attached) chip.

Nothing runs: these tests catch what interpret mode cannot — block shapes
Mosaic refuses, unlowerable primitives, scoped-VMEM overruns — before any
chip time is spent. The topology is described inside a module fixture, so
only the test worker that runs this file loads the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.compact.ops import _compact_impl
from repro.kernels.interval_join.ops import _trichotomy_jit, batch_interval_overlap
from repro.kernels.refine.ops import batch_edges_intersect
from repro.kernels.ri_and.ops import batch_aligned_and
from repro.spatial.distributed import april_filter_kernel_jnp

#: pair rows per kernel batch
B = 4096
#: the ``join_256k`` cell of ``launch/dryrun.py``: (pairs, intervals/list)
JOIN_256K = (262144, 64)


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache here; keep it off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    # an installation without the TPU library (a jax[cpu]-only lane) has
    # no compiler to rehearse with; any other failure is a real one
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("width", [64, 256])
def test_interval_overlap_compiles(one_chip, width):
    lists = _sds(one_chip, (B, width), jnp.int32)
    counts = _sds(one_chip, (B,), jnp.int32)
    _compile_kernel(
        lambda *a: batch_interval_overlap(*a, interpret=False),
        lists, lists, counts, lists, lists, counts)


@pytest.mark.parametrize("width", [64, 256])
def test_april_trichotomy_compiles(one_chip, width):
    lists = _sds(one_chip, (B, width), jnp.int32)
    counts = _sds(one_chip, (B,), jnp.int32)

    def tri(nra, nrf, nsa, nsf, *m):
        mats = tuple((m[i], m[i + 1]) for i in range(0, 8, 2))
        return _trichotomy_jit(nra, nrf, nsa, nsf, mats, interpret=False,
                               block_b=8)

    _compile_kernel(tri, *[counts] * 4, *[lists] * 8)


@pytest.mark.parametrize("edges", [128, 256])
def test_refine_kernel_compiles(one_chip, edges):
    pts = _sds(one_chip, (B, edges, 2), jnp.float32)
    mask = _sds(one_chip, (B, edges), jnp.bool_)
    _compile_kernel(lambda *a: batch_edges_intersect(*a, interpret=False),
                    pts, pts, mask, pts, pts, mask)


@pytest.mark.parametrize("words", [4, 128])
def test_ri_and_kernel_compiles(one_chip, words):
    w = _sds(one_chip, (B, words), jnp.uint32)
    _compile_kernel(lambda *a: batch_aligned_and(*a, interpret=False),
                    w, w, _sds(one_chip, (B, 4), jnp.int32),
                    _sds(one_chip, (words,), jnp.uint32))


@pytest.mark.parametrize("lane", [1 << 21, 1 << 24])
def test_compact_kernel_compiles(one_chip, lane):
    _compile_kernel(
        lambda m: _compact_impl(m, backend="pallas", interpret=False),
        _sds(one_chip, (lane,), jnp.bool_))


def test_mesh_april_filter_compiles(topo):
    """The sharded APRIL filter step over the four described chips."""
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    n, width = JOIN_256K
    rows = NamedSharding(mesh, P("data", None))
    col = NamedSharding(mesh, P("data"))
    batch = {k: _sds(rows, (n, width), jnp.int32)
             for k in ("ra_s", "ra_l", "rf_s", "rf_l",
                       "sa_s", "sa_l", "sf_s", "sf_l")}
    batch.update({k: _sds(col, (n,), jnp.int32)
                  for k in ("ra_n", "rf_n", "sa_n", "sf_n")})

    def step(b):
        verd = april_filter_kernel_jnp(b)
        return verd, jnp.stack([jnp.sum(verd == v) for v in range(3)])

    compiled = jax.jit(step).lower(batch).compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    assert mem.argument_size_in_bytes <= 16 * 2**30
