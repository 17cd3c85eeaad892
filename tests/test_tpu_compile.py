"""Compile the main path for a TPU v5e without the chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip's compiler
would refuse — block shapes off the (8, 128) tiling, primitives Mosaic
cannot lower, programs that do not fit the device — which interpret-mode
tests cannot see. Nothing runs, so these tests say nothing about results
or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests. Keep every such compile in this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                sharding=sharding)


@pytest.mark.parametrize("mask_parked", [False, True])
def test_bin_disp_tile_compiles_for_v5e(one_chip, mask_parked):
    """The fused re-bin + motion-statistics kernel at the particle
    session's size (262,144 points): its per-tile partial outputs must
    sit on Mosaic's block tiling."""
    from repro.core.grid import choose_grid_spec
    from repro.kernels.update_tile import bin_disp_tile
    spec = choose_grid_spec(
        np.random.default_rng(0).random((4096, 3), dtype=np.float32), 0.03)
    pts = jax.ShapeDtypeStruct((262_144, 3), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda p, a: bin_disp_tile(p, a, spec, mask_parked=mask_parked,
                                   interpret=False)).lower(pts, pts).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_merge_topk_compiles_for_v5e(one_chip):
    """The streaming top-K merge of the fused kNN kernel (K=16 padded to
    128 lanes, a 512-candidate chunk): its first-occurrence selection
    must lower without a cumsum, which Mosaic does not implement."""
    from jax.experimental import pallas as pl
    from repro.kernels.knn_tile import _merge_topk

    def kernel(bd, bi, d, i, out_d, out_i):
        out_d[...], out_i[...] = _merge_topk(bd[...], bi[...], d[...],
                                             i[...], 16)

    def merge(bd, bi, d, i):
        return pl.pallas_call(
            kernel, interpret=False,
            out_shape=[jax.ShapeDtypeStruct(bd.shape, jnp.float32),
                       jax.ShapeDtypeStruct(bi.shape, jnp.int32)],
        )(bd, bi, d, i)

    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(merge).lower(
        s((256, 128), jnp.float32), s((256, 128), jnp.int32),
        s((256, 512), jnp.float32), s((256, 512), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def query_program(one_chip):
    """jax.jit(api.query) compiled for one v5e chip on a LiDAR-like frame
    with the smoke's search parameters."""
    import repro.api as api
    from repro.data.pointclouds import kitti_like_cloud
    pts = kitti_like_cloud(32_768, seed=0)
    params = api.SearchParams(radius=0.01, k=16, knn_window="exact")
    index = api.build_index(pts, params)
    shapes = jax.tree.map(lambda x: _shape(x, one_chip), index)
    return jax.jit(api.query).lower(shapes, _shape(pts, one_chip)).compile()


def test_query_compiles_for_v5e(query_program):
    """jax.jit(api.query) — the default search path, as one program —
    within one chip's HBM."""
    compiled = query_program
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES


def test_query_program_carries_the_search_scopes(query_program):
    """The scopes the device-trace metrics read survive the chip's
    compiler: each stage of the per-tile search and each ladder level is
    the op_name of at least one operation of the compiled program."""
    import re
    op_names = re.findall(r'op_name="([^"]+)"', query_program.as_text())
    for stage in ("window_gather", "row_gather", "distance", "select"):
        assert any(f"repro.search.{stage}" in n for n in op_names), stage
    assert any(re.search(r"repro\.launch\.level\d+_w\d+", n)
               for n in op_names)



def test_no_radius_batch_query_compiles_for_v5e(one_chip):
    """jax.jit(api.query) over DGCNN's batch of 32 clouds of 1,024 points
    with no radius (k=20), as one program for one v5e chip: the
    whole-cloud search compiles within one chip's HBM, and its scopes
    survive the compiler."""
    import math
    import re

    import repro.api as api
    pts = np.random.default_rng(0).random((32, 1024, 3), dtype=np.float32)
    index = api.build_index(pts, api.SearchParams(radius=math.inf, k=20))
    shapes = jax.tree.map(lambda x: _shape(x, one_chip), index)
    compiled = jax.jit(api.query).lower(shapes,
                                        _shape(pts, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert 0 < (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    op_names = re.findall(r'op_name="([^"]+)"', compiled.as_text())
    for scope in ("launch.cloud", "search.cloud_gather", "search.distance",
                  "search.select"):
        assert any(f"repro.{scope}" in n for n in op_names), scope
