"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel (or the traversal that launches
it) with ``interpret=False`` against one device of a ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what interpret mode
accepts — blocks the (8, 128) tiling rejects, casts and reductions Mosaic
lacks, more VMEM than a kernel may use.  T = 128 tiles, 64 colors (W = 2),
as the samplers and the query engine use them.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sparse, tiled_traversal, tiles
from repro.kernels import coverage, fused_expand, lt_select_expand

T, C, W = 128, 64, 2
NT = 24                      # tiles
VO = 8 * T                   # visited rows (a shard's rows, graph-parallel)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]


def _assert_tpu_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _tile_graph(sharding, num_vertices):
    prob, eid, ts, td, fi = _shapes(
        sharding, ((NT, T, T), jnp.float32), ((NT, T, T), jnp.uint32),
        ((NT,), jnp.int32), ((NT,), jnp.int32), ((NT,), jnp.int32))
    return tiles.TiledGraph(prob=prob, edge_id=eid, tile_src=ts,
                            tile_dst=td, first_of_dst=fi,
                            num_vertices=num_vertices, num_edges=NT * 100,
                            tile_size=T)


def test_cover_counts_compiles(one_chip):
    vis, act = _shapes(one_chip, ((875_713, W), jnp.uint32),
                       ((W,), jnp.uint32))
    _assert_tpu_kernel(jax.jit(
        lambda v, a: coverage.cover_counts(v, a, interpret=False)
    ).lower(vis, act))


@pytest.mark.parametrize("frontier_rows", [VO, 2 * VO],
                         ids=["one_device", "graph_parallel_local"])
def test_fused_expand_compiles(one_chip, frontier_rows):
    """Dense grid; with a global frontier over shard-local visited rows
    as the graph-parallel leg calls it."""
    args = _shapes(one_chip, ((NT, T, T), jnp.float32),
                   ((NT, T, T), jnp.uint32), ((NT,), jnp.int32),
                   ((NT,), jnp.int32), ((NT,), jnp.int32),
                   ((frontier_rows, W), jnp.uint32), ((VO, W), jnp.uint32),
                   ((), jnp.uint32), ((), jnp.uint32))
    _assert_tpu_kernel(jax.jit(
        lambda *a: fused_expand.fused_expand(*a, interpret=False)
    ).lower(*args))


@pytest.mark.parametrize("frontier_rows", [VO, 2 * VO],
                         ids=["one_device", "graph_parallel_local"])
def test_lt_select_expand_compiles(one_chip, frontier_rows):
    args = _shapes(one_chip, ((NT, T, T), jnp.float32),
                   ((NT, T, T), jnp.float32), ((NT,), jnp.int32),
                   ((NT,), jnp.int32), ((NT,), jnp.int32),
                   ((frontier_rows, W), jnp.uint32), ((VO, W), jnp.uint32),
                   ((W * 32, VO), jnp.float32))
    _assert_tpu_kernel(jax.jit(
        lambda *a: lt_select_expand.lt_select_expand(*a, interpret=False)
    ).lower(*args))


@pytest.mark.parametrize("diffusion", ["ic", "lt"])
def test_sparse_grid_traversal_compiles(one_chip, diffusion):
    """The compacted (gathered) kernel grid of the sparse frontier, inside
    the full traversal that builds it level by level."""
    tg = _tile_graph(one_chip, VO - 5)
    starts, seed = _shapes(one_chip, ((C,), jnp.int32), ((), jnp.uint32))
    kw = dict(max_levels=64, use_kernel=True, interpret=False,
              frontier="sparse", ladder=sparse.bucket_ladder(NT))
    if diffusion == "lt":
        (cb,) = _shapes(one_chip, ((NT, T, T), jnp.float32))
        lowered = tiled_traversal.run_fused_lt_tiled.lower(
            tg, cb, starts, C, seed, **kw)
    else:
        lowered = tiled_traversal.run_fused_tiled.lower(
            tg, starts, C, seed, **kw)
    _assert_tpu_kernel(lowered)
