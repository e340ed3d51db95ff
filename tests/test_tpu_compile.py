"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel (or the traversal that launches
it) with ``interpret=False`` against one device of a ``v5e:2x2`` topology
and compiles it with the TPU compiler, which refuses what interpret mode
accepts — blocks the (8, 128) tiling rejects, casts and reductions Mosaic
lacks, more VMEM than a kernel may use.  T = 128 tiles, 64 colors (W = 2),
as the samplers and the query engine use them.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sparse, tiled_traversal, tiles, traversal
from repro.graph import csr
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
                   ((), jnp.uint32))
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


# `%name = u32[2,65536]{1,0:T(2,128)S(1)} copy(%operand), metadata=...`
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\](\{[^}]*\})? "
                    r"(\S+?)\(([^)]*)\)(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")


def _edge_buffers(text, e, w):
    """(padded, relaid) of an optimized TPU program: the ops, outside
    fusion bodies, that define an edge-length u32 buffer with the word
    axis minor — `u32[E,W]{1,0:...}` or `u32[W,E]{0,1:...}` — and the
    copies that move an edge-length array into another layout or tiling
    (memory space `S(n)` aside).  Each op as (name, opcode, op_name)."""
    fused = set(re.findall(r"calls=%([\w.\-]+)", text))
    ops, layout, computation = [], {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head[1]
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        layout[m[1]] = re.sub(r"S\(\d+\)", "", m[4] or "")
        if computation not in fused:
            op_name = re.search(r'op_name="([^"]*)"', m[7])
            ops.append((m, op_name[1] if op_name else ""))
    minor = {f"{e},{w}": "{1,0:", f"{w},{e}": "{0,1:"}
    padded = [(m[1], m[5], n) for m, n in ops if m[2] == "u32"
              and (m[4] or "").startswith(minor.get(m[3], "-"))]
    relaid = [(m[1], m[5], n) for m, n in ops
              if m[5] == "copy" and str(e) in m[3].split(",")
              and layout[m[1]] != layout.get(m[6].lstrip("%"))]
    return padded, relaid


def test_dense_level_keeps_edge_arrays_lane_dense(one_chip):
    """`run_fused_block`'s dense level works word-major, (G·W, E): no
    edge-length array has the word axis as its minor dimension, which the
    (8, 128) tiling pads to 128 lanes (`u32[E,8]{1,0:T(8,128)}`), and no
    copy moves an edge-length array into another layout — with one
    exception, the source-word row gather `frontier[:, src]`
    (`bpt.gather`), whose output the compiler writes padded and relays
    once.  One 1-D gather per word would avoid both, but costs far more
    per index on the chip (PERF.md §5).  The edge-major level pads the RNG
    words and the AND besides, and relays them, and fails.

    A block of b = 4 batches at 64 colors runs as one loop of G = 4
    batches, 8 words wide (`traversal.batches_per_loop`), so that one
    gather fills 8 of the padded buffer's 128 lanes; its scratch stays
    within 10 % of the one-batch, 2-word program's at the same E.  The
    data_parallel backend's shard program (`_dense_block` under
    ``shard_map``, here on a one-chip mesh) must compile to the same
    level: with only its masks live the compiler dropped the count and
    laid the edge arrays out edge-major.

    E = 2**20 at 16 edges a vertex, Graph500's edge factor: below about
    2**19 edges the compiler also relays the OR scan's accumulator for
    its row pick, which it does not at the benchmark's 7.6M edges; from
    2**19 up the program has the benchmark's ops and layouts."""
    v, e, b = 2 ** 16, 2 ** 20, 4
    ints, words = jnp.int32, jnp.uint32
    indptr, src, dst, prob, order, rank, last, max_rank = _shapes(
        one_chip, ((v + 1,), ints), ((e,), ints), ((e,), ints),
        ((e,), jnp.float32), ((e,), ints), ((e,), ints), ((v,), ints),
        ((), ints))
    g = csr.Graph(indptr=indptr, src=src, dst=dst, prob=prob,
                  num_vertices=v, num_edges=e)
    seg = traversal.DstSegments(order, rank, last, max_rank)

    def compiled(num_batches):
        starts, seeds = _shapes(one_chip, ((num_batches, C), ints),
                                ((num_batches,), words))
        return traversal.run_fused_block.lower(
            g, starts, seeds, C, max_levels=1024, segments=seg).compile()

    fused = compiled(b)
    width = traversal.batches_per_loop(b, C) * W
    assert width == 8
    one_batch = compiled(1).memory_analysis().temp_size_in_bytes
    gather = "/bpt.gather/gather"
    for program in (fused, _data_parallel_compiled(one_chip, g, seg, b)):
        padded, relaid = _edge_buffers(program.as_text(), e, width)
        assert [n.endswith(gather) for _, _, n in padded] == [True], padded
        assert [n.endswith(gather) for _, _, n in relaid] == [True], relaid
        temp = program.memory_analysis().temp_size_in_bytes
        assert temp <= 1.1 * one_batch, (temp, one_batch)


def _data_parallel_compiled(one_chip, g, seg, num_batches):
    """The data_parallel shard program for ``num_batches`` batches on a
    one-device mesh of the described chip, compiled."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.sampling import SamplerSpec
    from repro.sampling.sampler import _data_parallel_block_fn

    mesh = make_mesh((1,), ("data",), devices=list(one_chip.device_set))

    def on_mesh(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    data = jax.tree.map(lambda x: on_mesh(x, P()), (g, seg))
    starts = on_mesh(jax.ShapeDtypeStruct((num_batches, C), jnp.int32),
                     P("data"))
    seeds = on_mesh(jax.ShapeDtypeStruct((num_batches,), jnp.uint32),
                    P("data"))
    fn = _data_parallel_block_fn(
        mesh, "data", SamplerSpec(backend="data_parallel", num_colors=C,
                                  max_iters=1024), None)
    return fn.lower(data, starts, seeds).compile()
