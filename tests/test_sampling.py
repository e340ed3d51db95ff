"""Unified Sampler API: spec validation, cross-backend bit-identity (dense
vs tiled vs kernel vs single-device graph_parallel, single process), LT
serving end-to-end, PoolConfig spec rules, and the manifest diffusion
guard.  (Multi-device data_parallel / graph_parallel need forced host
devices — covered by tests/serve_distributed_check.py.)"""
import dataclasses

import numpy as np
import pytest

from repro import sampling
from repro.core import imm, lt, rrr
from repro.graph import csr, generators
from repro.launch.mesh import make_mesh
from repro.serve.influence import (MicroBatcher, PoolConfig, QueryEngine,
                                   ResultCache, SketchStore)


@pytest.fixture(scope="module")
def graph():
    """Dedupe-clean graph: the tile layout (tiled/kernel backends) needs
    parallel edges merged, and bit-identity requires one shared edge list."""
    return csr.dedupe(
        generators.powerlaw_cluster(250, 6.0, prob=(0.1, 0.6), seed=23))


# ----------------------------------------------------------------- spec
def test_spec_rejects_unknown_fields_and_combos():
    with pytest.raises(ValueError):
        sampling.SamplerSpec(diffusion="sir")
    with pytest.raises(ValueError):
        sampling.SamplerSpec(backend="warp")
    # The support matrix is complete: every (diffusion, backend) cell has
    # an implementation (LT's Pallas cell is `kernels.lt_select_expand`).
    for backend in ("dense", "tiled", "kernel", "data_parallel",
                    "graph_parallel"):
        for diffusion in ("ic", "lt"):
            assert sampling.supported(diffusion, backend)
    sampling.SamplerSpec(diffusion="lt", backend="kernel")  # constructs
    # graph_parallel needs distinct batch and row axes
    with pytest.raises(ValueError, match="DISTINCT"):
        sampling.SamplerSpec(backend="graph_parallel", mesh_axis="x",
                             model_axis="x")


def test_spec_is_hashable_and_manifest_round_trips():
    spec = sampling.SamplerSpec(diffusion="lt", num_colors=96, master_seed=4)
    assert hash(spec) == hash(dataclasses.replace(spec))
    assert sampling.SamplerSpec.from_manifest(spec.to_manifest()) == spec
    # forward compat: unknown manifest keys are ignored
    d = spec.to_manifest() | {"future_knob": 1}
    assert sampling.SamplerSpec.from_manifest(d) == spec


def test_spec_from_sample_kw_warns_and_converts(graph):
    with pytest.warns(DeprecationWarning):
        spec = sampling.spec_from_sample_kw(
            {"model": "lt", "max_levels": 32, "sort_starts": True},
            num_colors=32, master_seed=9)
    assert spec == sampling.SamplerSpec(
        diffusion="lt", backend="dense", num_colors=32, master_seed=9,
        max_iters=32, sort_starts=True)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="unknown sample_kw"):
            sampling.spec_from_sample_kw({"bogus": 1})


# --------------------------------------------- cross-backend bit identity
def test_dense_tiled_kernel_bit_identical(graph):
    """Same (master_seed, batch_index) ⇒ identical RRRBatch.visited on
    every backend (the facade's core contract)."""
    specs = {b: sampling.SamplerSpec(backend=b, num_colors=64, master_seed=5)
             for b in ("dense", "tiled", "kernel")}
    samplers = {b: sampling.make_sampler(graph, s) for b, s in specs.items()}
    for bi in (0, 3):
        ref = samplers["dense"].sample(bi)
        assert ref.batch_index == bi
        for b in ("tiled", "kernel"):
            got = samplers[b].sample(bi)
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(ref.visited))
            np.testing.assert_array_equal(got.roots, ref.roots)


def test_sampler_matches_legacy_sample_batch(graph):
    s = sampling.make_sampler(graph, sampling.SamplerSpec(num_colors=64,
                                                          master_seed=11))
    ref = rrr.sample_batch(csr.transpose(graph), 64, 11, 2)
    got = s.sample(2)
    np.testing.assert_array_equal(np.asarray(got.visited),
                                  np.asarray(ref.visited))


def test_lt_sampler_normalizes_weights_itself(graph):
    """The facade owns LT normalization: a raw IC-weighted graph and a
    pre-normalized one sample identically (normalization is idempotent)."""
    spec = sampling.SamplerSpec(diffusion="lt", num_colors=64, master_seed=7)
    raw = sampling.make_sampler(graph, spec)
    pre = sampling.make_sampler(
        graph, spec, g_rev=lt.normalize_lt_weights(csr.transpose(graph)))
    np.testing.assert_array_equal(np.asarray(raw.sample(1).visited),
                                  np.asarray(pre.sample(1).visited))


def test_tiled_backend_rejects_parallel_edges():
    src = np.array([0, 0, 1]); dst = np.array([1, 1, 2])
    g = csr.from_edges(src, dst, np.full(3, 0.5, np.float32), 3)
    with pytest.raises(ValueError, match="dedupe"):
        sampling.make_sampler(g, sampling.SamplerSpec(backend="tiled"))


def test_lt_tiled_bit_identical_to_dense(graph):
    """The ("lt", "tiled") matrix cell: tile expansion under the fixed
    live-edge selection reproduces the dense LT sweep bit for bit."""
    spec = sampling.SamplerSpec(diffusion="lt", num_colors=64, master_seed=5)
    dense = sampling.make_sampler(graph, spec)
    tiled = sampling.make_sampler(graph, spec.replace(backend="tiled"))
    for bi in (0, 3):
        ref = dense.sample(bi)
        got = tiled.sample(bi)
        assert got.batch_index == bi
        np.testing.assert_array_equal(np.asarray(got.visited),
                                      np.asarray(ref.visited))
        np.testing.assert_array_equal(got.roots, np.asarray(ref.roots))


def test_lt_kernel_bit_identical_to_dense(graph):
    """The ("lt", "kernel") matrix cell — the Pallas `lt_select_expand`
    kernel (interpret mode on CPU) reproduces the dense LT sweep bit for
    bit, on the dense grid AND the compacted sparse grid, and the sparse
    grid runs strictly fewer grid steps.  tile_size=16 gives the ladder
    enough tiles (255) that compaction has headroom on this 250-vertex
    fixture."""
    spec = sampling.SamplerSpec(diffusion="lt", backend="kernel",
                                num_colors=64, master_seed=5, tile_size=16)
    dense_ref = sampling.make_sampler(graph, spec.replace(backend="dense"))
    kern = sampling.make_sampler(graph, spec)
    kern_sparse = sampling.make_sampler(graph,
                                        spec.replace(frontier="sparse"))
    for bi in (0, 3):
        ref = dense_ref.sample(bi)
        got = kern.sample(bi)
        np.testing.assert_array_equal(np.asarray(got.visited),
                                      np.asarray(ref.visited))
        dense_steps = kern.last_grid_steps
        assert dense_steps == kern.last_levels * kern.tg_rev.num_tiles
        got_sp = kern_sparse.sample(bi)
        np.testing.assert_array_equal(np.asarray(got_sp.visited),
                                      np.asarray(ref.visited))
        assert 0 < kern_sparse.last_grid_steps < dense_steps


def test_graph_parallel_bit_identical_on_trivial_mesh(graph):
    """The whole row-partitioned block program (frontier all-gather,
    psum-agreed termination, 2-D batch × row sharding) on a 1×1 mesh —
    runnable in the single-device suite — must equal dense exactly."""
    mesh = make_mesh((1, 1), ("data", "model"))
    for diffusion in ("ic", "lt"):
        spec = sampling.SamplerSpec(diffusion=diffusion,
                                    backend="graph_parallel",
                                    num_colors=64, master_seed=9)
        gp = sampling.make_sampler(graph, spec, mesh=mesh)
        dense = sampling.make_sampler(graph, spec.replace(backend="dense"))
        got = gp.sample_many([0, 2])
        for b in got:
            ref = dense.sample(b.batch_index)
            np.testing.assert_array_equal(np.asarray(b.visited),
                                          np.asarray(ref.visited))
            np.testing.assert_array_equal(b.roots, np.asarray(ref.roots))
    stacked = gp.sample_stacked([1])
    assert stacked.shape == (1, graph.num_vertices, 2)


def test_mesh_backends_require_mesh_and_axes(graph):
    with pytest.raises(ValueError, match="mesh"):
        sampling.make_sampler(
            graph, sampling.SamplerSpec(backend="data_parallel"))
    with pytest.raises(ValueError, match="mesh"):
        sampling.make_sampler(
            graph, sampling.SamplerSpec(backend="graph_parallel"))
    # graph_parallel refuses a mesh without the row-partition axis
    with pytest.raises(ValueError, match="model"):
        sampling.make_sampler(
            graph, sampling.SamplerSpec(backend="graph_parallel"),
            mesh=make_mesh((1,), ("data",)))


# -------------------------------------------------- sparse frontier mode
def test_sparse_frontier_bit_identical_across_matrix(graph):
    """frontier="sparse" must be BIT-identical to the dense path on every
    single-process cell of the (diffusion × backend) matrix — compaction
    changes what gets computed, never what comes out."""
    mesh = make_mesh((1, 1), ("data", "model"))
    for diffusion in ("ic", "lt"):
        backends = ["dense", "tiled", "kernel"]
        ref = sampling.make_sampler(graph, sampling.SamplerSpec(
            diffusion=diffusion, num_colors=64, master_seed=5))
        for backend in backends + ["graph_parallel"]:
            spec = sampling.SamplerSpec(
                diffusion=diffusion, backend=backend, num_colors=64,
                master_seed=5, frontier="sparse")
            m = mesh if backend == "graph_parallel" else None
            s = sampling.make_sampler(graph, spec, mesh=m)
            for bi in (0, 2):
                got = s.sample(bi)
                want = ref.sample(bi)
                np.testing.assert_array_equal(np.asarray(got.visited),
                                              np.asarray(want.visited))
                np.testing.assert_array_equal(got.roots,
                                              np.asarray(want.roots))


def test_sparse_frontier_work_counters_equal_dense(graph):
    """The deterministic work-proportionality contract: sparse counts
    exactly the edges the dense sweep counts (an edge is visited iff its
    source row carries an active color — all of which live in gathered
    tiles), for single batches AND fused sample_many blocks."""
    spec = sampling.SamplerSpec(num_colors=64, master_seed=5)
    dense = sampling.make_sampler(graph, spec)
    sparse_ = sampling.make_sampler(graph, spec.replace(frontier="sparse"))
    for a, b in zip(dense.sample_many([0, 1, 2]),
                    sparse_.sample_many([0, 1, 2])):
        assert a.fused_edge_visits == b.fused_edge_visits > 0
        assert a.unfused_edge_visits == b.unfused_edge_visits
        one = sparse_.sample(a.batch_index)       # single-batch path too
        assert one.fused_edge_visits == a.fused_edge_visits


def test_sparse_frontier_dead_frontier_and_all_active():
    """Edge cases: a graph whose frontier dies immediately (every edge
    prob 0 — level 1 is empty) and one where every tile is active by
    level 1 (complete-ish, prob ~1 — compaction runs at the ladder's top
    rung)."""
    n = 40
    src, dst = np.nonzero(~np.eye(n, dtype=bool))
    for prob in (0.0, 0.999):
        g = csr.from_edges(src, dst, np.full(len(src), prob, np.float32),
                           n, dedupe=True)
        for diffusion in ("ic", "lt"):
            for backend in ("dense", "tiled", "kernel"):
                spec = sampling.SamplerSpec(
                    diffusion=diffusion, backend=backend, num_colors=64,
                    master_seed=3, tile_size=8)
                ref = sampling.make_sampler(g, spec).sample(0)
                got = sampling.make_sampler(
                    g, spec.replace(frontier="sparse")).sample(0)
                np.testing.assert_array_equal(np.asarray(got.visited),
                                              np.asarray(ref.visited))
            if prob == 0.0 and diffusion == "ic":
                # only the start colors survive
                assert np.count_nonzero(np.asarray(ref.visited)) <= 64


def test_sparse_frontier_capacity_bucket_boundaries(graph):
    """Every ladder shape — a 1-wide bottom rung, a two-rung explicit
    capacity, the degenerate single top rung — must reproduce dense bits
    AND stats exactly (the top rung always fits, so correctness never
    depends on the knob)."""
    from repro.core import sparse, traversal, rrr
    g_rev = csr.transpose(graph)
    fidx = sparse.build_frontier_index(g_rev, tile_rows=64)
    starts = rrr.batch_starts(graph.num_vertices, 64, 5, 0)
    seed = rrr.batch_seed(5, 0)
    ref = traversal.run_fused(g_rev, starts, 64, seed)
    nb = fidx.num_blocks
    for ladder in ((1, nb), (2, 16, nb), (nb,),
                   sparse.bucket_ladder(nb, capacity=7)):
        res = sparse.run_fused_sparse(fidx, starts, 64, seed, ladder=ladder)
        np.testing.assert_array_equal(np.asarray(res.visited),
                                      np.asarray(ref.visited))
        np.testing.assert_array_equal(
            np.asarray(res.stats.fused_edge_visits),
            np.asarray(ref.stats.fused_edge_visits))
        np.testing.assert_array_equal(
            np.asarray(res.stats.unfused_edge_visits),
            np.asarray(ref.stats.unfused_edge_visits))
        assert int(res.stats.levels_run) == int(ref.stats.levels_run)


def test_sparse_frontier_padded_edge_blocks_inert(graph):
    """Block padding (edge_block ∤ per-row-block edge counts) and the
    appended null block must never contribute: a tiny edge_block maximizes
    padding, and the visited mask still matches dense bit for bit."""
    from repro.core import sparse, traversal, rrr
    g_rev = csr.transpose(graph)
    fidx = sparse.build_frontier_index(g_rev, tile_rows=32, edge_block=16)
    assert int(np.asarray(fidx.blk_valid).sum()) == g_rev.padded_edges
    assert not np.asarray(fidx.blk_valid[-1]).any()      # null block inert
    starts = rrr.batch_starts(graph.num_vertices, 64, 5, 1)
    seed = rrr.batch_seed(5, 1)
    res = sparse.run_fused_sparse(fidx, starts, 64, seed)
    ref = traversal.run_fused(g_rev, starts, 64, seed)
    np.testing.assert_array_equal(np.asarray(res.visited),
                                  np.asarray(ref.visited))


def test_spec_validates_frontier_knobs():
    with pytest.raises(ValueError, match="frontier"):
        sampling.SamplerSpec(frontier="compact")
    with pytest.raises(ValueError, match="frontier_capacity"):
        sampling.SamplerSpec(frontier_capacity=-1)
    spec = sampling.SamplerSpec(frontier="sparse", frontier_capacity=128)
    assert sampling.SamplerSpec.from_manifest(spec.to_manifest()) == spec


# ------------------------------------------------------------ PoolConfig
def test_pool_config_resolves_default_spec():
    cfg = PoolConfig(num_colors=32, master_seed=6)
    assert cfg.spec == sampling.SamplerSpec(num_colors=32, master_seed=6)
    assert hash(cfg) == hash(PoolConfig(num_colors=32, master_seed=6))


def test_pool_config_spec_wins_and_conflicts_raise():
    spec = sampling.SamplerSpec(num_colors=128, master_seed=3)
    cfg = PoolConfig(spec=spec)                 # defaults adopt the spec
    assert cfg.num_colors == 128 and cfg.master_seed == 3
    with pytest.raises(ValueError, match="conflicts"):
        PoolConfig(num_colors=64, master_seed=9, spec=spec)


def test_pool_config_sample_kw_shim_is_gone():
    """The deprecated ``sample_kw`` InitVar (warned since the Sampler-API
    PR) is removed — a typed spec is the only way to configure sampling."""
    with pytest.raises(TypeError, match="sample_kw"):
        PoolConfig(num_colors=64, master_seed=2, sample_kw={"model": "lt"})


def test_pool_config_instances_share_no_mutable_state():
    """The old frozen-dataclass-with-dict-default bug: two default configs
    must not alias a mutable field (the spec is frozen and hashable now)."""
    a, b = PoolConfig(), PoolConfig()
    assert a == b and a.spec == b.spec
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.spec = None
    with pytest.raises(dataclasses.FrozenInstanceError):
        a.spec.num_colors = 1


# -------------------------------------------- LT serving smoke end-to-end
def test_lt_pool_serves_topk_end_to_end(graph):
    cfg = PoolConfig(max_batches=64,
                     spec=sampling.SamplerSpec(diffusion="lt", num_colors=64,
                                               master_seed=13))
    store = SketchStore(graph, cfg)
    store.ensure(6)
    engine = QueryEngine(store)
    batcher = MicroBatcher(engine, cache=ResultCache())
    t = batcher.submit_top_k(4)
    seeds, sigma = batcher.flush()[t]
    assert len(set(seeds.tolist())) == 4 and sigma > 0
    # LT seeds must agree with greedy max-cover over the same LT pool
    ref, cov = imm.greedy_max_cover(store.visited_stack(), 4, 64)
    np.testing.assert_array_equal(seeds, ref)
    # and run_imm under the same spec routes through the pool identically
    fresh = SketchStore(graph, cfg)
    res = imm.run_imm(graph, k=4, eps=0.5, spec=cfg.spec, theta_cap=512,
                      pool=fresh)
    plain = imm.run_imm(graph, k=4, eps=0.5, spec=cfg.spec, theta_cap=512)
    np.testing.assert_array_equal(res.seeds, plain.seeds)


def test_run_imm_legacy_sample_kw_warns(graph):
    with pytest.warns(DeprecationWarning):
        res = imm.run_imm(graph, k=2, eps=0.5, num_colors=64, master_seed=1,
                          theta_cap=256, sort_starts=True)
    assert len(res.seeds) == 2


# -------------------------------------------------- manifest spec guard
def test_restore_refuses_diffusion_mismatch(graph, tmp_path):
    """An IC-sampled pool must never silently serve as LT (or vice versa)."""
    ic_cfg = PoolConfig(num_colors=64, master_seed=8)
    store = SketchStore(graph, ic_cfg)
    store.ensure(2)
    store.save(str(tmp_path))
    lt_cfg = PoolConfig(
        spec=sampling.SamplerSpec(diffusion="lt", num_colors=64,
                                  master_seed=8))
    with pytest.raises(ValueError, match="diffusion"):
        SketchStore.restore(str(tmp_path), graph, lt_cfg)
    # matching spec restores bit-identically and keeps the spec
    r = SketchStore.restore(str(tmp_path), graph, ic_cfg)
    assert r.spec == store.spec
    np.testing.assert_array_equal(np.asarray(store.visited_stack()),
                                  np.asarray(r.visited_stack()))


def test_manifest_records_sampler_spec(graph, tmp_path):
    from repro.checkpoint import manager
    spec = sampling.SamplerSpec(diffusion="lt", num_colors=64, master_seed=1)
    store = SketchStore(graph, PoolConfig(spec=spec))
    store.ensure(1)
    store.save(str(tmp_path))
    extra = manager.read_manifest(str(tmp_path)).get("extra", {})
    assert sampling.SamplerSpec.from_manifest(extra["sampler_spec"]) == spec
