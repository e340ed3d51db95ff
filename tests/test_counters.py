"""The traversal counters every block program returns per batch — levels
run and colors truncated by the level cap — where they go (`RRRBatch`,
`IMMResult`), and the program's host spans (`repro.obs`).

On a chain 0 → 1 → … → n-1 with every edge certain, the RRR set of root r
is {0, …, r}: its traversal runs r + 1 levels (the last one finds nothing),
and a cap of L levels leaves the colors with r ≥ L on the frontier.  Both
hold under IC (p = 1) and LT (one in-edge of weight 1)."""
from __future__ import annotations

import contextlib

import numpy as np
import pytest

from repro import obs, sampling
from repro.core import imm, lt, rrr, sparse, traversal
from repro.graph import csr, generators
from repro.launch.mesh import make_mesh

CHAIN = 40


@pytest.fixture(scope="module")
def chain():
    src = np.arange(CHAIN - 1)
    return csr.from_edges(src, src + 1, np.ones(CHAIN - 1, np.float32),
                          CHAIN)


@pytest.fixture(scope="module")
def graph():
    return csr.dedupe(generators.powerlaw_cluster(200, 6.0, prob=(0.05, 0.3),
                                                  seed=3))


@pytest.mark.parametrize("cap", [8, 1024])
@pytest.mark.parametrize("frontier", ["dense", "sparse"])
@pytest.mark.parametrize("diffusion", ["ic", "lt"])
def test_block_counts_levels_and_truncated(chain, diffusion, frontier, cap):
    spec = sampling.SamplerSpec(diffusion=diffusion, frontier=frontier,
                                num_colors=64, master_seed=4, max_iters=cap)
    batches = sampling.make_sampler(chain, spec).sample_many([0, 1, 2])
    for b in batches:
        roots = np.asarray(b.roots)
        assert b.levels == min(int(roots.max()) + 1, cap)
        assert b.truncated_colors == int(np.sum(roots >= cap))
    if cap == 1024:
        assert all(b.truncated_colors == 0 for b in batches)
    else:
        assert sum(b.truncated_colors for b in batches) > 0


@pytest.mark.parametrize("cap", [3, 64])
def test_block_levels_equal_run_fused(graph, cap):
    """Dense and sparse IC blocks count the levels `run_fused` runs, and
    truncate the colors it leaves on the frontier."""
    spec = sampling.SamplerSpec(num_colors=64, master_seed=2, max_iters=cap)
    dense = sampling.make_sampler(graph, spec).sample_many([0, 1, 2])
    sp = sampling.make_sampler(graph, spec.replace(frontier="sparse")
                               ).sample_many([0, 1, 2])
    g_rev = csr.transpose(graph)
    for b, s in zip(dense, sp):
        ref = traversal.run_fused(g_rev, b.roots, 64,
                                  sampling.make_sampler(graph, spec)
                                  .batch_seed(b.batch_index),
                                  max_levels=cap)
        assert b.levels == s.levels == int(ref.stats.levels_run)
        assert (b.truncated_colors == s.truncated_colors
                == int(ref.stats.truncated_colors))
        assert b.fused_edge_visits == s.fused_edge_visits
    if cap == 3:
        assert sum(b.truncated_colors for b in dense) > 0


@pytest.mark.parametrize("frontier", ["dense", "sparse"])
def test_lt_block_levels_equal_the_walk(graph, frontier):
    """An LT block counts the levels of its walk, as the host-paced
    `sparse.profile_traversal` steps them one by one."""
    spec = sampling.SamplerSpec(diffusion="lt", frontier=frontier,
                                num_colors=64, master_seed=6)
    smp = sampling.make_sampler(graph, spec)
    fidx = sparse.build_frontier_index(
        smp.g_rev, cb=np.asarray(sampling.make_sampler(
            graph, spec.replace(frontier="sparse"))._lt_cb()))
    for b in smp.sample_many([0, 1]):
        walk = sparse.profile_traversal(fidx, b.roots, 64,
                                        smp.batch_seed(b.batch_index),
                                        diffusion="lt")
        assert b.levels == len(walk) > 0
        assert b.truncated_colors == 0


def test_data_parallel_carries_the_sentinel(graph):
    spec = sampling.SamplerSpec(backend="data_parallel", num_colors=64)
    smp = sampling.make_sampler(graph, spec, mesh=make_mesh((1,), ("data",)))
    for b in smp.sample_many([0, 1]):
        assert (b.levels, b.truncated_colors) == (-1, -1)
        assert (b.fused_edge_visits, b.unfused_edge_visits) == (-1, -1)


def test_restored_batch_carries_the_sentinel(graph, tmp_path):
    """Snapshots keep their format: the counters are not saved."""
    from repro.serve.influence import PoolConfig, SketchStore
    store = SketchStore(graph, PoolConfig(max_batches=2))
    store.ensure(2)
    assert all(b.levels > 0 and b.truncated_colors == 0
               for b in store.batches)
    store.save(str(tmp_path))
    back = SketchStore.restore(str(tmp_path), graph, PoolConfig(max_batches=2))
    assert [(b.levels, b.truncated_colors) for b in back.batches] == \
        [(-1, -1)] * 2
    for a, b in zip(store.batches, back.batches):
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))


@pytest.mark.parametrize("cap,cut", [(8, True), (1024, False)])
def test_imm_counts_truncated_sets(chain, cap, cut):
    spec = sampling.SamplerSpec(num_colors=64, master_seed=1, max_iters=cap)
    res = imm.run_imm(chain, k=2, eps=0.5, spec=spec, theta_cap=256)
    assert (res.truncated_sets > 0) == cut


@pytest.mark.parametrize("diffusion", ["ic", "lt"])
def test_one_readback_per_block_on_first_read(chain, diffusion,
                                              monkeypatch):
    """`sample_many` returns without reading the block back; the first
    counter read fetches every batch's counters in one `device_get`."""
    from repro.sampling import sampler as sampler_mod
    reads = []
    get = sampler_mod.jax.device_get
    monkeypatch.setattr(sampler_mod.jax, "device_get",
                        lambda x: reads.append(1) or get(x))
    spec = sampling.SamplerSpec(diffusion=diffusion, num_colors=64,
                                master_seed=9, max_iters=8)
    batches = sampling.make_sampler(chain, spec).sample_many([0, 1, 2])
    assert reads == []
    got = [(b.levels, b.truncated_colors, b.fused_edge_visits)
           for b in batches]
    assert len(reads) == 1
    assert all(levels > 0 for levels, _, _ in got)
    assert all(fused > 0 for _, _, fused in got)


@pytest.mark.parametrize("block", ["dense_ic", "dense_lt", "sparse_ic",
                                   "sparse_lt"])
def test_block_programs_share_one_output_shape(chain, block):
    """(visited (B, V, W), edge_visits (B, 2), level_counts (B, 2)) from
    every block program; LT counts its edge visits as IC does."""
    frontier, diffusion = block.split("_")
    spec = sampling.SamplerSpec(diffusion=diffusion, frontier=frontier,
                                num_colors=64, master_seed=3, max_iters=8)
    smp = sampling.make_sampler(chain, spec)
    starts = np.stack([np.asarray(smp.batch_starts(b)) for b in (0, 1)])
    seeds = np.asarray([smp.batch_seed(b) for b in (0, 1)], np.uint32)
    if frontier == "sparse":
        vis, visits, counts = sparse.sparse_block(
            smp._frontier_index(), starts, seeds, 64, 8, smp._ladder,
            diffusion=diffusion)
    elif diffusion == "lt":
        vis, visits, counts = lt.run_fused_lt_block(
            smp.g_rev, smp._lt_cb(), starts, seeds, 64, max_levels=8)
    else:
        vis, visits, counts = traversal.run_fused_block(
            smp.g_rev, starts, seeds, 64, max_levels=8)
    assert vis.shape == (2, CHAIN, 2)
    assert visits.shape == counts.shape == (2, 2)
    assert visits.dtype == counts.dtype == np.int32
    assert (np.asarray(visits) > 0).all()
    for b, row, pair in zip(smp.sample_many([0, 1]), np.asarray(counts),
                            np.asarray(visits)):
        assert (b.levels, b.truncated_colors) == tuple(row)
        assert (b.fused_edge_visits, b.unfused_edge_visits) == tuple(pair)
        # A single batch is a block of one, with the same four counters
        # as the block's row and, on the sparse frontier, as the
        # single-batch sparse programs count them.
        one = smp.sample(b.batch_index)
        one = (one.fused_edge_visits, one.unfused_edge_visits, one.levels,
               one.truncated_colors)
        assert one == (*pair, *row)
        if frontier == "sparse":
            i = b.batch_index
            if diffusion == "lt":
                _, levels, truncated, v = sparse.run_fused_lt_sparse(
                    smp._frontier_index(), starts[i], 64, seeds[i],
                    max_levels=8, ladder=smp._ladder)
                want = (*map(int, v), int(levels), int(truncated))
            else:
                want = rrr.stats_counts(sparse.run_fused_sparse(
                    smp._frontier_index(), starts[i], 64, seeds[i],
                    max_levels=8, ladder=smp._ladder).stats)
            assert one == want


@pytest.mark.parametrize("diffusion,batches,per_loop,loops", [
    ("ic", 4, 4, 1), ("ic", 5, 4, 2), ("ic", 1, 1, 1), ("lt", 3, 1, 3)])
def test_dispatch_span_says_how_batches_share_a_loop(
        chain, monkeypatch, diffusion, batches, per_loop, loops):
    """The ``sampling.dispatch`` host span carries the batches a level
    loop runs (dense IC: as many as fill a tile's 8 sublanes) and the
    loops the block ran."""
    seen = []

    def span(name, **args):
        seen.append((name, args))
        return contextlib.nullcontext()

    monkeypatch.setattr(obs, "span", span)
    spec = sampling.SamplerSpec(diffusion=diffusion, num_colors=64,
                                master_seed=3, max_iters=8)
    sampling.make_sampler(chain, spec).sample_many(range(batches))
    (args,) = [a for n, a in seen if n == "sampling.dispatch"]
    assert args == dict(batches=batches, batches_per_loop=per_loop,
                        loops=loops)


def test_span_runs_without_a_profiler():
    with obs.span("store.refresh", epoch=3, first_batch=12):
        pass


@pytest.mark.parametrize("frontier", ["dense", "sparse"])
def test_lt_edge_visits_count_each_member_once(graph, frontier):
    """IC's definitions on an LT walk: each (vertex, color) of a set is on
    the frontier at exactly one level, so the unfused visits are the sum
    over colors of the out-degrees (reversed graph) of the set's members;
    the fused visits count an edge once a level whatever its colors, and
    dense and sparse agree on both."""
    spec = sampling.SamplerSpec(diffusion="lt", frontier=frontier,
                                num_colors=64, master_seed=12,
                                max_iters=1024)
    smp = sampling.make_sampler(graph, spec)
    deg = np.bincount(np.asarray(smp.g_rev.src),
                      minlength=graph.num_vertices)
    dense = sampling.make_sampler(graph, spec.replace(frontier="dense"))
    for b, d in zip(smp.sample_many([0, 1, 2]),
                    dense.sample_many([0, 1, 2])):
        vis = np.asarray(b.visited)
        members = sum(int(deg[np.flatnonzero((vis[:, c // 32] >> (c % 32))
                                             & 1)].sum())
                      for c in range(64))
        assert b.truncated_colors == 0
        assert b.unfused_edge_visits == members
        assert 0 < b.fused_edge_visits <= b.unfused_edge_visits
        assert (b.fused_edge_visits, b.unfused_edge_visits) == \
            (d.fused_edge_visits, d.unfused_edge_visits)
