"""Linear Threshold diffusion: live-edge selection invariants + fused LT
traversal behaviour + the plain reference's sets + Table-1 dataset clones.

`g_lt` is used as a REVERSED graph, as the sampling facade holds one: the
vertex that picks is an edge's source, among its own row (its forward
in-edges)."""
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitmask, lt, traversal
from repro.graph import csr, datasets, generators


@pytest.fixture(scope="module")
def g_lt():
    g = generators.powerlaw_cluster(300, 6.0, prob=(0.2, 1.0), seed=6)
    return lt.normalize_lt_weights(g)


def test_normalize_in_weights_leq_one(g_lt):
    """Each row's weights — a vertex's forward in-weights — sum to ≤ 1."""
    e = g_lt.num_edges
    src = np.asarray(g_lt.src)[:e]
    prob = np.asarray(g_lt.prob)[:e].astype(np.float64)
    sums = np.zeros(g_lt.num_vertices)
    np.add.at(sums, src, prob)
    assert sums.max() <= 1.0 + 1e-5


def test_selection_at_most_one_in_edge_per_color(g_lt):
    """THE LT invariant: every (vertex, color) selects ≤ 1 incoming edge —
    on the reversed graph, ≤ 1 edge of the vertex's own row."""
    sel = lt._selection_mask(g_lt, 64, jnp.uint32(3))
    e = g_lt.num_edges
    src = np.asarray(g_lt.src)[:e]
    bits = np.asarray(bitmask.unpack_bits(sel[:e]))       # (E, W, 32)
    per_color = bits.reshape(e, -1)                       # (E, C)
    counts = np.zeros((g_lt.num_vertices, per_color.shape[1]), np.int32)
    np.add.at(counts, src, per_color.astype(np.int32))
    assert counts.max() <= 1
    assert counts.sum() > 0


@pytest.mark.parametrize("seed", [0, 1, 3])
def test_selection_intervals_tile_where_float64_prefixes_overlap(seed):
    """A row of 12 weights (Σ < 1) on which prefixes summed in float64 and
    rounded once overlap: f32(cb_i + w_i) passes cb_{i+1}, so a uniform
    there would keep two in-edges.  The float32 scan of
    `lt.selection_cum_before` makes each interval end, added in float32
    as the selection adds it, the next edge's start bit for bit; every
    uniform at or near a boundary falls in exactly one interval below the
    row's total; the reference's intervals are the same numbers."""
    from lt_reference import ref_lt

    n = 12
    w = (np.random.default_rng(seed).uniform(0.01, 1.0, n) / n * 0.9
         ).astype(np.float32)
    cb64 = np.concatenate(
        [[0.0], np.cumsum(w.astype(np.float64))[:-1]]).astype(np.float32)
    assert np.any((cb64 + w)[:-1] > cb64[1:])             # the old overlap

    # Reversed graph: row 0 is vertex 0's forward in-edges from 1..n.
    g = lt.normalize_lt_weights(csr.from_edges(
        np.zeros(n, np.int64), np.arange(1, n + 1), w, n + 1))
    np.testing.assert_array_equal(np.asarray(g.prob)[:n], w)
    cb = jnp.asarray(lt.selection_cum_before(g))[:n]
    hi = np.asarray(cb + g.prob[:n])                      # device f32 add
    cb = np.asarray(cb)
    np.testing.assert_array_equal(hi[:-1], cb[1:])

    bounds = np.concatenate([cb64, cb64 + w, cb, hi]).astype(np.float32)
    us = np.concatenate([bounds, np.nextafter(bounds, np.float32(0)),
                         np.nextafter(bounds, np.float32(1))])
    held = ((cb[None, :] <= us[:, None]) & (us[:, None] < hi[None, :])
            ).sum(1)
    assert held.max() == 1
    np.testing.assert_array_equal(held[us < hi[-1]], 1)

    fwd = SimpleNamespace(num_vertices=n + 1, num_edges=n,
                          src=np.arange(1, n + 1, dtype=np.int32),
                          dst=np.zeros(n, np.int32), prob=w)
    srcs, lo, end = ref_lt.InGraph.from_edges(fwd).intervals(0)
    np.testing.assert_array_equal(srcs, np.arange(1, n + 1))
    np.testing.assert_array_equal(lo, cb)
    np.testing.assert_array_equal(end, hi)


def test_selection_rate_matches_weight(g_lt):
    """P(edge selected) == its LT weight (over many colors)."""
    C = 512
    sel = lt._selection_mask(g_lt, C, jnp.uint32(11))
    e = g_lt.num_edges
    rate = np.asarray(bitmask.count_colors(sel[:e])) / C
    prob = np.asarray(g_lt.prob)[:e]
    heavy = prob > 0.2
    assert heavy.sum() > 10
    np.testing.assert_allclose(rate[heavy], prob[heavy], atol=0.08)


def test_run_fused_lt_reaches_starts_and_is_deterministic(g_lt):
    starts = traversal.random_starts(jax.random.key(0),
                                     g_lt.num_vertices, 32)
    v1 = lt.run_fused_lt(g_lt, starts, 32, 9)
    v2 = lt.run_fused_lt(g_lt, starts, 32, 9)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    vis = np.asarray(v1)
    for c, s in enumerate(np.asarray(starts)):
        assert vis[s, c // 32] >> (c % 32) & 1
    # selections are fixed per traversal: a different seed changes them
    v3 = lt.run_fused_lt(g_lt, starts, 32, 10)
    assert not np.array_equal(np.asarray(v1), np.asarray(v3))


def test_fused_lt_matches_naive_bfs_over_selected_edges(g_lt):
    """Gold test: fused LT traversal ≡ per-color BFS over exactly the
    live edges the selection mask chose (deterministic oracle)."""
    C = 32
    starts = traversal.random_starts(jax.random.key(2),
                                     g_lt.num_vertices, C)
    seed = jnp.uint32(5)
    vis = np.asarray(lt.run_fused_lt(g_lt, starts, C, 5))
    sel = np.asarray(lt._selection_mask(g_lt, C, seed))
    e = g_lt.num_edges
    src = np.asarray(g_lt.src)[:e]
    dst = np.asarray(g_lt.dst)[:e]
    for c in range(C):
        live = (sel[:e, c // 32] >> (c % 32)) & 1
        adj = {}
        for s, d, l in zip(src, dst, live):
            if l:
                adj.setdefault(int(s), []).append(int(d))
        seen, stack = {int(starts[c])}, [int(starts[c])]
        while stack:
            v = stack.pop()
            for u in adj.get(v, []):
                if u not in seen:
                    seen.add(u)
                    stack.append(u)
        got = {int(v) for v in
               np.flatnonzero((vis[:, c // 32] >> (c % 32)) & 1)}
        assert got == seen, f"color {c}"


# ------------------------------------------------------------------ datasets
def test_table1_clone_sizes():
    g = datasets.table1_clone("web-Google", scale=0.01)
    assert abs(g.num_vertices - 8757) < 200
    deg = g.num_edges / g.num_vertices
    assert 5 < deg < 25      # clone tracks the table's avg degree loosely


def test_table1_unknown_raises():
    with pytest.raises(KeyError):
        datasets.table1_clone("not-a-graph")


def test_load_snap_roundtrip(tmp_path):
    p = tmp_path / "tiny.txt"
    p.write_text("# comment\n0 1\n1 2\n2 0\n")
    g = datasets.load_snap(str(p))
    assert g.num_vertices == 3 and g.num_edges == 3


# --------------------------------------------------------------- LT in IMM
def test_imm_pipeline_under_lt(g_lt):
    """RRR sampling + greedy max-cover run end-to-end under LT; the chosen
    seeds beat random seeds on a fresh LT collection."""
    from repro.core import imm, rrr
    g_rev = csr.transpose(g_lt)
    g_rev = lt.normalize_lt_weights(g_rev)
    batches = [rrr.sample_batch(g_rev, 64, 3, b, model="lt")
               for b in range(16)]
    visited = rrr.stack_visited(batches)
    seeds, cov = imm.greedy_max_cover(visited, 4, 64)
    assert 0 < cov <= 1 and len(set(seeds.tolist())) == 4
    fresh = rrr.stack_visited(
        [rrr.sample_batch(g_rev, 64, 99, b, model="lt") for b in range(16)])
    rng0 = np.random.default_rng(1)
    rand_cov = np.mean([imm.coverage_of(
        fresh, rng0.integers(0, g_lt.num_vertices, 4), 64)
        for _ in range(8)])
    assert imm.coverage_of(fresh, seeds, 64) > rand_cov


# ------------------------------------------- the plain reference's sets
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dense_lt_equals_plain_reference(seed):
    """Dense LT through `make_sampler` and `SketchStore.refresh` equals
    `bench/reference/lt.py` bit for bit, every set of every batch, on
    seeded random directed graphs with asymmetric weights."""
    from repro import sampling
    from repro.serve.influence import PoolConfig, SketchStore
    from lt_reference import differing_sets, random_edges

    edges = random_edges(120 + 40 * seed, 700 + 300 * seed, seed)
    g = csr.from_edges(edges.src, edges.dst, edges.prob, edges.num_vertices)
    spec = sampling.SamplerSpec(diffusion="lt", num_colors=64,
                                master_seed=seed + 3, max_iters=1024)
    batches = sampling.make_sampler(g, spec).sample_many(range(3))
    assert differing_sets(edges, batches, spec.master_seed) == 0
    store = SketchStore(g, PoolConfig(max_batches=2, spec=spec))
    store.ensure(2)
    slots = store.refresh(1.0)
    assert differing_sets(edges, [store.batches[i] for i in slots],
                          spec.master_seed) == 0
    assert all(b.levels > 0 and b.truncated_colors == 0 for b in batches)


def test_two_in_neighbours_keep_exactly_one():
    """Forward edges 1→0 and 2→0, weight ½ each: vertex 0's in-weights sum
    to 1, so LT keeps exactly one of them, half the time each, and every
    set rooted at 0 is {0,1} or {0,2}.  (Picking by the destination of
    the reversed edge gives {0}, {0,1}, {0,2} and {0,1,2}.)"""
    from repro import sampling
    g = csr.from_edges(np.array([1, 2]), np.array([0, 0]),
                       np.full(2, 0.5, np.float32), 3)
    spec = sampling.SamplerSpec(diffusion="lt", backend="dense",
                                num_colors=64, master_seed=0)
    counts = {}
    for b in sampling.make_sampler(g, spec).sample_many(range(40)):
        vis = np.asarray(b.visited)
        for c, root in enumerate(np.asarray(b.roots)):
            members = frozenset(np.flatnonzero(
                (vis[:, c // 32] >> (c % 32)) & 1).tolist())
            if root == 0:
                counts[members] = counts.get(members, 0) + 1
            else:
                assert members == {int(root)}
    assert set(counts) == {frozenset({0, 1}), frozenset({0, 2})}, counts
    total = sum(counts.values())
    assert total >= 400
    for n in counts.values():
        assert 0.35 <= n / total <= 0.65, counts
