"""Fused-BPT behaviour tests: coupled fused/unfused equivalence, Theorem 1,
monotonicity, determinism, and Fig.-3-style hand-checkable cases."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitmask, rng, traversal
from repro.graph import csr, generators


SEED = jnp.uint32(2024)


def _run(g, n_colors, seed=SEED, key=0, sort=False):
    starts = traversal.random_starts(jax.random.key(key), g.num_vertices,
                                     n_colors, sort=sort)
    return starts, traversal.run_fused(g, starts, n_colors, seed)


def test_fused_equals_unfused_coupled(small_graph):
    """Bit-for-bit: fused visited == union of single-color runs on the SAME
    RNG streams. This is the exactness the counter RNG buys us."""
    starts, res = _run(small_graph, 64)
    vis_unfused, _ = traversal.run_unfused(small_graph, np.asarray(starts),
                                           64, SEED)
    np.testing.assert_array_equal(np.asarray(res.visited),
                                  np.asarray(vis_unfused))


def test_theorem1_fused_visits_leq_unfused(small_graph):
    """Theorem 1 on coupled realizations: fused edge visits ≤ unfused."""
    _, res = _run(small_graph, 128)
    fused = int(res.stats.fused_edge_visits.sum())
    unfused = int(res.stats.unfused_edge_visits.sum())
    assert fused <= unfused
    assert fused > 0


@given(st.integers(0, 10_000))
@settings(max_examples=8, deadline=None)
def test_theorem1_property(seed):
    """Theorem 1 must hold for every graph/seed — property test."""
    g = generators.erdos_renyi(120, 5.0, prob=0.4, seed=seed % 97)
    starts = traversal.random_starts(jax.random.key(seed), g.num_vertices, 32)
    res = traversal.run_fused(g, starts, 32, jnp.uint32(seed))
    assert int(res.stats.fused_edge_visits.sum()) <= \
        int(res.stats.unfused_edge_visits.sum())


def test_start_vertices_always_visited(small_graph):
    starts, res = _run(small_graph, 64)
    vis = np.asarray(res.visited)
    for c, v in enumerate(np.asarray(starts)):
        assert vis[v, c // 32] >> (c % 32) & 1, f"color {c} missing own start"


def test_visited_closed_under_reachability_p1(tiny_graph):
    """With p=1 the BPT is a plain BFS: visited == reachable set."""
    g = tiny_graph
    e = g.num_edges
    g1 = csr.from_edges(np.asarray(g.src)[:e], np.asarray(g.dst)[:e],
                        np.ones(e, np.float32), g.num_vertices)
    starts = jnp.zeros((1,), jnp.int32)          # single color from vertex 0
    res = traversal.run_fused(g1, starts, 1, SEED)
    vis = np.asarray(res.visited)[:, 0] & 1
    # host BFS oracle
    adj = {}
    for s, d in zip(np.asarray(g1.src)[:e], np.asarray(g1.dst)[:e]):
        adj.setdefault(int(s), []).append(int(d))
    seen, stack = {0}, [0]
    while stack:
        v = stack.pop()
        for u in adj.get(v, []):
            if u not in seen:
                seen.add(u)
                stack.append(u)
    expected = np.zeros(g1.num_vertices, np.uint32)
    expected[list(seen)] = 1
    np.testing.assert_array_equal(vis, expected)


def test_zero_prob_never_propagates(tiny_graph):
    g = tiny_graph
    e = g.num_edges
    g0 = csr.from_edges(np.asarray(g.src)[:e], np.asarray(g.dst)[:e],
                        np.zeros(e, np.float32), g.num_vertices)
    starts = jnp.asarray([2, 5], jnp.int32)
    res = traversal.run_fused(g0, starts, 2, SEED)
    assert int(bitmask.count_colors(res.visited).sum()) == 2  # only starts


def test_determinism_same_seed(small_graph):
    s1, r1 = _run(small_graph, 32, key=5)
    s2, r2 = _run(small_graph, 32, key=5)
    np.testing.assert_array_equal(np.asarray(r1.visited), np.asarray(r2.visited))


def test_different_seed_differs(small_graph):
    starts, _ = _run(small_graph, 32)
    r1 = traversal.run_fused(small_graph, starts, 32, jnp.uint32(1))
    r2 = traversal.run_fused(small_graph, starts, 32, jnp.uint32(2))
    assert not np.array_equal(np.asarray(r1.visited), np.asarray(r2.visited))


def test_visited_monotone_in_prob():
    """Stochastic-dominance sanity: higher p ⇒ more visited (coupled draws
    share the same uniforms, so dominance is exact per color)."""
    rng = np.random.default_rng(0)
    src = rng.integers(0, 200, 1200)
    dst = (src + 1 + rng.integers(0, 199, 1200)) % 200
    starts = jnp.arange(16, dtype=jnp.int32)
    sizes = []
    for p in (0.05, 0.3, 0.8):
        g = csr.from_edges(src, dst, np.full(1200, p, np.float32), 200)
        res = traversal.run_fused(g, starts, 16, SEED)
        sizes.append(int(bitmask.count_colors(res.visited).sum()))
    assert sizes[0] <= sizes[1] <= sizes[2]


def test_multiple_colors_same_start(tiny_graph):
    """Paper Fig. 3: several traversals may start at one vertex."""
    starts = jnp.asarray([1, 1, 1], jnp.int32)
    res = traversal.run_fused(tiny_graph, starts, 3, SEED)
    vis = np.asarray(res.visited)
    assert vis[1, 0] & 0b111 == 0b111
    # colors evolve independently despite the shared start
    cols = [(vis[:, 0] >> c) & 1 for c in range(3)]
    assert not (np.array_equal(cols[0], cols[1])
                and np.array_equal(cols[1], cols[2]))


def test_max_levels_cap():
    """A long path graph with p=1 stops at the level cap but keeps frontier
    colors in visited."""
    n = 50
    src = np.arange(n - 1)
    dst = np.arange(1, n)
    g = csr.from_edges(src, dst, np.ones(n - 1, np.float32), n)
    res = traversal.run_fused(g, jnp.zeros((1,), jnp.int32), 1, SEED,
                              max_levels=10)
    assert int(res.stats.levels_run) == 10
    vis = np.asarray(res.visited)[:, 0]
    assert vis[:11].all() and not vis[12:].any()


def test_stats_occupancy_bounds(small_graph):
    _, res = _run(small_graph, 64)
    occ = np.asarray(res.stats.occupancy_num)
    assert (occ >= 0).all() and (occ <= 1.0 + 1e-6).all()
    frac = np.asarray(res.stats.active_tile_frac)
    assert (frac >= 0).all() and (frac <= 1.0).all()


def _assert_block_matches_per_batch(g, starts, seeds, n_colors, **kw):
    """`run_fused_block` on a block equals `run_fused` on each of its
    batches alone: visited masks, summed (fused, unfused) edge visits,
    levels and truncated colors."""
    vis, visits, counts = traversal.run_fused_block(g, starts, seeds,
                                                    n_colors, **kw)
    assert vis.shape == (len(seeds), g.num_vertices,
                         bitmask.num_words(n_colors))
    fused, unfused = np.asarray(visits).T
    levels, truncated = np.asarray(counts).T
    for i in range(len(seeds)):
        ref = traversal.run_fused(g, starts[i], n_colors, seeds[i], **kw)
        np.testing.assert_array_equal(np.asarray(vis[i]),
                                      np.asarray(ref.visited))
        assert int(fused[i]) == int(np.asarray(
            ref.stats.fused_edge_visits, np.int64).sum())
        assert int(unfused[i]) == int(np.asarray(
            ref.stats.unfused_edge_visits, np.int64).sum())
        assert int(levels[i]) == int(ref.stats.levels_run)
        assert int(truncated[i]) == int(ref.stats.truncated_colors)
    return levels, truncated


# (batches, colors): every block size at one, two, four and eight words
# a batch, so G = 8, 4, 2, 1 batches a loop; full groups, a tail group,
# and G = 1.  The three-batch cases keep their ids.
BLOCKS = [pytest.param(b, c, id=str(c) if b == 3 else f"b{b}-c{c}")
          for b in (1, 3, 4, 5, 9) for c in (32, 64, 128, 256)]


@pytest.mark.parametrize("num_batches,n_colors", BLOCKS)
def test_run_fused_block_matches_per_batch(small_graph, num_batches,
                                           n_colors):
    """The fused multi-batch sweep (ONE dispatch — the pool-build fast
    path, G batches a level loop) must reproduce per-batch run_fused
    exactly: visited masks AND summed edge-visit counters, levels and
    truncated colors — at one, two, four and eight words, so the
    word-major level loop is checked at every width it transposes, and
    at block sizes that leave a tail group."""
    starts = jnp.stack([
        traversal.random_starts(jax.random.key(k), small_graph.num_vertices,
                                n_colors) for k in range(num_batches)])
    seeds = jnp.asarray(7 + np.arange(num_batches), jnp.uint32)
    _assert_block_matches_per_batch(small_graph, starts, seeds, n_colors)


def test_run_fused_block_group_of_uneven_batches():
    """One loop of four batches in which one batch empties at its first
    level and another is cut by ``max_levels``: each still counts its own
    levels, visits and truncated colors, as a loop of its own would."""
    n, path = 40, 30
    src = np.arange(path - 1)                 # a path 0 → 1 → … → 29
    dst = np.arange(1, path)
    g = csr.from_edges(np.concatenate([src, [31, 32]]),
                       np.concatenate([dst, [32, 33]]),
                       np.ones(path + 1, np.float32), n)
    sink = n - 1                              # no out-edges
    starts = jnp.asarray([np.full(64, sink), np.zeros(64),
                          np.arange(64) % n, np.full(64, 31)], jnp.int32)
    seeds = jnp.asarray([3, 4, 5, 6], jnp.uint32)
    assert traversal.batches_per_loop(4, 64) == 4
    levels, truncated = _assert_block_matches_per_batch(
        g, starts, seeds, 64, max_levels=8)
    assert levels.tolist()[0] == 1 and levels.tolist()[3] == 3
    assert levels.tolist()[1] == 8 and truncated[1] == 64


def _edge_major_level(g, frontier, visited, level, seed):
    """One level written edge-major in CSR order — (E, W) gathers and RNG
    words, a sort-based OR — independent of `_expand`'s layout."""
    visited = visited | frontier
    fr_src = frontier[g.src]                                # (E, W)
    edge_ids = jnp.arange(g.padded_edges, dtype=jnp.uint32)
    rand = jnp.stack([rng.bernoulli_word(seed, jnp.uint32(level), edge_ids,
                                         jnp.uint32(w), g.prob)
                      for w in range(frontier.shape[1])], axis=1)
    nf = traversal._scatter_or(jnp.zeros_like(visited), g.dst,
                               fr_src & rand) & ~visited
    return nf, visited


@pytest.mark.parametrize("n_colors", [32, 64, 128])
def test_word_major_level_matches_edge_major(small_graph, n_colors):
    """The dense level's (W, V) / (W, E) layout draws the same bits, keyed
    by (seed, level, CSR edge id, color), as the edge-major formulation:
    frontier and visited agree level by level."""
    starts = traversal.random_starts(jax.random.key(3),
                                     small_graph.num_vertices, n_colors)
    fr = traversal.init_frontier(small_graph.num_vertices, n_colors, starts)
    vis = jnp.zeros_like(fr)
    ref_fr, ref_vis = fr, vis
    for level in range(6):
        fr, vis, _ = traversal.fused_step(small_graph, fr, vis,
                                          jnp.int32(level), SEED)
        ref_fr, ref_vis = _edge_major_level(small_graph, ref_fr, ref_vis,
                                            level, SEED)
        np.testing.assert_array_equal(np.asarray(fr), np.asarray(ref_fr))
        np.testing.assert_array_equal(np.asarray(vis), np.asarray(ref_vis))
    assert int(bitmask.count_colors(vis).sum()) > n_colors


def test_run_fused_lt_block_matches_per_batch(small_graph):
    from repro.core import lt
    g = lt.normalize_lt_weights(small_graph)
    cb = jnp.asarray(lt.selection_cum_before(g))
    starts = jnp.stack([
        traversal.random_starts(jax.random.key(k), g.num_vertices, 64)
        for k in range(2)])
    seeds = jnp.asarray([3, 4], jnp.uint32)
    vis = lt.run_fused_lt_block(g, cb, starts, seeds, 64)[0]
    for i in range(2):
        ref = lt.run_fused_lt(g, starts[i], 64, seeds[i])
        np.testing.assert_array_equal(np.asarray(vis[i]), np.asarray(ref))
