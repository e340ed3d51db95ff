"""Per-kernel tests: interpret-mode Pallas vs pure-jnp oracle, swept over
shapes/dtypes/graphs, plus end-to-end tiled-vs-CSR traversal coupling."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitmask, tiles, tiled_traversal, traversal
from repro.graph import csr, generators
from repro.kernels import coverage, flash_attention, fused_expand, ops, ref


def _random_graph(n, e, p, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    if isinstance(p, tuple):
        probs = rng.uniform(*p, e).astype(np.float32)
    else:
        probs = np.full(e, p, np.float32)
    return csr.from_edges(src, dst, probs, n, dedupe=True)


# ---------------------------------------------------------------- fused_expand
@pytest.mark.parametrize("tile_size", [64, 128])
@pytest.mark.parametrize("n_colors", [32, 64, 96])
@pytest.mark.parametrize("p", [0.0, 0.3, 1.0, (0.1, 0.9)])
def test_fused_expand_kernel_matches_ref(tile_size, n_colors, p):
    g = _random_graph(300, 1500, p, seed=tile_size + n_colors)
    tg = tiles.from_graph(g, tile_size=tile_size)
    starts = traversal.random_starts(jax.random.key(0), g.num_vertices, n_colors)
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, n_colors, starts),
        tg.padded_vertices)
    seed, level = jnp.uint32(5), jnp.uint32(0)
    out_ref = ref.fused_expand_ref(tg.prob, tg.edge_id, tg.tile_src,
                                   tg.tile_dst, fr, fr, seed, level)
    out_ker = fused_expand.fused_expand(
        tg.prob, tg.edge_id, tg.tile_src, tg.tile_dst, tg.first_of_dst,
        fr, fr, seed, level, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_ker))


def test_fused_expand_matches_csr_step():
    """Tile path ≡ CSR edge-centric path, bit-for-bit (coupled RNG)."""
    g = _random_graph(500, 4000, (0.2, 0.8), seed=3)
    tg = tiles.from_graph(g)
    starts = traversal.random_starts(jax.random.key(2), g.num_vertices, 64)
    fr = traversal.init_frontier(g.num_vertices, 64, starts)
    nf_csr, _, _ = traversal.fused_step(
        g, fr, bitmask.make_mask(g.num_vertices, 64), jnp.int32(0),
        jnp.uint32(11))
    fr_p = tiles.pad_mask_rows(fr, tg.padded_vertices)
    nf_tile = ops.fused_expand(tg, fr_p, fr_p, 11, 0)
    np.testing.assert_array_equal(
        np.asarray(nf_tile)[: g.num_vertices], np.asarray(nf_csr))


def test_fused_expand_empty_frontier():
    g = _random_graph(200, 800, 0.5)
    tg = tiles.from_graph(g)
    fr = jnp.zeros((tg.padded_vertices, 2), jnp.uint32)
    out = ops.fused_expand(tg, fr, fr, 0, 0)
    assert int(np.asarray(out).sum()) == 0


def test_fused_expand_padded_tiles_are_noops():
    g = _random_graph(300, 1200, 0.6, seed=9)
    tg = tiles.from_graph(g)
    tg_pad = tiles.from_graph(g, pad_tiles_to=tg.num_tiles + 7)
    starts = traversal.random_starts(jax.random.key(1), g.num_vertices, 32)
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, 32, starts),
        tg.padded_vertices)
    a = ops.fused_expand(tg, fr, fr, 4, 0)
    b = ops.fused_expand(tg_pad, fr, fr, 4, 0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("use_kernel", [False, True])
def test_tiled_traversal_equals_csr_traversal(use_kernel):
    g = _random_graph(400, 2500, (0.1, 0.7), seed=17)
    n_colors = 64
    starts = traversal.random_starts(jax.random.key(5), g.num_vertices, n_colors)
    res_csr = traversal.run_fused(g, starts, n_colors, jnp.uint32(21))
    tg = tiles.from_graph(g)
    vis_tiled, levels, grid_steps = tiled_traversal.run_fused_tiled(
        tg, starts, n_colors, 21, use_kernel=use_kernel,
        interpret=ops._interpret())
    np.testing.assert_array_equal(np.asarray(vis_tiled),
                                  np.asarray(res_csr.visited))
    assert int(levels) == int(res_csr.stats.levels_run)
    assert int(grid_steps) == int(levels) * tg.num_tiles   # dense grid


# ------------------------------------------------------------ lt_select_expand
@pytest.mark.parametrize("tile_size", [32, 64, 128])
@pytest.mark.parametrize("n_colors", [32, 64, 96])
def test_lt_select_expand_kernel_matches_ref(tile_size, n_colors):
    """One LT expansion level: Pallas kernel ≡ jnp oracle, bit for bit,
    across tile sizes (incl. padded last blocks) and multi-word colors."""
    from repro.core import lt
    from repro.kernels import lt_select_expand as lse
    g = lt.normalize_lt_weights(
        _random_graph(300, 1500, (0.1, 0.9), seed=tile_size + n_colors))
    tg = tiles.from_graph(g, tile_size=tile_size)
    cb = tiles.edge_values_to_tiles(tg, lt.selection_cum_before(g))
    starts = traversal.random_starts(jax.random.key(0), g.num_vertices,
                                     n_colors)
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, n_colors, starts),
        tg.padded_vertices)
    seed = jnp.uint32(5)
    out_ref = ref.lt_select_expand_ref(tg.prob, cb, tg.tile_src,
                                       tg.tile_dst, fr, fr, seed)
    out_ker = lse.lt_select_expand(tg.prob, cb, tg.tile_src, tg.tile_dst,
                                   tg.first_of_dst, fr, fr, seed,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_ker))


@pytest.mark.parametrize("frontier", ["dense", "sparse"])
def test_lt_tiled_kernel_traversal_equals_dense_lt(frontier):
    """Full LT traversal through the Pallas kernel (dense grid and the
    compacted sparse grid) ≡ `lt.run_fused_lt` on the CSR path; the sparse
    grid must run no more steps than the dense grid."""
    from repro.core import lt
    g = lt.normalize_lt_weights(_random_graph(400, 2500, (0.1, 0.7),
                                              seed=11))
    starts = traversal.random_starts(jax.random.key(4), g.num_vertices, 64)
    ref_vis = lt.run_fused_lt(g, starts, 64, jnp.uint32(9))
    tg = tiles.from_graph(g)
    cb = tiles.edge_values_to_tiles(tg, lt.selection_cum_before(g))
    vis, levels, gs = tiled_traversal.run_fused_lt_tiled(
        tg, cb, starts, 64, 9, use_kernel=True, interpret=ops._interpret(),
        frontier=frontier)
    np.testing.assert_array_equal(np.asarray(vis), np.asarray(ref_vis))
    if frontier == "dense":
        assert int(gs) == int(levels) * tg.num_tiles
    else:
        assert 0 < int(gs) <= int(levels) * tg.num_tiles


# -------------------------------------------------------------------- coverage
@pytest.mark.parametrize("rows,words", [(128, 1), (256, 2), (384, 4), (1024, 32)])
def test_cover_counts_matches_ref(rows, words):
    rng = np.random.default_rng(rows + words)
    vis = jnp.asarray(rng.integers(0, 2**32, (rows, words), dtype=np.uint32))
    act = jnp.asarray(rng.integers(0, 2**32, (words,), dtype=np.uint32))
    out_k = coverage.cover_counts(vis, act, interpret=True)
    out_r = ref.cover_counts_ref(vis, act)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


def test_cover_counts_unpadded_rows():
    rng = np.random.default_rng(0)
    vis = jnp.asarray(rng.integers(0, 2**32, (300, 2), dtype=np.uint32))
    act = jnp.asarray([0xFFFFFFFF, 0xFF], dtype=jnp.uint32)
    out = ops.cover_counts(vis, act)
    assert out.shape == (300,)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.cover_counts_ref(vis, act)))


def test_cover_counts_active_mask_excludes():
    vis = jnp.full((128, 1), 0xFFFFFFFF, jnp.uint32)
    assert int(ops.cover_counts(vis, jnp.asarray([0x0F], jnp.uint32))[0]) == 4


# ------------------------------------------------------------- flash attention
@pytest.mark.parametrize("L,H,D", [(128, 2, 64), (256, 4, 128), (384, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(L, H, D, dtype, causal):
    k1, k2, k3 = jax.random.split(jax.random.key(L + H), 3)
    q = jax.random.normal(k1, (L, H, D), dtype)
    k = jax.random.normal(k2, (L, H, D), dtype)
    v = jax.random.normal(k3, (L, H, D), dtype)
    out = flash_attention.flash_attention(q, k, v, causal=causal,
                                          block_q=128, block_k=128,
                                          interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_decode_offset():
    """Decode: 128 new queries against a 512 cache with kv_offset."""
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(k1, (128, 2, 64), jnp.float32)
    k = jax.random.normal(k2, (512, 2, 64), jnp.float32)
    v = jax.random.normal(k3, (512, 2, 64), jnp.float32)
    out = flash_attention.flash_attention(q, k, v, causal=True, kv_offset=384,
                                          interpret=True)
    expected = ref.flash_attention_ref(q, k, v, causal=True, kv_offset=384)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=1e-5, rtol=1e-5)


def test_flash_attention_block_shape_invariance():
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(k1, (256, 2, 64), jnp.float32)
    k = jax.random.normal(k2, (256, 2, 64), jnp.float32)
    v = jax.random.normal(k3, (256, 2, 64), jnp.float32)
    a = flash_attention.flash_attention(q, k, v, block_q=128, block_k=128,
                                        interpret=True)
    b = flash_attention.flash_attention(q, k, v, block_q=256, block_k=64,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                               rtol=1e-5)


# --------------------------------------------------------- quantized kernel
def test_fused_expand_q_kernel_matches_ref():
    from repro.kernels import fused_expand_q as feq
    g = _random_graph(400, 2500, (0.1, 0.9), seed=5)
    tg = tiles.from_graph(g)
    q8 = feq.quantize_probs(tg.prob)
    starts = traversal.random_starts(jax.random.key(0), g.num_vertices, 64)
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, 64, starts),
        tg.padded_vertices)
    k = feq.fused_expand_q(q8, tg.tile_src, tg.tile_dst, tg.first_of_dst,
                           fr, fr, jnp.uint32(3), jnp.uint32(0),
                           interpret=True)
    r = feq.fused_expand_q_ref(q8, tg.tile_src, tg.tile_dst, fr, fr,
                               jnp.uint32(3), jnp.uint32(0))
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


def test_fused_expand_q_gathered_matches_dense_grid():
    """The sparse-grid q kernel: a compacted (null-padded) tile list with
    ORIGINAL tile ids prefetched draws the dense grid's position-derived
    RNG bits — output ≡ the dense-grid kernel on the full stacks."""
    from repro.core import sparse
    from repro.kernels import fused_expand_q as feq
    g = _random_graph(400, 2500, (0.1, 0.9), seed=6)
    tg = tiles.from_graph(g)
    q8 = feq.quantize_probs(tg.prob)
    # Low-occupancy frontier: all 64 colors rooted on one vertex.
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, 64,
                                jnp.zeros((64,), jnp.int32)),
        tg.padded_vertices)
    dense = feq.fused_expand_q(q8, tg.tile_src, tg.tile_dst,
                               tg.first_of_dst, fr, fr, jnp.uint32(3),
                               jnp.uint32(0), interpret=True)
    tgn = tiles.with_null_tile(tg)
    q8n = feq.quantize_probs(tgn.prob)
    act = sparse.row_block_activity(fr, tg.tile_size)
    nt = tg.num_tiles
    n_active = int(np.asarray(
        act[tg.tile_src].astype(jnp.int32)).sum())
    assert 0 < n_active < nt                    # genuinely compacted
    cap = n_active + 3                          # force null-tile padding
    ids = tiles.active_tile_ids(tg.tile_src, act, cap, nt)
    fi = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (tgn.tile_dst[ids][1:] != tgn.tile_dst[ids][:-1])
         .astype(jnp.int32)])
    gathered = feq.fused_expand_q_gathered(
        q8n[ids], ids, tgn.tile_src[ids], tgn.tile_dst[ids], fi, fr, fr,
        jnp.uint32(3), jnp.uint32(0), interpret=True)
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(dense))


def test_quantize_probs_endpoints_exact():
    from repro.kernels import fused_expand_q as feq
    q = np.asarray(feq.quantize_probs(jnp.asarray([0.0, 1.0, 0.5, 1e-9])))
    assert q[0] == 0, "p=0 must stay never-activate"
    assert q[1] == 255, "p=1 must stay always-activate"
    # accept ⇔ u8 ≤ q ∧ q>0: p̂(255) = 256/256 = 1 exactly
    assert q[2] in (127, 128)


def test_fused_expand_q_statistics_match_exact_path():
    """Quantized and exact kernels must agree on expansion statistics
    within Monte-Carlo noise (they use different RNG streams)."""
    from repro.kernels import fused_expand_q as feq
    g = _random_graph(600, 6000, 0.4, seed=8)
    tg = tiles.from_graph(g)
    q8 = feq.quantize_probs(tg.prob)
    starts = traversal.random_starts(jax.random.key(2), g.num_vertices, 128)
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, 128, starts),
        tg.padded_vertices)
    a = b = 0
    for seed in range(5):
        out_q = feq.fused_expand_q(q8, tg.tile_src, tg.tile_dst,
                                   tg.first_of_dst, fr, fr,
                                   jnp.uint32(seed), jnp.uint32(0),
                                   interpret=True)
        out_f = ref.fused_expand_ref(tg.prob, tg.edge_id, tg.tile_src,
                                     tg.tile_dst, fr, fr, jnp.uint32(seed),
                                     jnp.uint32(0))
        from repro.core import bitmask
        a += int(bitmask.count_colors(out_q).sum())
        b += int(bitmask.count_colors(out_f).sum())
    assert abs(a - b) / max(b, 1) < 0.05, (a, b)


def test_fused_expand_q_p1_full_bfs():
    """p=1 quantizes exactly: quantized expansion == deterministic BFS."""
    from repro.kernels import fused_expand_q as feq
    g = _random_graph(300, 1500, 1.0, seed=2)
    tg = tiles.from_graph(g)
    q8 = feq.quantize_probs(tg.prob)
    starts = traversal.random_starts(jax.random.key(1), g.num_vertices, 32)
    fr = tiles.pad_mask_rows(
        traversal.init_frontier(g.num_vertices, 32, starts),
        tg.padded_vertices)
    out_q = feq.fused_expand_q(q8, tg.tile_src, tg.tile_dst,
                               tg.first_of_dst, fr, fr, jnp.uint32(0),
                               jnp.uint32(0), interpret=True)
    out_f = ref.fused_expand_ref(tg.prob, tg.edge_id, tg.tile_src,
                                 tg.tile_dst, fr, fr, jnp.uint32(0),
                                 jnp.uint32(0))
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_f))
