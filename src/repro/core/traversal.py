"""Fused breadth-first probabilistic traversals (paper §3, Listing 1).

TPU-native formulation: the frontier is a dense packed color
bitmask ``(V, W)`` and one level of the fused traversal is an edge-centric
sweep

    contrib[e] = frontier[src[e]] & bernoulli(prob[e]) & ~visited[dst[e]]
    frontier'  = scatter_or(dst, contrib) & ~visited'
    visited'   = visited | frontier

which is an OR-AND-semiring SpMM.  Because every mask update is
bitwise-independent per color, the fused traversal restricted to color ``c``
is *exactly* the single-color BPT driven by the same counter RNG — fused and
unfused runs are coupled bit-for-bit (used by tests to check equivalence and
Theorem 1 without sampling error).

Level-synchronous semantics (matching the paper's Ripples port §4.2): the
whole frontier is folded into ``visited`` first, then expansion excludes all
previously-visited colors per destination.  A vertex may re-enter the frontier
in a later level, but only with colors it has never carried (Listing 1 line 11).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask, rng
from repro.graph.csr import Graph


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TraversalStats:
    """Per-level instrumentation (sized ``max_levels``; host sums avoid i32
    overflow across levels)."""
    levels_run: jnp.ndarray            # () int32
    # "Edge visit" accounting mirrors the paper's Fig. 4: the fused algorithm
    # visits edge e at level t iff any color is active at src[e]; the unfused
    # equivalent visits it once *per* active color.
    fused_edge_visits: jnp.ndarray     # (max_levels,) int32
    unfused_edge_visits: jnp.ndarray   # (max_levels,) int32
    frontier_vertices: jnp.ndarray     # (max_levels,) int32  active vertices
    frontier_colors: jnp.ndarray       # (max_levels,) int32  Σ popcount(frontier)
    occupancy_num: jnp.ndarray         # (max_levels,) f32  Σ popcount / active
    # Fig. 9 analogue: fraction of 128-row tiles containing an active vertex.
    active_tile_frac: jnp.ndarray      # (max_levels,) f32
    # Kernel-grid work: grid steps launched this level.  Sparse-frontier
    # paths record the capacity rung that ran (compacted tile count); the
    # dense tiled grid records num_tiles; non-gridded (CSR edge-centric)
    # paths record 0 — the counter prices the *grid*, not edge work.
    grid_steps: jnp.ndarray            # (max_levels,) int32


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TraversalResult:
    visited: jnp.ndarray               # (V, W) uint32 — column c is RRR set c
    stats: TraversalStats


def init_frontier(num_vertices: int, num_colors: int,
                  starts: jnp.ndarray) -> jnp.ndarray:
    """(V, W) frontier with bit ``c`` set at row ``starts[c]``.

    Multiple colors may share a start vertex (paper Fig. 3 vertex 1)."""
    colors = jnp.arange(num_colors, dtype=jnp.int32)
    frontier = bitmask.make_mask(num_vertices, num_colors)
    return bitmask.set_color(frontier, jnp.asarray(starts, jnp.int32), colors)


def random_starts(key: jax.Array, num_vertices: int, num_colors: int,
                  sort: bool = False) -> jnp.ndarray:
    """Uniform-random start vertices (Listing 1 lines 1-3).  ``sort=True``
    pre-sorts starts for locality (paper §5 'sorted variant')."""
    starts = jax.random.randint(key, (num_colors,), 0, num_vertices, jnp.int32)
    return jnp.sort(starts) if sort else starts


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DstSegments:
    """Edges grouped by destination, for the scatter-free `_scatter_or`.

    ``order`` (E,) sorts the edges by destination (stable); ``rank`` (E,)
    is sorted edge i's position among its destination's in-edges;
    ``last`` (R,) is row r's last sorted in-edge, -1 for a row with none;
    ``max_rank`` () is the largest in-degree minus one.  A function of
    ``dst`` alone: build it once per graph (`dst_segments`), outside the
    programs that run the level loops."""
    order: jnp.ndarray
    rank: jnp.ndarray
    last: jnp.ndarray
    max_rank: jnp.ndarray


@partial(jax.jit, static_argnames=("num_rows",))
def dst_segments(dst: jnp.ndarray, num_rows: int) -> DstSegments:
    """`DstSegments` of an edge-destination array over ``num_rows`` rows."""
    e = dst.shape[0]
    order = jnp.argsort(dst, stable=True).astype(jnp.int32)
    d = dst[order]
    pos = jnp.arange(e, dtype=jnp.int32)
    change = d[1:] != d[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), change])
    is_last = jnp.concatenate([change, jnp.ones((1,), bool)])
    start_of = jnp.full((num_rows,), 0, jnp.int32).at[
        jnp.where(first, d, num_rows)].set(pos, mode="drop")
    last = jnp.full((num_rows,), -1, jnp.int32).at[
        jnp.where(is_last, d, num_rows)].set(pos, mode="drop")
    rank = pos - start_of[d]
    return DstSegments(order, rank, last, jnp.max(rank, initial=0))


def _or_by_dst(base_words: jnp.ndarray, contrib: jnp.ndarray,
               seg: DstSegments) -> jnp.ndarray:
    """base[dst] |= contrib for ``contrib`` already in destination order.

    No scatter: a segmented Hillis-Steele OR scan — ⌈log₂ max in-degree⌉
    shifted ORs — folds each destination's in-edges into its last one,
    which one gather per row picks.  OR is order-free, so this equals a
    per-bit scatter-max exactly (which on TPU costs 32× the updates and
    serializes on duplicate destinations)."""
    zero = jnp.uint32(0)

    def shift_or(carry):
        acc, s = carry
        prev = jnp.roll(acc, s, axis=0)          # wrapped rows have rank < s
        return acc | jnp.where((seg.rank >= s)[:, None], prev, zero), s * 2

    acc, _ = jax.lax.while_loop(lambda c: c[1] <= seg.max_rank, shift_or,
                                (contrib, jnp.int32(1)))
    got = jnp.where((seg.last >= 0)[:, None], acc[jnp.maximum(seg.last, 0)],
                    zero)
    return base_words | got


def _scatter_or(base_words: jnp.ndarray, dst: jnp.ndarray,
                contrib: jnp.ndarray,
                segments: DstSegments | None = None) -> jnp.ndarray:
    """base[dst] |= contrib with duplicate destinations ORed together
    (`_or_by_dst` after sorting ``contrib`` by destination).  Pass
    ``segments`` (`dst_segments(dst, rows)`) to reuse them across levels;
    without it they are built here."""
    seg = (segments if segments is not None
           else dst_segments(dst, base_words.shape[0]))
    return _or_by_dst(base_words, contrib[seg.order], seg)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DstView:
    """The edge arrays a level loop reads, in destination order: sources,
    probabilities and CSR edge ids (the RNG counter, so every draw is the
    one the CSR order makes).  Built once per traversal (`dst_view`), so a
    level gathers only the frontier rows of its edge sources."""
    src: jnp.ndarray
    prob: jnp.ndarray
    edge_id: jnp.ndarray
    seg: DstSegments


def dst_view(g: Graph, segments: DstSegments | None = None) -> DstView:
    seg = (segments if segments is not None
           else dst_segments(g.dst, g.num_vertices))
    return DstView(g.src[seg.order], g.prob[seg.order],
                   seg.order.astype(jnp.uint32), seg)


def _expand(view: DstView, frontier: jnp.ndarray, visited: jnp.ndarray,
            level: jnp.ndarray, seed: jnp.ndarray):
    """One level of the fused traversal on a `DstView`.  Returns
    (frontier', visited', info)."""
    num_words = frontier.shape[-1]
    visited = visited | frontier                            # Listing 1 line 8
    fr_src = frontier[view.src]                             # (E, W) gather
    # Independent Bernoulli(p_e) per (edge, color): one packed word per
    # (edge, word) pair.  Padding edges have prob 0 → never propagate.
    word_ids = jnp.arange(num_words, dtype=jnp.uint32)
    rand = jax.vmap(
        lambda w: rng.bernoulli_word(seed, level.astype(jnp.uint32),
                                     view.edge_id, w, view.prob),
        out_axes=1)(word_ids)                               # (E, W)
    # Lines 11-13.  Masking the visited colors once per destination after
    # the OR equals masking every in-edge before it.
    next_frontier = _or_by_dst(jnp.zeros_like(visited), fr_src & rand,
                               view.seg) & ~visited

    active_src = bitmask.count_colors(fr_src)               # (E,) per-edge
    info = dict(
        fused_visits=jnp.sum((active_src > 0).astype(jnp.int32)),
        unfused_visits=jnp.sum(active_src),
        frontier_vertices=jnp.sum(
            (bitmask.count_colors(frontier) > 0).astype(jnp.int32)),
        frontier_colors=jnp.sum(bitmask.count_colors(frontier)),
    )
    return next_frontier, visited, info


def fused_step(g: Graph, frontier: jnp.ndarray, visited: jnp.ndarray,
               level: jnp.ndarray, seed: jnp.ndarray, segments=None):
    """One level of the fused traversal.  Returns (frontier', visited', info).

    Level loops build the `dst_view` once and call `_expand` instead."""
    return _expand(dst_view(g, segments), frontier, visited, level, seed)


def _tile_activity(frontier: jnp.ndarray, tile_rows: int = 128) -> jnp.ndarray:
    """Fraction of row tiles with ≥1 active vertex (Fig. 9 analogue)."""
    v = frontier.shape[0]
    pad = (-v) % tile_rows
    act = (bitmask.count_colors(frontier) > 0)
    act = jnp.pad(act, (0, pad))
    tiles = act.reshape(-1, tile_rows).any(axis=1)
    return jnp.mean(tiles.astype(jnp.float32))


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def run_fused(g: Graph, starts: jnp.ndarray, num_colors: int,
              seed: jnp.ndarray, max_levels: int = 64,
              segments: DstSegments | None = None) -> TraversalResult:
    """Run the fused BPT to frontier exhaustion (≤ max_levels).
    ``segments``: `dst_segments(g.dst, V)` when the caller holds it."""
    v = g.num_vertices
    frontier = init_frontier(v, num_colors, starts)
    visited = bitmask.make_mask(v, num_colors)
    zeros_i = jnp.zeros((max_levels,), jnp.int32)
    zeros_f = jnp.zeros((max_levels,), jnp.float32)
    stats = TraversalStats(jnp.int32(0), zeros_i, zeros_i, zeros_i, zeros_i,
                           zeros_f, zeros_f, zeros_i)
    view = dst_view(g, segments)

    def cond(carry):
        frontier, _, level, _ = carry
        return jnp.logical_and(bitmask.any_set(frontier), level < max_levels)

    def body(carry):
        frontier, visited, level, stats = carry
        tile_frac = _tile_activity(frontier)
        nf, nv, info = _expand(view, frontier, visited, level, seed)
        occ = jnp.where(info["frontier_vertices"] > 0,
                        info["frontier_colors"].astype(jnp.float32)
                        / jnp.maximum(info["frontier_vertices"], 1)
                        / jnp.float32(num_colors), 0.0)
        stats = TraversalStats(
            levels_run=stats.levels_run + 1,
            fused_edge_visits=stats.fused_edge_visits.at[level].set(
                info["fused_visits"]),
            unfused_edge_visits=stats.unfused_edge_visits.at[level].set(
                info["unfused_visits"]),
            frontier_vertices=stats.frontier_vertices.at[level].set(
                info["frontier_vertices"]),
            frontier_colors=stats.frontier_colors.at[level].set(
                info["frontier_colors"]),
            occupancy_num=stats.occupancy_num.at[level].set(occ),
            active_tile_frac=stats.active_tile_frac.at[level].set(tile_frac),
            grid_steps=stats.grid_steps,          # CSR path: not gridded
        )
        return nf, nv, level + 1, stats

    frontier, visited, _, stats = jax.lax.while_loop(
        cond, body, (frontier, visited, jnp.int32(0), stats))
    # Vertices still on the frontier at the level cap count as visited (their
    # colors have reached them even if not expanded further).
    visited = visited | frontier
    return TraversalResult(visited=visited, stats=stats)


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def run_fused_block(g: Graph, starts: jnp.ndarray, seeds: jnp.ndarray,
                    num_colors: int, max_levels: int = 64,
                    segments: DstSegments | None = None):
    """Fused multi-batch sweep: ONE dispatch traverses a whole block of
    batches via ``lax.map`` (sequential per batch — one (V, W) transient
    at a time — so a pool build stops paying per-batch dispatch).

    starts (B, C) int32 / seeds (B,) uint32 → (visited (B, V, W),
    fused (B,), unfused (B,)) with the edge-visit totals equal to
    ``run_fused``'s per-level stats summed (same int32 arithmetic).
    ``segments`` as in `run_fused`.
    """
    view = dst_view(g, segments)

    def one(args):
        st, sd = args
        frontier = init_frontier(g.num_vertices, num_colors, st)
        visited = jnp.zeros_like(frontier)

        def cond(c):
            fr, _, lvl, _, _ = c
            return jnp.logical_and(bitmask.any_set(fr), lvl < max_levels)

        def body(c):
            fr, vis, lvl, fused, unfused = c
            nf, nv, info = _expand(view, fr, vis, lvl, sd)
            return (nf, nv, lvl + 1, fused + info["fused_visits"],
                    unfused + info["unfused_visits"])

        fr, vis, _, fused, unfused = jax.lax.while_loop(
            cond, body,
            (frontier, visited, jnp.int32(0), jnp.int32(0), jnp.int32(0)))
        return vis | fr, fused, unfused

    return jax.lax.map(one, (starts, seeds))


@partial(jax.jit, static_argnames=("color_id", "max_levels"))
def run_single_color(g: Graph, start: jnp.ndarray, color_id: int,
                     seed: jnp.ndarray, max_levels: int = 64) -> TraversalResult:
    """Unfused baseline: one BPT using the *global* color id's RNG stream.

    Coupled with ``run_fused``: bit ``color_id`` of the fused visited mask is
    identical to this run's visited mask (tests rely on this)."""
    v = g.num_vertices
    word, lane = divmod(color_id, bitmask.WORD_BITS)
    frontier = jnp.zeros((v, 1), jnp.uint32).at[start, 0].set(
        jnp.uint32(1) << jnp.uint32(lane))
    visited = jnp.zeros((v, 1), jnp.uint32)
    edge_ids = jnp.arange(g.padded_edges, dtype=jnp.uint32)
    lane_bit = jnp.uint32(1) << jnp.uint32(lane)
    zeros_i = jnp.zeros((max_levels,), jnp.int32)
    zeros_f = jnp.zeros((max_levels,), jnp.float32)
    stats = TraversalStats(jnp.int32(0), zeros_i, zeros_i, zeros_i, zeros_i,
                           zeros_f, zeros_f, zeros_i)
    segments = dst_segments(g.dst, v)

    def cond(carry):
        frontier, _, level, _ = carry
        return jnp.logical_and(bitmask.any_set(frontier), level < max_levels)

    def body(carry):
        frontier, visited, level, stats = carry
        visited = visited | frontier
        fr_src = frontier[g.src]                            # (E, 1)
        # Same counter stream as the fused run's word `word`, restricted to
        # this lane: identical hash inputs ⇒ identical draw.
        bits = rng.hash_u32(seed, level.astype(jnp.uint32), edge_ids,
                            jnp.uint32(word * 32 + lane))
        draw = (rng.uniform_from_u32(bits) < g.prob)
        rand = jnp.where(draw, lane_bit, jnp.uint32(0))[:, None]
        contrib = fr_src & rand & ~visited[g.dst]
        nf = _scatter_or(jnp.zeros_like(visited), g.dst, contrib,
                         segments) & ~visited
        visits = jnp.sum((fr_src[:, 0] & lane_bit) > 0, dtype=jnp.int32)
        stats = TraversalStats(
            levels_run=stats.levels_run + 1,
            fused_edge_visits=stats.fused_edge_visits.at[level].set(visits),
            unfused_edge_visits=stats.unfused_edge_visits.at[level].set(visits),
            frontier_vertices=stats.frontier_vertices.at[level].set(
                jnp.sum((frontier[:, 0] > 0).astype(jnp.int32))),
            frontier_colors=stats.frontier_colors,
            occupancy_num=stats.occupancy_num,
            active_tile_frac=stats.active_tile_frac,
            grid_steps=stats.grid_steps,
        )
        return nf, visited, level + 1, stats

    frontier, visited, _, stats = jax.lax.while_loop(
        cond, body, (frontier, visited, jnp.int32(0), stats))
    visited = visited | frontier
    return TraversalResult(visited=visited, stats=stats)


def run_unfused(g: Graph, starts: np.ndarray, num_colors: int,
                seed: jnp.ndarray, max_levels: int = 64):
    """Run ``num_colors`` separate single-color BPTs (the unfused baseline of
    Figs. 7/8).  Returns (visited (V, W) assembled, total edge visits)."""
    w = bitmask.num_words(num_colors)
    visited = np.zeros((g.num_vertices, w), np.uint32)
    total_visits = 0
    for c in range(num_colors):
        res = run_single_color(g, int(starts[c]), c, seed,
                               max_levels=max_levels)
        visited[:, c // 32] |= np.asarray(res.visited[:, 0])
        total_visits += int(np.asarray(res.stats.fused_edge_visits,
                                       np.int64).sum())
    return jnp.asarray(visited), total_visits
