"""Fused breadth-first probabilistic traversals (paper §3, Listing 1).

TPU-native formulation: the frontier is a dense packed color
bitmask and one level of the fused traversal is an edge-centric sweep

    contrib[e] = frontier[src[e]] & bernoulli(prob[e]) & ~visited[dst[e]]
    frontier'  = scatter_or(dst, contrib) & ~visited'
    visited'   = visited | frontier

which is an OR-AND-semiring SpMM.  Because every mask update is
bitwise-independent per color, the fused traversal restricted to color ``c``
is *exactly* the single-color BPT driven by the same counter RNG — fused and
unfused runs are coupled bit-for-bit (used by tests to check equivalence and
Theorem 1 without sampling error).

Layout: masks enter and leave as ``(V, W)`` (`init_frontier`, the results),
but the dense CSR level loops (`run_fused`, `run_fused_block`) carry them
word-major, ``(W, V)``, and every edge-length intermediate of a level
(`_expand`) is ``(W, E)``: the gathered source words, the RNG words,
``contrib``, the OR scan's accumulator, so that the edge axis is the minor
one.  Under TPU's (8, 128) tiling an edge-major ``(E, W)`` array pads its
minor dimension from W = 2 to 128 lanes — 64× its data — and the compiler
relays every such buffer to and from a lane-dense layout.  Each loop
transposes once on entry and once on exit.  One padded buffer is left: the
compiler writes the source row gather ``frontier[:, src]`` edge-major and
relays it once; one 1-D gather per word would avoid that, but a 1-D gather
costs far more per index on TPU v5e (PERF.md §5).  The gather costs the
same per row at up to 128 words, so `run_fused_block` runs G batches of a
block in one loop of G·W words (`batches_per_loop`: G·W = 8 at 64 colors,
one tile's sublanes), and one gather a level serves all G.

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
    # Colors still on the frontier when the loop stopped: 0 when it
    # emptied, > 0 only when ``max_levels`` cut the traversal.
    truncated_colors: jnp.ndarray      # () int32


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


def live_colors(frontier: jnp.ndarray, word_axis: int = -1) -> jnp.ndarray:
    """() int32: the colors set anywhere on ``frontier`` (V, W), or on a
    word-major (W, V) one with ``word_axis=0``."""
    rows = 1 if word_axis == 0 else 0
    words = jax.lax.reduce(frontier, jnp.uint32(0), jax.lax.bitwise_or,
                           (rows,))
    return jnp.sum(bitmask.popcount(words)).astype(jnp.int32)


def _empty_stats(max_levels: int) -> TraversalStats:
    zeros_i = jnp.zeros((max_levels,), jnp.int32)
    zeros_f = jnp.zeros((max_levels,), jnp.float32)
    return TraversalStats(jnp.int32(0), zeros_i, zeros_i, zeros_i, zeros_i,
                          zeros_f, zeros_f, zeros_i, jnp.int32(0))


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
               seg: DstSegments, axis: int = 0) -> jnp.ndarray:
    """base[dst] |= contrib for ``contrib`` already in destination order.

    No scatter: a segmented Hillis-Steele OR scan — ⌈log₂ max in-degree⌉
    shifted ORs — folds each destination's in-edges into its last one,
    which one gather per row picks.  OR is order-free, so this equals a
    per-bit scatter-max exactly (which on TPU costs 32× the updates and
    serializes on duplicate destinations).

    ``axis`` is the edge axis of ``contrib`` and the row axis of
    ``base_words``: 0 for edge-major (E, W) / (R, W), 1 for the dense
    level's word-major (W, E) / (W, R), whose rolls and gather then run
    along the lane-dense minor axis (see the module docstring)."""
    zero = jnp.uint32(0)
    edges = (slice(None), None) if axis == 0 else (None, slice(None))

    def shift_or(carry):
        acc, s = carry
        prev = jnp.roll(acc, s, axis=axis)       # wrapped edges have rank < s
        return acc | jnp.where((seg.rank >= s)[edges], prev, zero), s * 2

    acc, _ = jax.lax.while_loop(lambda c: c[1] <= seg.max_rank, shift_or,
                                (contrib, jnp.int32(1)))
    last = jnp.maximum(seg.last, 0)
    picked = acc[last] if axis == 0 else acc[:, last]
    got = jnp.where((seg.last >= 0)[edges], picked, zero)
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
    """One level of the fused traversal on a `DstView`, word-major: takes
    and returns ``frontier`` and ``visited`` as (W, V).  Returns
    (frontier', visited', info).

    ``seed`` is one batch's () counter seed, or the (G,) seeds of a group
    of G batches whose (W, V) masks ``frontier`` and ``visited`` stack,
    (G·W, V).  Then word ``g·W + w`` draws as word ``w`` of batch ``g``
    under its own seed (a (G·W,) seed vector and word ids
    ``tile(arange(W), G)``), so every batch gets the bits of a loop of its
    own, and each counter in ``info`` is (G,), one per batch.

    Every edge-length intermediate is (W, E) — the gathered source words,
    the RNG words, ``contrib``, the OR scan — or (E,), never (E, W): a
    minor dimension of W = 2 pads to 128 lanes under TPU's (8, 128)
    tiling, and the compiler then relays each such buffer (it still does
    so once, for the gather's output: module docstring).

    Each phase runs under a ``jax.named_scope`` (``bpt.gather``,
    ``bpt.rng``, ``bpt.or_scan``, ``bpt.count``), so the device trace's
    name stack ties every op of a level to its phase."""
    grouped = jnp.ndim(seed) == 1
    batches = seed.shape[0] if grouped else 1
    num_words = frontier.shape[0] // batches
    visited = visited | frontier                            # Listing 1 line 8
    with jax.named_scope("bpt.gather"):
        fr_src = frontier[:, view.src]                      # (G·W, E)
    # Independent Bernoulli(p_e) per (edge, color): one packed word per
    # (word, edge) pair.  Padding edges have prob 0 → never propagate.
    with jax.named_scope("bpt.rng"):
        word_ids = jnp.tile(jnp.arange(num_words, dtype=jnp.uint32), batches)
        word_seeds = jnp.repeat(seed, num_words) if grouped else seed
        rand = jax.vmap(
            lambda s, w: rng.bernoulli_word(s, level.astype(jnp.uint32),
                                            view.edge_id, w, view.prob),
            in_axes=(0 if grouped else None, 0),
        )(word_seeds, word_ids)                             # (G·W, E)
    # Lines 11-13.  Masking the visited colors once per destination after
    # the OR equals masking every in-edge before it.
    with jax.named_scope("bpt.or_scan"):
        next_frontier = _or_by_dst(jnp.zeros_like(visited), fr_src & rand,
                                   view.seg, axis=1) & ~visited

    with jax.named_scope("bpt.count"):
        if grouped:                          # (G, E) and (G, V)
            active_src = _count_by_batch(fr_src, num_words)
            per_row = _count_by_batch(frontier, num_words)
        else:                                # (E,) and (V,)
            active_src = bitmask.count_colors(fr_src, word_axis=0)
            per_row = bitmask.count_colors(frontier, word_axis=0)
        info = dict(
            fused_visits=jnp.sum((active_src > 0).astype(jnp.int32), -1),
            unfused_visits=jnp.sum(active_src, -1),
            frontier_vertices=jnp.sum((per_row > 0).astype(jnp.int32), -1),
            frontier_colors=jnp.sum(per_row, -1),
        )
    return next_frontier, visited, info


def _count_by_batch(words: jnp.ndarray, num_words: int) -> jnp.ndarray:
    """(G·W, N) word-major masks of G batches → (G, N) int32: each batch's
    set bits in every column, its W rows' popcounts summed by a window of
    W rows.  (A reshape to (G, W, N) would make the compiler relay the
    gathered source words into a (W, 128) tiling; a window keeps them in
    the gather's.)"""
    return jax.lax.reduce_window(
        bitmask.popcount(words).astype(jnp.int32), jnp.int32(0), jax.lax.add,
        (num_words, 1), (num_words, 1), "VALID")


def fused_step(g: Graph, frontier: jnp.ndarray, visited: jnp.ndarray,
               level: jnp.ndarray, seed: jnp.ndarray, segments=None):
    """One level of the fused traversal on (V, W) masks.  Returns
    (frontier', visited', info).

    Level loops build the `dst_view` once, carry the masks word-major and
    call `_expand` instead."""
    nf, nv, info = _expand(dst_view(g, segments), frontier.T, visited.T,
                           level, seed)
    return nf.T, nv.T, info


def _tile_activity(frontier: jnp.ndarray, tile_rows: int = 128,
                   word_axis: int = -1) -> jnp.ndarray:
    """Fraction of row tiles with ≥1 active vertex (Fig. 9 analogue);
    ``word_axis`` as in `bitmask.count_colors`."""
    act = (bitmask.count_colors(frontier, word_axis) > 0)
    pad = (-act.shape[0]) % tile_rows
    act = jnp.pad(act, (0, pad))
    tiles = act.reshape(-1, tile_rows).any(axis=1)
    return jnp.mean(tiles.astype(jnp.float32))


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def run_fused(g: Graph, starts: jnp.ndarray, num_colors: int,
              seed: jnp.ndarray, max_levels: int = 64,
              segments: DstSegments | None = None) -> TraversalResult:
    """Run the fused BPT to frontier exhaustion (≤ max_levels).
    ``segments``: `dst_segments(g.dst, V)` when the caller holds it.
    The loop carries the masks word-major (W, V); ``visited`` is (V, W)."""
    frontier = init_frontier(g.num_vertices, num_colors, starts).T
    visited = jnp.zeros_like(frontier)
    stats = _empty_stats(max_levels)
    view = dst_view(g, segments)

    def cond(carry):
        frontier, _, level, _ = carry
        return jnp.logical_and(bitmask.any_set(frontier), level < max_levels)

    def body(carry):
        frontier, visited, level, stats = carry
        tile_frac = _tile_activity(frontier, word_axis=0)
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
            truncated_colors=stats.truncated_colors,
        )
        return nf, nv, level + 1, stats

    frontier, visited, _, stats = jax.lax.while_loop(
        cond, body, (frontier, visited, jnp.int32(0), stats))
    # Vertices still on the frontier at the level cap count as visited (their
    # colors have reached them even if not expanded further).
    visited = (visited | frontier).T
    stats = dataclasses.replace(
        stats, truncated_colors=live_colors(frontier, word_axis=0))
    return TraversalResult(visited=visited, stats=stats)


def batches_per_loop(num_batches: int, num_colors: int) -> int:
    """G: the batches of a block that `run_fused_block` runs in one level
    loop — as many as fill the 8 sublanes of one (8, 128) u32 tile with
    their W words, and no more than the block holds."""
    return min(num_batches, max(1, 8 // bitmask.num_words(num_colors)))


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def run_fused_block(g: Graph, starts: jnp.ndarray, seeds: jnp.ndarray,
                    num_colors: int, max_levels: int = 64,
                    segments: DstSegments | None = None):
    """Fused multi-batch sweep: ONE dispatch traverses a whole block of
    batches, so a pool build stops paying per-batch dispatch.

    starts (B, C) int32 / seeds (B,) uint32 → (visited (B, V, W),
    edge_visits (B, 2), level_counts (B, 2)), all int32 but ``visited``.
    ``edge_visits`` holds (fused, unfused): ``run_fused``'s per-level
    stats summed (same int32 arithmetic).  ``level_counts`` holds
    (levels, truncated): its ``levels_run``, and the colors still on the
    frontier when the loop stopped (> 0 only when ``max_levels`` cut the
    batch).  ``segments`` as in `run_fused`.

    The block runs in groups of G = `batches_per_loop` batches, one
    word-major level loop of G·W words per group (`_group_loop`): every
    level's source row gather then fills G·W of the 128 lanes the
    compiler pads its output to (module docstring), for the whole group at
    once.  Full groups run one after another under ``lax.map``; the last
    B mod G batches run as one smaller loop.  Each batch's results are
    those of a loop of its own, bit for bit.
    """
    view = dst_view(g, segments)
    b, c = starts.shape
    per_loop = batches_per_loop(b, num_colors)
    full = b - b % per_loop

    def loop(st, sd):
        return _group_loop(view, g.num_vertices, st, sd, num_colors,
                           max_levels)

    out = jax.lax.map(lambda a: loop(*a),
                      (starts[:full].reshape(-1, per_loop, c),
                       seeds[:full].reshape(-1, per_loop)))
    out = [x.reshape(full, *x.shape[2:]) for x in out]
    if full < b:
        tail = loop(starts[full:], seeds[full:])
        out = [jnp.concatenate([x, t]) for x, t in zip(out, tail)]
    return tuple(out)


def _group_loop(view: DstView, num_vertices: int, starts: jnp.ndarray,
                seeds: jnp.ndarray, num_colors: int, max_levels: int):
    """G batches (starts (G, C), seeds (G,)) in one level loop, to the
    exhaustion of the deepest (≤ max_levels): `run_fused_block`'s three
    outputs for the G batches.

    The loop carries the G batches' (W, V) masks stacked word-major,
    (G·W, V), and transposes once on entry and once on exit.  A batch
    counts a level where its own words were live; once its frontier has
    emptied it expands nothing and counts no visits.  With G = 1 this is
    one batch's loop, under its scalar seed."""
    num_batches = starts.shape[0]
    frontier = jnp.concatenate(
        [init_frontier(num_vertices, num_colors, st).T for st in starts])
    visited = jnp.zeros_like(frontier)
    seed = seeds if num_batches > 1 else seeds[0]
    zeros = jnp.zeros((num_batches,), jnp.int32)

    def cond(c):
        fr, _, lvl, *_ = c
        return jnp.logical_and(bitmask.any_set(fr), lvl < max_levels)

    def body(c):
        fr, vis, lvl, levels, fused, unfused = c
        nf, nv, info = _expand(view, fr, vis, lvl, seed)
        live = (info["frontier_colors"] > 0 if num_batches > 1
                else jnp.ones((1,), bool))
        return (nf, nv, lvl + 1, levels + live.astype(jnp.int32),
                fused + info["fused_visits"],
                unfused + info["unfused_visits"])

    fr, vis, _, levels, fused, unfused = jax.lax.while_loop(
        cond, body, (frontier, visited, jnp.int32(0), zeros, zeros, zeros))
    words = bitmask.num_words(num_colors)
    visited = (vis | fr).reshape(num_batches, words, num_vertices)
    truncated = bitmask.popcount(jax.lax.reduce(
        fr, jnp.uint32(0), jax.lax.bitwise_or, (1,)))       # (G·W,)
    truncated = truncated.reshape(num_batches, words).sum(1).astype(jnp.int32)
    return (visited.transpose(0, 2, 1), jnp.stack([fused, unfused], 1),
            jnp.stack([levels, truncated], 1))


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
    stats = _empty_stats(max_levels)
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
            truncated_colors=stats.truncated_colors,
        )
        return nf, visited, level + 1, stats

    frontier, visited, _, stats = jax.lax.while_loop(
        cond, body, (frontier, visited, jnp.int32(0), stats))
    visited = visited | frontier
    stats = dataclasses.replace(stats,
                                truncated_colors=live_colors(frontier))
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
