"""Full fused-BPT traversal on the block-sparse tile layout.

Same level-synchronous semantics as ``core.traversal.run_fused`` (the CSR
edge-centric path) but expansion goes through the tile formulation — either
the Pallas kernels (``use_kernel=True``: `kernels.fused_expand` for IC,
`kernels.lt_select_expand` for LT) or their pure-jnp oracles.  Because all
paths share the counter RNG keyed by *CSR edge id*, their visited masks
are bit-for-bit identical; tests rely on it.

``run_fused_lt_tiled`` is the LT analogue: the same tile sweep with the
per-(edge, color) Bernoulli replaced by the fixed LT live-edge selection
(`kernels.lt_select_expand` / `kernels.ref.lt_select_expand_ref`),
bit-identical to ``lt.run_fused_lt``.

Both support the **sparse-frontier** execution mode (``frontier="sparse"``):
per level, the active source row-blocks are computed from the packed
frontier, the ids of tiles sourcing from them compact into a capacity
bucket (`core.sparse.bucket_ladder` — nested ``lax.cond`` picks the
smallest rung that fits, top rung = all tiles so nothing can overflow),
and ONLY the gathered tiles expand.  Compaction preserves the
dst-sorted tile order (ascending ids; padding gathers the appended null
tile targeting the last block — `tiles.with_null_tile`), and
``first_of_dst`` is recomputed on the gathered list, so the Pallas
kernel's revisiting accumulation runs unchanged on the compacted grid —
the kernel grid itself shrinks to the capacity rung.  Skipped tiles have
no active source row, hence zero contribution: sparse is bit-identical
to dense by construction.

Both runners return ``(visited, levels_run, grid_steps)`` where
``grid_steps`` is the TOTAL number of kernel grid steps launched across
levels — ``levels · num_tiles`` for the dense grid, the sum of the per-level
capacity rungs for the sparse grid.  The ratio is the ``active_grid_frac``
benchmark column and the `scripts/check_work_counters.py` guard.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitmask, sparse, tiles
from repro.core.traversal import init_frontier
from repro.kernels import fused_expand as fe
from repro.kernels import lt_select_expand as lse
from repro.kernels import ref as kref


def _gathered_first_of_dst(tile_dst: jnp.ndarray) -> jnp.ndarray:
    """Recompute ``first_of_dst`` on a gathered (still dst-sorted) tile
    list — a run's global first tile may not have been gathered."""
    return jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (tile_dst[1:] != tile_dst[:-1]).astype(jnp.int32)])


def _sparse_tile_expand(tgn: tiles.TiledGraph, num_tiles: int,
                        ladder: tuple[int, ...], frontier, expand_gathered):
    """Ladder-compacted tile expansion: gather the tiles whose source
    block is active (``tgn`` = null-extended stacks) and hand the
    compacted stacks to ``expand_gathered(prob, eid, ts, td, ids)``.
    Returns ``(next_frontier, grid_steps)`` — the rung that ran."""
    act = sparse.row_block_activity(frontier, tgn.tile_size)
    real_src = tgn.tile_src[:num_tiles]
    count = jnp.sum(act[real_src].astype(jnp.int32))

    def step_at(cap: int):
        def run(_):
            ids = tiles.active_tile_ids(real_src, act, cap, num_tiles)
            nf = expand_gathered(tgn.prob[ids], tgn.edge_id[ids],
                                 tgn.tile_src[ids], tgn.tile_dst[ids], ids)
            return nf, jnp.int32(cap)
        return run

    return sparse.cond_ladder(count, ladder, step_at)


@partial(jax.jit, static_argnames=("num_colors", "max_levels", "use_kernel",
                                   "interpret", "frontier", "ladder"))
def run_fused_lt_tiled(tg: tiles.TiledGraph, cb_tiles, starts,
                       num_colors: int, seed, max_levels: int = 64, *,
                       use_kernel: bool, interpret: bool,
                       frontier: str = "dense",
                       ladder: tuple[int, ...] | None = None):
    """LT fused traversal on the block-sparse tile layout.

    Expansion goes through `kernels.lt_select_expand` (``use_kernel=True``)
    or its oracle `kernels.ref.lt_select_expand_ref` — the fixed live-edge
    selection recomputed per level from the counter hash — so the visited
    mask is bit-for-bit identical to `lt.run_fused_lt` on the same
    (LT-normalized) graph.  ``cb_tiles`` is the selection-CDF prefix in tile
    layout (``tiles.edge_values_to_tiles(tg, lt.selection_cum_before(g))``).
    ``frontier="sparse"`` compacts to the active tiles per level (see
    module docstring); ``ladder`` overrides the capacity buckets.
    ``interpret`` runs the kernel in the Pallas interpreter
    (`kernels.ops._interpret` decides it for the backend).
    Returns (visited (V, W) uint32, levels_run int32, grid_steps int32).
    """
    vp = tg.padded_vertices
    fr0 = tiles.pad_mask_rows(
        init_frontier(tg.num_vertices, num_colors, starts), vp)
    visited = jnp.zeros_like(fr0)
    seed = jnp.uint32(seed)

    def expand_tiles(p, cbt, ts, td, fi, fr, vis):
        if use_kernel:
            return lse.lt_select_expand(p, cbt, ts, td, fi, fr, vis, seed,
                                        interpret=interpret)
        return kref.lt_select_expand_ref(p, cbt, ts, td, fr, vis, seed)

    if frontier == "sparse":
        if ladder is None:
            ladder = sparse.bucket_ladder(tg.num_tiles)
        tgn = tiles.with_null_tile(tg)
        cbn = jnp.concatenate(
            [cb_tiles, jnp.zeros((1,) + cb_tiles.shape[1:],
                                 cb_tiles.dtype)])

        def expand(fr, vis, level):
            def gathered(p, eid, ts, td, ids):
                return expand_tiles(p, cbn[ids], ts, td,
                                    _gathered_first_of_dst(td), fr, vis)
            return _sparse_tile_expand(tgn, tg.num_tiles, ladder, fr,
                                       gathered)
    else:
        def expand(fr, vis, level):
            nf = expand_tiles(tg.prob, cb_tiles, tg.tile_src, tg.tile_dst,
                              tg.first_of_dst, fr, vis)
            return nf, jnp.int32(tg.num_tiles)

    def cond(carry):
        fr, _, level, _ = carry
        return jnp.logical_and(bitmask.any_set(fr), level < max_levels)

    def body(carry):
        fr, vis, level, gs = carry
        vis = vis | fr
        nf, step_gs = expand(fr, vis, level)
        return nf, vis, level + 1, gs + step_gs

    fr, visited, levels, grid_steps = jax.lax.while_loop(
        cond, body, (fr0, visited, jnp.int32(0), jnp.int32(0)))
    visited = visited | fr                               # cap-level colors
    return visited[: tg.num_vertices], levels, grid_steps


@partial(jax.jit, static_argnames=("num_colors", "max_levels", "use_kernel",
                                   "interpret", "frontier", "ladder"))
def run_fused_tiled(tg: tiles.TiledGraph, starts, num_colors: int, seed,
                    max_levels: int = 64, *, use_kernel: bool,
                    interpret: bool, frontier: str = "dense",
                    ladder: tuple[int, ...] | None = None):
    """Returns (visited (V, W) uint32, levels_run int32, grid_steps int32).

    ``frontier="sparse"`` compacts each level's expansion to the tiles
    with an active source block (module docstring); works through both
    the Pallas kernel and the jnp oracle, bit-identical to dense.
    ``interpret`` as in `run_fused_lt_tiled`."""
    vp = tg.padded_vertices
    fr0 = tiles.pad_mask_rows(
        init_frontier(tg.num_vertices, num_colors, starts), vp)
    visited = jnp.zeros_like(fr0)
    seed = jnp.uint32(seed)

    def expand_tiles(p, eid, ts, td, fi, fr, vis, level):
        if use_kernel:
            return fe.fused_expand(p, eid, ts, td, fi, fr, vis, seed,
                                   level, interpret=interpret)
        return kref.fused_expand_ref(p, eid, ts, td, fr, vis, seed, level)

    if frontier == "sparse":
        if ladder is None:
            ladder = sparse.bucket_ladder(tg.num_tiles)
        tgn = tiles.with_null_tile(tg)

        def expand(fr, vis, level):
            def gathered(p, eid, ts, td, ids):
                return expand_tiles(p, eid, ts, td,
                                    _gathered_first_of_dst(td), fr, vis,
                                    level)
            return _sparse_tile_expand(tgn, tg.num_tiles, ladder, fr,
                                       gathered)
    else:
        def expand(fr, vis, level):
            nf = expand_tiles(tg.prob, tg.edge_id, tg.tile_src,
                              tg.tile_dst, tg.first_of_dst, fr, vis,
                              level)
            return nf, jnp.int32(tg.num_tiles)

    def cond(carry):
        fr, _, level, _ = carry
        return jnp.logical_and(bitmask.any_set(fr), level < max_levels)

    def body(carry):
        fr, vis, level, gs = carry
        vis = vis | fr                                   # Listing 1 line 8
        nf, step_gs = expand(fr, vis, level.astype(jnp.uint32))
        return nf, vis, level + 1, gs + step_gs

    fr, visited, levels, grid_steps = jax.lax.while_loop(
        cond, body, (fr0, visited, jnp.int32(0), jnp.int32(0)))
    visited = visited | fr                               # cap-level colors
    return visited[: tg.num_vertices], levels, grid_steps
