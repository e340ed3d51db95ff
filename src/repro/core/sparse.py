"""Sparse-frontier traversal engine: active-tile compaction over edge blocks.

The paper's premise (Fig. 9) is that the unified frontier COLLAPSES after
the first couple of levels — yet the dense sweep (`traversal.fused_step`)
gathers and Bernoulli-samples every padded edge at every level.  This
module makes per-level work proportional to the *active* part of the graph
instead:

  * Host-side, ONCE per graph: edges are grouped by their source row-block
    (``tile_rows`` rows per block, the same 128-row tiles `_tile_activity`
    measures) and padded into fixed-size **edge blocks** of ``edge_block``
    slots each (`FrontierIndex`) — the tile-id → edge-block index.
  * Per level, traced: compute the active row-blocks from the packed
    frontier, compact the ids of their edge blocks into a padded capacity
    buffer, gather ONLY those blocks' edges, and run expansion +
    `rng.bernoulli_word` over the gathered edges — per-level FLOPs and RNG
    traffic scale with ``active_blocks × edge_block`` instead of ``E``.

Capacity buffers need static shapes, so the compaction runs on a **ladder
of power-of-two buckets** (`bucket_ladder`): a nested ``lax.cond`` picks
the smallest bucket that fits the level's active-block count at runtime,
and the top rung always equals the total block count, so no level can
overflow — there is no separate dense fallback to keep bit-equal.  The
ladder is a static tuple, so recompiles are bounded by its length (≤ ~5),
and the whole step stays traceable: it runs unchanged inside
``lax.while_loop``, ``lax.map`` batch blocks, and ``shard_map`` bodies.

Bit-identity with the dense sweep is structural, not statistical: the
counter RNG is keyed by CSR edge id, so a gathered edge draws the exact
word the dense sweep would, and every *skipped* edge has no active source
color — its dense contribution is zero.  The same argument covers the
per-level work counters (`TraversalStats.fused_edge_visits` counts edges
whose source row carries any active color — all of which are gathered), so
sparse and dense agree on the counters exactly, which `scripts/ci.sh`
asserts as a deterministic no-flake guard.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask, rng
from repro.core.traversal import (TraversalResult, TraversalStats,
                                  _empty_stats, _scatter_or, _tile_activity,
                                  init_frontier, live_colors)
from repro.graph.csr import Graph


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FrontierIndex:
    """Edge blocks grouped by source row-block (host-built, device-resident).

    All per-edge arrays are ``(NB + 1, EB)`` — the extra trailing block is
    an all-invalid null block that compaction's ``fill_value`` indexes, so
    padded capacity slots gather inert edges (prob 0, valid False).
    ``blk_rowblock`` is ``(NB,)`` — the source row-block of each REAL
    block, the key the per-level activity gather compacts on.
    """
    blk_src: jnp.ndarray       # (NB+1, EB) int32   edge source vertex
    blk_dst: jnp.ndarray       # (NB+1, EB) int32   edge destination vertex
    blk_prob: jnp.ndarray      # (NB+1, EB) float32 IC prob / LT in-weight
    blk_eid: jnp.ndarray       # (NB+1, EB) uint32  CSR edge id (RNG counter)
    blk_valid: jnp.ndarray     # (NB+1, EB) bool    real CSR slot (incl. CSR
    #                            padding edges — the dense sweep counts them)
    blk_cb: jnp.ndarray | None  # (NB+1, EB) f32 LT selection-CDF prefix
    blk_rowblock: jnp.ndarray  # (NB,) int32 source row-block per real block
    num_vertices: int = dataclasses.field(metadata=dict(static=True))
    num_blocks: int = dataclasses.field(metadata=dict(static=True))
    edge_block: int = dataclasses.field(metadata=dict(static=True))
    tile_rows: int = dataclasses.field(metadata=dict(static=True))

    @property
    def num_row_blocks(self) -> int:
        return -(-self.num_vertices // self.tile_rows)


def build_frontier_index(g_rev: Graph, tile_rows: int = 128,
                         edge_block: int = 128,
                         cb: np.ndarray | None = None) -> FrontierIndex:
    """Group the reversed graph's edges by source row-block (host-side).

    Every CSR array slot rides along — including the prob-0 CSR padding
    edges (src 0), because the dense sweep's work counters include them
    whenever their source row is active and the sparse counters must agree
    exactly.  ``cb`` attaches the LT selection-CDF prefixes
    (`lt.selection_cum_before`) in the same block layout.
    """
    e_pad = g_rev.padded_edges
    src = np.asarray(g_rev.src)[:e_pad]
    dst = np.asarray(g_rev.dst)[:e_pad]
    prob = np.asarray(g_rev.prob)[:e_pad]
    eid = np.arange(e_pad, dtype=np.uint32)
    cb = None if cb is None else np.asarray(cb, np.float32)[:e_pad]

    rb = src // tile_rows
    order = np.argsort(rb, kind="stable")
    nrb = -(-g_rev.num_vertices // tile_rows)
    counts = np.bincount(rb, minlength=nrb)
    blocks_per = -(-counts // edge_block)          # 0 for empty row-blocks
    nb = int(blocks_per.sum())

    def alloc(dtype, fill=0):
        return np.full((nb + 1, edge_block), fill, dtype)

    S, D = alloc(np.int32), alloc(np.int32)
    P, E = alloc(np.float32), alloc(np.uint32)
    V = alloc(bool, False)
    C = alloc(np.float32) if cb is not None else None
    rowblock = np.zeros(nb, np.int32)

    pos = 0          # read cursor into the rb-sorted edge order
    blk = 0
    for r in range(nrb):
        n = int(counts[r])
        if not n:
            continue
        sel = order[pos:pos + n]
        pos += n
        k = int(blocks_per[r])
        flat = slice(blk * edge_block, blk * edge_block + n)
        S.reshape(-1)[flat] = src[sel]
        D.reshape(-1)[flat] = dst[sel]
        P.reshape(-1)[flat] = prob[sel]
        E.reshape(-1)[flat] = eid[sel]
        V.reshape(-1)[flat] = True
        if C is not None:
            C.reshape(-1)[flat] = cb[sel]
        rowblock[blk:blk + k] = r
        blk += k

    return FrontierIndex(
        blk_src=jnp.asarray(S), blk_dst=jnp.asarray(D),
        blk_prob=jnp.asarray(P), blk_eid=jnp.asarray(E),
        blk_valid=jnp.asarray(V),
        blk_cb=None if C is None else jnp.asarray(C),
        blk_rowblock=jnp.asarray(rowblock),
        num_vertices=g_rev.num_vertices, num_blocks=nb,
        edge_block=edge_block, tile_rows=tile_rows)


def patch_frontier_index(fidx: FrontierIndex, g_rev: Graph,
                         touched_row_blocks,
                         cb: np.ndarray | None = None) -> FrontierIndex:
    """Re-derive ONLY the edge blocks of ``touched_row_blocks`` from a
    values-mutated graph — the churn-priced alternative to the O(|E|)
    host rebuild after a streaming delta.

    Precondition (the caller's to check — `Sampler.rebind` compares the
    edge arrays): ``g_rev`` has the SAME ``(src, dst)`` layout and padded
    length as the graph ``fidx`` was built from, i.e. the delta only
    changed probabilities in place (tombstone / resurrect / LT renorm).
    Then block membership, edge ids, and validity are all unchanged, and
    the patch is a pure gather: for every selected block,
    ``prob = where(valid, g_rev.prob[eid], 0)`` — exactly what
    `build_frontier_index` writes — plus the same for the LT
    selection-CDF prefixes when the index carries them.  Bit-identical
    to a fresh build by construction; cost scales with the touched
    blocks, not E.
    """
    if (fidx.blk_cb is None) != (cb is None):
        raise ValueError("cb must be given iff the index carries blk_cb")
    sel = np.isin(np.asarray(fidx.blk_rowblock),
                  np.asarray(touched_row_blocks, np.int64))
    ids = np.flatnonzero(sel)
    if not len(ids):
        return fidx
    ids_j = jnp.asarray(ids, jnp.int32)
    eid = fidx.blk_eid[ids_j]                       # (k, EB) uint32
    valid = fidx.blk_valid[ids_j]
    vals = jnp.where(valid, jnp.asarray(g_rev.prob)[eid], jnp.float32(0))
    fields = {"blk_prob": fidx.blk_prob.at[ids_j].set(vals)}
    if cb is not None:
        cbv = jnp.where(valid, jnp.asarray(cb, jnp.float32)[eid],
                        jnp.float32(0))
        fields["blk_cb"] = fidx.blk_cb.at[ids_j].set(cbv)
    return dataclasses.replace(fidx, **fields)


def bucket_ladder(num_blocks: int, capacity: int = 0) -> tuple[int, ...]:
    """Static capacity buckets for the compaction buffer.

    The top rung always equals ``num_blocks`` (compaction can never
    overflow — correctness never depends on the knob).  ``capacity = 0``
    (auto) builds a geometric ladder 8, 64, 512, … so a level pays for the
    smallest bucket that fits its active count; an explicit ``capacity``
    gives a two-rung ladder {pow2(capacity), num_blocks} for callers that
    profiled their workload (`benchmarks/bench_frontier_profile.py` prints
    the occupancy histogram this knob wants).
    """
    n = max(int(num_blocks), 1)
    if capacity and capacity > 0:
        top = 1
        while top < min(capacity, n):
            top *= 2
        rungs = {min(top, n), n}
    else:
        rungs = {n}
        r = 8
        while r < n:
            rungs.add(r)
            r *= 8
    return tuple(sorted(rungs))


def row_block_activity(frontier: jnp.ndarray, tile_rows: int) -> jnp.ndarray:
    """(n_row_blocks,) bool — row blocks holding ≥ 1 active vertex."""
    v = frontier.shape[0]
    act = bitmask.count_colors(frontier) > 0
    act = jnp.pad(act, (0, (-v) % tile_rows))
    return act.reshape(-1, tile_rows).any(axis=1)


def cond_ladder(count, ladder: tuple[int, ...], step_at):
    """Run ``step_at(K)`` for the smallest ladder rung with ``count ≤ K``
    via nested ``lax.cond`` — the last rung runs unconditionally (ladders
    from `bucket_ladder` end at the total block count, so it always fits).
    ``step_at(K)`` must return a one-operand callable; all rungs must
    agree on output shapes."""
    def chain(rungs):
        if len(rungs) == 1:
            return step_at(rungs[0])
        return lambda op: jax.lax.cond(count <= rungs[0], step_at(rungs[0]),
                                       chain(rungs[1:]), op)
    return chain(list(ladder))(None)


def _sparse_step(fidx: FrontierIndex, frontier, visited, level, seed,
                 ladder: tuple[int, ...], u=None):
    """One compacted expansion level.  ``visited`` must already include the
    current frontier (level-sync semantics).  Returns
    ``(next_frontier, fused_visits, unfused_visits, grid_steps)`` — the
    visit counters are bit-equal to the dense sweep's (`fused_step` info
    dict); ``grid_steps`` is the capacity rung that ran (the compacted
    work-list length the level paid for).

    ``u = None`` selects the IC per-(edge, color, level) Bernoulli gate;
    an ``(V, W·32)`` LT uniform table (`kernels.ref.lt_selection_uniforms`)
    selects the fixed live-edge gate instead (level-independent, computed
    once per traversal by the caller), read at each edge's SOURCE row:
    on the reversed graph the source is the vertex that picks (`core.lt`).
    """
    num_words = frontier.shape[1]
    act = row_block_activity(frontier, fidx.tile_rows)
    blk_act = act[fidx.blk_rowblock]                     # (NB,)
    count = jnp.sum(blk_act.astype(jnp.int32))

    def step_at(cap: int):
        def run(_):
            ids = jnp.nonzero(blk_act, size=cap,
                              fill_value=fidx.num_blocks)[0]
            s, d = fidx.blk_src[ids], fidx.blk_dst[ids]
            p, valid = fidx.blk_prob[ids], fidx.blk_valid[ids]
            fr_src = frontier[s]                         # (K, EB, W)
            if u is None:
                word_ids = jnp.arange(num_words, dtype=jnp.uint32)
                eid = fidx.blk_eid[ids]
                gate = jax.vmap(
                    lambda wd: rng.bernoulli_word(seed, level, eid, wd, p),
                    out_axes=-1)(word_ids)               # (K, EB, W)
            else:
                cbt = fidx.blk_cb[ids]
                ug = u[s]                                # (K, EB, W·32)
                sel = jnp.logical_and(ug >= cbt[..., None],
                                      ug < (cbt + p)[..., None])
                gate = rng.pack_bool_word(
                    sel.reshape(*p.shape, -1, 32))       # (K, EB, W)
            contrib = fr_src & gate & ~visited[d]
            nf = _scatter_or(jnp.zeros_like(visited), d.reshape(-1),
                             contrib.reshape(-1, num_words)) & ~visited
            active_src = bitmask.count_colors(fr_src)    # (K, EB)
            fused = jnp.sum(jnp.where(valid, (active_src > 0)
                                      .astype(jnp.int32), 0))
            unfused = jnp.sum(jnp.where(valid, active_src, 0))
            return nf, fused, unfused, jnp.int32(cap)
        return run

    return cond_ladder(count, ladder, step_at)


@partial(jax.jit, static_argnames=("num_colors", "max_levels", "ladder"))
def run_fused_sparse(fidx: FrontierIndex, starts, num_colors: int, seed,
                     max_levels: int = 64,
                     ladder: tuple[int, ...] | None = None) -> TraversalResult:
    """`traversal.run_fused` on the sparse-frontier engine — visited mask
    AND every `TraversalStats` field bit-equal to the dense sweep."""
    if ladder is None:
        ladder = bucket_ladder(fidx.num_blocks)
    v = fidx.num_vertices
    frontier = init_frontier(v, num_colors, starts)
    visited = bitmask.make_mask(v, num_colors)
    stats = _empty_stats(max_levels)

    def cond(carry):
        frontier, _, level, _ = carry
        return jnp.logical_and(bitmask.any_set(frontier), level < max_levels)

    def body(carry):
        frontier, visited, level, stats = carry
        tile_frac = _tile_activity(frontier)
        per_row = bitmask.count_colors(frontier)
        fr_vertices = jnp.sum((per_row > 0).astype(jnp.int32))
        fr_colors = jnp.sum(per_row)
        visited = visited | frontier                     # Listing 1 line 8
        nf, fused, unfused, gs = _sparse_step(
            fidx, frontier, visited, level.astype(jnp.uint32),
            jnp.asarray(seed, jnp.uint32), ladder)
        occ = jnp.where(fr_vertices > 0,
                        fr_colors.astype(jnp.float32)
                        / jnp.maximum(fr_vertices, 1)
                        / jnp.float32(num_colors), 0.0)
        stats = TraversalStats(
            levels_run=stats.levels_run + 1,
            fused_edge_visits=stats.fused_edge_visits.at[level].set(fused),
            unfused_edge_visits=stats.unfused_edge_visits.at[level].set(
                unfused),
            frontier_vertices=stats.frontier_vertices.at[level].set(
                fr_vertices),
            frontier_colors=stats.frontier_colors.at[level].set(fr_colors),
            occupancy_num=stats.occupancy_num.at[level].set(occ),
            active_tile_frac=stats.active_tile_frac.at[level].set(tile_frac),
            grid_steps=stats.grid_steps.at[level].set(gs),
            truncated_colors=stats.truncated_colors,
        )
        return nf, visited, level + 1, stats

    frontier, visited, _, stats = jax.lax.while_loop(
        cond, body, (frontier, visited, jnp.int32(0), stats))
    visited = visited | frontier
    stats = dataclasses.replace(stats,
                                truncated_colors=live_colors(frontier))
    return TraversalResult(visited=visited, stats=stats)


@partial(jax.jit, static_argnames=("num_colors", "max_levels", "ladder"))
def run_fused_lt_sparse(fidx: FrontierIndex, starts, num_colors: int, seed,
                        max_levels: int = 64,
                        ladder: tuple[int, ...] | None = None):
    """`lt.run_fused_lt` on the sparse-frontier engine: (visited (V, W),
    levels, truncated colors, (fused, unfused) edge visits) as
    `lt.lt_traversal_program` returns them.

    The LT live-edge selection is recomputed per gathered edge from the
    level-independent uniform table — the same (seed, 0x17, src, color)
    counters as `lt.selection_mask_from_cb`, so the result is bit-identical
    to the dense LT sweep without ever materializing the (E, W) mask.
    """
    from repro.kernels import ref as kref

    if ladder is None:
        ladder = bucket_ladder(fidx.num_blocks)
    if fidx.blk_cb is None:
        raise ValueError("LT needs a FrontierIndex built with cb="
                         "lt.selection_cum_before(g_rev)")
    seed = jnp.asarray(seed, jnp.uint32)
    u = kref.lt_selection_uniforms(seed, fidx.num_vertices, num_colors)
    frontier = init_frontier(fidx.num_vertices, num_colors, starts)
    visited = jnp.zeros_like(frontier)

    def cond(carry):
        fr, _, level, _, _ = carry
        return jnp.logical_and(bitmask.any_set(fr), level < max_levels)

    def body(carry):
        fr, vis, level, fused, unfused = carry
        vis = vis | fr
        nf, f, uf, _ = _sparse_step(fidx, fr, vis, level.astype(jnp.uint32),
                                    seed, ladder, u=u)
        return nf, vis, level + 1, fused + f, unfused + uf

    zero = jnp.int32(0)
    fr, vis, levels, fused, unfused = jax.lax.while_loop(
        cond, body, (frontier, visited, zero, zero, zero))
    return (vis | fr, levels, live_colors(fr),
            jnp.stack([fused, unfused]).astype(jnp.int32))


@partial(jax.jit, static_argnames=("num_colors", "max_levels", "ladder",
                                   "diffusion"))
def sparse_block(fidx: FrontierIndex, starts, seeds, num_colors: int,
                 max_levels: int, ladder: tuple[int, ...],
                 diffusion: str = "ic"):
    """Fused multi-batch pool build on the sparse engine: ONE dispatch
    traverses a whole block of batches via ``lax.map`` (one batch's
    transients at a time on the device).

    starts (B, C) int32, seeds (B,) uint32 → (visited (B, V, W),
    edge_visits (B, 2), level_counts (B, 2)), the outputs of
    `traversal.run_fused_block`, for IC and LT alike.
    """
    def one(args):
        st, sd = args
        if diffusion == "lt":
            vis, levels, truncated, visits = run_fused_lt_sparse(
                fidx, st, num_colors, sd, max_levels=max_levels,
                ladder=ladder)
            return vis, visits, jnp.stack([levels, truncated])
        res = run_fused_sparse(fidx, st, num_colors, sd,
                               max_levels=max_levels, ladder=ladder)
        return (res.visited,
                jnp.stack([res.stats.fused_edge_visits.sum(),
                           res.stats.unfused_edge_visits.sum()]),
                jnp.stack([res.stats.levels_run,
                           res.stats.truncated_colors]))

    return jax.lax.map(one, (starts, seeds))


def profile_traversal(fidx: FrontierIndex, starts, num_colors: int, seed,
                      max_levels: int = 64,
                      ladder: tuple[int, ...] | None = None,
                      diffusion: str = "ic") -> list[dict]:
    """Host-paced level loop for profiling: per level, the active
    row-block / edge-block counts, the ladder bucket that level would pick,
    and the work counters — the data `bench_frontier_profile` histograms
    so the ``frontier_capacity`` knob can be set from evidence.

    Runs the SAME traced `_sparse_step` as the production while_loop (at
    the level's chosen bucket), so the profile is the real execution, not
    a model of it.
    """
    from repro.kernels import ref as kref

    if ladder is None:
        ladder = bucket_ladder(fidx.num_blocks)
    seed = jnp.asarray(seed, jnp.uint32)
    u = (kref.lt_selection_uniforms(seed, fidx.num_vertices, num_colors)
         if diffusion == "lt" else None)
    fr = init_frontier(fidx.num_vertices, num_colors, starts)
    vis = jnp.zeros_like(fr)
    rowblocks = np.asarray(fidx.blk_rowblock)

    @partial(jax.jit, static_argnames=("cap",))
    def step(fr, vis, level, cap: int):
        return _sparse_step(fidx, fr, vis, level, seed, (cap,), u=u)

    out = []
    level = 0
    while level < max_levels and bool(bitmask.any_set(fr)):
        act = np.asarray(row_block_activity(fr, fidx.tile_rows))
        n_blk = int(act[rowblocks].sum())
        bucket = next(k for k in ladder if n_blk <= k)
        vis = vis | fr
        fr, fused, unfused, _ = step(fr, vis, jnp.uint32(level), bucket)
        out.append(dict(
            level=level,
            active_row_blocks=int(act.sum()),
            active_edge_blocks=n_blk,
            bucket=bucket,
            fused_edge_visits=int(fused),
            unfused_edge_visits=int(unfused),
        ))
        level += 1
    return out
