"""Fused traversals under the Linear Threshold (LT) diffusion model.

The paper evaluates IC but defines both models (§2).  For RIS under LT the
classic live-edge equivalence (Kempe et al. 2003) applies: each vertex
selects AT MOST ONE incoming edge, edge (v→u) with probability w(v,u)
(Σ_v w(v,u) ≤ 1, none with 1−Σw); an RRR set is the reverse-reachable set
over the selected edges.  Fusion carries over directly: the selection is
*per (vertex, color)* — vertex u's chosen in-edge for color c is a pure
counter-hash of (seed, u, c), so the whole traversal stays level-sync
bitmask propagation and edge (v→u) propagates color c iff it IS u's
selection for c.

Unlike IC there is no per-level redraw: selections are fixed per traversal
(the live-edge subgraph is sampled once), which the hash structure encodes
by excluding ``level`` from the counters.

Split for distribution (repro.sampling's ``data_parallel`` backend): the
per-graph CDF prefix sums precompute on host ONCE (``selection_cum_before``)
while the per-seed selection (``selection_mask_from_cb``) and the level loop
(``lt_traversal_program``) are pure traceable jnp — so a shard_map body can
draw each shard's batches with its own RNG streams, bit-identical to the
single-device path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask, rng
from repro.core.traversal import init_frontier
from repro.graph import csr


def normalize_lt_weights(g: csr.Graph) -> csr.Graph:
    """Scale each vertex's IN-edge weights to sum ≤ 1 (LT requirement).

    Incoming weight mass w(v,u) = prob(v,u) / max(1, Σ_in prob(·,u)).
    Idempotent: an already-normalized graph has Σ_in ≤ 1 ⇒ scale 1.

    Order-preserving: only ``prob`` is rewritten — edge array positions
    (the CSR edge ids that key the counter RNG) and ``indptr`` are kept.
    Streamed graphs (`repro.stream.apply_delta`) are not src-sorted, so a
    rebuild through ``csr.from_edges`` would re-sort and renumber every
    edge id; for sorted graphs the two constructions are bit-identical.
    """
    import dataclasses

    e = g.num_edges
    dst = np.asarray(g.dst)[:e]
    prob = np.asarray(g.prob)[:e].astype(np.float64)
    in_sum = np.zeros(g.num_vertices)
    np.add.at(in_sum, dst, prob)
    scale = 1.0 / np.maximum(in_sum[dst], 1.0)
    new_prob = np.asarray(g.prob).copy()
    new_prob[:e] = (prob * scale).astype(np.float32)
    return dataclasses.replace(g, prob=jnp.asarray(new_prob))


def selection_cum_before(g: csr.Graph) -> np.ndarray:
    """(E_pad,) float32: Σ of in-edge probabilities *before* each edge in
    its destination's CDF (host-side precompute — needs concrete arrays).

    Per-graph, seed-independent: compute once, reuse across every batch."""
    e_pad = g.padded_edges
    e = g.num_edges
    dst_np = np.asarray(g.dst)[:e]
    prob_np = np.asarray(g.prob)[:e].astype(np.float64)
    order = np.argsort(dst_np, kind="stable")
    sorted_prob = prob_np[order]
    sorted_dst = dst_np[order]
    csum = np.cumsum(sorted_prob)
    group_start = np.searchsorted(sorted_dst, sorted_dst, side="left")
    prefix = csum - sorted_prob                       # Σ p before i (global)
    cum_before_sorted = prefix - prefix[group_start]  # per-dst prefix
    cum_before = np.zeros(e_pad, np.float32)
    cum_before[order] = cum_before_sorted.astype(np.float32)
    return cum_before


def selection_mask_from_cb(g: csr.Graph, cb: jnp.ndarray, num_colors: int,
                           seed) -> jnp.ndarray:
    """(E_pad, W) uint32: bit c of edge e set iff e is dst[e]'s live edge
    for color c.  Inverse-CDF over each vertex's in-edge list: edge e is
    selected for color c iff  cum_before[e] ≤ u(dst,c) < cum_before[e]+p[e]
    where u ~ U[0,1) per (dst, color) — at most one edge wins, and the
    no-edge case (u ≥ Σp) selects nothing, all per the LT live-edge rule.

    Pure jnp given the host-precomputed ``cb`` — traceable (jit/shard_map).
    """
    dst = g.dst
    prob = g.prob.astype(jnp.float32)
    seed = jnp.asarray(seed, jnp.uint32)
    words = []
    for w in range(bitmask.num_words(num_colors)):
        lanes = []
        for lane in range(32):
            c = w * 32 + lane
            # one uniform per (destination vertex, color): edges into the
            # same vertex share it — at most one falls in its CDF slot.
            u = rng.uniform_from_u32(
                rng.hash_u32(seed, jnp.uint32(0x17), dst.astype(jnp.uint32),
                             jnp.uint32(c)))
            sel = jnp.logical_and(u >= cb, u < cb + prob)
            lanes.append(sel)
        words.append(rng.pack_bool_word(jnp.stack(lanes, -1)))
    return jnp.stack(words, -1)


def _selection_mask(g: csr.Graph, num_colors: int, seed) -> jnp.ndarray:
    """Host-precompute + selection in one call (single-device convenience)."""
    return selection_mask_from_cb(g, jnp.asarray(selection_cum_before(g)),
                                  num_colors, seed)


def lt_traversal_program(g: csr.Graph, sel, starts, num_colors: int,
                         max_levels: int, segments=None):
    """Level loop over a fixed live-edge selection — trace-time program
    (callers jit or stage inside shard_map).  Returns (visited (V, W),
    levels the loop ran, colors still on the frontier when it stopped).
    ``segments``: `traversal.dst_segments(g.dst, V)` when the caller holds
    it (built here otherwise)."""
    from repro.core.traversal import _or_by_dst, dst_segments, live_colors

    frontier = init_frontier(g.num_vertices, num_colors, starts)
    visited = jnp.zeros_like(frontier)
    seg = (segments if segments is not None
           else dst_segments(g.dst, g.num_vertices))
    # Edge sources and selections in destination order, once per traversal.
    src, sel = g.src[seg.order], sel[seg.order]

    def cond(c):
        fr, _, lvl = c
        return jnp.logical_and(bitmask.any_set(fr), lvl < max_levels)

    def body(c):
        fr, vis, lvl = c
        vis = vis | fr
        # Visited colors are masked once per destination, after the OR.
        nf = _or_by_dst(jnp.zeros_like(vis), fr[src] & sel, seg) & ~vis
        return nf, vis, lvl + 1

    fr, vis, levels = jax.lax.while_loop(cond, body,
                                         (frontier, visited, jnp.int32(0)))
    return vis | fr, levels, live_colors(fr)


def run_fused_lt(g: csr.Graph, starts, num_colors: int, seed,
                 max_levels: int = 64):
    """Fused LT traversal: visited (V, W) — column c = LT RRR set c.

    The live-edge selection mask precomputes on host (CDF prefix sums need
    concrete arrays); selection + level loop are jitted."""
    seed = jnp.uint32(seed)
    cb = jnp.asarray(selection_cum_before(g))
    return _run_fused_lt_jit(g, cb, starts, seed, num_colors, max_levels)


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def _run_fused_lt_jit(g: csr.Graph, cb, starts, seed, num_colors: int,
                      max_levels: int):
    sel = selection_mask_from_cb(g, cb, num_colors, seed)
    return lt_traversal_program(g, sel, starts, num_colors, max_levels)[0]


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def run_fused_lt_block(g: csr.Graph, cb, starts, seeds, num_colors: int,
                       max_levels: int = 64, segments=None):
    """Fused multi-batch LT sweep: ONE dispatch traverses a block of
    batches via ``lax.map`` (each batch draws its own live-edge selection
    from its seed, one (E, W) selection transient at a time).

    starts (B, C) int32 / seeds (B,) uint32 → (visited (B, V, W),
    edge_visits (B, 2), level_counts (B, 2)), the outputs of
    `traversal.run_fused_block`; the edge visits are the -1 "not
    instrumented" sentinel.  ``segments`` as in `lt_traversal_program`."""
    if segments is None:
        from repro.core.traversal import dst_segments
        segments = dst_segments(g.dst, g.num_vertices)

    def one(args):
        st, sd = args
        sel = selection_mask_from_cb(g, cb, num_colors, sd)
        vis, levels, truncated = lt_traversal_program(
            g, sel, st, num_colors, max_levels, segments)
        return (vis, jnp.full((2,), -1, jnp.int32),
                jnp.stack([levels, truncated]))

    return jax.lax.map(one, (starts, seeds))
