"""Fused traversals under the Linear Threshold (LT) diffusion model.

The paper evaluates IC but defines both models (§2).  For RIS under LT the
classic live-edge equivalence (Kempe et al. 2003) applies: each vertex x
keeps AT MOST ONE of its in-edges, edge (u→x) with probability w(u,x)
(Σ_u w(u,x) ≤ 1, none with 1−Σw); an RRR set is every vertex that reaches
the root over the kept edges.  Read backwards from the root that is a
walk: the root's kept in-edge leads to u₁, u₁'s to u₂, and so on, until a
vertex keeps no in-edge or the walk meets a vertex it has visited.

Which side picks.  Every program here runs on the REVERSED graph the
sampling facade holds: its edge x→u is the forward edge u→x.  So the
vertex that picks is the edge's *source* x — the vertex on the frontier —
and the edges it picks among are its own row of the reversed CSR (its
forward in-edges).  Weights are normalized over that row
(`normalize_lt_weights`), the CDF runs along it in ascending order of the
forward source u (`selection_cum_before`), and the uniform that picks is
hashed on (stream seed, 0x17, x, color).  The level loop is IC's:
propagate ``frontier[src] & selection`` and OR by ``dst``.

Fusion carries over directly: the selection is *per (vertex, color)* — a
pure counter-hash of (seed, x, c) — so the whole traversal stays
level-sync bitmask propagation, and edge (x→u) propagates color c iff it
IS x's pick for c.  Unlike IC there is no per-level redraw: the live-edge
subgraph is sampled once per traversal, which the hash encodes by leaving
``level`` out of its counters.

Split for distribution (repro.sampling's ``data_parallel`` backend): the
per-graph CDF prefix sums precompute on host ONCE (``selection_cum_before``)
while the per-seed selection (``selection_mask_from_cb``) and the level loop
(``lt_traversal_program``) are pure traceable jnp — so a shard_map body can
draw each shard's batches with its own RNG streams, bit-identical to the
single-device path.  `bench/reference/lt.py` states the same sets without
this code.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask, rng
from repro.core.traversal import init_frontier
from repro.graph import csr


def _row_order(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Edge positions sorted by (row ``src``, other end ``dst``), ties in
    array order: the order every LT row sum and CDF runs in.  A CSR built
    from a (src, dst)-sorted edge list is in that order already."""
    key = src.astype(np.int64) << 32 | dst.astype(np.int64)
    if np.all(key[1:] >= key[:-1]):
        return np.arange(len(src))
    return np.argsort(key, kind="stable")


def _row_scan(rows: np.ndarray, values: np.ndarray):
    """(exclusive prefix, row total) of ``values`` laid out row by row
    (``rows`` sorted, each row contiguous), both per element, each sum
    rounded to ``values``' dtype at every step.  Each row accumulates left
    to right, one row-length class at a time, so a row's sums do not
    depend on the rows around it."""
    n = len(rows)
    prefix, total = np.zeros(n, values.dtype), np.zeros(n, values.dtype)
    if not n:
        return prefix, total
    starts = np.flatnonzero(np.r_[True, rows[1:] != rows[:-1]])
    lens = np.diff(np.r_[starts, n])
    for d in np.unique(lens):
        idx = starts[lens == d][:, None] + np.arange(d)
        inc = np.cumsum(values[idx], axis=1)          # sequential per row
        prefix[idx[:, 1:]] = inc[:, :-1]
        total[idx] = inc[:, -1:]
    return prefix, total


def normalized_row_weights(src: np.ndarray, dst: np.ndarray,
                           prob: np.ndarray) -> np.ndarray:
    """float32 weights w = p · (1 / max(1, Σ_row p)) per edge, rows by
    ``src``: the row sum in float64, in `_row_order`, the product in
    float64, one rounding to float32.  A row's weights depend on its own
    edges alone, so callers may pass any set of whole rows."""
    order = _row_order(src, dst)
    p64 = prob[order].astype(np.float64)
    _, row_sum = _row_scan(src[order], p64)
    w = np.empty(len(src), np.float32)
    w[order] = (p64 * (1.0 / np.maximum(row_sum, 1.0))).astype(np.float32)
    return w


def normalize_lt_weights(g: csr.Graph) -> csr.Graph:
    """Scale each row's edge weights to sum ≤ 1 (the LT requirement).

    On the reversed graph a row is the forward in-edges of the vertex that
    picks among them (module docstring): w = p / max(1, Σ_row p), computed
    by `normalized_row_weights`.  Idempotent: a row with Σ ≤ 1 keeps scale 1.

    Order-preserving: only ``prob`` is rewritten — edge array positions
    (the CSR edge ids that key the counter RNG) and ``indptr`` are kept.
    Streamed graphs (`repro.stream.apply_delta`) are not src-sorted, so a
    rebuild through ``csr.from_edges`` would re-sort and renumber every
    edge id; for sorted graphs the two constructions are bit-identical.
    """
    import dataclasses

    e = g.num_edges
    new_prob = np.asarray(g.prob).copy()
    new_prob[:e] = normalized_row_weights(
        np.asarray(g.src)[:e], np.asarray(g.dst)[:e], new_prob[:e])
    return dataclasses.replace(g, prob=jnp.asarray(new_prob))


def selection_cum_before(g: csr.Graph) -> np.ndarray:
    """(E_pad,) float32: Σ of the row's weights *before* each edge in its
    row's CDF — rows by ``src`` (the picking vertex), the CDF in ascending
    ``dst`` (the forward source) — as a float32 scan, cb_{i+1} =
    f32(cb_i + w_i) (host-side precompute — needs concrete arrays).  So
    the interval end ``cb + w`` that `selection_mask_from_cb` adds in
    float32 IS the next edge's ``cb``, bit for bit: a row's intervals
    tile, and no uniform falls in two of them.  Keyed by edge position,
    so a streamed graph whose rows are not sorted keeps the rule.

    Per-graph, seed-independent: compute once, reuse across every batch."""
    e = g.num_edges
    src = np.asarray(g.src)[:e]
    order = _row_order(src, np.asarray(g.dst)[:e])
    prefix, _ = _row_scan(src[order],
                          np.asarray(g.prob)[:e][order].astype(np.float32))
    cum_before = np.zeros(g.padded_edges, np.float32)
    cum_before[order] = prefix
    return cum_before


def selection_mask_from_cb(g: csr.Graph, cb: jnp.ndarray, num_colors: int,
                           seed) -> jnp.ndarray:
    """(E_pad, W) uint32: bit c of edge e set iff e is its source's pick
    for color c.  Inverse CDF along each row: edge e carries color c iff
    cb[e] ≤ u(src[e], c) < cb[e] + p[e] (float32), where u ~ U[0,1) per
    (row vertex, color) — the intervals tile (`selection_cum_before`), so
    at most one edge of a row wins, and u ≥ Σp picks none, as the LT
    live-edge rule has it.

    Pure jnp given the host-precomputed ``cb`` — traceable (jit/shard_map).
    """
    with jax.named_scope("lt.select"):
        src = g.src.astype(jnp.uint32)
        hi = cb + g.prob.astype(jnp.float32)
        seed = jnp.asarray(seed, jnp.uint32)
        words = []
        for w in range(bitmask.num_words(num_colors)):
            lanes = []
            for lane in range(32):
                # one uniform per (row vertex, color): the edges of one
                # row share it, and at most one falls in its CDF slot.
                u = rng.uniform_from_u32(
                    rng.hash_u32(seed, jnp.uint32(0x17), src,
                                 jnp.uint32(w * 32 + lane)))
                lanes.append(jnp.logical_and(u >= cb, u < hi))
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
    levels the loop ran, colors still on the frontier when it stopped,
    (fused, unfused) edge visits (2,) int32 with IC's definitions: an edge
    is visited at a level when its source carries any color, once per
    color unfused).  ``segments``: `traversal.dst_segments(g.dst, V)`
    when the caller holds it (built here otherwise).

    A level's phases run under IC's scope names (``bpt.gather``,
    ``bpt.or_scan``, ``bpt.count``) and the selection's reorder under
    ``lt.select``, so a device trace splits the LT level as it does IC's."""
    from repro.core.traversal import _or_by_dst, dst_segments, live_colors

    frontier = init_frontier(g.num_vertices, num_colors, starts)
    visited = jnp.zeros_like(frontier)
    seg = (segments if segments is not None
           else dst_segments(g.dst, g.num_vertices))
    # Edge sources and selections in destination order, once per traversal.
    src = g.src[seg.order]
    with jax.named_scope("lt.select"):
        sel = sel[seg.order]

    def cond(c):
        fr, _, lvl, _, _ = c
        return jnp.logical_and(bitmask.any_set(fr), lvl < max_levels)

    def body(c):
        fr, vis, lvl, fused, unfused = c
        vis = vis | fr
        with jax.named_scope("bpt.gather"):
            fr_src = fr[src]
        # Visited colors are masked once per destination, after the OR.
        with jax.named_scope("bpt.or_scan"):
            nf = _or_by_dst(jnp.zeros_like(vis), fr_src & sel, seg) & ~vis
        with jax.named_scope("bpt.count"):
            active_src = bitmask.count_colors(fr_src)          # (E,)
            fused = fused + jnp.sum((active_src > 0).astype(jnp.int32))
            unfused = unfused + jnp.sum(active_src)
        return nf, vis, lvl + 1, fused, unfused

    zero = jnp.int32(0)
    fr, vis, levels, fused, unfused = jax.lax.while_loop(
        cond, body, (frontier, visited, zero, zero, zero))
    return (vis | fr, levels, live_colors(fr),
            jnp.stack([fused, unfused]).astype(jnp.int32))


def run_fused_lt(g: csr.Graph, starts, num_colors: int, seed,
                 max_levels: int = 64):
    """Fused LT traversal on the reversed graph ``g``: visited (V, W) —
    column c = LT RRR set c.

    The selection-CDF prefixes precompute on host (they need concrete
    arrays); selection + level loop are jitted."""
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
    `traversal.run_fused_block`, with the same definitions: (fused,
    unfused) edge visits summed over the levels, and (levels, truncated
    colors).  ``segments`` as in `lt_traversal_program`."""
    if segments is None:
        from repro.core.traversal import dst_segments
        segments = dst_segments(g.dst, g.num_vertices)

    def one(args):
        st, sd = args
        sel = selection_mask_from_cb(g, cb, num_colors, sd)
        vis, levels, truncated, visits = lt_traversal_program(
            g, sel, st, num_colors, max_levels, segments)
        return vis, visits, jnp.stack([levels, truncated])

    return jax.lax.map(one, (starts, seeds))
