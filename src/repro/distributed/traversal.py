"""Graph-parallel fused-BPT traversal: the block program behind the
`repro.sampling` ``graph_parallel`` backend, and its frontier exchange.

Sample parallelism (independent batches over a ``data`` axis, zero
collectives during traversal) needs no program of its own: the
``data_parallel`` backend runs the dense block program on each shard
(`repro.sampling.sampler._dense_block`).  Graph parallelism partitions
destination rows over ``model``: each level exchanges the (packed)
frontier and expands only locally owned tiles.

``graph_parallel_block`` composes the two on ONE mesh: batches sharded
over ``data``, rows over ``model``, every collective naming only the model
axis (IC and LT; LT derives its live-edge selection shard-locally from the
global ids of its tiles' source rows, hashed per tile — so neither an
(E, W) selection mask nor a uniform table over every vertex is ever
replicated).

It reuses the exact single-device expansion math (coupled RNG), so its
results are bit-for-bit equal to single-device runs; tests assert this
under a forced multi-device host platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bitmask, tiles
from repro.core.traversal import init_frontier
from repro.graph import partition as part_lib
from repro.kernels import ref as kref


# ------------------------------------------------------------- graph parallel
def gather_capacity_words(rows: int, num_words: int, capacity: int = 0) -> int:
    """Per-shard capacity (packed words) of the sparse frontier all-gather.

    ``capacity = 0`` (auto) budgets an eighth of the shard's ``rows × W``
    words, rounded up to a power of two — levels above it (the dense early
    levels of Fig. 9) take the full all-gather, levels below it (the
    collapsed tail, where ButterFly BFS shows full gathers waste
    bandwidth) ship only the active words."""
    n = rows * num_words
    want = capacity if capacity > 0 else max(n // 8, 1)
    k = 1
    while k < min(want, n):
        k *= 2
    return min(k, n)


def _frontier_gather_loop(expand, frontier_local, max_levels: int, axis: str,
                          num_shards: int = 1, sparse_words: int = 0,
                          sync_axes: tuple = ()):
    """THE graph-parallel level loop: per-level frontier exchange over
    ``axis``, local expansion, psum-agreed termination.  ``expand`` maps
    (fr_global (Vp, W), vis_local (rows, W), level) → new local frontier.
    Returns (visited_local, levels, gather_words) where ``gather_words``
    is a (max_levels,) int32 vector of the packed words each level moved
    over ``axis`` (summed across shards; replicated, zero past the last
    level) — the interconnect-traffic observable
    (`GraphParallelSampler.last_gather_words`).  The exchange collectives
    name only ``axis``, but the loop's CONTROL decisions (keep going?
    sparse or dense leg?) reduce over ``sync_axes`` (default: just
    ``axis``): every mesh axis named there runs the level loop in
    lockstep, which real SPMD execution implies anyway and the
    host-device emulation REQUIRES —
    ``ppermute`` lowers to one collective-permute spanning every device,
    so shards that diverge on trip count or branch deadlock the
    rendezvous.  A shard whose frontier drained early just exchanges
    zeros until the slowest sibling finishes (recorded in its
    ``gather_words`` — that traffic really moves in lockstep SPMD).

    ``sparse_words > 0`` arms the ButterFly-BFS-style sparse leg: each
    level, every shard counts its nonzero frontier words and a pmax over
    ``sync_axes`` agrees on the global maximum; when it fits the
    capacity, shards compact their frontier to ``(word_idx, word)`` pairs
    and run the ``⌈log₂ S⌉``-stage pairwise exchange
    (`_butterfly_exchange`) — each stage ships only the pairs accumulated
    so far, so tail levels stop paying the ``S × rows × W`` dense gather.
    Overflowing levels fall back to the dense all-gather via ``lax.cond``
    — the pmax'd count is replicated, so every shard takes the same
    branch.  Either leg reconstructs the exact global frontier:
    bit-identical by construction.
    """
    rows, num_words = frontier_local.shape
    n = rows * num_words
    s = num_shards
    sync = sync_axes or (axis,)
    # Dense all-gather semantic traffic: every shard ships its n words to
    # the S-1 peers (0 when the model axis is trivial).
    dense_words = jnp.int32(s * (s - 1) * n)

    def dense_gather(fr):
        return jax.lax.all_gather(fr, axis, tiled=True)

    def dense_leg(fr):
        return dense_gather(fr), dense_words

    def butterfly_leg(fr):
        buf_i, buf_w, sent = _butterfly_exchange(fr, axis, s, n, sparse_words)
        return (_scatter_pairs(buf_i, buf_w, rows, num_words, s),
                jax.lax.psum(sent, axis))

    def cond(carry):
        fr, _, lvl, _ = carry
        any_local = bitmask.any_set(fr)
        any_global = jax.lax.psum(any_local.astype(jnp.int32), sync) > 0
        return jnp.logical_and(any_global, lvl < max_levels)

    def body(carry):
        fr, vis, lvl, gw = carry
        vis = vis | fr
        if sparse_words and sparse_words < n:
            nz = jnp.count_nonzero(fr).astype(jnp.int32)
            fits = jax.lax.pmax(nz, sync) <= sparse_words
            fr_global, words = jax.lax.cond(fits, butterfly_leg, dense_leg,
                                            fr)
        else:
            # THE collective: gather every shard's (rows, W) frontier words.
            fr_global = dense_gather(fr)
            words = dense_words
        gw = gw.at[lvl].set(words)
        nf = expand(fr_global, vis, lvl.astype(jnp.uint32))
        return nf, vis, lvl + 1, gw

    visited = jnp.zeros_like(frontier_local)
    fr, vis, lvl, gather_words = jax.lax.while_loop(
        cond, body, (frontier_local, visited, jnp.int32(0),
                     jnp.zeros((max_levels,), jnp.int32)))
    return vis | fr, lvl, gather_words


def _scatter_pairs(buf_i, buf_w, rows: int, num_words: int, num_shards: int):
    """Reconstruct the (S·rows, W) global frontier from the exchanged
    ``(global_word_idx, word)`` pairs (sentinel-padded capacity slots).

    Pad slots target a per-slot scratch word past the real rows, keeping
    EVERY scattered index globally unique (the packed fast path's
    contract); real global indices are disjoint per source shard."""
    s = num_shards
    n = rows * num_words
    rows_g = s * rows
    cap = buf_i.shape[0]
    sentinel = jnp.uint32(s * n)
    tgt = jnp.where(buf_i < sentinel, buf_i,
                    sentinel + jnp.arange(cap, dtype=jnp.uint32))
    scratch = -(-cap // num_words)
    buf = jnp.zeros((rows_g + scratch, num_words), jnp.uint32)
    full = bitmask.scatter_or_words(
        buf, (tgt // num_words).astype(jnp.int32),
        (tgt % num_words).astype(jnp.int32), buf_w, unique=True)
    return full[:rows_g]


def _butterfly_exchange(fr, axis: str, num_shards: int, n: int, k: int):
    """ButterFly-BFS-style dissemination all-gather of the compacted
    frontier (arXiv 2103.13577): ``⌈log₂ S⌉`` pairwise ``ppermute``
    stages instead of one flat all-gather.

    Each shard compacts its frontier to ≤ ``k`` ``(global_word_idx,
    word)`` pairs (the caller guarantees the fit via the pmax'd count).
    Stage ``t`` sends the WHOLE accumulated pair set to shard
    ``(i − 2ᵗ) mod S`` and receives from ``(i + 2ᵗ) mod S`` — after
    stage ``t`` every shard holds the pairs of source shards
    ``[i, i + 2ᵗ⁺¹)`` (mod S), so ⌈log₂ S⌉ stages cover any S,
    power-of-two or not.  A per-shard ``have`` bitmap drops re-delivered
    source blocks exactly (non-power-of-two schedules overlap on the
    last stage), and received pairs compact onto the end of the real
    prefix — the buffer doubles per stage (static shapes, capped at
    ``S·k``) so early stages ship tiny buffers.

    Returns ``(buf_idx (≤S·k,) uint32, buf_word (≤S·k,) uint32, sent)``
    — global word indices (pad slots carry the ``S·n`` sentinel), their
    words, and the packed words THIS shard shipped (pairs + count/have
    metadata); psum ``sent`` for the level's total traffic.  Real pair
    indices are globally unique: each global word index originates on
    exactly one shard and block dedup delivers it once.
    """
    s = num_shards
    flat = fr.reshape(-1)
    idx = jnp.nonzero(flat, size=k, fill_value=n)[0].astype(jnp.int32)
    w = jnp.where(idx < n, flat[jnp.minimum(idx, n - 1)], jnp.uint32(0))
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    sentinel = jnp.uint32(s * n)
    buf_i = jnp.where(idx < n, (me * n + idx).astype(jnp.uint32), sentinel)
    buf_w = w
    count = jnp.count_nonzero(fr).astype(jnp.int32)
    have = jnp.zeros((s,), jnp.int32).at[me].set(1)
    sent = jnp.int32(0)
    shift = 1
    while shift < s:                     # static: unrolled ⌈log₂ S⌉ stages
        cap = buf_i.shape[0]
        perm = [(i, (i - shift) % s) for i in range(s)]
        payload = jnp.stack([buf_i, buf_w])                    # (2, cap)
        meta = jnp.concatenate([count[None], have])            # (S+1,)
        r_pay = jax.lax.ppermute(payload, axis, perm)
        r_meta = jax.lax.ppermute(meta, axis, perm)
        sent = sent + 2 * count + (s + 1)
        r_i, r_w = r_pay[0], r_pay[1]
        r_have = r_meta[1:]
        src_shard = jnp.minimum(r_i // n, s - 1).astype(jnp.int32)
        keep = (r_i < sentinel) & (have[src_shard] == 0)
        new_cap = min(2 * cap, s * k)
        ni = jnp.full((new_cap,), sentinel).at[:cap].set(buf_i)
        nw = jnp.zeros((new_cap,), jnp.uint32).at[:cap].set(buf_w)
        # Compact kept pairs onto the end of the real prefix; dropped
        # ones target new_cap (out of bounds → mode="drop").
        pos = count + jnp.cumsum(keep.astype(jnp.int32)) - 1
        pos = jnp.where(keep, pos, new_cap)
        buf_i = ni.at[pos].set(r_i, mode="drop")
        buf_w = nw.at[pos].set(r_w, mode="drop")
        count = count + jnp.sum(keep.astype(jnp.int32))
        have = jnp.minimum(have + r_have, 1)
        shift *= 2
    return buf_i, buf_w, sent


def _local_expand(ptg_local, diffusion: str, cb_local, seed, *,
                  use_kernel: bool, interpret: bool):
    """Per-shard expansion closure over the shard's (leading-dim-1) tile
    stacks: IC draws per-(edge, color, level) Bernoullis keyed by CSR edge
    id; LT derives the fixed live-edge selection from the GLOBAL ids of
    the tiles' source rows (the picking vertices, `core.lt`; the
    expansion reads them from the all-gathered frontier), each tile
    hashing its own source block's uniforms, so a shard's memory follows
    its tiles and not the whole graph's vertex count.

    ``use_kernel=True`` runs each shard's partitioned tile stack through
    the Pallas tile kernels (`fused_expand` / `lt_select_expand`) instead
    of the jnp oracles — the tiles are dst-sorted within a shard with
    ``first_of_dst`` rebased per shard, and the kernels already accept a
    global frontier with shard-local visited rows, so the kernel grid is
    exactly the single-device one on the local stack (padding tiles are
    prob-0 and share the last real tile's dst block: inert).  Bits are
    identical either way."""
    if diffusion == "lt":
        from repro.kernels import lt_select_expand as lse

        def expand(fr_global, vis_local, level):
            if use_kernel:
                return lse.lt_select_expand(
                    ptg_local.prob[0], cb_local[0], ptg_local.tile_src[0],
                    ptg_local.tile_dst[0], ptg_local.first_of_dst[0],
                    fr_global, vis_local, seed, interpret=interpret)
            return kref.lt_select_expand_ref(
                ptg_local.prob[0], cb_local[0], ptg_local.tile_src[0],
                ptg_local.tile_dst[0], fr_global, vis_local, seed)
    else:
        from repro.kernels import fused_expand as fe

        def expand(fr_global, vis_local, level):
            if use_kernel:
                return fe.fused_expand(
                    ptg_local.prob[0], ptg_local.edge_id[0],
                    ptg_local.tile_src[0], ptg_local.tile_dst[0],
                    ptg_local.first_of_dst[0], fr_global, vis_local,
                    seed, level, interpret=interpret)
            return kref.fused_expand_ref(
                ptg_local.prob[0], ptg_local.edge_id[0],
                ptg_local.tile_src[0], ptg_local.tile_dst[0],
                fr_global, vis_local, seed, level)
    return expand


# Module-level cache of compiled 2-D block programs, keyed on (mesh, axes,
# spec knobs, partition STATICS) — mirroring the data_parallel
# `_DP_BLOCK_FNS` fix: the partitioned graph is a traced argument and the
# program closes over statics only, so streaming deltas that rebind tile
# VALUES (same partition shape) reuse the compiled program instead of
# re-tracing per delta.
_GP_BLOCK_FNS: dict = {}


def graph_parallel_block(ptg: part_lib.PartitionedTiledGraph, mesh: Mesh, *,
                         data_axis: str = "data", model_axis: str = "model",
                         num_colors: int, max_levels: int = 64,
                         diffusion: str = "ic", frontier: str = "dense",
                         gather_capacity: int = 0, use_kernel: bool,
                         interpret: bool):
    """Build (or fetch the cached) 2-D (data × model) fused-BPT block program.

    The composition the `repro.sampling` ``graph_parallel`` backend runs:
    a block of B independent batches is sharded over ``data_axis`` while
    the graph's destination rows are sharded over ``model_axis`` — every
    device holds only its (batch slice × row slice).  The per-level
    frontier exchange names ONLY the model axis; the level loop's control
    decisions (termination, sparse-vs-dense leg) sync over BOTH axes so
    the whole mesh steps levels in lockstep — what SPMD execution implies
    anyway, and what keeps the butterfly's collective-permutes from
    deadlocking when data shards drain at different depths.

    Returns a jitted ``fn(ptg, starts, seeds)`` (IC) or
    ``fn(ptg, cb_tiles, starts, seeds)`` (LT, ``cb_tiles`` =
    `partition_tile_values` of the selection-CDF prefixes) mapping
    starts (B, C) int32 / seeds (B,) uint32, both sharded ``P(data_axis)``,
    to ``(visited, gather_words)``: visited (B, Vp, W) uint32 sharded
    ``P(data_axis, model_axis)`` and gather_words (B, max_levels) int32
    sharded ``P(data_axis)`` — per batch, the packed words each level
    moved over the model axis (replicated across model shards).
    B must be a multiple of the data-axis size (callers pad).

    The tile stacks are runtime ARGUMENTS (closing over them would bake
    them into the jit program as replicated constants, defeating the row
    partition); the program itself closes over partition STATICS only
    (vertex/row counts, tile size, shard counts), so any
    ``PartitionedTiledGraph`` with the same statics — e.g. a streaming
    rebind that swapped tile values in place — runs through the same
    cached program.

    ``frontier="sparse"`` arms the ButterFly-style sparse leg of
    `_frontier_gather_loop` (log(M)-stage pairwise exchange of compacted
    (word_idx, word) pairs whenever the pmax'd active-word count fits
    ``gather_capacity`` words per shard, `gather_capacity_words` default)
    — same bits, less model-axis traffic on the collapsed late levels.

    ``use_kernel=True`` swaps each shard's local tile expansion from the
    jnp oracle to the Pallas kernels (`_local_expand`'s kernel leg);
    ``interpret`` is forwarded to them (`kernels.ops._interpret` decides
    it).  Both are part of the compile cache key.
    """
    key = (mesh, data_axis, model_axis, num_colors, max_levels, diffusion,
           frontier, gather_capacity, use_kernel, interpret,
           ptg.num_vertices, ptg.num_edges,
           ptg.tile_size, ptg.num_shards, ptg.blocks_per_shard)
    fn = _GP_BLOCK_FNS.get(key)
    if fn is None:
        fn = _build_graph_parallel_block(
            ptg, mesh, data_axis=data_axis, model_axis=model_axis,
            num_colors=num_colors, max_levels=max_levels,
            diffusion=diffusion, frontier=frontier,
            gather_capacity=gather_capacity, use_kernel=use_kernel,
            interpret=interpret)
        _GP_BLOCK_FNS[key] = fn
    return fn


def _build_graph_parallel_block(ptg, mesh, *, data_axis, model_axis,
                                num_colors, max_levels, diffusion, frontier,
                                gather_capacity, use_kernel, interpret):

    v, vp = ptg.num_vertices, ptg.padded_vertices
    rows, tile = ptg.rows_per_shard, ptg.tile_size
    num_shards = ptg.num_shards
    tile_specs = part_lib.partition_specs(ptg, model_axis)
    sparse_words = (gather_capacity_words(rows, bitmask.num_words(num_colors),
                                          gather_capacity)
                    if frontier == "sparse" else 0)

    def block_body(ptg_local, cb_local, starts_local, seeds_local):
        base = (jax.lax.axis_index(model_axis).astype(jnp.int32)
                * ptg_local.blocks_per_shard)

        def one(starts, seed):
            # Full (Vp, W) frontier is a transient; persistent state is the
            # (rows, W) local slice each shard keeps through the loop.
            fr = tiles.pad_mask_rows(init_frontier(v, num_colors, starts), vp)
            fr_local = jax.lax.dynamic_slice_in_dim(fr, base * tile, rows)
            expand = _local_expand(ptg_local, diffusion, cb_local, seed,
                                   use_kernel=use_kernel,
                                   interpret=interpret)
            vis, _, gw = _frontier_gather_loop(
                expand, fr_local, max_levels, model_axis,
                num_shards=num_shards, sparse_words=sparse_words,
                sync_axes=(data_axis, model_axis))
            return vis, gw

        # Sequential over the shard's local batch slice: one traversal's
        # transients at a time per device, parallel across data shards.
        return jax.lax.map(lambda a: one(*a), (starts_local, seeds_local))

    out_specs = (P(data_axis, model_axis), P(data_axis))
    if diffusion == "lt":
        fn = jax.shard_map(
            block_body, mesh=mesh,
            in_specs=(tile_specs, P(model_axis), P(data_axis), P(data_axis)),
            out_specs=out_specs, check_vma=False)
    else:
        fn = jax.shard_map(
            lambda ptg_l, st, sd: block_body(ptg_l, None, st, sd),
            mesh=mesh,
            in_specs=(tile_specs, P(data_axis), P(data_axis)),
            out_specs=out_specs, check_vma=False)
    return jax.jit(fn)
