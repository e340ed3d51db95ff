"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the *definition* of its kernel's semantics; kernel tests
sweep shapes/dtypes and assert bit-exact (integer kernels) or allclose
(float kernels) agreement in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitmask, rng


def _tile_expand(gate_fn, gate_args, tile_src, tile_dst, frontier, visited):
    """Shared tile-expansion scaffolding for the traversal oracles:

        out[dst] = OR over tiles( OR_src(frontier[src] & gate) ) & ~visited[dst]

    ``gate_fn((per-tile arrays), ts, td) -> (T, T, W)`` packed gate words —
    the IC Bernoulli draw or the LT live-edge selection; ``gate_args`` is a
    tuple of (nt, T, T) arrays vmapped alongside tile_src/tile_dst.  One
    scaffolding, so IC and LT can never diverge on the reshape / OR-reduce
    / scatter-max mechanics the kernel tests pin down.
    """
    T = gate_args[0].shape[1]
    W = frontier.shape[1]
    n_blocks = visited.shape[0] // T
    fr_blocks = frontier.reshape(-1, T, W)
    vis_blocks = visited.reshape(n_blocks, T, W)

    def one_tile(args, ts, td):
        F = fr_blocks[ts]                                   # (T, W)
        V = vis_blocks[td]                                  # (T, W)
        x = F[:, None, :] & gate_fn(args, ts, td)           # (T, T, W)
        contrib = jax.lax.reduce(x, jnp.uint32(0),
                                 jnp.bitwise_or, (0,))      # (T, W) per dst
        return contrib & ~V

    contribs = jax.vmap(one_tile)(gate_args, tile_src, tile_dst)  # (nt,T,W)
    out = jnp.zeros_like(visited).reshape(n_blocks, T, W)
    out = bitmask.pack_bits(
        bitmask.unpack_bits(out).at[tile_dst].max(bitmask.unpack_bits(contribs)))
    return out.reshape(-1, W)


def fused_expand_ref(prob, edge_id, tile_src, tile_dst, frontier, visited,
                     seed, level):
    """Oracle for kernels.fused_expand — one level of tile-based expansion.

    Args:
      prob:     (nt, T, T) f32 tile activation probabilities (0 ⇒ no edge).
      edge_id:  (nt, T, T) uint32 CSR edge ids (RNG counters).
      tile_src: (nt,) i32 source block per tile (indexes ``frontier``).
      tile_dst: (nt,) i32 destination block per tile (indexes ``visited``).
      frontier: (Vf, W) uint32 packed color mask (padded rows).
      visited:  (Vo, W) uint32 — ALREADY folded with the current frontier.
                Vo == Vf single-device; Vo = shard rows graph-parallel.
      seed, level: uint32 RNG counters.
    Returns:
      next_frontier (Vo, W) uint32 = OR over tiles of
        OR_i( frontier[src_i] & Bernoulli_word(edge) ) & ~visited[dst]
    """
    W = frontier.shape[1]

    def gate(args, ts, td):
        p, eid = args
        word_ids = jnp.arange(W, dtype=jnp.uint32)
        # (T, T, W): Bernoulli word for every (src-lane, dst-lane, word).
        return jax.vmap(
            lambda w: rng.bernoulli_word(seed, level, eid, w, p),
            out_axes=-1)(word_ids)

    return _tile_expand(gate, (prob, edge_id), tile_src, tile_dst,
                        frontier, visited)


def lt_selection_uniforms(seed, num_rows: int, num_colors: int, row_base=0):
    """(num_rows, W·32) f32 LT selection uniforms ``u(x, color)`` — the
    same (seed, 0x17, x, color) counters as `lt.selection_mask_from_cb`,
    one per (picking vertex, color lane).  The picking vertex is an edge's
    SOURCE row of the reversed graph (`core.lt`).  ``row_base`` is the
    global vertex id of row 0 (the hash needs GLOBAL ids): 0 for a whole
    table, a tile's first source row for a tile's slice.  Lanes pad to
    full words like the dense path; padded lanes never meet a live
    frontier bit."""
    seed = jnp.asarray(seed, jnp.uint32)
    rows = (row_base + jnp.arange(num_rows, dtype=jnp.int32)) \
        .astype(jnp.uint32)
    lanes = jnp.arange(bitmask.num_words(num_colors) * 32, dtype=jnp.uint32)
    return rng.uniform_from_u32(
        rng.hash_u32(seed, jnp.uint32(0x17), rows[:, None], lanes[None, :]))


def lt_select_expand_ref(prob, cb, tile_src, tile_dst, frontier, visited,
                         seed):
    """One level of tile-based expansion under the LT live-edge selection.

    Same tile formulation as `fused_expand_ref`, but the per-(edge, color)
    Bernoulli gate is replaced by the fixed LT selection (`core.lt`): edge
    ``(src, dst)`` carries color ``c`` iff ``cb ≤ u(src, c) < cb + prob``
    — bit-identical to the dense `lt.selection_mask_from_cb` sweep without
    materializing the (E, W) selection mask.  Each tile hashes the
    uniforms of its own source block (T·W·32 hashes, small next to the
    tile's T·T·W·32 compares), so no table over every vertex is held —
    a graph-parallel shard would otherwise keep one of the whole graph.

    Args:
      prob:     (nt, T, T) f32 LT-normalized in-weights (0 ⇒ no edge).
      cb:       (nt, T, T) f32 selection-CDF prefix per edge slot
                (`tiles.edge_values_to_tiles` of `lt.selection_cum_before`).
      tile_src: (nt,) i32 source block per tile (indexes ``frontier``;
                global ids, so its rows are the picking vertices).
      tile_dst: (nt,) i32 destination block per tile (indexes ``visited``).
      frontier: (Vf, W) uint32 packed color mask (padded rows).
      visited:  (Vo, W) uint32 — ALREADY folded with the current frontier.
                Vo == Vf single-device; Vo = shard rows graph-parallel.
      seed:     uint32 stream seed of the traversal.
    """
    T = prob.shape[1]
    W = frontier.shape[1]

    def gate(args, ts, td):
        p, cbt = args
        U = lt_selection_uniforms(seed, T, W * 32,
                                  row_base=ts * T)          # (T_src, W·32)
        # One broadcast compare for every (src, dst, color) at once —
        # colors group row-major into words, lane c%32 = bit c%32, exactly
        # the per-lane packing order of the dense path.
        sel = jnp.logical_and(U[:, None, :] >= cbt[:, :, None],
                              U[:, None, :] < (cbt + p)[:, :, None])
        return rng.pack_bool_word(
            sel.reshape(T, T, -1, 32))                      # (T, T, W)

    return _tile_expand(gate, (prob, cb), tile_src, tile_dst,
                        frontier, visited)


def cover_counts_ref(visited, active):
    """Oracle for kernels.coverage — marginal-gain counts for max-k-cover.

    counts[v] = |{colors c : visited[v, c] ∧ active[c]}|
    """
    return jnp.sum(bitmask.popcount(visited & active[None, :]),
                   axis=-1).astype(jnp.int32)


def flash_attention_ref(q, k, v, *, causal=True, scale=None, kv_offset=0):
    """Oracle for kernels.flash_attention — plain softmax attention.

    q: (Lq, H, D), k/v: (Lk, H, D).  ``kv_offset`` shifts query positions for
    decode (query i attends keys ≤ i + kv_offset).
    """
    d = q.shape[-1]
    scale = scale or d ** -0.5
    logits = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qi = jnp.arange(q.shape[0])[:, None] + kv_offset
        ki = jnp.arange(k.shape[0])[None, :]
        logits = jnp.where((ki <= qi)[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)).astype(q.dtype)
