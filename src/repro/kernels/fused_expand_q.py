"""Quantized fused-BPT expansion kernel (§Perf iteration B1, beyond-paper).

The f32 kernel's working set is 8 B per tile slot (prob f32 + edge-id u32)
and needs 32 hash calls per (tile, word).  This variant:

* quantizes activation probability to a u8 threshold ``q`` with the
  *endpoint-exact* rule  accept ⇔ (u8 ≤ q) ∧ (q > 0),  p̂ = (q+1)/256 for
  q>0 — p=1.0 and p=0.0 stay exact, max quantization error 1/256 ≪ the IC
  Monte-Carlo noise (validated statistically in tests);
* derives the RNG counter from the (tile, row, col) grid position instead
  of a stored edge id — the edge-id tile disappears entirely;
* extracts FOUR u8 lanes from every 32-bit hash → 8 hash calls per
  (tile, word) instead of 32.

Net: 1 B per tile slot (8× memory), 4× fewer hash ops.  The price is that
draws no longer couple bit-for-bit with the CSR/f32 paths — this kernel
validates against its own oracle (ref) + statistical agreement with the
exact path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import checked_interpret, expand_grid_params

from repro.core import rng


def quantize_probs(prob: jnp.ndarray) -> jnp.ndarray:
    """f32 prob in [0,1] → u8 threshold (module docstring semantics):
    p̂ = (q+1)/256 for q>0, exactly 0 for q==0; p=1 → q=255 → exact."""
    q = jnp.clip(jnp.round(prob * 256.0) - 1.0, 0, 255)
    return jnp.where(prob > 0, q, 0).astype(jnp.uint8)


def _bern_word_q(seed, level, cell_id, word, q8):
    """Packed 32-lane Bernoulli word from 8 hashes (4 u8 lanes per hash).

    Lane c draws byte (c % 4) of hash(seed, level, cell_id, word·8 + c//4);
    accept ⇔ u8 ≤ q8 ∧ q8 > 0.
    """
    out = jnp.zeros(q8.shape, jnp.uint32)
    valid = (q8 > 0)
    q16 = q8.astype(jnp.uint32)
    for h in range(8):
        bits = rng.hash_u32(seed, level, cell_id,
                            word * jnp.uint32(8) + jnp.uint32(h))
        for byte in range(4):
            u = (bits >> jnp.uint32(8 * byte)) & jnp.uint32(0xFF)
            c = h * 4 + byte
            accept = jnp.logical_and(u <= q16, valid)
            out = out | (accept.astype(jnp.uint32) << jnp.uint32(c))
    return out


def _expand_q_kernel(tile_src_ref, tile_dst_ref, first_ref, scalar_ref,
                     q_ref, frontier_ref, visited_ref, out_ref,
                     *, num_words: int, tile_size: int):
    t = pl.program_id(0)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seed = scalar_ref[0]
    level = scalar_ref[1]
    q8 = q_ref[0]                            # (T, T) uint8
    fr = frontier_ref[...]                   # (T, W)
    vis = visited_ref[...]                   # (T, W)
    T = tile_size
    row = jax.lax.broadcasted_iota(jnp.uint32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (T, T), 1)
    cell = (t.astype(jnp.uint32) * jnp.uint32(T * T)
            + row * jnp.uint32(T) + col)

    for w in range(num_words):
        rand_w = _bern_word_q(seed, level, cell, jnp.uint32(w), q8)
        x = fr[:, w][:, None] & rand_w
        n = T
        while n > 1:
            n //= 2
            x = x[:n] | x[n:]
        out_ref[:, w] |= x[0] & ~vis[:, w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_expand_q(q8_tiles, tile_src, tile_dst, first_of_dst,
                   frontier, visited, seed, level, *, interpret: bool):
    """Quantized one-level expansion; same contract as fused_expand."""
    nt, T, _ = q8_tiles.shape
    _, W = frontier.shape
    Vp = visited.shape[0]
    n_blocks = Vp // T
    scalars = jnp.asarray([seed, level], jnp.uint32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi, sc: (t, 0, 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi, sc: (ts[t], 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi, sc: (td[t], 0)),
        ],
        out_specs=pl.BlockSpec((T, W), lambda t, ts, td, fi, sc: (td[t], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_expand_q_kernel, num_words=W, tile_size=T),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Vp, W), jnp.uint32),
        interpret=checked_interpret(interpret),
        compiler_params=expand_grid_params(),
    )(tile_src, tile_dst, first_of_dst, scalars,
      q8_tiles, frontier, visited)
    covered = jnp.zeros((n_blocks,), jnp.uint32).at[tile_dst].set(1)
    return out * jnp.repeat(covered, T)[:, None]


def _expand_q_gathered_kernel(ids_ref, tile_src_ref, tile_dst_ref,
                              first_ref, scalar_ref, q_ref, frontier_ref,
                              visited_ref, out_ref,
                              *, num_words: int, tile_size: int):
    t = pl.program_id(0)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seed = scalar_ref[0]
    level = scalar_ref[1]
    q8 = q_ref[0]
    fr = frontier_ref[...]
    vis = visited_ref[...]
    T = tile_size
    row = jax.lax.broadcasted_iota(jnp.uint32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (T, T), 1)
    # RNG counters derive from the ORIGINAL tile id (prefetched), not the
    # grid position — the compacted grid must draw the dense grid's bits.
    cell = (ids_ref[t].astype(jnp.uint32) * jnp.uint32(T * T)
            + row * jnp.uint32(T) + col)

    for w in range(num_words):
        rand_w = _bern_word_q(seed, level, cell, jnp.uint32(w), q8)
        x = fr[:, w][:, None] & rand_w
        n = T
        while n > 1:
            n //= 2
            x = x[:n] | x[n:]
        out_ref[:, w] |= x[0] & ~vis[:, w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_expand_q_gathered(q8_gathered, tile_ids, tile_src, tile_dst,
                            first_of_dst, frontier, visited, seed, level,
                            *, interpret: bool):
    """Sparse-grid variant of `fused_expand_q`: the grid iterates a
    compacted (dst-sorted, null-padded) tile list; ``tile_ids`` carries
    each slot's ORIGINAL tile id so the position-derived RNG counters
    match the dense grid bit for bit."""
    nt, T, _ = q8_gathered.shape
    _, W = frontier.shape
    Vp = visited.shape[0]
    n_blocks = Vp // T
    scalars = jnp.asarray([seed, level], jnp.uint32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, T, T), lambda t, ids, ts, td, fi, sc: (t, 0, 0)),
            pl.BlockSpec((T, W), lambda t, ids, ts, td, fi, sc: (ts[t], 0)),
            pl.BlockSpec((T, W), lambda t, ids, ts, td, fi, sc: (td[t], 0)),
        ],
        out_specs=pl.BlockSpec(
            (T, W), lambda t, ids, ts, td, fi, sc: (td[t], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_expand_q_gathered_kernel, num_words=W,
                          tile_size=T),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Vp, W), jnp.uint32),
        interpret=checked_interpret(interpret),
        compiler_params=expand_grid_params(),
    )(tile_ids, tile_src, tile_dst, first_of_dst, scalars,
      q8_gathered, frontier, visited)
    covered = jnp.zeros((n_blocks,), jnp.uint32).at[tile_dst].set(1)
    return out * jnp.repeat(covered, T)[:, None]


def fused_expand_q_ref(q8_tiles, tile_src, tile_dst, frontier, visited,
                       seed, level):
    """Pure-jnp oracle with identical counters/quantization semantics."""
    nt, T, _ = q8_tiles.shape
    W = frontier.shape[1]
    n_blocks = visited.shape[0] // T
    fr_blocks = frontier.reshape(-1, T, W)
    vis_blocks = visited.reshape(n_blocks, T, W)
    row = jax.lax.broadcasted_iota(jnp.uint32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, (T, T), 1)

    def one_tile(tid, q8, ts, td):
        cell = (tid.astype(jnp.uint32) * jnp.uint32(T * T)
                + row * jnp.uint32(T) + col)
        F = fr_blocks[ts]
        V = vis_blocks[td]

        def one_word(w):
            rand_w = _bern_word_q(seed, level, cell, jnp.uint32(w), q8)
            x = F[:, w][:, None] & rand_w
            return jax.lax.reduce(x, jnp.uint32(0), jnp.bitwise_or, (0,))

        contrib = jax.vmap(one_word, out_axes=1)(
            jnp.arange(W, dtype=jnp.uint32))
        return contrib & ~V

    contribs = jax.vmap(one_tile)(jnp.arange(nt), q8_tiles, tile_src,
                                  tile_dst)
    from repro.core import bitmask
    out = jnp.zeros_like(visited).reshape(n_blocks, T, W)
    out = bitmask.pack_bits(
        bitmask.unpack_bits(out).at[tile_dst].max(
            bitmask.unpack_bits(contribs)))
    return out.reshape(-1, W)
