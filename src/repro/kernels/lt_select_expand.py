"""Pallas TPU kernel: LT live-edge selection + frontier expansion per tile.

The LT analogue of `kernels.fused_expand`: one grid step processes one
non-empty T×T adjacency tile entirely in VMEM, but the per-(edge, color)
Bernoulli gate is replaced by the *fixed* LT live-edge selection — edge
``(src, dst)`` carries color ``c`` iff

    cb[src, dst] ≤ u(src, c) < cb[src, dst] + prob[src, dst]

where ``cb`` is the per-edge selection-CDF prefix
(`tiles.edge_values_to_tiles(tg, lt.selection_cum_before(g))`) and ``u`` is
the per-(src, color) uniform of the (seed, 0x17, src, color) counters: on
the reversed graph the edge's source is the vertex that picks (`core.lt`).
Each grid step hashes the uniforms of its tile's SOURCE block itself —
one hash state per source row, one fold per color lane, (1, T) vectors
next to the tile's (T, T) compares — so no uniform table over every
vertex is held (a graph-parallel shard would hold the whole graph's).
The gate is built transposed, (dst, src), one color lane at a time: lane
``c``'s uniforms are a (1, T) row, broadcast down the transposed stencils'
rows, so every transient is a lane-dense (T, T) array; each word's gate
is transposed back once.

Tiles are pre-sorted by destination block (revisiting accumulation,
zero-init on ``first_of_dst``) exactly like the IC kernel, and the gate
computation reproduces `ref.lt_select_expand_ref` term for term, so the
kernel is bit-for-bit equal to the oracle and to the dense
``lt.run_fused_lt`` sweep.

VMEM per grid step (T=128, W words):
    prob + cb tiles        2·128·128·4   = 128 KiB
    frontier/visited/out   3·128·W·4
    transients             a few (T, T) arrays of 64 KiB each
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import rng
from repro.kernels.common import checked_interpret, expand_grid_params
from repro.kernels.fused_expand import _or_reduce_rows


def _lt_kernel(tile_src_ref, tile_dst_ref, first_ref, seed_ref,
               prob_ref, cb_ref, frontier_ref, visited_ref, out_ref,
               *, num_words: int):
    t = pl.program_id(0)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    cb = cb_ref[0]                          # (T, T) f32 selection-CDF prefix
    hi = cb + prob_ref[0]                   # rows = src lanes, cols = dst
    cb_t, hi_t = cb.T, hi.T                 # rows = dst lanes, cols = src
    fr = frontier_ref[...]                  # (T, W) u32, rows = src lanes
    vis = visited_ref[...]                  # (T, W) u32, rows = dst lanes
    T = cb.shape[0]
    # Hash state of the tile's source rows (global ids), shared by lanes.
    rows = (tile_src_ref[t] * T
            + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1))
    state = rng.edge_state(seed_ref[0], 0x17, rows.astype(jnp.uint32))

    for w in range(num_words):              # static unroll over color words
        def lane(c, gate_t, w=w):
            # Fixed live-edge selection of color w·32 + c for every
            # (src, dst) — identical uniforms, interval test and f32
            # rounding to the ref oracle.  Columns = src lanes.
            U = rng.uniform_from_u32(
                rng.absorb(state, w * 32 + c))          # (1, T_src)
            sel = jnp.logical_and(U >= cb_t, U < hi_t)
            bit = jnp.uint32(1) << c.astype(jnp.uint32)
            return gate_t | jnp.where(sel, bit, jnp.uint32(0))

        # (T, T) gate words, built (dst, src) and turned to (src, dst)
        gate = jax.lax.fori_loop(0, 32, lane,
                                 jnp.zeros(cb.shape, jnp.uint32)).T
        x = fr[:, w][:, None] & gate
        contrib = _or_reduce_rows(x)        # (T,) per-dst OR over sources
        out_ref[:, w] |= contrib & ~vis[:, w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lt_select_expand(tg_prob, cb_tiles, tile_src, tile_dst, first_of_dst,
                     frontier, visited, seed, *, interpret: bool):
    """One fused-LT level on the tiled graph.  See module docstring.

    ``frontier`` is (Vf, W) and ``visited`` (Vo, W), both multiples of T;
    ``tile_src`` holds global source blocks (a graph-parallel shard's
    frontier is the whole graph's), whose rows key the uniforms.
    ``visited`` must already include the current frontier.  ``seed`` is
    the traversal's uint32 stream seed.
    """
    nt, T, _ = tg_prob.shape
    _, W = frontier.shape
    Vp = visited.shape[0]
    n_blocks = Vp // T
    seeds = jnp.asarray(seed, jnp.uint32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi, sd: (t, 0, 0)),
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi, sd: (t, 0, 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi, sd: (ts[t], 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi, sd: (td[t], 0)),
        ],
        out_specs=pl.BlockSpec((T, W), lambda t, ts, td, fi, sd: (td[t], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_lt_kernel, num_words=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Vp, W), jnp.uint32),
        interpret=checked_interpret(interpret),
        compiler_params=expand_grid_params(),
    )(tile_src, tile_dst, first_of_dst, seeds,
      tg_prob, cb_tiles, frontier, visited)

    # Destination blocks with no incoming tile were never written; Pallas
    # leaves them undefined — mask them via the tile_dst coverage set.
    covered = jnp.zeros((n_blocks,), jnp.uint32).at[tile_dst].set(1)
    return out * jnp.repeat(covered, T)[:, None]
