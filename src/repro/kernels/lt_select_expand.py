"""Pallas TPU kernel: LT live-edge selection + frontier expansion per tile.

The LT analogue of `kernels.fused_expand`: one grid step processes one
non-empty T×T adjacency tile entirely in VMEM, but the per-(edge, color)
Bernoulli gate is replaced by the *fixed* LT live-edge selection — edge
``(src, dst)`` carries color ``c`` iff

    cb[src, dst] ≤ u(dst, c) < cb[src, dst] + prob[src, dst]

where ``cb`` is the per-edge selection-CDF prefix
(`tiles.edge_values_to_tiles(tg, lt.selection_cum_before(g))`) and ``u`` is
the level-independent per-(dst, color) uniform table
(`kernels.ref.lt_selection_uniforms`), computed ONCE per traversal by the
caller, passed transposed — ``(W·32, Vo)``, one row per color lane — and
block-sliced per grid step by destination block.  No RNG runs inside the
kernel at all: the selection is a pure f32 interval test, so the tile needs
only two f32 stencils (prob, cb) plus a (W·32, T) slice of the uniform
table.  The gate word is built one color lane at a time: lane ``c``'s
uniforms are one row of that slice, broadcast down the tile's source rows,
so every transient is a lane-dense (T, T) array.

Tiles are pre-sorted by destination block (revisiting accumulation,
zero-init on ``first_of_dst``) exactly like the IC kernel, and the gate
computation reproduces `ref.lt_select_expand_ref` term for term, so the
kernel is bit-for-bit equal to the oracle and to the dense
``lt.run_fused_lt`` sweep.

VMEM per grid step (T=128, W words):
    prob + cb tiles        2·128·128·4   = 128 KiB
    uniform slice          W·32·128·4    = 16·W KiB
    frontier/visited/out   3·128·W·4
    transients             a few (T, T) arrays of 64 KiB each
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import checked_interpret, expand_grid_params
from repro.kernels.fused_expand import _or_reduce_rows


def _lt_kernel(tile_src_ref, tile_dst_ref, first_ref,
               prob_ref, cb_ref, u_ref, frontier_ref, visited_ref, out_ref,
               *, num_words: int):
    t = pl.program_id(0)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    prob = prob_ref[0]                      # (T, T) f32, rows = src lanes
    cb = cb_ref[0]                          # (T, T) f32 selection-CDF prefix
    fr = frontier_ref[...]                  # (T, W) u32, rows = src lanes
    vis = visited_ref[...]                  # (T, W) u32, rows = dst lanes
    hi = cb + prob

    for w in range(num_words):              # static unroll over color words
        def lane(c, gate, w=w):
            # Fixed live-edge selection of color w·32 + c for every
            # (src, dst) — identical interval test (and f32 rounding) to
            # the ref oracle.  u_ref row = color lane, columns = dst lanes.
            U = u_ref[pl.ds(w * 32 + c, 1), :]          # (1, T_dst)
            sel = jnp.logical_and(U >= cb, U < hi)
            bit = jnp.uint32(1) << c.astype(jnp.uint32)
            return gate | jnp.where(sel, bit, jnp.uint32(0))

        # (T, T) gate words: src lane i → dst lane j
        gate = jax.lax.fori_loop(0, 32, lane,
                                 jnp.zeros(prob.shape, jnp.uint32))
        x = fr[:, w][:, None] & gate
        contrib = _or_reduce_rows(x)        # (T,) per-dst OR over sources
        out_ref[:, w] |= contrib & ~vis[:, w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def lt_select_expand(tg_prob, cb_tiles, tile_src, tile_dst, first_of_dst,
                     frontier, visited, u_t, *, interpret: bool):
    """One fused-LT level on the tiled graph.  See module docstring.

    ``frontier`` is (Vf, W) and ``visited`` (Vo, W), both multiples of T;
    ``u_t`` is the (W·32, Vo) transpose of `ref.lt_selection_uniforms`,
    columns aligned with ``visited`` rows (global-id hashed, so
    graph-parallel shards pass their row slice).  ``visited`` must already
    include the current frontier.
    """
    nt, T, _ = tg_prob.shape
    _, W = frontier.shape
    Vp = visited.shape[0]
    n_blocks = Vp // T
    UW = u_t.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi: (t, 0, 0)),
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi: (t, 0, 0)),
            pl.BlockSpec((UW, T), lambda t, ts, td, fi: (0, td[t])),
            pl.BlockSpec((T, W), lambda t, ts, td, fi: (ts[t], 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi: (td[t], 0)),
        ],
        out_specs=pl.BlockSpec((T, W), lambda t, ts, td, fi: (td[t], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_lt_kernel, num_words=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Vp, W), jnp.uint32),
        interpret=checked_interpret(interpret),
        compiler_params=expand_grid_params(),
    )(tile_src, tile_dst, first_of_dst,
      tg_prob, cb_tiles, u_t, frontier, visited)

    # Destination blocks with no incoming tile were never written; Pallas
    # leaves them undefined — mask them via the tile_dst coverage set.
    covered = jnp.zeros((n_blocks,), jnp.uint32).at[tile_dst].set(1)
    return out * jnp.repeat(covered, T)[:, None]
