"""Pallas TPU kernel: fused-BPT frontier expansion over block-sparse tiles.

This is the compute hot-spot the paper optimizes (its GPU kernels in §4).
TPU adaptation: one grid step processes one non-empty T×T
adjacency tile entirely in VMEM —

    out[dst_blk] |= ( OR_i frontier[src_blk][i] & Bernoulli_word(edge ij) )
                    & ~visited[dst_blk]

Tiles are pre-sorted by destination block, so all grid steps writing one
output block are consecutive and the kernel uses the Pallas *revisiting*
accumulation pattern (zero-init on ``first_of_dst``).  The per-(edge, color)
Bernoulli draws use the same counter hash as the pure-JAX paths, so the
kernel is bit-for-bit equal to ``ref.fused_expand_ref`` and to the CSR
edge-centric traversal.

The draws are built one color lane at a time over the whole (T, T) tile
(`rng.bernoulli_word_lanes`), so every transient is a lane-dense (T, T)
array.  VMEM per grid step (T=128, W words):
    prob tile        128·128·4      =  64 KiB
    edge-id tile     128·128·4      =  64 KiB
    frontier/visited/out blocks     3·128·W·4
    transients       a few (T, T) u32 arrays of 64 KiB each
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import checked_interpret, expand_grid_params

from repro.core import rng


def _or_reduce_rows(x: jnp.ndarray) -> jnp.ndarray:
    """OR-fold axis 0 (length power-of-two) with a log2 tree of full-lane ops."""
    n = x.shape[0]
    while n > 1:
        n //= 2
        x = x[:n] | x[n:]
    return x[0]


def _expand_kernel(tile_src_ref, tile_dst_ref, first_ref, scalar_ref,
                   prob_ref, eid_ref, frontier_ref, visited_ref, out_ref,
                   *, num_words: int):
    t = pl.program_id(0)

    @pl.when(first_ref[t] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seed = scalar_ref[0]
    level = scalar_ref[1]
    prob = prob_ref[0]                      # (T, T) f32
    eid = eid_ref[0]                        # (T, T) u32
    fr = frontier_ref[...]                  # (T, W) u32, rows = src lanes
    vis = visited_ref[...]                  # (T, W) u32, rows = dst lanes

    state = rng.edge_state(seed, level, eid)    # (T, T), shared by all words
    for w in range(num_words):              # static unroll over color words
        # Independent Bernoulli(p_e) per (edge, color lane): 32 hash lanes.
        rand_w = rng.bernoulli_word_lanes(state, w, prob)
        x = fr[:, w][:, None] & rand_w      # (T, T): src lane i → dst lane j
        contrib = _or_reduce_rows(x)        # (T,) per-dst OR over sources
        out_ref[:, w] |= contrib & ~vis[:, w]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_expand(tg_prob, tg_eid, tile_src, tile_dst, first_of_dst,
                 frontier, visited, seed, level, *, interpret: bool):
    """One fused-BPT level on the tiled graph.  See module docstring.

    ``frontier`` is (Vf, W) and ``visited`` (Vo, W), both multiples of T.
    ``tile_src`` indexes frontier blocks, ``tile_dst`` visited/output blocks;
    on the single-device path Vf == Vo, on the graph-parallel path the
    frontier is the all-gathered global mask while visited/output are the
    shard-local rows.  ``visited`` must already include the current frontier
    (level-sync semantics).
    """
    nt, T, _ = tg_prob.shape
    _, W = frontier.shape
    Vp = visited.shape[0]
    n_blocks = Vp // T
    scalars = jnp.asarray([seed, level], jnp.uint32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi, sc: (t, 0, 0)),
            pl.BlockSpec((1, T, T), lambda t, ts, td, fi, sc: (t, 0, 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi, sc: (ts[t], 0)),
            pl.BlockSpec((T, W), lambda t, ts, td, fi, sc: (td[t], 0)),
        ],
        out_specs=pl.BlockSpec((T, W), lambda t, ts, td, fi, sc: (td[t], 0)),
    )
    out = pl.pallas_call(
        functools.partial(_expand_kernel, num_words=W),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Vp, W), jnp.uint32),
        interpret=checked_interpret(interpret),
        compiler_params=expand_grid_params(),      # sequential: accumulation
    )(tile_src, tile_dst, first_of_dst, scalars,
      tg_prob, tg_eid, frontier, visited)

    # Destination blocks with no incoming tile were never written; Pallas
    # leaves them undefined — mask them via the tile_dst coverage set.
    covered = jnp.zeros((n_blocks,), jnp.uint32).at[tile_dst].set(1)
    return out * jnp.repeat(covered, T)[:, None]
