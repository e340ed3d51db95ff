"""Pallas TPU kernel: max-k-cover marginal-gain counts.

Seed selection (Listing 1 lines 18-21 + IMM's greedy max-cover) reduces the
(V, W) visited bitmask against the mask of still-uncovered colors:

    counts[v] = Σ_w popcount(visited[v, w] & active[w])

On GPUs this is the atomic-append RRR-set construction; on TPU it is a
bandwidth-bound row sweep.  A (V, W) array with W of 1-4 words would fill
W of a vector register's 128 lanes, and the TPU's (8, 128) block tiling
would pad it to 128 lanes in HBM — 64× the bytes at W = 2.  So the kernel
reads the mask *lane-dense*: W is padded to a power of two Wp ≤ 128 and
the row-major mask viewed as (V·Wp/128, 128), each 128-lane row holding
128/Wp whole vertices.  One grid step ANDs a row block with the active
mask tiled across the lanes, takes SWAR popcounts, and sums each vertex's
Wp lanes with one matmul against a 0/1 lane-group matrix (the MXU adds
small integers exactly), writing a (rows, 128/Wp) block of counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import bitmask
from repro.kernels.common import checked_interpret

LANES = 128


def _coverage_kernel(vis_ref, act_ref, group_ref, out_ref):
    hits = bitmask.popcount(vis_ref[...] & act_ref[...])   # (R, 128) uint32
    # Per-lane counts are ≤ 32, so every partial sum is exact in f32.
    counts = jnp.dot(hits.astype(jnp.int32).astype(jnp.float32),
                     group_ref[...], preferred_element_type=jnp.float32)
    out_ref[...] = counts.astype(jnp.int32)                # (R, 128/Wp)


def _padded_words(w: int) -> int:
    if w > LANES:
        raise ValueError(f"{w} words > {LANES}: at most {LANES * 32} colors")
    return 1 << (w - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cover_counts(visited, active, *, interpret: bool, block_rows: int = 512):
    """counts[v] = popcount(visited[v] & active) — see module docstring.

    visited: (V, W) uint32, any V, W ≤ 128.
    active:  (W,) uint32 mask of not-yet-covered colors.
    block_rows: 128-lane rows per grid step (a multiple of 8).
    Returns (V,) int32.
    """
    V, W = visited.shape
    Wp = _padded_words(W)
    per_row = LANES // Wp                                  # vertices per row
    rows = -(-V // per_row)
    R = min(block_rows, -(-rows // 8) * 8)
    rows_p = -(-rows // R) * R
    vis = jnp.pad(visited, ((0, rows_p * per_row - V), (0, Wp - W)))
    act = jnp.tile(jnp.pad(active, (0, Wp - W)), per_row)[None, :]
    group = jnp.asarray(
        np.arange(LANES)[:, None] // Wp == np.arange(per_row)[None, :],
        jnp.float32)                                       # (128, 128/Wp)
    out = pl.pallas_call(
        _coverage_kernel,
        grid=(rows_p // R,),
        in_specs=[
            pl.BlockSpec((R, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, LANES), lambda i: (0, 0)),
            pl.BlockSpec((LANES, per_row), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((R, per_row), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, per_row), jnp.int32),
        interpret=checked_interpret(interpret),
    )(vis.reshape(rows_p, LANES), act, group)
    return out.reshape(-1)[:V]
