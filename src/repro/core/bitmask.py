"""Packed color-bitmask utilities.

A *color* is one traversal in a fused group (paper §3).  Masks are stored as
``(..., W)`` uint32 arrays with ``W = ceil(colors / 32)`` words — the same
blocked-bitmask layout the paper's Ripples port uses (§4.2), chosen there for
warp alignment and here because 32 colors/word matches the VPU lane width.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import rng

WORD_BITS = 32


def num_words(num_colors: int) -> int:
    return -(-num_colors // WORD_BITS)


def color_tail_mask(num_colors: int) -> np.ndarray:
    """(W,) uint32 mask that zeroes bits past ``num_colors`` in the last word."""
    w = num_words(num_colors)
    out = np.full((w,), 0xFFFFFFFF, dtype=np.uint32)
    rem = num_colors % WORD_BITS
    if rem:
        out[-1] = np.uint32((1 << rem) - 1)
    return out


def make_mask(num_items: int, num_colors: int) -> jnp.ndarray:
    """All-zeros packed mask of shape (num_items, W)."""
    return jnp.zeros((num_items, num_words(num_colors)), jnp.uint32)


def set_color(mask: jnp.ndarray, item: jnp.ndarray, color: jnp.ndarray) -> jnp.ndarray:
    """Set bit ``color`` of row ``item`` (vectorized over both)."""
    item = jnp.asarray(item)
    color = jnp.asarray(color)
    word = color // WORD_BITS
    bit = jnp.uint32(1) << (color % WORD_BITS).astype(jnp.uint32)
    # Scatter-OR via max on one-hot-per-bit contributions: build per-row word
    # updates and OR them in.  Duplicate (item, word) pairs are combined with
    # a bitwise-or segment reduction implemented as unpack→max→pack.
    flat = jnp.zeros(mask.shape, jnp.uint32)
    flat = scatter_or_words(flat, item, word, bit)
    return mask | flat


def scatter_or_words(dst: jnp.ndarray, rows: jnp.ndarray, words: jnp.ndarray,
                     values: jnp.ndarray, *,
                     unique: bool = False) -> jnp.ndarray:
    """dst[rows, words] |= values with duplicate-index OR semantics.

    Bitwise-or is not a native scatter combiner; since OR over packed words is
    per-bit max, we unpack each contribution to 32 bool lanes, scatter with
    ``max``, and repack.  Cost: 32× the index traffic — fine for the pure-JAX
    path; the Pallas kernel keeps everything packed.

    ``unique=True`` is the packed fast path for callers whose contributions
    are already OR-combined per (row, word) target — every (rows[i],
    words[i]) pair distinct, e.g. segment-locally pre-OR'd compaction
    output or the distributed sparse-frontier reconstruction.  With no
    duplicate to combine, a gather-OR-scatter of whole uint32 words is
    exact (no lost updates) and pays 1× the index traffic instead of 32×.
    """
    if unique:
        cur = dst[rows, words]
        return dst.at[rows, words].set(cur | values, unique_indices=True)
    lanes = unpack_bits(values[..., None])[..., 0, :]          # (..., 32) bool
    dst_lanes = unpack_bits(dst)                               # (R, W, 32)
    dst_lanes = dst_lanes.at[rows, words].max(lanes)
    return pack_bits(dst_lanes)


def unpack_bits(mask: jnp.ndarray) -> jnp.ndarray:
    """(..., W) uint32 → (..., W, 32) bool."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return ((mask[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.bool_)


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., W, 32) bool → (..., W) uint32."""
    return rng.pack_bool_word(bits)


def popcount(mask: jnp.ndarray) -> jnp.ndarray:
    """Per-word population count (SWAR — no lookup tables, kernel-safe)."""
    x = mask
    x = x - ((x >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    x = (x + (x >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def any_set(mask: jnp.ndarray) -> jnp.ndarray:
    """True if any bit set anywhere in the mask tensor."""
    return jnp.any(mask != 0)


def count_colors(mask: jnp.ndarray) -> jnp.ndarray:
    """Total set bits per row: (R, W) → (R,) int32."""
    return jnp.sum(popcount(mask), axis=-1).astype(jnp.int32)
