"""Counter-based stateless RNG shared by kernels, references, and baselines.

The paper's GPU codes draw one uniform per (edge, color) attempt via curand.
On TPU we need an RNG that (a) is a pure function of its counters so fused and
unfused traversals can be *coupled* on identical edge realizations (used to
test Theorem 1 exactly), and (b) lowers inside a Pallas kernel body with plain
integer ops.  We use a small Philox/threefry-style mixer over a 4-tuple
``(seed, level, edge_id, word_id)`` producing one uint32 word == 32 color
lanes per call.

All functions are pure jnp and dtype-stable (uint32 in / uint32 out).  They
also lower for the TPU's Mosaic compiler, which casts no uint32 to float and
reduces no unsigned integer: the conversions below go through int32, which
is exact at the widths they use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Constants from splitmix64 / murmur3 finalizers, truncated to 32-bit ops.
# Plain Python ints, cast at use sites: module-level jnp scalars would be
# captured device constants, which Pallas kernel bodies reject.
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLDEN = 0x9E3779B9


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3 fmix32 finalizer — full-avalanche 32-bit mixer."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> jnp.uint32(16))
    return x


def absorb(h: jnp.ndarray, x) -> jnp.ndarray:
    """Fold one uint32 counter ``x`` into hash state ``h``."""
    g = jnp.uint32(_GOLDEN)
    x = jnp.asarray(x, jnp.uint32)
    return _mix32(h ^ (x + g + (h << jnp.uint32(6)) + (h >> jnp.uint32(2))))


def edge_state(seed, level, edge_id) -> jnp.ndarray:
    """Hash state after the (seed, level, edge_id) counters — the part of
    `hash_u32` that every word and lane of one edge share."""
    seed = jnp.asarray(seed, jnp.uint32)
    h = absorb(seed * jnp.uint32(_GOLDEN), level)
    return absorb(h, edge_id)


def hash_u32(seed, level, edge_id, word_id) -> jnp.ndarray:
    """Hash 4 counters to one uint32 word (vectorized over any of them)."""
    return absorb(edge_state(seed, level, edge_id), word_id)


def uniform_from_u32(bits: jnp.ndarray) -> jnp.ndarray:
    """Map uint32 → float32 uniform in [0, 1) using the top 24 bits.

    ``bits >> 8 < 2**24`` fits int32 and float32 exactly, so the detour
    through int32 changes no value."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def bernoulli_word(seed, level, edge_id, word_id, prob, lanes: int = 32) -> jnp.ndarray:
    """Packed uint32 word of ``lanes`` independent Bernoulli(prob) bits.

    Bit ``c`` of the result is the draw for color ``word_id*32 + c`` of edge
    ``edge_id`` at traversal ``level``.  One hash call per lane (vectorized) —
    each (edge, color) attempt is an independent draw, as the IC model and
    Listing 1 line 13 require.
    """
    lane = jnp.arange(lanes, dtype=jnp.uint32)
    # Fold the lane into the word counter so every color gets its own stream.
    bits = hash_u32(seed, level, edge_id[..., None], word_id * jnp.uint32(32) + lane)
    draws = uniform_from_u32(bits) < jnp.asarray(prob, jnp.float32)[..., None]
    return pack_bool_word(draws)


def bernoulli_word_lanes(state, word_id, prob, lanes: int = 32) -> jnp.ndarray:
    """`bernoulli_word` from a precomputed `edge_state`, built one lane at a
    time: every intermediate keeps the shape of ``state``.  Bit-identical
    to `bernoulli_word`; the form a TPU kernel wants, where a trailing
    32-lane axis would pad to 128 lanes in vector memory."""
    word_id = jnp.asarray(word_id, jnp.uint32)

    def lane(c, word):
        c = c.astype(jnp.uint32)
        u = uniform_from_u32(absorb(state, word_id * jnp.uint32(32) + c))
        return word | jnp.where(u < prob, jnp.uint32(1) << c, jnp.uint32(0))

    return jax.lax.fori_loop(0, lanes, lane,
                             jnp.zeros(state.shape, jnp.uint32))


def pack_bool_word(bits_bool: jnp.ndarray) -> jnp.ndarray:
    """Pack trailing axis of ≤32 bools into a uint32 (bit c = lane c).

    An int32 sum of disjoint powers of two carries nowhere, so it equals the
    OR of the set bits (bit 31 as the int32 sign), bitcast back to uint32."""
    lanes = bits_bool.shape[-1]
    weights = jnp.int32(1) << jnp.arange(lanes, dtype=jnp.int32)
    packed = jnp.sum(jnp.where(bits_bool, weights, jnp.int32(0)), axis=-1,
                     dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(packed, jnp.uint32)
