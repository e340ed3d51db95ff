"""Public jit'd wrappers over the Pallas kernels.

``_interpret`` is the one place that picks the Pallas execution mode:
kernels run compiled on a TPU backend and in interpret mode (Python
evaluation of the kernel body) everywhere else, so CPU tests exercise the
kernel bodies while the BlockSpecs/grids target the TPU's lowering.  Every
caller of a kernel passes ``interpret=_interpret()``; a kernel refuses
interpret mode on a TPU (`kernels.common.checked_interpret`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import tiles as tiles_lib
from repro.kernels import coverage as _coverage
from repro.kernels import fused_expand as _fused_expand
from repro.kernels import flash_attention as _flash


def _interpret() -> bool:
    """True exactly where no TPU backend runs the kernels."""
    return jax.default_backend() != "tpu"


def fused_expand(tg: tiles_lib.TiledGraph, frontier, visited, seed, level):
    """One fused-BPT expansion level on a TiledGraph (padded row masks)."""
    return _fused_expand.fused_expand(
        tg.prob, tg.edge_id, tg.tile_src, tg.tile_dst, tg.first_of_dst,
        frontier, visited, jnp.uint32(seed), jnp.uint32(level),
        interpret=_interpret())


def cover_counts(visited, active):
    """Marginal-gain counts for greedy max-k-cover: (V, W) × (W,) → (V,)."""
    return _coverage.cover_counts(visited, active, interpret=_interpret())


def cover_counts_batched(visited, active):
    """Per-batch marginal-gain counts: (B, V, W) × (B, W) → (B, V).

    vmap of the coverage kernel over the batch axis — the per-batch grid and
    BlockSpecs are unchanged, so the TPU lowering is the same row sweep with
    a batched outer grid dimension.  Shared by the incremental greedy kernel
    (`core.imm.greedy_extend`) and the online query engine.
    """
    return jax.vmap(cover_counts)(visited, active)


def flash_attention(q, k, v, *, causal=True, scale=None, kv_offset=0,
                    block_q=128, block_k=128):
    """Blocked online-softmax attention (prefill hot-spot)."""
    return _flash.flash_attention(
        q, k, v, causal=causal, scale=scale, kv_offset=kv_offset,
        block_q=block_q, block_k=block_k, interpret=_interpret())
