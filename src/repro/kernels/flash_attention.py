"""Pallas TPU kernel: blocked online-softmax (flash) attention.

The LM substrate's prefill at 32K context cannot materialize (L, L) score
matrices (32768² × 2B ≈ 2 GiB per head); this kernel streams K/V blocks
through VMEM with the online-softmax recurrence, so the working set is
O(block_q · block_k) per grid step.  Matmul dims are MXU-aligned (blocks are
multiples of 128; D is the head dim).

Layout: q (Lq, H, D), k/v (Lk, H, D), grid (H, Lq/bq, Lk/bk) with the K axis
innermost and sequential (accumulation).  ``kv_offset`` shifts query
positions for decode: query i attends to keys ≤ i + kv_offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import checked_interpret

_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, kv_offset: int,
                  block_q: int, block_k: int, num_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[:, 0, :].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[:, 0, :].astype(jnp.float32)                  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0) + kv_offset
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)

    m_prev = m_ref[...]                                     # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)              # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                  # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                         # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[:, 0, :].astype(jnp.float32)                  # (bk, D)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[:, 0, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "kv_offset", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, scale=None, kv_offset=0,
                    block_q=128, block_k=128, interpret: bool):
    """See module docstring. q: (Lq, H, D); k, v: (Lk, H, D)."""
    Lq, H, D = q.shape
    Lk = k.shape[0]
    scale = float(scale) if scale is not None else D ** -0.5
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, "pad sequence to block multiples"
    nq, nk = Lq // bq, Lk // bk

    grid = (H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, kv_offset=kv_offset,
        block_q=bq, block_k=bk, num_k_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, 1, D), lambda h, i, j: (i, h, 0)),
            pl.BlockSpec((bk, 1, D), lambda h, i, j: (j, h, 0)),
            pl.BlockSpec((bk, 1, D), lambda h, i, j: (j, h, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1, D), lambda h, i, j: (i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((Lq, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=checked_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
