"""Kernel micro-benchmarks: tile expansion + coverage + flash attention.

Interpret-mode wall times are NOT TPU times; reported per-call to track
relative regressions, alongside the analytic VMEM working set and FLOPs
per tile that the §Roofline BlockSpec reasoning uses.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import tiles, traversal
from repro.graph import csr
from repro.kernels import coverage, flash_attention, fused_expand, ops


def _time(fn, reps=3):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def run(out=print):
    out("# kernels: name,config,us_per_call,notes")
    rng = np.random.default_rng(0)
    rows = []

    # fused_expand over a 300-tile graph, 64 colors
    n, e = 2000, 16000
    src = rng.integers(0, n, e)
    dst = (src + 1 + rng.integers(0, n - 1, e)) % n
    g = csr.from_edges(src, dst, np.full(e, 0.3, np.float32), n,
                       dedupe=True)
    tg = tiles.from_graph(g)
    starts = traversal.random_starts(jax.random.key(0), n, 64)
    fr = tiles.pad_mask_rows(traversal.init_frontier(n, 64, starts),
                             tg.padded_vertices)
    t = _time(lambda: fused_expand.fused_expand(
        tg.prob, tg.edge_id, tg.tile_src, tg.tile_dst, tg.first_of_dst,
        fr, fr, jnp.uint32(1), jnp.uint32(0), interpret=ops._interpret()))
    vmem_kb = (2 * 128 * 128 * 4 + 3 * 128 * 2 * 4) / 1024
    row = ("fused_expand", f"tiles={tg.num_tiles},W=2",
           round(1e6 * t, 1), f"vmem_tile={vmem_kb:.0f}KiB")
    rows.append(row)
    out(",".join(str(x) for x in row))

    vis = jnp.asarray(rng.integers(0, 2**32, (4096, 16), dtype=np.uint32))
    act = jnp.asarray(rng.integers(0, 2**32, (16,), dtype=np.uint32))
    t = _time(lambda: coverage.cover_counts(vis, act,
                                            interpret=ops._interpret()))
    row = ("cover_counts", "V=4096,W=16", round(1e6 * t, 1),
           "popcount-SWAR")
    rows.append(row)
    out(",".join(str(x) for x in row))

    q = jax.random.normal(jax.random.key(1), (512, 4, 64), jnp.float32)
    t = _time(lambda: flash_attention.flash_attention(
        q, q, q, causal=True, interpret=ops._interpret()))
    row = ("flash_attention", "L=512,H=4,D=64", round(1e6 * t, 1),
           f"flops={2*2*512*512*4*64/1e6:.0f}MF")
    rows.append(row)
    out(",".join(str(x) for x in row))
    return rows


if __name__ == "__main__":
    run()
