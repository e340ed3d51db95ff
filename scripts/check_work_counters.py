"""CI guard: sparse-frontier work counters == dense, deterministically.

The sparse engine's claim is *work proportionality without changing the
work accounting*: `TraversalStats.fused_edge_visits` counts edges whose
source row carries an active color, and every such edge lives in an
active (hence gathered) tile — so sparse and dense must agree EXACTLY,
per batch, on a fixed graph.  Counter equality is deterministic (same
counter RNG, same int32 arithmetic), so this can gate CI without flaking
the way a wall-clock threshold would.

The second section makes the same deterministic claim for the Pallas
kernel grid: on a low-occupancy graph (frontier collapses after the
first levels), the `kernel` backend's sparse-frontier grid must produce
bit-identical visited masks to the dense reference for BOTH diffusions
while running STRICTLY fewer grid steps (`Sampler.last_grid_steps`, the
Σ-of-rung-capacities counter) than the dense grid's
``levels · num_tiles`` — work proportionality of the kernel launch
itself, not just of the jnp oracle.

Run from the repo root (ci.sh does):

    PYTHONPATH=src python scripts/check_work_counters.py
"""
from __future__ import annotations

import numpy as np

from repro import sampling
from repro.graph import csr, generators


def check_sparse_counters() -> None:
    g = csr.dedupe(generators.powerlaw_cluster(500, 6.0, prob=(0.05, 0.3),
                                               seed=17))
    spec = sampling.SamplerSpec(num_colors=64, master_seed=9)
    dense = sampling.make_sampler(g, spec)
    sparse = sampling.make_sampler(g, spec.replace(frontier="sparse"))
    for bi in range(4):
        a, b = dense.sample(bi), sparse.sample(bi)
        assert a.fused_edge_visits >= 0, "dense batch not instrumented"
        if (a.fused_edge_visits != b.fused_edge_visits
                or a.unfused_edge_visits != b.unfused_edge_visits):
            raise SystemExit(
                f"work-counter mismatch at batch {bi}: dense "
                f"(fused={a.fused_edge_visits}, "
                f"unfused={a.unfused_edge_visits}) vs sparse "
                f"(fused={b.fused_edge_visits}, "
                f"unfused={b.unfused_edge_visits})")
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    print(f"[check_work_counters] OK: 4 batches, sparse == dense "
          f"(fused={a.fused_edge_visits}, unfused={a.unfused_edge_visits} "
          "at batch 3)")


def check_kernel_grid() -> None:
    # Low-occupancy graph (the BENCH low_occupancy regime, sized for
    # interpret-mode kernels): most levels touch a fraction of the tiles.
    g = csr.dedupe(generators.powerlaw_cluster(400, 8.0, prob=(0.0, 0.05),
                                               seed=17))
    for diffusion in ("ic", "lt"):
        spec = sampling.SamplerSpec(diffusion=diffusion, backend="kernel",
                                    num_colors=64, master_seed=9,
                                    tile_size=32)
        ref = sampling.make_sampler(g, spec.replace(backend="dense"))
        kern = sampling.make_sampler(g, spec)
        ksp = sampling.make_sampler(g, spec.replace(frontier="sparse"))
        for bi in range(2):
            a = np.asarray(ref.sample(bi).visited)
            kb = kern.sample(bi)
            b = np.asarray(kb.visited)
            dense_steps = kern.last_grid_steps
            c = np.asarray(ksp.sample(bi).visited)
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
            assert dense_steps == kb.levels * kern.tg_rev.num_tiles
            if not 0 < ksp.last_grid_steps < dense_steps:
                raise SystemExit(
                    f"kernel sparse grid not work-proportional at "
                    f"({diffusion}, batch {bi}): sparse "
                    f"{ksp.last_grid_steps} vs dense {dense_steps} steps")
        print(f"[check_work_counters] OK: {diffusion} kernel grid "
              f"bit-identical, sparse {ksp.last_grid_steps} < dense "
              f"{dense_steps} grid steps at batch 1")


def main() -> None:
    check_sparse_counters()
    check_kernel_grid()


if __name__ == "__main__":
    main()
