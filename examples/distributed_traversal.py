"""Distributed fused-BPT demo on 8 forced host devices.

Shows the two distribution axes of DESIGN.md §3 working together and
matching the single-device result bit-for-bit:
  * sample parallelism  — batches sharded over "data",
  * graph parallelism   — 1-D vertex partition over "model" with the
    per-level frontier all-gather,
plus the distributed greedy max-cover reduction.

    PYTHONPATH=src python examples/distributed_traversal.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.core import imm, tiles, traversal            # noqa: E402
from repro.distributed import traversal as dtrav        # noqa: E402
from repro.graph import csr, generators, partition      # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402


def main():
    print("devices:", jax.devices())
    g = generators.powerlaw_cluster(1500, 8.0, prob=0.25, seed=3)

    # --- sample parallel: 16 batches over 8 devices -----------------------
    mesh = make_mesh((8,), ("data",))
    B, C = 16, 64
    starts = jnp.stack([traversal.random_starts(jax.random.key(b),
                                                g.num_vertices, C)
                        for b in range(B)])
    seeds = jnp.arange(B, dtype=jnp.uint32)
    visited = dtrav.sample_parallel_visited(g, starts, seeds, C, mesh)
    print(f"sample-parallel: {B} batches × {C} colors = "
          f"{B*C} traversals; visited sharded as "
          f"{visited.sharding.spec}")

    seeds_sel, cov = dtrav.distributed_greedy_max_cover(visited, 5, C, mesh)
    print(f"distributed greedy: seeds={seeds_sel.tolist()} "
          f"coverage={cov:.4f}")

    # --- graph parallel: vertex partition over 'model' --------------------
    mesh2 = make_mesh((2, 4), ("data", "model"))
    g2 = csr.dedupe(g)
    ptg = partition.partition(tiles.from_graph(g2), num_shards=4)
    st = traversal.random_starts(jax.random.key(9), g2.num_vertices, C)
    vis_gp, levels = dtrav.graph_parallel_traversal(ptg, st, C, 11, mesh2)
    ref = traversal.run_fused(g2, st, C, jnp.uint32(11))
    same = bool((np.asarray(vis_gp) == np.asarray(ref.visited)).all())
    print(f"graph-parallel: {ptg.num_shards} vertex shards, "
          f"{int(levels)} levels, bit-identical to single-device: {same}")


if __name__ == "__main__":
    main()
