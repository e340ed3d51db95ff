"""Distributed fused-BPT demo on 8 forced host devices.

Shows the two distribution axes of the sampling facade working together
and matching the single-device result bit-for-bit:
  * sample parallelism  — the ``data_parallel`` backend: batches sharded
    over "data", each shard running the dense block program,
  * graph parallelism   — the ``graph_parallel`` backend: destination
    rows partitioned over "model" with the per-level frontier exchange,
plus greedy max-cover over the sharded pool with one psum per round
(`DistributedQueryEngine`).

    PYTHONPATH=src python examples/distributed_traversal.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                      # noqa: E402
import numpy as np              # noqa: E402

from repro import sampling                                   # noqa: E402
from repro.core import imm                                   # noqa: E402
from repro.graph import csr, generators                      # noqa: E402
from repro.launch.mesh import make_mesh                      # noqa: E402
from repro.serve.distributed import (DistributedQueryEngine,  # noqa: E402
                                     ShardedSketchStore)
from repro.serve.influence import PoolConfig                 # noqa: E402


def main():
    print("devices:", jax.devices())
    # Dedupe-clean, so that the tile layout of graph_parallel and the CSR
    # sweep of the other backends sample the same edge list.
    g = csr.dedupe(generators.powerlaw_cluster(1500, 8.0, prob=0.25, seed=3))
    B, C = 16, 64
    spec = sampling.SamplerSpec(num_colors=C, master_seed=9)
    dense = sampling.make_sampler(g, spec).sample_stacked(range(B))

    # --- sample parallel: 16 batches over 8 devices -----------------------
    mesh = make_mesh((8,), ("data",))
    dp = sampling.make_sampler(g, spec.replace(backend="data_parallel"),
                               mesh=mesh)
    visited = dp.sample_stacked(range(B))
    same = bool((np.asarray(visited) == np.asarray(dense)).all())
    print(f"sample-parallel: {B} batches × {C} colors = {B * C} "
          f"traversals; visited sharded as {visited.sharding.spec}; "
          f"bit-identical to single-device: {same}")

    store = ShardedSketchStore(
        g, PoolConfig(max_batches=B,
                      spec=spec.replace(backend="data_parallel")), mesh)
    store.ensure(B)
    seeds_sel, sigma = DistributedQueryEngine(store,
                                              use_kernel=False).top_k(5)
    ref, cov = imm.greedy_max_cover(dense, 5, C, use_kernel=False)
    print(f"distributed greedy: seeds={seeds_sel.tolist()} "
          f"sigma={sigma:.1f}; same seeds as one device: "
          f"{np.array_equal(seeds_sel, ref)}")

    # --- graph parallel: vertex partition over 'model' --------------------
    mesh2 = make_mesh((2, 4), ("data", "model"))
    gp = sampling.make_sampler(g, spec.replace(backend="graph_parallel"),
                               mesh=mesh2)
    vis_gp = gp.sample_stacked(range(4))
    same = bool((np.asarray(vis_gp) == np.asarray(dense[:4])).all())
    print(f"graph-parallel: {gp.ptg.num_shards} vertex shards × "
          f"{gp.data_shards} batch shards, bit-identical to "
          f"single-device: {same}")


if __name__ == "__main__":
    main()
