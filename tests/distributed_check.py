"""Multi-device equivalence checks, executed by tests/test_distributed.py in
a subprocess with 8 forced host devices (so the main pytest process keeps a
single device).  Prints "OK <name>" per passing check; any exception fails.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np          # noqa: E402
import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402

from repro.core import imm, rrr, tiles, traversal          # noqa: E402
from repro.distributed import traversal as dtrav           # noqa: E402
from repro.graph import csr, generators, partition         # noqa: E402
from repro.launch.mesh import make_mesh                    # noqa: E402


def main():
    assert len(jax.devices()) == 8, jax.devices()
    g = generators.powerlaw_cluster(500, 8.0, prob=0.3, seed=2)

    # ---- sample parallel ≡ per-batch single-device -------------------------
    mesh = make_mesh((8,), ("data",))
    B, C = 16, 64
    starts = jnp.stack([
        traversal.random_starts(jax.random.key(b), g.num_vertices, C)
        for b in range(B)])
    seeds = jnp.asarray([int(rrr.batch_seed(5, b)) for b in range(B)],
                        jnp.uint32)
    vis_dist = dtrav.sample_parallel_visited(g, starts, seeds, C, mesh)
    for b in range(B):
        res = traversal.run_fused(g, starts[b], C, seeds[b])
        np.testing.assert_array_equal(np.asarray(vis_dist[b]),
                                      np.asarray(res.visited))
    print("OK sample_parallel")

    # ---- distributed greedy ≡ single-device greedy -------------------------
    s_dist, cov_dist = dtrav.distributed_greedy_max_cover(vis_dist, 4, C, mesh)
    s_one, cov_one = imm.greedy_max_cover(vis_dist, 4, C, use_kernel=False)
    np.testing.assert_array_equal(s_dist, s_one)
    assert abs(cov_dist - cov_one) < 1e-12
    print("OK distributed_greedy")

    # ---- graph parallel ≡ single-device (coupled RNG) ----------------------
    mesh2 = make_mesh((2, 4), ("data", "model"))
    g2 = csr.dedupe(g)
    tg = tiles.from_graph(g2)
    ptg = partition.partition(tg, num_shards=4)
    st = traversal.random_starts(jax.random.key(3), g2.num_vertices, C)
    vis_gp, levels = dtrav.graph_parallel_traversal(ptg, st, C, 17, mesh2)
    res_single = traversal.run_fused(g2, st, C, jnp.uint32(17))
    np.testing.assert_array_equal(np.asarray(vis_gp),
                                  np.asarray(res_single.visited))
    assert int(levels) == int(res_single.stats.levels_run)
    print("OK graph_parallel")

    # ---- graph parallel on a mesh slice with pod axis ----------------------
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    ptg2 = partition.partition(tg, num_shards=2)
    vis_gp2, _ = dtrav.graph_parallel_traversal(ptg2, st, C, 17, mesh3)
    np.testing.assert_array_equal(np.asarray(vis_gp2),
                                  np.asarray(res_single.visited))
    print("OK graph_parallel_multipod")


if __name__ == "__main__":
    main()
