"""Multi-device equivalence checks, executed by tests/test_distributed.py in
a subprocess with 8 forced host devices (so the main pytest process keeps a
single device).  Every check goes through the sampling facade and the
distributed query engine.  Prints "OK <name>" per passing check; any
exception fails.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np          # noqa: E402
import jax                  # noqa: E402

from repro import sampling                                 # noqa: E402
from repro.core import imm, rrr                            # noqa: E402
from repro.graph import csr, generators                    # noqa: E402
from repro.launch.mesh import make_mesh                    # noqa: E402
from repro.serve.distributed import (DistributedQueryEngine,  # noqa: E402
                                     ShardedSketchStore)
from repro.serve.influence import PoolConfig               # noqa: E402


def _assert_same(got, want):
    for a, b in zip(got, want, strict=True):
        assert a.batch_index == b.batch_index
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
        np.testing.assert_array_equal(np.asarray(a.roots),
                                      np.asarray(b.roots))


def main():
    assert len(jax.devices()) == 8, jax.devices()
    # Dedupe-clean: the graph_parallel tile layout needs parallel edges
    # merged, and every check samples the one edge list.
    g = csr.dedupe(generators.powerlaw_cluster(500, 8.0, prob=0.3, seed=2))
    g_rev = csr.transpose(g)
    C = 64
    spec = sampling.SamplerSpec(num_colors=C, master_seed=5)

    # ---- data_parallel ≡ the per-batch single-device reference ------------
    # 18 batches over 8 shards: the block pads to 24 with repeats.
    mesh = make_mesh((8,), ("data",))
    dp = sampling.make_sampler(g, spec.replace(backend="data_parallel"),
                               mesh=mesh)
    got = dp.sample_many(range(18))
    _assert_same(got, [rrr.sample_batch(g_rev, C, spec.master_seed, b)
                       for b in range(18)])
    print("OK sample_parallel")

    # ---- distributed greedy ≡ single-device greedy -------------------------
    store = ShardedSketchStore(
        g, PoolConfig(max_batches=16,
                      spec=spec.replace(backend="data_parallel")), mesh)
    store.ensure(16)
    vis = np.asarray(store.visited_stack())
    np.testing.assert_array_equal(
        vis, np.stack([np.asarray(b.visited) for b in got[:16]]))
    s_dist, sigma_dist = DistributedQueryEngine(
        store, use_kernel=False).top_k(4)
    s_one, cov_one = imm.greedy_max_cover(vis, 4, C, use_kernel=False)
    np.testing.assert_array_equal(s_dist, s_one)
    assert abs(sigma_dist - cov_one * g.num_vertices) < 1e-9
    print("OK distributed_greedy")

    # ---- graph parallel ≡ dense (coupled RNG) ------------------------------
    dense = sampling.make_sampler(g, spec).sample_many(range(4))
    gp_spec = spec.replace(backend="graph_parallel")
    gp = sampling.make_sampler(g, gp_spec,
                               mesh=make_mesh((2, 4), ("data", "model")))
    _assert_same(gp.sample_many(range(4)), dense)
    print("OK graph_parallel")

    # ---- graph parallel on a mesh with a pod axis --------------------------
    # Rows over "model", batches over "data"; the "pod" axis replicates.
    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    gp3 = sampling.make_sampler(g, gp_spec, mesh=mesh3)
    _assert_same(gp3.sample_many(range(4)), dense)
    print("OK graph_parallel_multipod")


if __name__ == "__main__":
    main()
