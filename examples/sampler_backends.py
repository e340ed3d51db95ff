"""Unified Sampler API demo: one pool, every backend, identical bits.

Builds the SAME sketch pool under the dense single-device backend and the
shard_map ``data_parallel`` backend (8 forced host devices), verifies the
pools are bit-identical slot for slot (the facade's cross-backend RNG
contract), serves identical top-k answers from both, and reports the
build-time comparison.  Also shows the LT diffusion riding the same spec,
and the ``graph_parallel`` backend on a 2-D (data × model) mesh — the
graph's rows sharded across devices — producing the same bits again.

    PYTHONPATH=src python examples/sampler_backends.py
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import time                     # noqa: E402

import jax                      # noqa: E402
import numpy as np              # noqa: E402

from repro import sampling      # noqa: E402
from repro.graph import generators                          # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.serve.distributed import (DistributedQueryEngine,    # noqa: E402
                                     ShardedSketchStore)
from repro.serve.influence import (PoolConfig, QueryEngine,     # noqa: E402
                                   SketchStore)


def main():
    print("devices:", jax.devices())
    # Dedupe once for every backend: the graph_parallel tile layout needs
    # parallel edges merged, and bit-identity needs one shared edge list.
    from repro.graph import csr
    g = csr.dedupe(generators.powerlaw_cluster(1000, 8.0, prob=0.25, seed=3))
    mesh = make_mesh((8,), ("data",))
    batches, colors = 16, 64

    # One spec per backend — everything else identical.
    dense_spec = sampling.SamplerSpec(diffusion="ic", backend="dense",
                                      num_colors=colors, master_seed=42)
    dp_spec = dense_spec.replace(backend="data_parallel")

    stores = {}
    for name, spec in (("dense", dense_spec), ("data_parallel", dp_spec)):
        cfg = PoolConfig(max_batches=batches, spec=spec)
        store = (ShardedSketchStore(g, cfg, mesh)
                 if name == "data_parallel" else SketchStore(g, cfg))
        store.ensure(1)                          # compile outside the timing
        t0 = time.perf_counter()
        store.ensure(batches)
        dt = time.perf_counter() - t0
        stores[name] = (store, dt)
        print(f"{name:>14}: built {batches} batches × {colors} colors "
              f"in {dt:.2f}s ({(batches - 1) / dt:.1f} batches/s)")

    # --- bit identity: the mesh only decides WHERE a slot is computed ------
    (s_dense, t_dense), (s_dp, t_dp) = stores["dense"], stores["data_parallel"]
    for a, b in zip(s_dense.batches, s_dp.batches):
        assert a.batch_index == b.batch_index
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    print(f"bit-identity: {batches} slots identical across backends "
          f"(dense {t_dense:.2f}s vs shard_map block {t_dp:.2f}s on a "
          "shared-silicon CPU mesh — the ratio is the pod trajectory)")

    # --- identical answers, single-device vs distributed engine ------------
    k = 5
    seeds1, sig1 = QueryEngine(s_dense).top_k(k)
    seeds8, sig8 = DistributedQueryEngine(s_dp).top_k(k)
    assert np.array_equal(seeds1, seeds8) and sig1 == sig8
    print(f"top-{k}: seeds={seeds8.tolist()} σ̂={sig8:.1f} "
          "(bit-identical on both engines)")

    # --- graph parallel: rows over 'model', batches over 'data' ------------
    mesh2d = make_mesh((4, 2), ("data", "model"))
    gp_store = ShardedSketchStore(
        g, PoolConfig(max_batches=batches,
                      spec=dense_spec.replace(backend="graph_parallel")),
        mesh2d)
    gp_store.ensure(batches)
    for a, b in zip(s_dense.batches, gp_store.batches):
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    gp_seeds, gp_sig = DistributedQueryEngine(gp_store).top_k(k)
    assert np.array_equal(seeds1, gp_seeds) and sig1 == gp_sig
    print(f"graph_parallel: rows sharded 2-way, batches 4-way — pool and "
          f"top-{k} still bit-identical (σ̂={gp_sig:.1f})")

    # --- sparse frontier: same bits, work-proportional levels --------------
    # Its regime is a LOW-occupancy frontier (paper Fig. 9: activity
    # collapses after a couple of levels) — the demo graph above is
    # dense-frontier by construction, so sparse shows ~1× there.
    g_lo = csr.dedupe(generators.powerlaw_cluster(4000, 16.0,
                                                  prob=(0.0, 0.05), seed=3))
    lo_spec = dense_spec.replace(tile_size=64)
    sp = sampling.make_sampler(g_lo, lo_spec.replace(frontier="sparse"))
    dn = sampling.make_sampler(g_lo, lo_spec)
    idx = list(range(batches, 2 * batches))
    # Warm with a same-shaped block: jit caches key on the block shape.
    sp.sample_many(list(range(batches)))
    dn.sample_many(list(range(batches)))
    t0 = time.perf_counter(); got = sp.sample_many(idx)
    t_sp = time.perf_counter() - t0
    t0 = time.perf_counter(); ref = dn.sample_many(idx)
    t_dn = time.perf_counter() - t0
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
        assert a.fused_edge_visits == b.fused_edge_visits
    print(f"sparse frontier: {batches} fused batches in {t_sp:.2f}s vs "
          f"dense {t_dn:.2f}s ({t_dn / max(t_sp, 1e-9):.1f}×) — masks AND "
          "work counters bit-identical")

    # --- LT rides the same spec --------------------------------------------
    lt_store = ShardedSketchStore(
        g, PoolConfig(max_batches=batches,
                      spec=dp_spec.replace(diffusion="lt")), mesh)
    lt_store.ensure(8)
    lt_seeds, lt_sig = DistributedQueryEngine(lt_store).top_k(k)
    print(f"LT top-{k}: seeds={lt_seeds.tolist()} σ̂={lt_sig:.1f} "
          "(same facade, diffusion='lt')")


if __name__ == "__main__":
    main()
