"""Worked walkthrough of the distributed influence-serving subsystem.

The paper scales fused BPTs across devices with sample parallelism; this
example applies the same axis to *serving*: the RRR sketch pool is sharded
over a mesh, each device reduces coverage over its local batches, and one
psum merges the partial counts.  Demonstrated end to end:

1. **Shard** a sketch pool over the mesh's ``data`` axis — slot ``i`` is
   bit-identical to what a single-device pool would hold, the mesh only
   picks which device owns it.
2. **Serve** through `DistributedQueryEngine` (one collective per coverage
   reduction) and check the answers are bit-for-bit the single-device ones.
3. **Go async**: a deadline-batched `AsyncFrontEnd` serves a burst of
   threaded clients — flush on full slot or oldest deadline — while a
   background worker refreshes stale shards between dispatches.
4. **Re-shard from a snapshot**: the manifest records the shard layout;
   restore re-slots the same batches onto a *different* mesh shape.

Runs on a laptop: 8 host CPU devices are forced before jax initializes.

    PYTHONPATH=src python examples/distributed_serve.py [--n 2000] [--k 8]
"""
import argparse
import os
import tempfile
import threading
import time

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

import jax                   # noqa: E402
import numpy as np           # noqa: E402

from repro.graph import generators                              # noqa: E402
from repro.launch.mesh import make_mesh                         # noqa: E402
from repro.serve.distributed import (AsyncFrontEnd,             # noqa: E402
                                     DistributedQueryEngine,
                                     ShardedSketchStore)
from repro.serve.influence import (MicroBatcher, PoolConfig,    # noqa: E402
                                   QueryEngine, ResultCache, SketchStore)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--deg", type=float, default=10.0)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--colors", type=int, default=64)
    ap.add_argument("--batches", type=int, default=16)
    ap.add_argument("--budget-mb", type=float, default=8.0,
                    help="PER-SHARD memory budget")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--deadline-ms", type=float, default=20.0)
    args = ap.parse_args()

    g = generators.powerlaw_cluster(args.n, args.deg, prob=(0.0, 0.25),
                                    seed=1)
    cfg = PoolConfig(num_colors=args.colors, max_batches=64,
                     memory_budget_mb=args.budget_mb, master_seed=7)

    # --- 1. shard a pool over the mesh's data axis -----------------------
    mesh = make_mesh((8,), ("data",))
    store = ShardedSketchStore(g, cfg, mesh)
    t0 = time.time()
    store.ensure(args.batches)
    print(f"sharded pool: {len(store.batches)} batches × {args.colors} "
          f"colors over {store.num_shards} shards in {time.time()-t0:.1f}s "
          f"(per-shard budget admits {store.capacity} total batches; "
          f"layout {store.shard_layout()})")

    # --- 2. distributed answers == single-device answers -----------------
    engine = DistributedQueryEngine(store)
    seeds, sigma = engine.top_k(args.k)
    single = SketchStore(g, cfg)
    single.ensure(args.batches)
    ref_seeds, ref_sigma = QueryEngine(single).top_k(args.k)
    assert np.array_equal(seeds, ref_seeds) and sigma == ref_sigma
    print(f"top-{args.k} over 8 shards: {seeds.tolist()}  σ̂={sigma:.1f}  "
          f"(bit-identical to the single-device engine)")

    # Snapshot NOW, before the async stage: its background refresh will
    # resample slots, and stage 4 asserts the restored pool reproduces
    # these exact pre-refresh answers.
    ckpt = tempfile.mkdtemp(prefix="sharded_pool_")
    store.save(ckpt)

    # --- 3. async deadline-batched serving under client threads ----------
    fe = AsyncFrontEnd(MicroBatcher(engine, cache=ResultCache()),
                       default_deadline=args.deadline_ms / 1e3,
                       refresh_every=5.0)
    rng = np.random.default_rng(0)
    futs, lock = [], threading.Lock()

    def client(q):
        f = fe.submit_sigma(q)
        with lock:
            futs.append((q, f))

    queries = [rng.integers(0, args.n, 3).tolist()
               for _ in range(args.clients)]
    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(q,)) for q in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    vals = [f.result(timeout=300) for _, f in futs]
    dt = time.perf_counter() - t0
    print(f"async: {args.clients} threaded clients in {dt:.2f}s — "
          f"{fe.stats.flushes} flushes ({fe.stats.slot_flushes} slot-full / "
          f"{fe.stats.deadline_flushes} deadline), worst queue wait "
          f"{fe.stats.max_queue_wait*1e3:.0f} ms "
          f"(deadline {args.deadline_ms:.0f} ms); mean σ̂ {np.mean(vals):.1f}")
    fe.close()

    # --- 4. restore the 8-shard snapshot under 2 shards ------------------
    mesh2 = make_mesh((2, 4), ("data", "model"))
    restored = ShardedSketchStore.restore(ckpt, g, cfg, mesh2)
    r_seeds, r_sigma = DistributedQueryEngine(restored).top_k(args.k)
    assert np.array_equal(seeds, r_seeds) and sigma == r_sigma
    print(f"elastic restore: snapshot written under "
          f"{ShardedSketchStore.saved_layout(ckpt)['num_shards']} shards, "
          f"restored under {restored.num_shards} — answers bit-identical "
          f"(manifest at {ckpt})")


if __name__ == "__main__":
    main()
