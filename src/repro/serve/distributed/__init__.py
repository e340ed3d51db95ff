"""Distributed influence-query serving: sharded pools, collective coverage
reduction, async deadline-batched front-end.

Layers over `repro.serve.influence` (which stays the single-device path):

* `ShardedSketchStore` — RRR sketch slots sharded over a mesh axis,
  bit-identical per slot to a single-device pool, per-shard memory
  budgets, elastic manifest restore onto any mesh shape.
* `DistributedQueryEngine` — shard_map query programs; each device reduces
  coverage over its local batches, ONE psum merges the partial counts, and
  greedy argmax runs on the replicated merged counts so shards agree with
  no second collective.  Drop-in for `QueryEngine` under `MicroBatcher`.
* `AsyncFrontEnd` — thread-safe request queue with futures, flush on full
  slot OR oldest-request deadline, background epoch refresh serialized
  with dispatch.

    mesh   = repro.launch.mesh.make_mesh((8,), ("data",))
    store  = ShardedSketchStore(graph, PoolConfig(num_colors=64), mesh)
    store.ensure(16)
    fe = AsyncFrontEnd(MicroBatcher(DistributedQueryEngine(store),
                                    cache=ResultCache()),
                       default_deadline=0.02, refresh_every=30.0)
    sigma = fe.submit_sigma([3, 17, 42]).result()
"""
from repro.serve.distributed.engine import DistributedQueryEngine
from repro.serve.distributed.frontend import AsyncFrontEnd, FrontEndStats
from repro.serve.distributed.sharded_store import ShardedSketchStore

__all__ = ["AsyncFrontEnd", "DistributedQueryEngine", "FrontEndStats",
           "ShardedSketchStore"]
