"""Distributed serving: query throughput / latency vs shard count + deadline.

Drives the full stack — `ShardedSketchStore` → `DistributedQueryEngine` →
`MicroBatcher` → `AsyncFrontEnd` — on a forced 8-device CPU host mesh (the
same trick the multi-device equivalence tests use), sweeping the pool's
shard count and the front-end flush deadline.  A burst of threaded clients
submits σ(S) queries; per-query latency is measured submit → future-done.

The sweep runs in a **subprocess** so the forced device count never leaks
into the parent (benchmarks share a process with single-device benches).

Emits the standard ``BENCH_<name>.json`` shape (this bench defines it —
the perf trajectory starts accumulating here)::

    {"bench": ..., "schema": 1, "unix_time": ..., "env": {...},
     "params": {...}, "rows": [{...}, ...]}

Shard count on a CPU host mesh does not speed anything up (all "devices"
share the same silicon) — the point is the *trajectory*: the same rows on
a real pod plot coverage-reduction scaling, and deadline vs p50/p99 shows
the batching-latency trade straight away.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_DEVICES = 8


# ------------------------------------------------------------------ worker
def _worker(args: dict) -> None:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={_DEVICES}").strip()
    import threading

    import jax
    import numpy as np

    from repro.graph import generators
    from repro.launch.mesh import make_mesh
    from repro.serve.distributed import (AsyncFrontEnd,
                                         DistributedQueryEngine,
                                         ShardedSketchStore)
    from repro.serve.influence import MicroBatcher, PoolConfig, ResultCache

    g = generators.powerlaw_cluster(args["n"], args["deg"],
                                    prob=(0.0, 0.25), seed=11)
    n = g.num_vertices
    for shards in args["shard_counts"]:
        mesh = make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])
        store = ShardedSketchStore(
            g, PoolConfig(num_colors=args["colors"],
                          max_batches=args["batches"]), mesh)
        t0 = time.perf_counter()
        store.ensure(args["batches"])
        sample_s = time.perf_counter() - t0
        engine = DistributedQueryEngine(store)
        engine.sigma([[0]])                     # compile outside the sweep
        for deadline_ms in args["deadlines_ms"]:
            fe = AsyncFrontEnd(MicroBatcher(engine, cache=ResultCache()),
                               default_deadline=deadline_ms / 1e3)
            rng = np.random.default_rng(shards * 1000 + deadline_ms)
            queries = [rng.integers(0, n, 3).tolist()
                       for _ in range(args["clients"])]
            lats, lock = [], threading.Lock()

            def client(q):
                t0 = time.monotonic()
                fut = fe.submit_sigma(q)
                fut.result(timeout=600)
                with lock:
                    lats.append(time.monotonic() - t0)

            threads = [threading.Thread(target=client, args=(q,))
                       for q in queries]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            fe.close()
            lats_ms = np.sort(np.asarray(lats)) * 1e3
            row = {
                "shards": shards,
                "pool_batches": len(store.batches),
                "theta": store.num_samples,
                "sample_s": round(sample_s, 3),
                "deadline_ms": deadline_ms,
                "clients": args["clients"],
                "qps": round(len(lats) / wall, 1),
                "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
                "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
                "flushes": fe.stats.flushes,
                "deadline_flushes": fe.stats.deadline_flushes,
                "max_queue_wait_ms": round(fe.stats.max_queue_wait * 1e3, 1),
            }
            print("ROW " + json.dumps(row), flush=True)
    print("ENV " + json.dumps({"backend": jax.default_backend(),
                               "devices": _DEVICES,
                               "jax": jax.__version__}), flush=True)


# ------------------------------------------------------------------ driver
def run(n=800, deg=8.0, colors=64, batches=8, shard_counts=(1, 2, 4, 8),
        deadlines_ms=(5, 25), clients=48, out=print,
        json_path="BENCH_distributed_serve.json"):
    params = {"n": n, "deg": deg, "colors": colors, "batches": batches,
              "shard_counts": list(shard_counts),
              "deadlines_ms": list(deadlines_ms), "clients": clients}
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), json.dumps(params)],
        capture_output=True, text=True, env=env, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed:\n{proc.stdout}\n{proc.stderr}")
    rows, bench_env = [], {}
    for line in proc.stdout.splitlines():
        if line.startswith("ROW "):
            rows.append(json.loads(line[4:]))
        elif line.startswith("ENV "):
            bench_env = json.loads(line[4:])

    out("# distributed serve: shards,theta,deadline_ms,clients,qps,"
        "p50_ms,p99_ms,flushes,max_queue_wait_ms")
    for r in rows:
        out(",".join(str(r[k]) for k in
                     ("shards", "theta", "deadline_ms", "clients", "qps",
                      "p50_ms", "p99_ms", "flushes", "max_queue_wait_ms")))

    record = {"bench": "distributed_serve", "schema": 1,
              "unix_time": int(time.time()), "env": bench_env,
              "params": params, "rows": rows}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=1)
        out(f"# wrote {json_path} ({len(rows)} rows)")
    return record


if __name__ == "__main__":
    if len(sys.argv) > 1:                   # worker mode: params as argv[1]
        _worker(json.loads(sys.argv[1]))
    else:
        run()
