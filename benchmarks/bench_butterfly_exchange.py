"""Butterfly frontier-exchange micro-bench: log(M) pairwise stages vs the
flat model-axis all-gather, at dialed frontier densities.

The graph-parallel backend's per-level exchange has two legs
(`repro.distributed.traversal._frontier_gather_loop`): the flat
``all_gather`` always ships ``S·(S−1)·rows·W`` packed words, while the
ButterFly-BFS-style leg (arXiv 2103.13577) compacts the frontier to
``(word_idx, word)`` pairs and disseminates them over ``⌈log₂ S⌉``
``ppermute`` stages — traffic proportional to what's actually lit.  This
bench isolates ONE exchange (no traversal around it) on a forced
8-device host mesh: for each (shard count, active-word count) cell both
legs reconstruct the same global frontier (asserted bit-identical), and
the rows record measured wall time next to the analytic words moved —
the crossover the `gather_capacity_words` auto-capacity targets.

S = 6 exercises the non-power-of-two dissemination schedule (stage
overlap deduped by the ``have`` bitmap).  Runs in a subprocess so the
forced device count never leaks into the parent.  Emits the standard
``BENCH_<name>.json`` shape.
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
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.distributed import traversal
    from repro.launch.mesh import make_mesh

    rows, num_words, iters = args["rows"], args["num_words"], args["iters"]
    n = rows * num_words
    rng = np.random.default_rng(5)

    for s in args["shard_counts"]:
        mesh = make_mesh((s,), ("model",), devices=jax.devices()[:s])
        cap = traversal.gather_capacity_words(rows, num_words, 0)

        def dense_leg(fr):
            return jax.lax.all_gather(fr, "model", tiled=True)

        def butterfly_leg(fr):
            buf_i, buf_w, sent = traversal._butterfly_exchange(
                fr, "model", s, n, cap)
            full = traversal._scatter_pairs(buf_i, buf_w, rows,
                                            num_words, s)
            return full, jax.lax.psum(sent, "model")

        dense = jax.jit(jax.shard_map(dense_leg, mesh=mesh,
                                      in_specs=P("model"), out_specs=P(),
                                      check_vma=False))
        bf = jax.jit(jax.shard_map(butterfly_leg, mesh=mesh,
                                   in_specs=P("model"),
                                   out_specs=(P(), P()), check_vma=False))

        for active in args["active_words"]:
            if active > cap:
                continue        # the loop's lax.cond takes the dense leg
            # `active` lit words per shard, distinct positions, nonzero
            # payloads — the compaction's worst case for that density.
            fr = np.zeros((s, n), np.uint32)
            for i in range(s):
                pos = rng.choice(n, size=active, replace=False)
                fr[i, pos] = rng.integers(1, 2 ** 32, active,
                                          dtype=np.uint64).astype(np.uint32)
            fr = jnp.asarray(fr.reshape(s * rows, num_words))

            ref = dense(fr)
            got, sent = bf(fr)
            np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

            def clock(fn):
                jax.block_until_ready(fn(fr))
                t0 = time.perf_counter()
                for _ in range(iters):
                    jax.block_until_ready(fn(fr))
                return (time.perf_counter() - t0) / iters * 1e3

            dense_words = s * (s - 1) * n
            row = {
                "shards": s, "rows": rows, "num_words": num_words,
                "capacity_words": cap, "active_words": active,
                "dense_words": dense_words,
                "butterfly_words": int(sent),
                "traffic_ratio": round(dense_words / max(int(sent), 1), 2),
                "dense_ms": round(clock(dense), 3),
                "butterfly_ms": round(clock(lambda x: bf(x)[0]), 3),
            }
            print("ROW " + json.dumps(row), flush=True)
    print("ENV " + json.dumps({"backend": jax.default_backend(),
                               "devices": _DEVICES,
                               "jax": jax.__version__}), flush=True)


# ------------------------------------------------------------------ driver
def run(rows=4096, num_words=2, shard_counts=(8, 6),
        active_words=(64, 256, 1024), iters=10, out=print,
        json_path="BENCH_butterfly_exchange.json"):
    params = {"rows": rows, "num_words": num_words,
              "shard_counts": list(shard_counts),
              "active_words": list(active_words), "iters": iters}
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), json.dumps(params)],
        capture_output=True, text=True, env=env, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed:\n{proc.stdout}\n{proc.stderr}")
    rows_out, bench_env = [], {}
    for line in proc.stdout.splitlines():
        if line.startswith("ROW "):
            rows_out.append(json.loads(line[4:]))
        elif line.startswith("ENV "):
            bench_env = json.loads(line[4:])

    out("# butterfly exchange: shards,active_words,dense_words,"
        "butterfly_words,traffic_ratio,dense_ms,butterfly_ms")
    for r in rows_out:
        out(",".join(str(r[k]) for k in
                     ("shards", "active_words", "dense_words",
                      "butterfly_words", "traffic_ratio", "dense_ms",
                      "butterfly_ms")))

    record = {"bench": "butterfly_exchange", "schema": 1,
              "unix_time": int(time.time()), "env": bench_env,
              "params": params, "rows": rows_out}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=1)
        out(f"# wrote {json_path} ({len(rows_out)} rows)")
    return record


if __name__ == "__main__":
    if len(sys.argv) > 1:                   # worker mode: params as argv[1]
        _worker(json.loads(sys.argv[1]))
    else:
        run()
