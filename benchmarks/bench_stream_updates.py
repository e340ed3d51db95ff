"""Streaming-delta refresh cost: churn fraction × backend →
incremental refresh seconds vs cold pool rebuild seconds.

The claim under test is the `repro.stream` design premise: after a graph
delta, refresh cost should scale with **churn** (the fraction of
`FrontierIndex` row-blocks the delta touches, which bounds the dirty
slot set) — not with |V| + |E| like the cold rebuild a static-topology
pool forces.  Two sweeps on a forced 8-device CPU host:

* ``churn`` — one graph, one warm pool per cell, deltas dialed to touch
  2% … 25% of the row-blocks (delta endpoints confined to a chosen
  block subset), under the ``dense`` single-device and
  ``data_parallel`` sharded backends.  Churn here is *row-block*
  fraction, not edge fraction: the dirty-set math is over row-blocks, so
  this is the axis the subsystem's cost curve is defined on (an
  edge-fraction dial would touch nearly every block of a power-law
  graph long before 10%).
* ``scale`` — fixed ~5% churn while |V| grows ×4: incremental seconds
  should track the (roughly constant) dirty slot count, while the cold
  rebuild grows with the graph.

Timing protocol: the initial ``ensure`` + stack staging warm every
traced program, an untimed tombstone delta warms the incremental path
(post-delta sampler build + dirty-slot resample) and supplies
resurrection targets, then the measured delta is shape-preserving
(resurrect + tombstone — the steady-state churn shape), so per churn
level the timers see

* ``incr_s`` — `stream.incremental_refresh`: graph swap + sampler
  rebuild + dirty-slot resample through the donated slot scatter;
* ``cold_s`` — a fresh store's ``ensure`` of the same batch count on
  the SAME mutated graph pair (+ its stack staging, the serving asset).

Each cell asserts the incremental pool is bit-identical — masks and
instrumented work counters — to the cold rebuild before its row is
emitted, so every recorded speedup is a *verified-equal* result.

Runs in a subprocess so the forced device count never leaks into the
parent.  Emits the standard ``BENCH_<name>.json`` shape.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_DEVICES = 8


# ------------------------------------------------------------------ worker
def _pick_edges(store, rows, count, rng, margin=64):
    """``count`` live forward-edge positions with dst in ``rows``,
    non-trailing in BOTH orientations (a tail delete in either the
    forward graph or ``g_rev`` would trim, changing the static array
    shapes the steady-state measurement wants stable)."""
    import numpy as np

    g, gr = store.graph, store.g_rev
    e, er = g.num_edges, gr.num_edges
    src = np.asarray(g.src)[:e]
    dst = np.asarray(g.dst)[:e]
    prob = np.asarray(g.prob)[:e]
    allowed = np.zeros(g.num_vertices, bool)
    allowed[rows] = True
    cand = np.nonzero((prob > 0) & allowed[dst])[0]
    cand = cand[cand < e - margin]
    rkeys = ((np.asarray(gr.src)[:er].astype(np.int64) << 32)
             | np.asarray(gr.dst)[:er].astype(np.int64))
    order = np.argsort(rkeys, kind="stable")
    want = ((dst[cand].astype(np.int64) << 32)
            | src[cand].astype(np.int64))
    rpos = order[np.searchsorted(rkeys[order], want)]
    cand = cand[rpos < er - margin]
    return rng.choice(cand, size=min(count, len(cand)), replace=False)


def _run_cell(g, cfg, mesh, churn, delta_edges, rng, make_store):
    """One (backend, churn) measurement on a fresh warm pool.

    The measured delta is the steady-state shape: edges flipping out
    (tombstone) and back in (resurrect) within the churn window.  Both
    ops keep ``num_edges``/``padded_edges``, so the timed incremental
    refresh is pure dirty-slot work — no jit recompile rides along (an
    untimed tombstone-making delta warms that path AND supplies the
    resurrection targets).  Fresh-insert deltas pay one extra recompile
    by design (static shape change) — a cost both paths share.
    """
    import numpy as np

    from repro import stream

    store = make_store(g, cfg, mesh)
    store.ensure(cfg.max_batches)
    store.visited_stack()
    tracker = stream.DirtySlotTracker.for_store(store)

    nrb = tracker.num_row_blocks
    blocks = rng.choice(nrb, size=max(1, round(churn * nrb)), replace=False)
    rows = np.concatenate([np.arange(b * tracker.tile_rows,
                                     min((b + 1) * tracker.tile_rows,
                                         g.num_vertices))
                           for b in blocks])

    # Untimed warm delta: tombstone half the churn set (also warms the
    # incremental path: post-delta sampler build + dirty-slot resample).
    k = delta_edges // 2
    out_pos = _pick_edges(store, rows, k, rng)
    src0 = np.asarray(store.graph.src)[out_pos].copy()
    dst0 = np.asarray(store.graph.dst)[out_pos].copy()
    w0 = np.asarray(store.graph.prob)[out_pos].copy()
    stream.incremental_refresh(store, tracker,
                               stream.EdgeDelta.deletes(src0, dst0))

    # Measured delta: resurrect those edges + tombstone k fresh ones.
    shapes = (store.graph.num_edges, store.graph.padded_edges,
              store.g_rev.num_edges, store.g_rev.padded_edges)
    next_pos = _pick_edges(store, rows, k, rng)
    delta = stream.EdgeDelta.concat(
        stream.EdgeDelta.inserts(src0, dst0, w0),
        stream.EdgeDelta.deletes(np.asarray(store.graph.src)[next_pos],
                                 np.asarray(store.graph.dst)[next_pos]))
    # Warm the exact dirty-slot count: the block samplers trace per block
    # SIZE (lax.map length / shard pad), so resampling this plan's slots
    # on the un-mutated graph (a semantic no-op — same streams, same
    # graph) compiles what the timed refresh will run.
    plan = stream.plan_refresh(store, tracker, delta)
    store.resample_slots(plan.dirty_slots)
    report = stream.incremental_refresh(store, tracker, delta)
    assert (store.graph.num_edges, store.graph.padded_edges,
            store.g_rev.num_edges, store.g_rev.padded_edges) == shapes

    t0 = time.perf_counter()
    cold = make_store(store.graph, cfg, mesh, g_rev=store.g_rev)
    cold.ensure(cfg.max_batches)
    cold.visited_stack()
    cold_s = time.perf_counter() - t0

    for bi, bc in zip(store.batches, cold.batches):
        np.testing.assert_array_equal(np.asarray(bi.visited),
                                      np.asarray(bc.visited))
        assert bi.fused_edge_visits == bc.fused_edge_visits
    return report, cold_s


def _worker(args: dict) -> None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_DEVICES}").strip()
    import jax
    import numpy as np

    from repro import sampling
    from repro.graph import csr, generators
    from repro.launch.mesh import make_mesh
    from repro.serve.distributed import ShardedSketchStore
    from repro.serve.influence import PoolConfig, SketchStore

    def make_store(g, cfg, mesh, g_rev=None):
        if mesh is None:
            return SketchStore(g, cfg, g_rev=g_rev)
        return ShardedSketchStore(g, cfg, mesh, g_rev=g_rev)

    for sweep in args["sweeps"]:
        g = csr.dedupe(generators.powerlaw_cluster(
            sweep["n"], sweep["deg"], prob=tuple(sweep["prob"]), seed=11))
        for backend, shards in sweep["backends"]:
            mesh = (make_mesh((shards,), ("data",))
                    if backend == "data_parallel" else None)
            spec = sampling.SamplerSpec(
                diffusion="ic", backend=backend,
                num_colors=sweep["colors"], master_seed=7,
                tile_size=sweep["tile"], frontier=sweep["frontier"])
            cfg = PoolConfig(max_batches=sweep["batches"], spec=spec)
            for churn in sweep["churn"]:
                rng = np.random.default_rng(5)
                report, cold_s = _run_cell(g, cfg, mesh, churn,
                                           sweep["delta_edges"], rng,
                                           make_store)
                row = {
                    "sweep": sweep["name"],
                    "backend": backend,
                    "n": sweep["n"],
                    "edges": g.num_edges,
                    "churn": churn,
                    "batches": sweep["batches"],
                    "colors": sweep["colors"],
                    "delta_edges": report.inserted + report.deleted,
                    "touched_row_blocks": report.touched_row_blocks,
                    "row_blocks": -(-sweep["n"] // sweep["tile"]),
                    "dirty_slots": report.dirty_slots,
                    "total_slots": report.total_slots,
                    "dirty_fraction": round(report.dirty_fraction, 4),
                    "incr_s": round(report.refresh_s, 3),
                    "cold_s": round(cold_s, 3),
                    "speedup": round(cold_s / max(report.refresh_s, 1e-9),
                                     2),
                }
                print("ROW " + json.dumps(row), flush=True)
    print("ENV " + json.dumps({"backend": jax.default_backend(),
                               "devices": _DEVICES,
                               "jax": jax.__version__}), flush=True)


# ------------------------------------------------------------------ driver
def standard_sweeps(churn_n=12000, scale_ns=(6000, 12000, 24000),
                    batches=16) -> list[dict]:
    """The two recorded sweeps (scaled down by callers like run.py).

    The cells sit in the pool's LOCALITY regime: few colors per slot and
    collapsing traversals (tiny edge probabilities), so each slot's
    visited-row-block footprint is a small fraction of the graph and a
    confined delta dirties a churn-proportional slot subset.  A
    64-colors-per-slot pool on a well-connected graph is the opposite
    regime — the union of 64 traversals covers most blocks, every delta
    dirties every slot, and incremental ≈ cold by construction (the
    subsystem is honest about that: `dirty_fraction` says so)."""
    return [
        dict(name="churn", n=churn_n, deg=16.0, prob=(0.0, 0.03),
             colors=8, tile=64, batches=batches, frontier="sparse",
             delta_edges=16, churn=[0.02, 0.05, 0.10, 0.25],
             backends=[["dense", 1], ["data_parallel", 4]]),
    ] + [
        dict(name="scale", n=n, deg=16.0, prob=(0.0, 0.03),
             colors=8, tile=64, batches=batches, frontier="sparse",
             delta_edges=16, churn=[0.05], backends=[["dense", 1]])
        for n in scale_ns
    ]


def run(sweeps=None, out=print, json_path="BENCH_stream_updates.json"):
    params = {"sweeps": [dict(s, prob=list(s["prob"]))
                         for s in (sweeps or standard_sweeps())]}
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), json.dumps(params)],
        capture_output=True, text=True, env=env, timeout=2400)
    if proc.returncode != 0:
        raise RuntimeError(f"worker failed:\n{proc.stdout}\n{proc.stderr}")
    rows, bench_env = [], {}
    for line in proc.stdout.splitlines():
        if line.startswith("ROW "):
            rows.append(json.loads(line[4:]))
        elif line.startswith("ENV "):
            bench_env = json.loads(line[4:])

    out("# stream updates: sweep,backend,n,churn,touched_row_blocks,"
        "dirty_slots,total_slots,incr_s,cold_s,speedup")
    for r in rows:
        out(",".join(str(r[k]) for k in
                     ("sweep", "backend", "n", "churn",
                      "touched_row_blocks", "dirty_slots", "total_slots",
                      "incr_s", "cold_s", "speedup")))

    record = {"bench": "stream_updates", "schema": 1,
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
