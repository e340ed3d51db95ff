"""Pool build throughput: backend × frontier mode × diffusion →
batches/sec, with the work counters that make sparse-frontier savings
measurable (not vibes).

Two sweeps over a sketch-pool build on a forced 8-device CPU host mesh
(the multi-device test-suite trick):

* ``low_occupancy`` — the standard sparse-frontier sweep: a graph whose
  unified frontier collapses after the first couple of levels (paper
  Fig. 9), where the dense sweep's every-edge-every-level cost is pure
  waste.  Backends ``dense`` and ``data_parallel``, each under
  ``frontier="dense"`` and ``"sparse"`` — same bits, different work.
* ``graph_parallel`` — the 2-D (data × model) mesh cells on a smaller
  graph (per-level frontier all-gathers on forced host devices are
  collective-bound, so the big graph would measure the CPU's psum, not
  the build mechanics), with its dense-backend reference alongside.
* ``kernel_interpret`` — the Pallas-kernel cells: single-device
  ``kernel`` backend rows and ``graph_parallel_kernel`` rows (the
  ``graph_parallel`` backend with ``REPRO_GP_KERNEL=1``, i.e. each
  shard's tile expansion through the kernels).  On CPU CI the kernels
  run in **interpret mode**, which emulates the grid tile-by-tile — the
  timings record the mechanics (and the bit-identity assertion versus
  the dense reference), not accelerator throughput, so this sweep is
  sized small enough for emulation.  On a real TPU/GPU host the same
  rows record compiled-kernel numbers.

Timing protocol (steady state, the serving regime): the cold ``ensure``
+ stack staging warm every program, then

* ``build_s``    — ``refresh(1.0)``: a WARM full-pool block resample
                   (every slot redrawn at fresh batch indices + the whole
                   stack rewritten in place);
* ``refresh_s``  — ``refresh(0.25)``: the launcher's default epoch
                   refresh, after one warm-up at that block size.  The
                   donated-buffer slot scatter (`sketch_store._set_slots`)
                   keeps the pool allocation — refresh cost is the
                   fraction's sampling, not a pool re-stage (the old
                   ``refresh_s ≈ build_s`` pathology).

Every cell runs the SAME ensure/refresh sequence, so all cells of a
(sweep, diffusion) hold bit-identical pools at the end — asserted.

Per row: ``fused_edge_visits`` (summed over the final pool's instrumented
batches; -1 where the backend doesn't instrument), ``active_tile_frac``
(mean per-level fraction of active source row-blocks from
`core.sparse.profile_traversal` — the Fig. 9 quantity sparse execution
exploits; identical for dense and sparse rows by construction), the 2-D
residency observables ``visited_rows_device`` / ``pool_mib_device``
(V/M rows per device when the pool is row-sharded over the model axis),
and — graph_parallel cells only — ``gather_words_level``: the packed
words the last refresh block moved over the model axis per traversal
level.  Dense-frontier rows record the flat all-gather's
``S·(S−1)·rows·W`` per level; sparse rows record the ButterFly-style
log(M) pairwise exchange where the compacted frontier fits
(`gather_capacity_words`) and the dense fallback where it doesn't —
the words saved per collapsed tail level, measured not claimed.

``active_grid_frac`` is the tile-backend analogue: for ``kernel`` (and
``tiled``) rows, the last sampled batch's kernel grid steps over the
dense grid's ``levels · num_tiles`` — exactly 1.0 under
``frontier="dense"``, strictly below 1.0 when the sparse frontier
compacts the grid to the active source tiles (-1 where the backend does
not run a tile grid).

Runs in a **subprocess** so the forced device count never leaks into the
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
def _mean_active_tile_frac(g, diffusion: str, colors: int, tile: int,
                           master_seed: int) -> float:
    """Mean per-level active source row-block fraction of batch 0."""
    import numpy as np

    from repro.core import lt, rrr, sparse
    from repro.graph import csr

    g_rev = csr.transpose(g)
    cb = None
    if diffusion == "lt":
        g_rev = lt.normalize_lt_weights(g_rev)
        cb = lt.selection_cum_before(g_rev)
    fidx = sparse.build_frontier_index(g_rev, tile_rows=tile, cb=cb)
    starts = rrr.batch_starts(g.num_vertices, colors, master_seed, 0)
    prof = sparse.profile_traversal(fidx, starts, colors,
                                    rrr.batch_seed(master_seed, 0),
                                    diffusion=diffusion)
    fracs = [r["active_row_blocks"] / fidx.num_row_blocks for r in prof]
    return float(np.mean(fracs)) if fracs else 0.0


def _worker(args: dict) -> None:
    # One-stop accelerator config: latency-hiding XLA flags (GPU) plus the
    # forced host-device shim (CPU CI) — before jax's backend materializes.
    from repro.launch import accel
    accel.configure(host_devices=_DEVICES)
    import jax
    import numpy as np

    from repro import sampling
    from repro.graph import csr, generators
    from repro.launch.mesh import make_mesh
    from repro.serve.distributed import ShardedSketchStore
    from repro.serve.influence import PoolConfig, SketchStore

    for sweep in args["sweeps"]:
        # Dedupe once per sweep: tile layouts need parallel edges merged,
        # and bit-identity needs one shared edge list across backends.
        g = csr.dedupe(generators.powerlaw_cluster(
            sweep["n"], sweep["deg"], prob=tuple(sweep["prob"]), seed=11))
        # (row label, SamplerSpec backend, mesh shape, REPRO_GP_KERNEL):
        # graph_parallel_kernel is the same backend as graph_parallel with
        # the per-shard Pallas kernel leg armed via the env knob.
        cells = [("dense", "dense", (1, 1), False)]
        if sweep.get("kernel_cells"):
            cells.append(("kernel", "kernel", (1, 1), False))
        cells += [("data_parallel", "data_parallel", (s, 1), False)
                  for s in sweep["shard_counts"]]
        for dm in sweep["gp_mesh_shapes"]:
            cells.append(("graph_parallel", "graph_parallel",
                          tuple(dm), False))
            if sweep.get("gp_kernel"):
                cells.append(("graph_parallel_kernel", "graph_parallel",
                              tuple(dm), True))

        for diffusion in sweep["diffusions"]:
            tile_frac = _mean_active_tile_frac(
                g, diffusion, sweep["colors"], sweep["tile"], 7)
            ref_store = None
            for label, backend, (d, m), gp_kernel in cells:
                for frontier in sweep["frontiers"]:
                    if gp_kernel:
                        os.environ["REPRO_GP_KERNEL"] = "1"
                    else:
                        os.environ.pop("REPRO_GP_KERNEL", None)
                    spec = sampling.SamplerSpec(
                        diffusion=diffusion, backend=backend,
                        num_colors=sweep["colors"], master_seed=7,
                        tile_size=sweep["tile"], frontier=frontier)
                    cfg = PoolConfig(max_batches=sweep["batches"], spec=spec)
                    if backend == "dense":
                        store = SketchStore(g, cfg)
                    else:
                        devs = np.array(jax.devices()[: d * m])
                        mesh = (make_mesh((d, m), ("data", "model"),
                                          devices=devs)
                                if m > 1 else
                                make_mesh((d,), ("data",), devices=devs))
                        store = ShardedSketchStore(g, cfg, mesh)
                    # Cold build compiles every program; stack staging
                    # arms the in-place refresh path.
                    store.ensure(sweep["batches"])
                    store.visited_stack()
                    t0 = time.perf_counter()
                    store.refresh(1.0)               # warm full resample
                    build_s = time.perf_counter() - t0
                    store.refresh(0.25)              # warm the 1/4 block
                    t0 = time.perf_counter()
                    store.refresh(0.25)              # steady-state epoch
                    refresh_s = time.perf_counter() - t0

                    if ref_store is None:
                        ref_store = store    # dense/dense row IS the ref
                    for a, b in zip(ref_store.batches, store.batches):
                        np.testing.assert_array_equal(
                            np.asarray(a.visited), np.asarray(b.visited))
                    visits = [b.fused_edge_visits for b in store.batches]
                    # 2-D observables: per-device visited-row residency
                    # (V/M rows when the pool is row-sharded over the
                    # model axis) and, for graph_parallel cells, the
                    # packed words the LAST refresh block moved over the
                    # model axis per level (dense rows record the flat
                    # all-gather, sparse rows the butterfly/dense mix —
                    # same refresh sequence, so rows are comparable).
                    m_rows = getattr(store, "row_shards", 1)
                    vis_rows = (getattr(store, "padded_vertices",
                                        g.num_vertices) // m_rows)
                    pool_mib = (store.bytes_per_batch
                                * getattr(store, "padded_batches",
                                          sweep["batches"])
                                / getattr(store, "num_shards", 1)
                                / m_rows / 2 ** 20)
                    gw = getattr(store.sampler, "last_gather_words", None)
                    if gw is not None:
                        lv = np.asarray(gw).sum(0)
                        last = (int(np.max(np.nonzero(lv)[0])) + 1
                                if lv.any() else 0)
                        gw_levels = [int(x) for x in lv[:last]]
                    else:
                        gw_levels = []
                    # Kernel/tiled rows: last batch's grid steps over the
                    # dense grid (1.0 dense frontier, < 1.0 sparse).
                    smp = store.sampler
                    agf = -1.0
                    if getattr(smp, "last_levels", 0) and \
                            hasattr(smp, "last_grid_steps"):
                        agf = (smp.last_grid_steps
                               / (smp.last_levels * smp.tg_rev.num_tiles))
                    row = {
                        "sweep": sweep["name"],
                        "diffusion": diffusion,
                        "backend": label,
                        "frontier": frontier,
                        "mesh": f"{d}x{m}",
                        "shards": getattr(store, "num_shards", 1),
                        "batches": sweep["batches"],
                        "colors": sweep["colors"],
                        "build_s": round(build_s, 3),
                        "batches_per_s": round(
                            sweep["batches"] / max(build_s, 1e-9), 2),
                        "refresh_s": round(refresh_s, 3),
                        "fused_edge_visits": (sum(visits)
                                              if min(visits) >= 0 else -1),
                        "active_tile_frac": round(tile_frac, 4),
                        "active_grid_frac": round(agf, 4),
                        "visited_rows_device": vis_rows,
                        "pool_mib_device": round(pool_mib, 3),
                        "gather_words_level": gw_levels,
                        "gather_words": sum(gw_levels),
                    }
                    print("ROW " + json.dumps(row), flush=True)
    print("ENV " + json.dumps({"backend": jax.default_backend(),
                               "devices": _DEVICES,
                               "jax": jax.__version__}), flush=True)


# ------------------------------------------------------------------ driver
def standard_sweeps(low_n=6000, gp_n=1200, batches=16) -> list[dict]:
    """The two recorded sweeps (scaled down by callers like run.py).

    ``batches`` is 4× the data_parallel shard count so a quarter-refresh
    still fills every shard (a 2-batch refresh padded to 4 shards would
    do build-half work for a quarter of the slots and skew the ratio)."""
    return [
        dict(name="low_occupancy", n=low_n, deg=16.0, prob=(0.0, 0.05),
             colors=64, tile=64, batches=batches,
             diffusions=["ic", "lt"], frontiers=["dense", "sparse"],
             shard_counts=[4], gp_mesh_shapes=[]),
        dict(name="graph_parallel", n=gp_n, deg=8.0, prob=(0.0, 0.1),
             colors=64, tile=64, batches=max(batches // 2, 8),
             diffusions=["ic", "lt"], frontiers=["dense", "sparse"],
             shard_counts=[], gp_mesh_shapes=[(2, 4)]),
        # Sized for CPU interpret-mode kernel emulation (~170 tiles): the
        # kernel rows record mechanics + bit-identity there, compiled
        # numbers on a real accelerator.
        dict(name="kernel_interpret", n=max(gp_n * 2 // 3, 400), deg=6.0,
             prob=(0.0, 0.1), colors=64, tile=64,
             batches=max(batches // 2, 8),
             diffusions=["ic", "lt"], frontiers=["dense", "sparse"],
             shard_counts=[], gp_mesh_shapes=[(2, 2)],
             kernel_cells=True, gp_kernel=True),
    ]


def run(sweeps=None, out=print, json_path="BENCH_pool_build.json"):
    params = {"sweeps": [dict(s, prob=list(s["prob"]),
                              gp_mesh_shapes=[list(dm) for dm
                                              in s["gp_mesh_shapes"]])
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

    out("# pool build: sweep,diffusion,backend,frontier,mesh,build_s,"
        "batches_per_s,refresh_s,fused_edge_visits,active_tile_frac,"
        "active_grid_frac,visited_rows_device,pool_mib_device,gather_words")
    for r in rows:
        out(",".join(str(r[k]) for k in
                     ("sweep", "diffusion", "backend", "frontier", "mesh",
                      "build_s", "batches_per_s", "refresh_s",
                      "fused_edge_visits", "active_tile_frac",
                      "active_grid_frac", "visited_rows_device",
                      "pool_mib_device", "gather_words")))

    record = {"bench": "pool_build", "schema": 4,
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
