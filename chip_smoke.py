#!/usr/bin/env python3
"""On-chip smoke test of the fused-BPT main path, through the library entry
points the launcher (`repro.launch.serve_influence`) uses.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # the multi-chip path on four chips

One chip, four phases, all on a `datasets.table1_clone("web-Google")` graph
(875,713 vertices, ~5M edges at scale 1) built from ``--seed``:

1. pool     — an IC and an LT `SketchStore` on the ``dense`` backend,
              64 colors × 64 batches each (4,096 RRR sets per pool);
2. queries  — one mixed micro-batched flush (top-k, σ(S), marginal gain)
              through the compiled coverage kernel, equal bit for bit to
              the ``use_kernel=False`` jnp counts; then ``refresh(0.25)``
              (the donated slot scatter) and the same flush again;
3. imm      — `imm.run_imm` routed through the IC serving pool, θ capped
              so the pool fits in HBM; seeds equal those of
              `imm.greedy_max_cover_ref` with jnp counts;
4. kernel   — the ``kernel`` backend for IC and LT, dense and sparse
              frontier, on the largest clone scale whose tile arrays fit
              in 4 GiB; each pool bit-identical to the ``dense`` pool of the
              same seeds, and the lowered program holds ``tpu_custom_call``.

``--chips 4`` runs only the multi-chip path: a ``data_parallel``
`ShardedSketchStore` on a 4×1 mesh at full size and a ``graph_parallel`` one
on a 2×2 mesh with the sparse (butterfly) frontier exchange at the kernel
phase's size, each bit-identical to a ``dense`` pool built on one device,
with equal top-k and σ through `DistributedQueryEngine`.

Each phase prints one ``[phase] {...}`` line (wall and compile seconds,
bytes, ``device_kind``, ``peak_bytes_in_use``).  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or outside a checkout of
the repository, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import time

SRC = pathlib.Path(__file__).resolve().parent / "src"

GRAPH_SCALE = 1.0             # web-Google clone at its published size
COLORS = 64                   # one fused batch = 64 RRR sets (W = 2 words)
POOL_BATCHES = 64             # per pool: 4,096 RRR sets
K = 50                        # IMM's experimental k
EPS = 0.5                     # IMM's ε
IMM_MAX_BATCHES = 256         # θ cap ceiling: 16,384 RRR sets
KERNEL_TILE_BYTES = 4 * 2**30  # tile arrays of the kernel-phase clone
KERNEL_BATCHES = 2            # per kernel-backend pool
DP_BATCHES = 8                # data_parallel pool, two per chip
GP_BATCHES = 2                # graph_parallel pool, one per data shard
SIGMA_QUERIES = 8             # σ(S) and marginal queries per flush


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Clock:
    """Compile seconds and persistent-cache hits, from JAX's own events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Smoke:
    def __init__(self, jax, seed: int):
        self.jax = jax
        self.seed = seed
        self.device = jax.devices()[0]
        self.clock = Clock(jax)
        self.t0 = time.perf_counter()

    @contextlib.contextmanager
    def phase(self, name: str):
        info: dict = {}
        t0, c0 = time.perf_counter(), self.clock.compile_s
        yield info
        stats = self.device.memory_stats() or {}
        print("[phase] " + json.dumps({
            "phase": name,
            "wall_s": round(time.perf_counter() - t0, 3),
            "compile_s": round(self.clock.compile_s - c0, 3),
            **info,
            "device_kind": self.device.device_kind,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }), flush=True)

    # ------------------------------------------------------------ helpers
    def spec(self, diffusion: str, backend: str = "dense", **kw):
        from repro.sampling import SamplerSpec
        return SamplerSpec(diffusion=diffusion, backend=backend,
                           num_colors=COLORS, master_seed=self.seed, **kw)

    def graph(self, scale: float):
        from repro.graph import csr, datasets
        return csr.dedupe(datasets.table1_clone("web-Google", scale=scale,
                                                seed=self.seed))

    def dense_store(self, g, diffusion: str, batches: int,
                    capacity: int | None = None):
        from repro.serve.influence import PoolConfig, SketchStore
        store = SketchStore(g, PoolConfig(max_batches=capacity or batches,
                                          spec=self.spec(diffusion)))
        store.ensure(batches)
        self.jax.block_until_ready(store.visited_stack())
        return store

    @staticmethod
    def host_masks(store):
        import numpy as np
        return np.stack([np.asarray(b.visited) for b in store.batches])

    def check_roots(self, store) -> None:
        """Every RRR set holds its own root (bit c of row roots[c])."""
        import numpy as np
        for b in store.batches[:4]:
            vis = np.asarray(b.visited)
            c = np.arange(COLORS)
            bits = (vis[b.roots, c // 32] >> (c % 32).astype(np.uint32)) & 1
            check(bits.all(), f"batch {b.batch_index}: an RRR set lacks "
                              "its root")

    def check_same_pool(self, got, want, what: str) -> None:
        import numpy as np
        a, b = self.host_masks(got), self.host_masks(want)
        check(a.shape == b.shape and np.array_equal(a, b),
              f"{what}: pool differs from the dense pool of the same seeds")

    def lowered_has_kernel(self, fn, *args, **kw) -> bool:
        return "tpu_custom_call" in fn.lower(*args, **kw).as_text()

    def imm_capacity(self, g) -> int:
        """Batches an IMM pool may grow to in HBM: its masks, their stack
        and the greedy program's relayout of it (~4 × the mask bytes), next
        to one traversal's transients and the graph (~6 GiB)."""
        limit = (self.device.memory_stats() or {}).get("bytes_limit",
                                                       16 * 2**30)
        fit = int((limit - 6 * 2**30)
                  // (4 * g.num_vertices * (COLORS // 32) * 4))
        return max(min(fit, IMM_MAX_BATCHES), POOL_BATCHES)

    # ------------------------------------------------------------ one chip
    def pools(self, g) -> dict:
        """The IC pool may later grow to the IMM capacity: IMM runs
        through it."""
        stores = {}
        for diffusion in ("ic", "lt"):
            with self.phase(f"pool_{diffusion}") as info:
                store = self.dense_store(
                    g, diffusion, POOL_BATCHES,
                    self.imm_capacity(g) if diffusion == "ic" else None)
                stack = store.visited_stack()
                check(stack.shape == (POOL_BATCHES, g.num_vertices,
                                      COLORS // 32),
                      f"{diffusion} pool shape {stack.shape}")
                self.check_roots(store)
                info.update(vertices=g.num_vertices, edges=g.num_edges,
                            batches=len(store.batches),
                            rrr_sets=store.num_samples,
                            pool_bytes=int(stack.nbytes))
            stores[diffusion] = store
        return stores

    def mixed_flush(self, store, engine, ref) -> dict:
        """One micro-batched flush of top-k, σ(S) and marginal queries
        through ``engine``; every answer equal to ``ref``'s."""
        import numpy as np
        from repro.serve.influence import MicroBatcher, engine as eng

        rng = np.random.default_rng(self.seed)
        n = store.graph.num_vertices
        sigma_sets = [rng.integers(0, n, rng.integers(1, 6)).tolist()
                      for _ in range(SIGMA_QUERIES)]
        excl_sets = [rng.integers(0, n, 2).tolist()
                     for _ in range(SIGMA_QUERIES)]
        batcher = MicroBatcher(engine)
        t_top = batcher.submit_top_k(K)
        t_sig = [batcher.submit_sigma(s) for s in sigma_sets]
        t_mar = [batcher.submit_marginal(s) for s in excl_sets]
        t0 = time.perf_counter()
        res = batcher.flush()
        flush_s = time.perf_counter() - t0

        seeds, sig = res[t_top]
        r_seeds, r_sig = ref.top_k(K)
        check(np.array_equal(seeds, r_seeds) and sig == r_sig,
              "top-k through the coverage kernel != jnp counts")
        check(np.array_equal([res[t] for t in t_sig], ref.sigma(sigma_sets)),
              "σ(S) answers differ from the reference engine")
        r_mar = ref.marginal_padded(*eng.pad_queries(
            excl_sets, ref.query_slots, ref.max_seeds))
        for q, t in enumerate(t_mar):
            check(np.array_equal(res[t], r_mar[q]),
                  "marginal gains through the coverage kernel != jnp counts")
        return {"flush_s": round(flush_s, 3),
                "queries": 1 + 2 * SIGMA_QUERIES,
                "dispatches": batcher.dispatches,
                "top_k_sigma": float(sig)}

    def queries(self, store) -> None:
        import numpy as np
        from repro.core import imm
        from repro.serve.influence import QueryEngine

        engine = QueryEngine(store)                 # compiled kernel
        ref = QueryEngine(store, use_kernel=False)  # jnp popcounts
        stack = store.visited_stack()
        check(self.lowered_has_kernel(
            imm._greedy_extend_jit, stack,
            imm.initial_active(stack.shape[0], COLORS), k=K,
            use_kernel=True), "coverage kernel missing from the top-k program")
        with self.phase("queries") as info:
            info.update(self.mixed_flush(store, engine, ref))
        with self.phase("queries_after_refresh") as info:
            slots = store.refresh(0.25)
            check(np.array_equal(np.asarray(store.visited_stack()),
                                 self.host_masks(store)),
                  "refreshed stack != the refreshed batches")
            info.update(self.mixed_flush(store, engine, ref),
                        refreshed_slots=len(slots), epoch=store.epoch)

    def imm_phase(self, pool) -> None:
        """`run_imm` through the serving pool, θ capped at its capacity."""
        import numpy as np
        from repro.core import imm

        with self.phase("imm") as info:
            theta_cap = pool.capacity * COLORS
            res = imm.run_imm(pool.graph, k=K, eps=EPS, spec=pool.spec,
                              theta_cap=theta_cap, pool=pool)
            ref_seeds, ref_cov = imm.greedy_max_cover_ref(
                pool.visited_stack()[:res.num_batches], K, COLORS,
                use_kernel=False)
            check(np.array_equal(res.seeds, ref_seeds)
                  and res.coverage == ref_cov,
                  "run_imm seeds != greedy_max_cover_ref seeds")
            info.update(theta_cap=theta_cap, theta=res.theta,
                        batches=res.num_batches, coverage=res.coverage,
                        sigma_estimate=res.sigma_estimate,
                        pool_bytes=res.num_batches * pool.bytes_per_batch)

    def kernel_scale(self) -> tuple[float, int]:
        """Largest clone scale (to 1%) whose tile arrays — prob, edge id
        and the LT selection prefix, 12 B per slot — fit the budget."""
        import numpy as np
        from repro.core import tiles

        def tile_count(scale):
            g = self.graph(scale)
            e, t = g.num_edges, tiles.TILE
            nb = -(-g.num_vertices // t)
            s = np.asarray(g.src)[:e].astype(np.int64) // t
            d = np.asarray(g.dst)[:e].astype(np.int64) // t
            return len(np.unique(s * nb + d))

        max_tiles = KERNEL_TILE_BYTES // (12 * tiles.TILE ** 2)
        lo, hi = 0.001, 1.0
        for _ in range(12):
            mid = (lo * hi) ** 0.5
            lo, hi = (mid, hi) if tile_count(mid) <= max_tiles else (lo, mid)
            if hi / lo < 1.01:
                break
        return lo, tile_count(lo)

    def kernels(self, gk, scale: float, num_tiles: int) -> None:
        from repro.core import tiled_traversal
        from repro.kernels import ops
        from repro.serve.influence import PoolConfig, SketchStore

        for diffusion in ("ic", "lt"):
            dense = self.dense_store(gk, diffusion, KERNEL_BATCHES)
            for frontier in ("dense", "sparse"):
                with self.phase(f"kernel_{diffusion}_{frontier}") as info:
                    spec = self.spec(diffusion, "kernel", frontier=frontier)
                    store = SketchStore(gk, PoolConfig(
                        max_batches=KERNEL_BATCHES, spec=spec))
                    store.ensure(KERNEL_BATCHES)
                    self.jax.block_until_ready(store.batches[-1].visited)
                    self.check_same_pool(store, dense,
                                         f"kernel {diffusion}/{frontier}")
                    s = store.sampler
                    kw = dict(max_levels=spec.max_iters, use_kernel=True,
                              interpret=ops._interpret(), frontier=frontier,
                              ladder=getattr(s, "_ladder", None)
                              if frontier == "sparse" else None)
                    starts, seed = s.batch_starts(0), s.batch_seed(0)
                    if diffusion == "lt":
                        has = self.lowered_has_kernel(
                            tiled_traversal.run_fused_lt_tiled, s.tg_rev,
                            s._cb_tiles, starts, COLORS, seed, **kw)
                    else:
                        has = self.lowered_has_kernel(
                            tiled_traversal.run_fused_tiled, s.tg_rev,
                            starts, COLORS, seed, **kw)
                    check(has, f"kernel {diffusion}/{frontier}: no "
                               "tpu_custom_call in the lowered traversal")
                    tile_bytes = sum(int(a.nbytes) for a in (
                        s.tg_rev.prob, s.tg_rev.edge_id)) + (
                        int(s._cb_tiles.nbytes) if diffusion == "lt" else 0)
                    info.update(scale=scale, vertices=gk.num_vertices,
                                edges=gk.num_edges, tiles=num_tiles,
                                tile_bytes=tile_bytes,
                                batches=KERNEL_BATCHES,
                                last_grid_steps=s.last_grid_steps)
                    del store, s
                    gc.collect()
            del dense
            gc.collect()

    def one_chip(self) -> None:
        with self.phase("graph") as info:
            g = self.graph(GRAPH_SCALE)
            info.update(vertices=g.num_vertices, edges=g.num_edges)
        stores = self.pools(g)
        self.queries(stores["ic"])
        pool = stores.pop("ic")
        del stores, g
        gc.collect()
        self.imm_phase(pool)
        del pool
        gc.collect()
        with self.phase("kernel_scale") as info:
            scale, num_tiles = self.kernel_scale()
            gk = self.graph(scale)
            info.update(scale=scale, tiles=num_tiles,
                        vertices=gk.num_vertices, edges=gk.num_edges)
        self.kernels(gk, scale, num_tiles)

    # ---------------------------------------------------------- four chips
    def sharded(self, g, mesh, spec, batches: int, what: str) -> dict:
        import numpy as np
        from repro.serve.distributed import (DistributedQueryEngine,
                                             ShardedSketchStore)
        from repro.serve.influence import PoolConfig, QueryEngine

        store = ShardedSketchStore(g, PoolConfig(max_batches=batches,
                                                 spec=spec), mesh)
        store.ensure(batches)
        self.jax.block_until_ready(store.visited_stack())
        build_s = time.perf_counter()
        dense = self.dense_store(g, spec.diffusion, batches)
        build_s = time.perf_counter() - build_s
        self.check_same_pool(store, dense, what)
        d_seeds, d_sig = DistributedQueryEngine(store, use_kernel=True) \
            .top_k(K)
        r = QueryEngine(dense)
        r_seeds, r_sig = r.top_k(K)
        check(np.array_equal(d_seeds, r_seeds) and d_sig == r_sig,
              f"{what}: top-k differs from the one-device engine")
        sets = [[0, 1], [5, 50, 99]]
        check(np.array_equal(DistributedQueryEngine(store).sigma(sets),
                             r.sigma(sets)),
              f"{what}: σ(S) differs from the one-device engine")
        return {"vertices": g.num_vertices, "edges": g.num_edges,
                "batches": batches, "shards": store.num_shards,
                "row_shards": store.row_shards,
                "dense_reference_s": round(build_s, 3)}

    def four_chips(self) -> None:
        from repro.launch.mesh import make_mesh

        with self.phase("data_parallel_4x1") as info:
            g = self.graph(GRAPH_SCALE)
            info.update(self.sharded(
                g, make_mesh((4,), ("data",)),
                self.spec("ic", "data_parallel"), DP_BATCHES,
                "data_parallel 4x1"))
        del g
        gc.collect()
        with self.phase("graph_parallel_2x2_sparse") as info:
            scale, num_tiles = self.kernel_scale()
            gk = self.graph(scale)
            info.update(self.sharded(
                gk, make_mesh((2, 2), ("data", "model")),
                self.spec("ic", "graph_parallel", frontier="sparse"),
                GP_BATCHES, "graph_parallel 2x2"),
                scale=scale, tiles=num_tiles)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the multi-chip "
                         "path and the one-device pools it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph and of every RNG stream")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch import accel
    cache_dir = accel.configure()["compilation_cache_dir"]

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} wants {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    smoke = Smoke(jax, args.seed)
    print(f"[chip_smoke] {len(devices)} x {devices[0].device_kind}, "
          f"compilation cache {cache_dir}", flush=True)
    if args.chips == 4:
        smoke.four_chips()
    else:
        smoke.one_chip()
    print("[chip_smoke] " + json.dumps({
        "total_s": round(time.perf_counter() - smoke.t0, 3),
        "compile_s": round(smoke.clock.compile_s, 3),
        "compilation_cache_hits": smoke.clock.cache_hits}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
