"""Influence-query serving launcher: sample a sketch pool, serve queries.

    python -m repro.launch.serve_influence --smoke
    python -m repro.launch.serve_influence --smoke --diffusion lt
    python -m repro.launch.serve_influence --smoke --sampler-backend kernel
    python -m repro.launch.serve_influence --smoke --mesh 8x1 --async
    python -m repro.launch.serve_influence --smoke --mesh 2x4 \
        --sampler-backend graph_parallel

``--diffusion ic|lt`` and ``--sampler-backend dense|tiled|kernel|
data_parallel|graph_parallel`` select the `repro.sampling.SamplerSpec` the
pool samples under; ``--frontier sparse`` arms the sparse-frontier
execution mode (per-level active-tile compaction, and on graph_parallel a
compacted frontier all-gather — bit-identical to dense, work proportional
to the live frontier; ``--frontier-capacity`` tunes its buckets).  Backend defaults: ``dense`` single-device; on a
``--mesh DxM`` mesh, ``data_parallel`` when M == 1 (shard_map batch blocks,
each shard's slots built on its own devices) and **graph parallelism when
M > 1**: the graph's destination rows shard over the ``model`` axis (size
M), batches over ``data`` (size D), with a frontier all-gather per level —
the regime for graphs too big for one device.

Single-device smoke exercises the full pool lifecycle on a synthetic
graph: sample → serve a mixed micro-batched query load (top-k, σ(S),
marginal-gain) → refresh an epoch → persist → restore bit-identically →
cross-check that offline ``run_imm`` routed through the shared incremental
max-cover kernel and the pool reproduces the pool-less seeds exactly.

``--mesh DxM`` serves from a mesh-sharded pool through the distributed
engine (slots sharded over the ``data`` axis, one psum per coverage
reduction).  Under ``JAX_PLATFORMS=cpu`` the launcher forces that many
host CPU devices — the same trick the multi-device equivalence tests use —
so the full distributed path smokes on a laptop; anywhere else the mesh is
built from the real devices, and too few of them is an error.
``--async`` fronts the batcher with the deadline-batched `AsyncFrontEnd`
and drives it from concurrent client threads.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import threading
import time

import numpy as np

from repro.core import imm
from repro.graph import csr, generators
from repro.launch.mesh import make_mesh
from repro.sampling import SamplerSpec
from repro.serve.influence import (MicroBatcher, PoolConfig, QueryEngine,
                                   ResultCache, SketchStore)


def _parse_mesh(spec: str) -> tuple[int, int]:
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise SystemExit(f"--mesh wants DxM (e.g. 8x1), got {spec!r}")
    return d, m


def build_graph(args):
    g = generators.powerlaw_cluster(args.n, args.degree, prob=args.prob,
                                    seed=args.graph_seed)
    # Dedupe unconditionally: the block-sparse tile layout needs parallel
    # edges merged, and using ONE edge list for every backend keeps the
    # facade's cross-backend bit-identity contract — the same CLI args
    # must sample the same bits whether the backend is dense or kernel
    # (a pool saved under one must refresh identically under another).
    return csr.dedupe(g)


def build_config(args, *, backend: str | None = None) -> PoolConfig:
    """One place maps CLI knobs → PoolConfig (with its `SamplerSpec`) for
    BOTH serving paths."""
    backend = backend or args.sampler_backend or "dense"
    spec = SamplerSpec(diffusion=args.diffusion, backend=backend,
                       num_colors=args.colors, master_seed=args.master_seed,
                       frontier=args.frontier,
                       frontier_capacity=args.frontier_capacity)
    return PoolConfig(max_batches=args.max_batches,
                      memory_budget_mb=args.memory_budget_mb, spec=spec)


def dense_variant(cfg: PoolConfig) -> PoolConfig:
    """Same pool under the single-device dense backend AND dense frontier
    (reference path) — with ``--frontier sparse`` the smoke's bit-identity
    assertions become a sparse-vs-dense equivalence check too."""
    return dataclasses.replace(
        cfg, spec=cfg.spec.replace(backend="dense", frontier="dense"))


def build_store(args) -> SketchStore:
    store = SketchStore(build_graph(args), build_config(args))
    store.ensure(args.batches)
    return store


def serve_mixed_batch(store, engine, batcher, k: int, num_queries: int):
    """One micro-batched flush mixing all three query kinds."""
    rng = np.random.default_rng(0)
    n = store.graph.num_vertices
    tickets = {"top_k": [batcher.submit_top_k(k)]}
    tickets["sigma"] = [
        batcher.submit_sigma(rng.integers(0, n, rng.integers(1, 5)).tolist())
        for _ in range(num_queries)]
    tickets["marginal"] = [
        batcher.submit_marginal(rng.integers(0, n, 2).tolist())
        for _ in range(num_queries)]
    t0 = time.perf_counter()
    results = batcher.flush()
    dt = time.perf_counter() - t0
    return tickets, results, dt


def _print_mixed(tag, args, tickets, results, dispatches, dt):
    seeds, sigma_topk = results[tickets["top_k"][0]]
    n_served = sum(len(v) for v in tickets.values())
    print(f"[{tag}] mixed batch: {n_served} queries in "
          f"{dispatches} dispatches, {dt:.2f}s")
    print(f"  top-{args.k}: seeds={seeds.tolist()} σ̂={sigma_topk:.1f}")
    print(f"  σ(S) samples: "
          f"{[round(float(results[t]), 1) for t in tickets['sigma'][:3]]}")
    gains = results[tickets["marginal"][0]]
    print(f"  marginal: best vertex {int(np.argmax(gains))} "
          f"Δσ̂={float(np.max(gains)):.1f}")


# ------------------------------------------------------------ single device
def run_single(args) -> None:
    t0 = time.time()
    if args.sampler_backend in ("data_parallel", "graph_parallel"):
        raise SystemExit(f"--sampler-backend {args.sampler_backend} needs "
                         "a mesh; add --mesh DxM (M>1 for graph_parallel)")
    store = build_store(args)
    print(f"[serve_influence] pool: {len(store.batches)} batches × "
          f"{store.num_colors} colors = {store.num_samples} RRR sets "
          f"({store.bytes_per_batch * len(store.batches) / 2**20:.2f} MiB, "
          f"capacity {store.capacity} batches; diffusion "
          f"{store.spec.diffusion!r}, backend {store.spec.backend!r})")

    engine = QueryEngine(store)
    batcher = MicroBatcher(engine, cache=ResultCache())
    tickets, results, dt = serve_mixed_batch(store, engine, batcher,
                                             args.k, args.queries)
    _print_mixed("serve_influence", args, tickets, results,
                 batcher.dispatches, dt)

    if not args.smoke:
        if args.async_frontend:
            _async_demo(args, engine)
        return

    # ---- cached re-serve + epoch refresh invalidation
    before = batcher.dispatches
    serve_mixed_batch(store, engine, batcher, args.k, args.queries)
    assert batcher.dispatches == before, "identical batch must be all hits"
    print(f"[smoke] re-serve: 100% cache hits "
          f"({batcher.cache.hits} hits / {batcher.cache.misses} misses)")
    slots = store.refresh(0.25)
    serve_mixed_batch(store, engine, batcher, args.k, args.queries)
    assert batcher.dispatches > before, "refresh must invalidate cache"
    print(f"[smoke] refresh: epoch {store.epoch}, resampled slots {slots}, "
          f"cache invalidated")

    # ---- persist + bit-identical restore
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="sketch_pool_")
    store.save(ckpt)
    restored = SketchStore.restore(ckpt, store.graph, build_config(args))
    assert np.array_equal(np.asarray(store.visited_stack()),
                          np.asarray(restored.visited_stack()))
    assert restored.epoch == store.epoch
    assert restored.next_batch_index == store.next_batch_index
    assert [b.batch_index for b in restored.batches] == \
        [b.batch_index for b in store.batches]
    r_seeds, _ = QueryEngine(restored).top_k(args.k)
    s_seeds, _ = engine.top_k(args.k)
    assert np.array_equal(r_seeds, s_seeds)
    print(f"[smoke] persist/restore: bit-identical pool at "
          f"{os.path.join(ckpt, f'step_{store.epoch:08d}')}")

    # ---- offline IMM through the shared incremental kernel + pool
    g = store.graph
    res_plain = imm.run_imm(g, k=args.k, eps=0.5, spec=store.spec,
                            theta_cap=1024)
    fresh = SketchStore(g, build_config(args))
    res_pool = imm.run_imm(g, k=args.k, eps=0.5, spec=store.spec,
                           theta_cap=1024, pool=fresh)
    assert np.array_equal(res_plain.seeds, res_pool.seeds)
    assert res_plain.coverage == res_pool.coverage
    ref_seeds, ref_cov = imm.greedy_max_cover_ref(
        fresh.visited_stack()[:res_plain.num_batches], args.k, args.colors)
    assert np.array_equal(res_plain.seeds, ref_seeds)
    print(f"[smoke] offline run_imm: pool-routed seeds == pool-less seeds "
          f"== host-loop reference ({res_plain.seeds.tolist()})")
    # Async demo last: its background refresh mutates the store, which
    # would invalidate the bit-identity assertions above.
    if args.async_frontend:
        _async_demo(args, engine)
    print(f"[smoke] PASS in {time.time() - t0:.1f}s")


# -------------------------------------------------------------- distributed
def run_distributed(args, shape: tuple[int, int]) -> None:
    import jax
    from repro.serve.distributed import (DistributedQueryEngine,
                                         ShardedSketchStore)

    t0 = time.time()
    d, m = shape
    if jax.device_count() < d * m:
        raise SystemExit(f"mesh {d}x{m} wants {d * m} devices, have "
                         f"{jax.device_count()}")
    # Mesh backend defaults: data_parallel shards batch blocks; M > 1
    # activates graph parallelism — rows over 'model', batches over 'data'.
    backend = args.sampler_backend or \
        ("graph_parallel" if m > 1 else "data_parallel")
    if backend == "graph_parallel" and m < 2:
        raise SystemExit("--sampler-backend graph_parallel wants a model "
                         f"axis: use --mesh DxM with M>1 (got {d}x{m})")
    mesh = make_mesh((d, m), ("data", "model")) if m > 1 else \
        make_mesh((d,), ("data",))
    g = build_graph(args)
    cfg = build_config(args, backend=backend)
    store = ShardedSketchStore(g, cfg, mesh)
    store.ensure(args.batches)
    layout = f"data={d}" + (f" × model={m}" if m > 1 else "")
    per_dev = (store.bytes_per_batch * store.padded_batches
               / store.num_shards / store.row_shards / 2**20)
    print(f"[serve_influence] sharded pool: {len(store.batches)} batches × "
          f"{store.num_colors} colors over {store.num_shards} shards "
          f"({layout} mesh; {per_dev:.2f} "
          f"MiB/device"
          + (f", visited rows V/{store.row_shards} per device"
             if store.row_shards > 1 else "")
          + f", capacity {store.capacity} batches; diffusion "
          f"{store.spec.diffusion!r}, backend {store.spec.backend!r})")

    engine = DistributedQueryEngine(store)
    batcher = MicroBatcher(engine, cache=ResultCache())
    tickets, results, dt = serve_mixed_batch(store, engine, batcher,
                                             args.k, args.queries)
    _print_mixed("distributed", args, tickets, results,
                 batcher.dispatches, dt)

    if not args.smoke:
        if args.async_frontend:
            _async_demo(args, engine)
        return

    # ---- sharded ≡ single-device, bit for bit (and, with a mesh backend
    # — data_parallel block builds or graph_parallel row-partitioned
    # traversals — distributed sampling ≡ dense per-batch)
    single = SketchStore(g, dense_variant(cfg))
    single.ensure(len(store.batches))
    ref = QueryEngine(single)
    s1, sig1 = ref.top_k(args.k)
    s8, sig8 = engine.top_k(args.k)
    assert np.array_equal(s1, s8) and sig1 == sig8
    sets = [[1, 2], [5, 50, 99]]
    assert np.array_equal(ref.sigma(sets), engine.sigma(sets))
    print(f"[smoke] sharded == single-device: top-{args.k} seeds "
          f"{s8.tolist()}, σ̂={sig8:.1f} bit-identical across "
          f"{store.num_shards} shards")

    # ---- row-sharded pool layout (M > 1): each device holds V/M rows
    if store.row_shards > 1:
        stack = store.visited_stack()
        vloc = store.padded_vertices // store.row_shards
        assert stack.shape[:2] == (store.padded_batches,
                                   store.padded_vertices), stack.shape
        blk = next(iter(stack.addressable_shards)).data
        assert blk.shape[1] == vloc, (blk.shape, vloc)
        print(f"[smoke] row-sharded stack {tuple(stack.shape)}: "
              f"{vloc} visited rows/device "
              f"(= V/{store.row_shards}), queries still bit-identical")
    if store.spec.backend == "graph_parallel" and \
            getattr(store.sampler, "last_gather_words", None) is not None:
        gw = np.asarray(store.sampler.last_gather_words).sum(0)
        print(f"[smoke] frontier exchange ({store.spec.frontier}): "
              f"{[int(x) for x in gw[:6]]}... packed words/level over "
              f"the model axis, {int(gw.sum())} total")

    # ---- elastic restore under a different mesh shape
    ckpt = args.ckpt_dir or tempfile.mkdtemp(prefix="sharded_pool_")
    store.save(ckpt)
    d2 = max(d // 2, 1)
    mesh2 = make_mesh((d2, (d * m) // d2), ("data", "model"))
    restored = ShardedSketchStore.restore(ckpt, g, cfg, mesh2)
    r_seeds, r_sig = DistributedQueryEngine(restored).top_k(args.k)
    assert np.array_equal(s8, r_seeds) and sig8 == r_sig
    print(f"[smoke] elastic restore: {store.num_shards} shards → "
          f"{restored.num_shards} shards, answers bit-identical "
          f"(layout {ShardedSketchStore.saved_layout(ckpt)['shard_layout']})")

    # ---- epoch refresh: block resample ≡ dense per-batch resample
    t_r = time.perf_counter()
    slots_sharded = store.refresh(0.5)
    dt_r = time.perf_counter() - t_r
    slots_single = single.refresh(0.5)
    assert slots_sharded == slots_single
    rs, rsig = engine.top_k(args.k)
    r1, rsig1 = ref.top_k(args.k)
    assert np.array_equal(rs, r1) and rsig == rsig1
    print(f"[smoke] refresh: {len(slots_sharded)} slots resampled via "
          f"{store.spec.backend!r} in {dt_r:.2f}s, still bit-identical to "
          "the dense single-device pool")
    # Async demo last: its background refresh mutates the store, which
    # would invalidate the bit-identity assertions above.
    if args.async_frontend:
        _async_demo(args, engine)
    print(f"[smoke] PASS in {time.time() - t0:.1f}s")


# --------------------------------------------------------------------- tier
def run_tier(args) -> None:
    """Production serving tier: admission → replicas → autoscale → SLOs.

    ``--tier --tenants N --replicas R [--autoscale]`` builds a warm pool,
    fronts it with `repro.serve.tier.ServingTier` (N tenants with mixed
    quotas over R bit-identical replicas), drives a burst of per-tenant
    client threads, and prints the metrics snapshot.  With ``--smoke`` it
    asserts the tier acceptance contract: sheds carry retry-after,
    in-quota answers are bit-identical to a direct single-engine
    `QueryEngine` on the same pool epoch, a mid-stream refresh never
    yields a mixed-epoch reply, and (with ``--autoscale``) a scale event
    is an epoch swap, not a rebuild.
    """
    from repro.serve.tier import EpochMixError, ServingTier, ShedError

    if args.sampler_backend in ("data_parallel", "graph_parallel"):
        raise SystemExit("--tier serves single-device replicas; mesh "
                         "backends arrive with cross-process replicas")
    t0 = time.time()
    store = build_store(args)
    reference = QueryEngine(store.clone())      # same epoch, direct engine
    autoscale = None
    if args.autoscale:
        autoscale = {"k": args.k, "target_eps": args.target_eps,
                     "target_p99_ms": args.target_p99_ms}
    tier = ServingTier.build(store, replicas=args.replicas,
                             quota_qps=args.quota_qps,
                             autoscale=autoscale,
                             default_deadline=args.deadline)
    tenants = [f"tenant{i}" for i in range(args.tenants)]
    # Tenant 0 is deliberately starved so the shed path exercises under
    # any load: 1 token, slow refill.
    tier.set_quota(tenants[0], rate=0.5, burst=1)
    print(f"[tier] {args.replicas} replicas × {len(store.batches)} batches, "
          f"{args.tenants} tenants (quota {args.quota_qps} qps, "
          f"{tenants[0]} pinned to 0.5 qps)"
          + (", autoscale armed" if autoscale else ""))

    n = store.graph.num_vertices
    rng = np.random.default_rng(2)
    queries = [rng.integers(0, n, 3).tolist() for _ in range(8)]
    sheds, futs = [], []            # futs: (query, future) per admitted
    for q in queries:
        for t in tenants:
            try:
                futs.append((q, tier.submit_sigma(t, q)))
            except ShedError as e:
                sheds.append(e)
    values = tier.gather([f for _, f in futs])
    print(f"[tier] {len(futs)} admitted / {len(sheds)} shed; "
          f"pending per replica {tier.group.pending()}")

    if not args.smoke:
        print(tier.to_json(indent=1))
        tier.close()
        return

    # ---- sheds carry retry-after; in-quota tenants unaffected
    assert sheds, "starved tenant must shed under this burst"
    assert all(e.retry_after > 0 and e.tenant == tenants[0] for e in sheds)
    # ---- in-quota answers ≡ direct single-engine QueryEngine, same epoch
    for (q, _), val in zip(futs, values):
        assert val == reference.sigma([q])[0], \
            "tier answers must be bit-identical to the direct engine"
    print(f"[smoke] {len(values)} in-quota answers bit-identical to direct "
          f"QueryEngine; {len(sheds)} sheds with retry-after "
          f"{sheds[0].retry_after:.2f}s")

    # ---- mid-stream refresh: epoch guard refuses mixed replies
    before = tier.submit_sigma(tenants[-1], queries[0])
    before.result()
    tier.group.replicas[0].frontend.refresh_now(0.5)    # half a sweep
    after = tier.submit_sigma(tenants[-1], queries[1],
                              deadline=0.0)
    after.result()
    mixed = False
    try:
        tier.gather([before, after])
    except EpochMixError as e:
        mixed = True
        assert len(e.versions) == 2
    assert mixed or before.pool_version == after.pool_version, \
        "mixed-epoch replies must be refused"
    # finish the sweep → replicas re-converge bit-identically
    for r in tier.group.replicas[1:]:
        r.frontend.refresh_now(0.5)
    assert tier.group.consistent()
    stacks = [np.asarray(r.store.visited_stack())
              for r in tier.group.replicas]
    assert all(np.array_equal(stacks[0], s) for s in stacks[1:])
    print(f"[smoke] mid-stream refresh: mixed-epoch gather "
          f"{'refused (EpochMixError)' if mixed else 'not provoked'}; "
          f"replicas re-converged bit-identically at "
          f"{tier.group.versions()[0]}")

    # ---- autoscale: scale events swap epochs, never cold-rebuild
    if tier.autoscaler is not None:
        b0 = tier.group.num_batches
        decision = tier.autoscaler.step()
        assert tier.group.consistent()
        print(f"[smoke] autoscale: {decision.action} {b0} → "
              f"{tier.group.num_batches} batches "
              f"(ε̂={decision.eps_bound}, θ={decision.theta}) — {decision.reason}")

    snap = tier.snapshot()
    assert snap["totals"]["shed"] == len(sheds)
    assert snap["latency"]["all"]["count"] >= len(futs)
    print(f"[smoke] metrics: shed_rate={snap['totals']['shed_rate']:.2f}, "
          f"p99={snap['latency']['all']['p99'] * 1e3:.1f}ms over "
          f"{snap['latency']['all']['count']} queries")
    tier.close()
    print(f"[smoke] PASS in {time.time() - t0:.1f}s")


# ---------------------------------------------------------------- streaming
def run_stream(args, shape: tuple[int, int] | None = None) -> None:
    """``--stream-smoke``: mutate the graph mid-serve, refresh
    incrementally, assert bit-identity against a cold rebuild.

    Default mode serves through the tier (2 replicas) and drives
    `ServingTier.apply_delta`; with ``--mesh Dx1`` the delta lands on a
    `ShardedSketchStore` instead and the refreshed sharded pool is
    checked against BOTH a cold rebuild and a single-device pool on the
    mutated graph.
    """
    from repro import stream

    t0 = time.time()
    rng = np.random.default_rng(args.graph_seed + 1)

    if shape is not None:
        import jax
        from repro.serve.distributed import (DistributedQueryEngine,
                                             ShardedSketchStore)
        d, m = shape
        if m != 1:
            raise SystemExit("--stream-smoke --mesh wants Dx1 (deltas on "
                             "graph_parallel pools arrive later)")
        mesh = make_mesh((d,), ("data",))
        g = build_graph(args)
        cfg = build_config(args, backend="data_parallel")
        store = ShardedSketchStore(g, cfg, mesh)
        store.ensure(args.batches)
        store.visited_stack()
        engine = DistributedQueryEngine(store)
        sig_pre = engine.sigma([[1, 2, 3]])[0]
        tracker = stream.DirtySlotTracker.for_store(store)
        delta = stream.random_delta(g, rng, num_deletes=args.queries,
                                    num_inserts=args.queries)
        report = stream.incremental_refresh(store, tracker, delta)
        print(f"[stream] sharded delta: +{report.inserted}/-{report.deleted} "
              f"edges, {report.touched_row_blocks} row-blocks → "
              f"{report.dirty_slots}/{report.total_slots} dirty slots "
              f"resampled in {report.refresh_s:.2f}s "
              f"(graph epoch {report.graph_epoch})")
        cold = stream.cold_rebuild_batches(store)
        for bi, bc in zip(store.batches, cold):
            assert np.array_equal(np.asarray(bi.visited),
                                  np.asarray(bc.visited))
            assert bi.fused_edge_visits == bc.fused_edge_visits
        single = SketchStore(store.graph, dense_variant(cfg),
                             g_rev=store.g_rev)
        single.ensure(len(store.batches))
        for bi, bs in zip(store.batches, single.batches):
            assert np.array_equal(np.asarray(bi.visited),
                                  np.asarray(bs.visited))
        sig_post = engine.sigma([[1, 2, 3]])[0]
        print(f"[stream] sharded pool ≡ cold rebuild ≡ single-device dense "
              f"on the mutated graph ({store.num_shards} shards); "
              f"σ̂(1,2,3) {sig_pre:.1f} → {sig_post:.1f}")
        print(f"[stream] PASS in {time.time() - t0:.1f}s")
        return

    # ---- tier mode: the delta is a serving event between live queries
    from repro.serve.tier import EpochMixError, ServingTier, ShedError

    store = build_store(args)
    tier = ServingTier.build(store, replicas=args.replicas,
                             quota_qps=args.quota_qps,
                             default_deadline=args.deadline)
    try:
        n = store.graph.num_vertices
        queries = [rng.integers(0, n, 3).tolist() for _ in range(4)]
        pre = [tier.submit_sigma("ops", q) for q in queries]
        pre_vals = tier.gather(pre)
        v0 = tier.group.versions()[0]

        delta = stream.random_delta(store.graph, rng,
                                    num_deletes=args.queries,
                                    num_inserts=args.queries)
        report = tier.apply_delta("ops", delta)
        print(f"[stream] tier delta: +{report.inserted}/-{report.deleted} "
              f"edges, {report.touched_row_blocks} row-blocks → "
              f"{report.dirty_slots}/{report.total_slots} dirty slots "
              f"({report.dirty_fraction:.0%}) resampled in "
              f"{report.refresh_s:.2f}s")

        # graph-epoch version bump, replicas converged bit-identically
        v1 = tier.group.versions()[0]
        assert v1[0] == v0[0] + 1 and tier.group.consistent(), (v0, v1)
        stacks = [np.asarray(r.store.visited_stack())
                  for r in tier.group.replicas]
        assert all(np.array_equal(stacks[0], s) for s in stacks[1:])

        # incremental pool ≡ cold rebuild on the mutated graph
        r0 = tier.group.replicas[0].store
        cold = stream.cold_rebuild_batches(r0)
        for bi, bc in zip(r0.batches, cold):
            assert np.array_equal(np.asarray(bi.visited),
                                  np.asarray(bc.visited))
            assert bi.fused_edge_visits == bc.fused_edge_visits
        print(f"[stream] replicas converged at graph epoch {v1[0]}, "
              f"pool ≡ cold rebuild on the mutated graph")

        # pre-delta and post-delta replies must never mix
        post = [tier.submit_sigma("ops", q) for q in queries]
        post_vals = tier.gather(post)
        mixed = False
        try:
            tier.gather([pre[0], post[0]])
        except EpochMixError as e:
            mixed = True
            assert len(e.versions) == 2
        assert mixed, "pre/post-delta replies must be refused as a mix"
        print(f"[stream] pre/post-delta gather refused (EpochMixError); "
              f"σ̂ samples {pre_vals[0]:.1f} → {post_vals[0]:.1f}")

        # deltas are admission-gated like any query
        tier.set_quota("vandal", rate=0.01, burst=1)
        tier.apply_delta("vandal", stream.EdgeDelta.deletes([], []))
        shed = False
        try:
            tier.apply_delta("vandal", stream.EdgeDelta.deletes([], []))
        except ShedError as e:
            shed = True
            assert e.retry_after > 0
        assert shed, "quota-starved tenant must shed delta spam"

        snap = tier.snapshot()
        s = snap["stream"]
        assert s["deltas_applied"] == 2 and s["tracker"]["slots"] == \
            len(r0.batches)
        print(f"[stream] admission gates deltas (1 shed); snapshot: "
              f"{s['deltas_applied']} deltas, dirty-fraction p50 "
              f"{s['dirty_fraction']['p50']:.2f}, tracker "
              f"{s['tracker']['tracker_bytes']} B")
    finally:
        tier.close()
    print(f"[stream] PASS in {time.time() - t0:.1f}s")


# -------------------------------------------------------------------- async
def _async_demo(args, engine) -> None:
    """Deadline-batched front-end under a burst of threaded clients."""
    from repro.serve.distributed import AsyncFrontEnd

    n = engine.store.graph.num_vertices
    fe = AsyncFrontEnd(MicroBatcher(engine, cache=ResultCache()),
                       default_deadline=args.deadline,
                       refresh_every=args.refresh_every)
    lone = fe.submit_sigma([1, 2, 3])
    lone.result(timeout=300)
    assert fe.stats.deadline_flushes >= 1, fe.stats

    futs: list = []
    lock = threading.Lock()
    rng = np.random.default_rng(1)
    queries = [rng.integers(0, n, 3).tolist() for _ in range(4 * 8)]

    def client(q):
        f = fe.submit_sigma(q)
        with lock:
            futs.append(f)

    threads = [threading.Thread(target=client, args=(q,)) for q in queries]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for f in futs:
        f.result(timeout=300)
    dt = time.perf_counter() - t0
    fe.close()
    assert fe.stats.max_queue_wait <= args.deadline + 2.0, fe.stats
    print(f"[async] {len(queries)} threaded clients + 1 lone request in "
          f"{dt:.2f}s: {fe.stats.flushes} flushes "
          f"({fe.stats.slot_flushes} slot / {fe.stats.deadline_flushes} "
          f"deadline / {fe.stats.drain_flushes} drain), worst queue wait "
          f"{fe.stats.max_queue_wait * 1e3:.0f} ms "
          f"(deadline {args.deadline * 1e3:.0f} ms)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="full lifecycle check on a synthetic graph")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="serve from a sharded pool on a DxM mesh "
                         "(forced host devices under JAX_PLATFORMS=cpu)")
    ap.add_argument("--async", dest="async_frontend", action="store_true",
                    help="front the batcher with the deadline-batched "
                         "AsyncFrontEnd and drive it from client threads")
    ap.add_argument("--tier", action="store_true",
                    help="serve through the production tier: per-tenant "
                         "admission control + replica routing "
                         "(+ --autoscale); see repro.serve.tier")
    ap.add_argument("--stream-smoke", action="store_true",
                    help="mutate the graph mid-serve (repro.stream delta), "
                         "refresh the pool incrementally, and assert "
                         "bit-identity against a cold rebuild; tier mode "
                         "by default, sharded with --mesh Dx1")
    ap.add_argument("--tenants", type=int, default=3,
                    help="tier tenant count (tenant0 is quota-starved in "
                         "the smoke so the shed path exercises)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="tier engine replicas over one epoch-tagged pool")
    ap.add_argument("--autoscale", action="store_true",
                    help="arm the signal-driven pool autoscaler "
                         "(coverage-error bound + query p99)")
    ap.add_argument("--quota-qps", type=float, default=50.0,
                    help="default per-tenant admission rate (tokens/s)")
    ap.add_argument("--target-eps", type=float, default=0.35,
                    help="autoscale coverage-error target (IMM ε)")
    ap.add_argument("--target-p99-ms", type=float, default=250.0,
                    help="autoscale query-latency target")
    ap.add_argument("--deadline", type=float, default=0.05,
                    help="async flush deadline in seconds")
    ap.add_argument("--refresh-every", type=float, default=None,
                    help="async background refresh period in seconds")
    ap.add_argument("--diffusion", choices=("ic", "lt"), default="ic",
                    help="diffusion model the pool samples under")
    ap.add_argument("--sampler-backend", default=None,
                    choices=("dense", "tiled", "kernel", "data_parallel",
                             "graph_parallel"),
                    help="traversal backend (default: dense single-device; "
                         "on a --mesh DxM: data_parallel when M==1, "
                         "graph_parallel — rows sharded over the model "
                         "axis — when M>1)")
    ap.add_argument("--frontier", choices=("dense", "sparse"),
                    default="dense",
                    help="per-level execution mode: sparse compacts each "
                         "level to the active tiles (bit-identical, work "
                         "scales with the live frontier)")
    ap.add_argument("--frontier-capacity", type=int, default=0,
                    help="sparse capacity knob (0 = auto bucket ladder; "
                         "see benchmarks/bench_frontier_profile.py)")
    ap.add_argument("--n", type=int, default=300)
    ap.add_argument("--degree", type=float, default=6.0)
    ap.add_argument("--prob", type=float, default=0.25)
    ap.add_argument("--graph-seed", type=int, default=7)
    ap.add_argument("--colors", type=int, default=64)
    ap.add_argument("--batches", type=int, default=8,
                    help="initial pool size (fused batches)")
    ap.add_argument("--max-batches", type=int, default=64)
    ap.add_argument("--memory-budget-mb", type=float, default=None)
    ap.add_argument("--master-seed", type=int, default=0)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--queries", type=int, default=6)
    ap.add_argument("--ckpt-dir", default=None,
                    help="pool snapshot directory (default: temp dir)")
    args = ap.parse_args(argv)

    # Standard accelerator config (host devices under JAX_PLATFORMS=cpu,
    # compilation cache) before any jax backend materializes.
    from repro.launch import accel
    shape = _parse_mesh(args.mesh) if args.mesh else None
    accel.configure(host_devices=shape[0] * shape[1] if shape else 1)

    if args.stream_smoke:
        run_stream(args, shape)
    elif args.tier:
        if args.mesh:
            raise SystemExit("--tier serves single-device replicas; mesh "
                             "backends arrive with cross-process replicas")
        if args.tenants < 2:
            raise SystemExit("--tier wants --tenants >= 2 (tenant0 is the "
                             "quota-starved one)")
        run_tier(args)
    elif args.mesh:
        run_distributed(args, shape)
    else:
        run_single(args)


if __name__ == "__main__":
    main()
