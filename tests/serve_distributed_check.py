"""Distributed-serving equivalence checks, executed by
tests/test_serve_distributed.py in a subprocess with 8 forced host devices
(the main pytest process keeps its 1-device invariant — see conftest.py).
Prints "OK <name>" per passing check; any exception fails.

The contract under test: an N-shard pool + DistributedQueryEngine is
**bit-for-bit** equal to the 1-device SketchStore + QueryEngine path —
same top-k seeds, same σ(S), same marginal gains — because sampling is
per-slot deterministic and every distributed reduction is an integer psum.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import tempfile                 # noqa: E402
import threading                # noqa: E402
import time                     # noqa: E402

import numpy as np              # noqa: E402
import jax                      # noqa: E402

from repro import sampling                                  # noqa: E402
from repro.graph import generators                          # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.serve.influence import (MicroBatcher, PoolConfig,    # noqa: E402
                                   QueryEngine, ResultCache, SketchStore)
from repro.serve.distributed import (AsyncFrontEnd,             # noqa: E402
                                     DistributedQueryEngine,
                                     ShardedSketchStore)


def main():
    # Watchdog: if anything ever wedges (thread deadlock, lost wakeup),
    # die with a full all-thread stack dump well inside the driving
    # test's 900 s subprocess timeout instead of hanging silently.
    import faulthandler
    faulthandler.dump_traceback_later(600, exit=True)

    assert len(jax.devices()) == 8, jax.devices()
    g = generators.powerlaw_cluster(200, 6.0, prob=0.25, seed=13)
    cfg = PoolConfig(num_colors=64, max_batches=32, master_seed=3)

    # ---- per-slot bit identity: mesh only decides placement ---------------
    single = SketchStore(g, cfg)
    single.ensure(8)
    mesh8 = make_mesh((8,), ("data",))
    sharded = ShardedSketchStore(g, cfg, mesh8)
    sharded.ensure(8)
    assert sharded.num_shards == 8
    for a, b in zip(single.batches, sharded.batches):
        assert a.batch_index == b.batch_index
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    print("OK shard_slots")

    # ---- engine equivalence: top-k / σ(S) / marginal bit-identical --------
    e1, e8 = QueryEngine(single), DistributedQueryEngine(sharded)
    s1, sig1 = e1.top_k(4)
    s8, sig8 = e8.top_k(4)
    np.testing.assert_array_equal(s1, s8)
    assert sig1 == sig8
    sets = [[0], [3, 50, 99], [10, 20, 30, 40]]
    np.testing.assert_array_equal(e1.sigma(sets), e8.sigma(sets))
    excl = [int(s1[0]), int(s1[1])]
    np.testing.assert_array_equal(e1.marginal_gains(excl),
                                  e8.marginal_gains(excl))
    np.testing.assert_array_equal(e1.best_extension(excl, 2),
                                  e8.best_extension(excl, 2))
    print("OK engine_equivalence")

    # ---- ragged slot count: 5 batches on 8 shards (zero-pad slots) --------
    s5 = SketchStore(g, cfg)
    s5.ensure(5)
    sh5 = ShardedSketchStore(g, cfg, mesh8)
    sh5.ensure(5)
    assert sh5.padded_batches == 8 and len(sh5.batches) == 5
    a1 = QueryEngine(s5).top_k(3)
    a8 = DistributedQueryEngine(sh5).top_k(3)
    np.testing.assert_array_equal(a1[0], a8[0])
    assert a1[1] == a8[1]
    print("OK ragged_shards")

    # ---- per-shard budget: N shards admit N× the per-device batches -------
    tight = PoolConfig(num_colors=64, max_batches=64, master_seed=3,
                       memory_budget_mb=2 * sharded.bytes_per_batch / 2**20)
    assert SketchStore(g, tight).capacity == 2
    assert ShardedSketchStore(g, tight, mesh8).capacity == 16
    print("OK per_shard_budget")

    # ---- elastic manifest restore: 8 shards → 2 shards → 1 device ---------
    with tempfile.TemporaryDirectory() as d:
        sharded.save(d)
        extra = ShardedSketchStore.saved_layout(d)
        assert extra["num_shards"] == 8
        assert extra["shard_layout"] == list(range(8))
        mesh2 = make_mesh((2, 4), ("data", "model"))
        r2 = ShardedSketchStore.restore(d, g, cfg, mesh2)
        assert r2.num_shards == 2 and r2.shard_layout() == [0] * 4 + [1] * 4
        s2, sig2 = DistributedQueryEngine(r2).top_k(4)
        np.testing.assert_array_equal(s1, s2)
        assert sig1 == sig2
        rp = SketchStore.restore(d, g, cfg)     # plain 1-device restore
        sp, sigp = QueryEngine(rp).top_k(4)
        np.testing.assert_array_equal(s1, sp)
        assert sig1 == sigp
    print("OK elastic_restore")

    # ---- data_parallel sampler: shard_map blocks ≡ dense per-batch --------
    # The unified Sampler contract on a real multi-device mesh: the same
    # (master_seed, batch_index) yields bit-identical visited masks whether
    # batches run one at a time on the default device (dense) or as a
    # sharded block with per-shard RNG streams (data_parallel), for both
    # diffusions.
    for diffusion in ("ic", "lt"):
        spec = sampling.SamplerSpec(diffusion=diffusion,
                                    backend="data_parallel",
                                    num_colors=64, master_seed=3)
        dp = sampling.make_sampler(g, spec, mesh=mesh8)
        dense = sampling.make_sampler(g, spec.replace(backend="dense"))
        for got in dp.sample_many(range(7)):        # ragged on 8 shards
            ref = dense.sample(got.batch_index)
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(ref.visited))
            np.testing.assert_array_equal(got.roots, np.asarray(ref.roots))
        stacked = dp.sample_stacked(range(8))
        assert stacked.sharding.spec == jax.sharding.PartitionSpec("data")
    print("OK data_parallel_sampling")

    # ---- data_parallel pool builds: ensure + refresh via shard_map --------
    # ShardedSketchStore with the data_parallel spec builds/refreshes shard
    # slots in one shard_map block (no per-batch default-device staging)
    # and stays bit-identical to the 1-device dense pool, slot for slot.
    dp_cfg = PoolConfig(max_batches=32,
                        spec=sampling.SamplerSpec(backend="data_parallel",
                                                  num_colors=64,
                                                  master_seed=3))
    dp_store = ShardedSketchStore(g, dp_cfg, mesh8)
    dp_store.ensure(8)
    ref_store = SketchStore(g, cfg)                 # dense, master_seed=3
    ref_store.ensure(8)
    for a, b in zip(ref_store.batches, dp_store.batches):
        assert a.batch_index == b.batch_index
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    slots_dp = dp_store.refresh(0.5)
    slots_ref = ref_store.refresh(0.5)
    assert slots_dp == slots_ref and dp_store.epoch == ref_store.epoch
    for a, b in zip(ref_store.batches, dp_store.batches):
        assert a.batch_index == b.batch_index
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    ed, er = DistributedQueryEngine(dp_store), QueryEngine(ref_store)
    sd, sigd = ed.top_k(4)
    sr, sigr = er.top_k(4)
    np.testing.assert_array_equal(sd, sr)
    assert sigd == sigr
    # spec rides the manifest: an LT restore of this IC pool must refuse
    with tempfile.TemporaryDirectory() as d:
        dp_store.save(d)
        assert ShardedSketchStore.saved_layout(d)["sampler_spec"][
            "backend"] == "data_parallel"
        try:
            ShardedSketchStore.restore(
                d, g, PoolConfig(spec=dp_cfg.spec.replace(diffusion="lt")),
                mesh8)
            raise AssertionError("diffusion mismatch must raise")
        except ValueError as e:
            assert "diffusion" in str(e)
        r = ShardedSketchStore.restore(d, g, dp_cfg, mesh8)
        s2, sig2 = DistributedQueryEngine(r).top_k(4)
        np.testing.assert_array_equal(sd, s2)
        assert sigd == sig2
    print("OK data_parallel_pool")

    # ---- LT diffusion through the full distributed stack ------------------
    lt_cfg = PoolConfig(max_batches=32,
                        spec=sampling.SamplerSpec(diffusion="lt",
                                                  backend="data_parallel",
                                                  num_colors=64,
                                                  master_seed=5))
    lt_store = ShardedSketchStore(g, lt_cfg, mesh8)
    lt_store.ensure(8)
    lt_single = SketchStore(
        g, PoolConfig(max_batches=32,
                      spec=lt_cfg.spec.replace(backend="dense")))
    lt_single.ensure(8)
    lt_seeds, lt_sig = DistributedQueryEngine(lt_store).top_k(4)
    l1_seeds, l1_sig = QueryEngine(lt_single).top_k(4)
    np.testing.assert_array_equal(lt_seeds, l1_seeds)
    assert lt_sig == l1_sig and lt_sig > 0
    print("OK lt_data_parallel")

    # ---- graph_parallel pools: 2-D (data × model) meshes ≡ 1-device dense -
    # The graph ITSELF is row-partitioned over 'model' (each device holds
    # only its slice of the adjacency tiles; the frontier is all-gathered
    # per level), batches shard over 'data' — and every pool slot is STILL
    # bit-identical to the 1-device dense pool, for both diffusions, on
    # both mesh orientations.
    # One dedupe-clean edge list for BOTH sides of the comparison: the tile
    # layout needs parallel edges merged, and bit-identity needs the dense
    # reference sampling the very same graph.
    from repro.graph import csr
    g2 = csr.dedupe(g)
    gp = dense_ref = None          # the ic stores feed the manifest section
    for diffusion in ("lt", "ic"):
        dense_ref = SketchStore(
            g2, PoolConfig(max_batches=32,
                          spec=sampling.SamplerSpec(diffusion=diffusion,
                                                    num_colors=64,
                                                    master_seed=3)))
        dense_ref.ensure(6)
        for d, m in ((2, 4), (4, 2)):
            mesh_dm = make_mesh((d, m), ("data", "model"))
            gp_cfg = PoolConfig(
                max_batches=32,
                spec=sampling.SamplerSpec(diffusion=diffusion,
                                          backend="graph_parallel",
                                          num_colors=64, master_seed=3))
            gp = ShardedSketchStore(g2, gp_cfg, mesh_dm)
            gp.ensure(6)
            assert gp.num_shards == d
            for a, b in zip(dense_ref.batches, gp.batches):
                assert a.batch_index == b.batch_index
                np.testing.assert_array_equal(np.asarray(a.visited),
                                              np.asarray(b.visited))
        # engine answers from the last (4 × 2) store
        s_gp, sig_gp = DistributedQueryEngine(gp).top_k(4)
        s_rf, sig_rf = QueryEngine(dense_ref).top_k(4)
        np.testing.assert_array_equal(s_gp, s_rf)
        assert sig_gp == sig_rf
    print("OK graph_parallel_pool")

    # ---- graph_parallel KERNEL leg: Pallas tile kernels per shard ---------
    # REPRO_GP_KERNEL=1 swaps every shard's local tile expansion from the
    # jnp oracle to the Pallas kernels (`fused_expand` / `lt_select_expand`,
    # interpret mode on these CPU host devices).  The pool must STILL be
    # bit-identical to the 1-device dense pool, slot for slot, for both
    # diffusions and both frontier modes — the kernel is an execution
    # engine, never an answer change.
    os.environ["REPRO_GP_KERNEL"] = "1"
    try:
        mesh_22 = make_mesh((2, 2), ("data", "model"))
        for diffusion in ("ic", "lt"):
            ref_k = SketchStore(
                g2, PoolConfig(max_batches=32,
                               spec=sampling.SamplerSpec(diffusion=diffusion,
                                                         num_colors=64,
                                                         master_seed=3)))
            ref_k.ensure(4)
            for frontier in ("dense", "sparse"):
                gpk = ShardedSketchStore(
                    g2, PoolConfig(max_batches=32,
                                   spec=sampling.SamplerSpec(
                                       diffusion=diffusion,
                                       backend="graph_parallel",
                                       num_colors=64, master_seed=3,
                                       tile_size=64, frontier=frontier)),
                    mesh_22)
                gpk.ensure(4)
                for a, b in zip(ref_k.batches, gpk.batches):
                    assert a.batch_index == b.batch_index
                    np.testing.assert_array_equal(np.asarray(a.visited),
                                                  np.asarray(b.visited))
            s_k, sig_k = DistributedQueryEngine(gpk).top_k(4)
            s_d, sig_d = QueryEngine(ref_k).top_k(4)
            np.testing.assert_array_equal(s_k, s_d)
            assert sig_k == sig_d
    finally:
        os.environ.pop("REPRO_GP_KERNEL", None)
    print("OK graph_parallel_kernel")

    # ---- graph_parallel refresh + manifest layout + restore refusal -------
    # (continues with the ic (4 × 2) store from the last loop iteration)
    slots_gp = gp.refresh(0.5)
    slots_rf = dense_ref.refresh(0.5)
    assert slots_gp == slots_rf and gp.epoch == dense_ref.epoch
    for a, b in zip(dense_ref.batches, gp.batches):
        assert a.batch_index == b.batch_index
        np.testing.assert_array_equal(np.asarray(a.visited),
                                      np.asarray(b.visited))
    with tempfile.TemporaryDirectory() as dir_:
        gp.save(dir_)
        extra = ShardedSketchStore.saved_layout(dir_)
        assert extra["mesh_shape"] == {"data": 4, "model": 2}
        assert extra["sampler_spec"]["backend"] == "graph_parallel"
        # layout mismatch: a graph_parallel restore onto a mesh with no
        # model axis must refuse (future refreshes could not row-partition)
        try:
            ShardedSketchStore.restore(dir_, g2, gp.config, mesh8)
            raise AssertionError("layout mismatch must raise")
        except ValueError as e:
            assert "model" in str(e)
        # a DIFFERENT (data × model) layout restores fine — elastic slot
        # re-sharding + fresh row partition for future refreshes
        mesh_24 = make_mesh((2, 4), ("data", "model"))
        r = ShardedSketchStore.restore(dir_, g2, gp.config, mesh_24)
        assert r.num_shards == 2
        s_r, sig_r = DistributedQueryEngine(r).top_k(4)
        s_g, sig_g = DistributedQueryEngine(gp).top_k(4)
        np.testing.assert_array_equal(s_r, s_g)
        assert sig_r == sig_g
        # config=None adopts the snapshot's recorded spec wholesale: the
        # pool comes back with a graph_parallel sampler, never a silent
        # dense fallback for a graph that may not fit one device
        r_def = ShardedSketchStore.restore(dir_, g2, None, mesh_24)
        assert r_def.spec.backend == "graph_parallel"
        assert r_def.spec == gp.spec
    print("OK graph_parallel_manifest")

    # ---- sparse frontier on real multi-device meshes ≡ dense --------------
    # The sparse execution mode end to end on forced devices: compacted
    # per-level expansion inside shard_map bodies (data_parallel, 8 shards)
    # and the compacted (word_idx, word) frontier all-gather over the model
    # axis (graph_parallel, 2×4 — a tiny gather capacity forces the sparse
    # leg at every level that fits).  Pools must stay bit-identical to the
    # dense-frontier dense-backend reference, and the donated-buffer
    # refresh must keep them that way with the stack already staged.
    for diffusion in ("ic", "lt"):
        ref = SketchStore(
            g2, PoolConfig(max_batches=32,
                           spec=sampling.SamplerSpec(diffusion=diffusion,
                                                     num_colors=64,
                                                     master_seed=3)))
        ref.ensure(8)
        mesh_24 = make_mesh((2, 4), ("data", "model"))
        stores = [
            ShardedSketchStore(
                g2, PoolConfig(max_batches=32, spec=sampling.SamplerSpec(
                    diffusion=diffusion, backend="data_parallel",
                    num_colors=64, master_seed=3, frontier="sparse")),
                mesh8),
            ShardedSketchStore(
                g2, PoolConfig(max_batches=32, spec=sampling.SamplerSpec(
                    diffusion=diffusion, backend="graph_parallel",
                    num_colors=64, master_seed=3, frontier="sparse",
                    frontier_capacity=4)), mesh_24),
        ]
        for st in stores:
            st.ensure(8)
            st.visited_stack()          # arm the in-place refresh path
        ref.refresh(0.5)
        for st in stores:
            st.refresh(0.5)
            for a, b in zip(ref.batches, st.batches):
                assert a.batch_index == b.batch_index
                np.testing.assert_array_equal(np.asarray(a.visited),
                                              np.asarray(b.visited))
            s_sp, sig_sp = DistributedQueryEngine(st).top_k(4)
            s_rf, sig_rf = QueryEngine(ref).top_k(4)
            np.testing.assert_array_equal(s_sp, s_rf)
            assert sig_sp == sig_rf
    print("OK sparse_frontier")

    # ---- butterfly log(M) frontier exchange: traffic + bit-identity -------
    # The sparse graph_parallel leg is a ⌈log₂M⌉-stage pairwise exchange
    # of compacted (word_idx, word) pairs.  Per level it must move FEWER
    # packed words over the model axis than the dense all-gather whenever
    # it engages, and the pool must stay bit-identical to the dense
    # single-device reference — including on a non-power-of-two model
    # axis (M=3, where the dissemination schedule's last stage overlaps
    # and the `have` bitmap dedups re-delivered blocks) and with a
    # capacity so tiny the dense early levels overflow back to the flat
    # all-gather via lax.cond.
    mesh_bf = make_mesh((2, 4), ("data", "model"),
                        devices=jax.devices())
    mesh_m3 = make_mesh((2, 3), ("data", "model"),
                        devices=jax.devices()[:6])
    for diffusion in ("ic", "lt"):
        ref_bf = SketchStore(g2, PoolConfig(
            max_batches=32, spec=sampling.SamplerSpec(
                diffusion=diffusion, num_colors=64, master_seed=3)))
        ref_bf.ensure(4)

        def bf_store(mesh_dm, capacity, frontier="sparse"):
            st = ShardedSketchStore(g2, PoolConfig(
                max_batches=32, spec=sampling.SamplerSpec(
                    diffusion=diffusion, backend="graph_parallel",
                    num_colors=64, master_seed=3, frontier=frontier,
                    frontier_capacity=capacity)), mesh_dm)
            st.ensure(4)
            for a, b in zip(ref_bf.batches, st.batches):
                assert a.batch_index == b.batch_index
                np.testing.assert_array_equal(np.asarray(a.visited),
                                              np.asarray(b.visited))
            return np.asarray(st.sampler.last_gather_words).sum(0), st
        gw_dense, _ = bf_store(mesh_bf, 0, frontier="dense")
        gw_bf, st_bf = bf_store(mesh_bf, 64)
        levels = np.flatnonzero(gw_dense)
        assert levels.size, "traversal must record per-level gather traffic"
        # never worse than dense, strictly better wherever it engaged
        assert (gw_bf[levels] <= gw_dense[levels]).all(), (gw_bf, gw_dense)
        assert (gw_bf[levels] < gw_dense[levels]).any(), (gw_bf, gw_dense)
        # capacity-overflow fallback: 1 packed word per shard — the dense
        # early levels MUST take the flat-gather leg (identical traffic)
        # and the bits must not care which leg any level took
        gw_ov, _ = bf_store(mesh_bf, 1)
        assert (gw_ov[levels] == gw_dense[levels]).any(), (gw_ov, gw_dense)
        assert (gw_ov[levels] >= gw_bf[levels]).all(), (gw_ov, gw_bf)
        # non-power-of-two model axis
        gw_m3, st_m3 = bf_store(mesh_m3, 64)
        s_m3, sig_m3 = DistributedQueryEngine(st_m3).top_k(4)
        s_bf, sig_bf = QueryEngine(ref_bf).top_k(4)
        np.testing.assert_array_equal(s_m3, s_bf)
        assert sig_m3 == sig_bf
    print("OK butterfly_exchange")

    # ---- model-sharded pool rows: V/M per device, elastic across D×M ------
    # On a mesh carrying a size-M model axis the pool's VERTEX rows shard
    # too: the stack is (Bp, Vp, W) with each device holding only its
    # (slot block × V/M row slice), the query engine merges with one psum
    # over data and one over model, and the answers stay bit-identical to
    # the 1-device engine.  Host batches stay full-V, so a snapshot saved
    # under 2×4 restores onto 4×2 or a model-free 8-shard mesh unchanged.
    mesh_rs = make_mesh((2, 4), ("data", "model"),
                        devices=jax.devices())
    rs = ShardedSketchStore(g, cfg, mesh_rs)
    rs.ensure(8)
    assert rs.row_shards == 4 and rs.padded_vertices % 4 == 0
    stack = rs.visited_stack()
    assert stack.shape[:2] == (rs.padded_batches, rs.padded_vertices)
    blk = next(iter(stack.addressable_shards)).data
    assert blk.shape[1] == rs.padded_vertices // 4      # V/M rows/device
    er = DistributedQueryEngine(rs)
    s_rs, sig_rs = er.top_k(4)
    np.testing.assert_array_equal(s1, s_rs)
    assert sig1 == sig_rs
    np.testing.assert_array_equal(e1.sigma(sets), er.sigma(sets))
    np.testing.assert_array_equal(e1.marginal_gains(excl),
                                  er.marginal_gains(excl))
    np.testing.assert_array_equal(e1.best_extension(excl, 2),
                                  er.best_extension(excl, 2))
    # in-place refresh keeps the 2-D placement consistent (vertex-padded
    # donated scatter), pad rows stay zero
    rs.refresh(0.5)
    after = np.asarray(rs.visited_stack())
    np.testing.assert_array_equal(
        after[:len(rs.batches), :g.num_vertices],
        np.stack([np.asarray(b.visited) for b in rs.batches]))
    assert not after[:, g.num_vertices:].any()
    with tempfile.TemporaryDirectory() as d_:
        rs.save(d_)
        extra = ShardedSketchStore.saved_layout(d_)
        assert extra["row_layout"]["shards"] == 4
        assert extra["row_layout"]["padded_vertices"] == rs.padded_vertices
        want = DistributedQueryEngine(rs).top_k(4)
        mesh_42 = make_mesh((4, 2), ("data", "model"),
                        devices=jax.devices())
        for new_mesh, m_new in ((mesh_42, 2), (mesh8, 1)):
            r_new = ShardedSketchStore.restore(d_, g, cfg, new_mesh)
            assert r_new.row_shards == m_new
            got = DistributedQueryEngine(r_new).top_k(4)
            np.testing.assert_array_equal(want[0], got[0])
            assert want[1] == got[1]
    print("OK rowsharded_pool")

    # ---- async front-end: deadline flush, concurrency, refresh ------------
    deadline = 0.2
    engine = DistributedQueryEngine(sharded)
    engine.sigma([[0]])     # compile before the deadline clock matters
    fe = AsyncFrontEnd(MicroBatcher(engine, cache=ResultCache()),
                       default_deadline=deadline, flush_slots=8,
                       refresh_every=1.5)
    # a lone request must flush at its deadline, not wait for a full slot
    lone = fe.submit_sigma([3, 50, 99])
    v = lone.result(timeout=30)
    assert v == engine.sigma([[3, 50, 99]])[0]
    assert fe.stats.deadline_flushes >= 1, fe.stats
    # concurrent callers from many threads, correct fan-out
    futs, expect = [], {}
    lock = threading.Lock()

    def client(i):
        q = [i % 50, (i * 7) % 50 + 50]
        f = fe.submit_sigma(q)
        with lock:
            futs.append((f, tuple(q)))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Drain every future BEFORE computing references: a direct
    # engine.sigma here while the dispatch thread is mid-flush would run
    # two 8-participant collective programs concurrently, and the CPU
    # backend's shared rendezvous pool can starve-deadlock on that.
    got = [(f.result(timeout=30), q) for f, q in futs]
    for v_got, q in got:
        assert v_got == engine.sigma([list(q)])[0], q
    # no request waited past its deadline (dispatch-start vs submit time);
    # generous epsilon for CPU scheduling jitter
    assert fe.stats.max_queue_wait <= deadline + 0.25, fe.stats
    time.sleep(2.0)                       # let the background refresh fire
    fe.close()
    assert fe.stats.refreshes >= 1, fe.stats
    # refresh bumped the epoch → old answers recompute under the new pool
    assert engine.store.epoch >= 1
    # close() joined the worker: the version must now hold still across a
    # full refresh period
    ver_after_close = engine.store.version
    time.sleep(1.6)
    assert engine.store.version == ver_after_close
    print("OK async_frontend")

    # ---- streaming deltas on sharded pools ≡ cold rebuild ≡ 1-device ------
    # A graph delta swept through the 8-shard data_parallel store via the
    # incremental (dirty-slot-only) path must leave the pool bit-identical
    # to (a) a cold rebuild of the same batch indices on the mutated pair
    # and (b) a 1-device dense SketchStore built fresh on that pair — for
    # both diffusions.  The donated-scatter stack must track it in place.
    from repro.stream import (DirtySlotTracker, cold_rebuild_batches,
                              incremental_refresh, random_delta)
    for diffusion in ("ic", "lt"):
        st_cfg = PoolConfig(max_batches=32, spec=sampling.SamplerSpec(
            diffusion=diffusion, backend="data_parallel", num_colors=64,
            master_seed=3, tile_size=64, frontier="sparse"))
        st8 = ShardedSketchStore(g2, st_cfg, mesh8)
        st8.ensure(8)
        st8.visited_stack()
        tracker = DirtySlotTracker.for_store(st8)
        rng = np.random.default_rng(29)
        delta = random_delta(st8.graph, rng, num_deletes=5, num_inserts=5)
        report = incremental_refresh(st8, tracker, delta)
        assert st8.version[0] == 1 and report.dirty_slots >= 1
        cold = cold_rebuild_batches(st8)
        single = SketchStore(st8.graph,
                             PoolConfig(max_batches=32,
                                        spec=st_cfg.spec.replace(
                                            backend="dense")),
                             g_rev=st8.g_rev)
        single.ensure(8)
        for got, want, ref in zip(st8.batches, cold, single.batches):
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(want.visited))
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(ref.visited))
            # Counters compare within one backend only: the shard_map
            # sampler reports the -1 "not tracked" sentinel.
            assert got.fused_edge_visits == want.fused_edge_visits
            assert got.unfused_edge_visits == want.unfused_edge_visits
        np.testing.assert_array_equal(
            np.asarray(st8.visited_stack()),
            np.stack([np.asarray(b.visited) for b in cold]))
    print("OK stream_updates")


if __name__ == "__main__":
    main()
