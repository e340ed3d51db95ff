"""Streaming graph updates: delta application with stable CSR edge ids,
dirty-slot tracking, churn-proportional incremental pool refresh, and the
serving-tier write path (`repro.stream` + `ServingTier.apply_delta`)."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import lt as lt_lib
from repro.graph import csr, generators
from repro.sampling import SamplerSpec
from repro.serve.influence import PoolConfig, SketchStore
from repro.serve.tier import EpochMixError, ServingTier, ShedError
from repro.stream import (DirtySlotTracker, EdgeDelta, apply_delta,
                          cold_rebuild_batches, compact_graph, compact_store,
                          incremental_refresh, plan_refresh, apply_plan,
                          random_delta, tombstone_fraction,
                          touched_row_blocks)


@pytest.fixture(scope="module")
def graph():
    return csr.dedupe(generators.powerlaw_cluster(
        300, 6.0, prob=(0.05, 0.3), seed=17))


def _arrays(g):
    """Every array a bit-identity claim is made over, padding included."""
    return (np.asarray(g.src), np.asarray(g.dst), np.asarray(g.prob),
            np.asarray(g.indptr), g.num_edges, g.padded_edges)


def _assert_graph_identical(a, b):
    for x, y in zip(_arrays(a), _arrays(b)):
        np.testing.assert_array_equal(x, y)


def _absent_pairs(g, count, seed=0):
    e = g.num_edges
    taken = set(zip(np.asarray(g.src)[:e].tolist(),
                    np.asarray(g.dst)[:e].tolist()))
    rng = np.random.default_rng(seed)
    pairs = []
    while len(pairs) < count:
        s, d = (int(x) for x in rng.integers(0, g.num_vertices, 2))
        if s != d and (s, d) not in taken:
            taken.add((s, d))
            pairs.append((s, d))
    return pairs


def _stream_store(g, *, diffusion="ic", frontier="dense", batches=6,
                  colors=32, tile=64, seed=9, mesh=None):
    """A dense-backend pool, or with ``mesh`` a data_parallel one."""
    spec = SamplerSpec(diffusion=diffusion, backend="dense",
                       num_colors=colors, master_seed=seed,
                       tile_size=tile, frontier=frontier)
    if mesh is None:
        store = SketchStore(g, PoolConfig(max_batches=16, spec=spec))
    else:
        from repro.serve.distributed import ShardedSketchStore
        store = ShardedSketchStore(
            g, PoolConfig(max_batches=16,
                          spec=spec.replace(backend="data_parallel")), mesh)
    store.ensure(batches)
    return store


# --------------------------------------------------------------- EdgeDelta
def test_edge_delta_validation_and_views():
    d = EdgeDelta.concat(EdgeDelta.inserts([1, 2], [3, 4], [0.5, 0.25]),
                         EdgeDelta.deletes([7], [8]))
    assert (len(d), d.num_inserts, d.num_deletes) == (3, 2, 1)
    r = d.reversed()
    np.testing.assert_array_equal(r.src, d.dst)
    np.testing.assert_array_equal(r.dst, d.src)
    inv = EdgeDelta.inserts([1], [2], [0.5]).inverse()
    assert inv.num_deletes == 1 and not inv.insert.any()

    with pytest.raises(ValueError, match="share one length"):
        EdgeDelta([1, 2], [3], [0.5], [True])
    for w in (0.0, -1.0, np.inf, np.nan):
        with pytest.raises(ValueError, match="finite and > 0"):
            EdgeDelta.inserts([1], [2], [w])
    with pytest.raises(ValueError, match="duplicate"):
        EdgeDelta.concat(EdgeDelta.inserts([1], [2], [0.5]),
                         EdgeDelta.deletes([1], [2]))
    with pytest.raises(ValueError, match="all-insert"):
        EdgeDelta.deletes([1], [2]).inverse()


def test_apply_delta_rejects_bad_ops(graph):
    e = graph.num_edges
    s0, d0 = int(np.asarray(graph.src)[0]), int(np.asarray(graph.dst)[0])
    (sa, da), = _absent_pairs(graph, 1)
    with pytest.raises(KeyError, match="absent"):
        apply_delta(graph, EdgeDelta.deletes([sa], [da]))
    with pytest.raises(KeyError, match="live"):
        apply_delta(graph, EdgeDelta.inserts([s0], [d0], [0.5]))
    with pytest.raises(ValueError, match="outside"):
        apply_delta(graph, EdgeDelta.deletes([graph.num_vertices], [0]))
    assert graph.num_edges == e, "apply_delta must be functional"


# ----------------------------------------------------- round-trip property
@settings(max_examples=12, deadline=None)
@given(st.lists(st.integers(0, 2 ** 30), min_size=1, max_size=10))
def test_insert_then_inverse_roundtrip_is_bit_identical(draws):
    """ISSUE satellite: apply_delta(apply_delta(g, ins), del_of_ins) is
    bit-identical to g — indices, indptr, weights, array LENGTHS — via
    the population-neutral extend/trim policy."""
    g = test_insert_then_inverse_roundtrip_is_bit_identical._graph
    pool = test_insert_then_inverse_roundtrip_is_bit_identical._pool
    pairs = sorted({pool[v % len(pool)] for v in draws})
    ins = EdgeDelta.inserts([p[0] for p in pairs], [p[1] for p in pairs],
                            np.linspace(0.05, 0.4, len(pairs)))
    g1, a1 = apply_delta(g, ins)
    assert a1.appended == len(pairs) and a1.inserted == len(pairs)
    assert g1.num_edges == g.num_edges + len(pairs)
    g2, a2 = apply_delta(g1, ins.inverse())
    assert a2.trimmed >= len(pairs)
    _assert_graph_identical(g2, g)


test_insert_then_inverse_roundtrip_is_bit_identical._graph = csr.dedupe(
    generators.powerlaw_cluster(200, 5.0, prob=(0.05, 0.3), seed=3))
test_insert_then_inverse_roundtrip_is_bit_identical._pool = _absent_pairs(
    test_insert_then_inverse_roundtrip_is_bit_identical._graph, 64)


@settings(max_examples=8, deadline=None)
@given(st.lists(st.integers(0, 2 ** 30), min_size=1, max_size=8))
def test_lt_roundtrip_bit_identical_while_sums_stay_below_one(draws):
    """On an LT-normalized graph the round-trip also restores the
    NORMALIZED weights bit-for-bit — provided the in-sums stay ≤ 1
    throughout (normalization is a lossy down-only projection, so tiny
    insert weights keep it the identity in both directions)."""
    g = test_lt_roundtrip_bit_identical_while_sums_stay_below_one._graph
    pool = test_lt_roundtrip_bit_identical_while_sums_stay_below_one._pool
    pairs = sorted({pool[v % len(pool)] for v in draws})
    ins = EdgeDelta.inserts([p[0] for p in pairs], [p[1] for p in pairs],
                            np.full(len(pairs), 1e-4, np.float32))
    g1, _ = apply_delta(g, ins, lt_normalized=True)
    g2, _ = apply_delta(g1, ins.inverse(), lt_normalized=True)
    _assert_graph_identical(g2, g)


test_lt_roundtrip_bit_identical_while_sums_stay_below_one._graph = \
    lt_lib.normalize_lt_weights(csr.dedupe(generators.powerlaw_cluster(
        200, 5.0, prob=(0.01, 0.02), seed=5)))
test_lt_roundtrip_bit_identical_while_sums_stay_below_one._pool = \
    _absent_pairs(
        test_lt_roundtrip_bit_identical_while_sums_stay_below_one._graph, 48)


def test_tombstone_then_resurrect_restores_bits(graph):
    e = graph.num_edges
    pos = np.array([5, 40, e - 100])
    s = np.asarray(graph.src)[pos]
    d = np.asarray(graph.dst)[pos]
    w = np.asarray(graph.prob)[pos]
    g1, a1 = apply_delta(graph, EdgeDelta.deletes(s, d))
    assert a1.deleted == 3 and a1.trimmed == 0
    assert np.asarray(g1.prob)[pos].tolist() == [0.0] * 3, "tombstones"
    np.testing.assert_array_equal(np.asarray(g1.src), np.asarray(graph.src))
    g2, a2 = apply_delta(g1, EdgeDelta.inserts(s, d, w))
    assert a2.resurrected == 3 and a2.appended == 0
    _assert_graph_identical(g2, graph)


def test_fresh_insert_and_trim_are_population_neutral(graph):
    """Padding slots carry src 0 and the dense work counters see them —
    the pad population (len - num_edges) must survive both extend and
    trim, or every row-0-visiting slot would dirty on ANY insert."""
    pad = len(np.asarray(graph.src)) - graph.num_edges
    pairs = _absent_pairs(graph, 4, seed=2)
    ins = EdgeDelta.inserts([p[0] for p in pairs], [p[1] for p in pairs],
                            [0.1] * 4)
    g1, a1 = apply_delta(graph, ins)
    assert a1.appended == 4
    assert len(np.asarray(g1.src)) - g1.num_edges == pad
    assert 0 not in set(a1.touched_rows.tolist()) - {p[0] for p in pairs}, \
        "row 0 must not be touched by the pad bookkeeping"
    g2, a2 = apply_delta(g1, ins.inverse())
    assert a2.trimmed >= 4
    assert len(np.asarray(g2.src)) - g2.num_edges == pad
    _assert_graph_identical(g2, graph)


def test_touched_rows_and_blocks(graph):
    e = graph.num_edges
    s0 = int(np.asarray(graph.src)[7])
    d0 = int(np.asarray(graph.dst)[7])
    _, a = apply_delta(graph, EdgeDelta.deletes([s0], [d0]))
    assert s0 in a.touched_rows
    blocks = touched_row_blocks(a.touched_rows, 64)
    assert s0 // 64 in blocks
    # LT: re-normalizing row s0 rescales the weights of ALL its live
    # edges, not just the deleted one; every row whose weights changed is
    # touched.
    gn = lt_lib.normalize_lt_weights(graph)
    gd, an = apply_delta(gn, EdgeDelta.deletes([s0], [d0]),
                         lt_normalized=True)
    changed = np.asarray(gd.prob)[:e] != np.asarray(gn.prob)[:e]
    rows = set(np.asarray(gn.src)[:e][changed].tolist())
    assert s0 in rows
    assert rows <= set(an.touched_rows.tolist())


def test_confined_lt_renorm_matches_full_normalize(graph):
    """The confined re-normalization must replicate `normalize_lt_weights`
    arithmetic exactly: structural-apply + full normalize on the whole
    graph is bit-identical to the lt_normalized=True fused path."""
    gn = lt_lib.normalize_lt_weights(graph)
    rng = np.random.default_rng(4)
    delta = random_delta(gn, rng, num_deletes=6, num_inserts=6,
                         weight_range=(0.3, 0.9))
    fused, _ = apply_delta(gn, delta, lt_normalized=True)
    structural, _ = apply_delta(gn, delta)
    reference = lt_lib.normalize_lt_weights(structural)
    _assert_graph_identical(fused, reference)


def test_normalize_lt_weights_is_order_preserving_and_idempotent(graph):
    # Simulate a streamed (un-sorted) edge array: apply a delta first.
    g1, _ = apply_delta(graph, EdgeDelta.inserts(
        *zip(*_absent_pairs(graph, 3, seed=6)), [0.9, 0.8, 0.7]))
    gn = lt_lib.normalize_lt_weights(g1)
    np.testing.assert_array_equal(np.asarray(gn.src), np.asarray(g1.src))
    np.testing.assert_array_equal(np.asarray(gn.dst), np.asarray(g1.dst))
    np.testing.assert_array_equal(np.asarray(gn.indptr),
                                  np.asarray(g1.indptr))
    e = gn.num_edges
    row_sum = np.zeros(gn.num_vertices)
    np.add.at(row_sum, np.asarray(gn.src)[:e],
              np.asarray(gn.prob)[:e].astype(np.float64))
    assert row_sum.max() <= 1.0 + 1e-6
    _assert_graph_identical(lt_lib.normalize_lt_weights(gn), gn)


def test_random_delta_is_well_formed_and_confined(graph):
    rng = np.random.default_rng(11)
    rows = np.arange(64, 192)
    d = random_delta(graph, rng, num_deletes=5, num_inserts=5,
                     dst_rows=rows)
    assert d.num_deletes == 5 and d.num_inserts == 5
    assert np.isin(d.dst, rows).all()
    apply_delta(graph, d)   # applies cleanly


# ---------------------------------------------------------------- tracker
def test_tracker_records_queries_and_stats(graph):
    store = _stream_store(graph, frontier="sparse")
    tracker = DirtySlotTracker.for_store(store)
    assert tracker.num_slots == len(store.batches)
    assert tracker.num_row_blocks == -(-graph.num_vertices // 64)
    # Recorded bits match the masks they were derived from.
    vis = np.asarray(store.batches[0].visited)
    rows = np.nonzero((vis != 0).any(axis=1))[0]
    np.testing.assert_array_equal(tracker.visited_blocks(0),
                                  np.unique(rows // 64))
    hit = tracker.dirty_slots([int(rows[0]) // 64])
    assert 0 in hit
    with pytest.raises(ValueError, match="row block outside"):
        tracker.dirty_slots([tracker.num_row_blocks])
    stats = tracker.stats()
    assert stats["slots"] == tracker.num_slots
    assert stats["tracker_bytes"] == tracker._bits.nbytes
    assert stats["mean_visited_blocks"] > 0


def test_tracker_sync_rerecords_only_changed_slots(graph):
    store = _stream_store(graph)
    tracker = DirtySlotTracker.for_store(store)
    assert tracker.sync(store) == 0, "clean re-sync is free"
    refreshed = store.refresh(fraction=0.34)
    assert tracker.sync(store) == len(refreshed)
    store.shrink(3)
    tracker.sync(store)
    assert tracker.num_slots == 3
    store.ensure(5)
    assert tracker.sync(store) == 2
    # A graph-epoch bump invalidates every recorded slot.
    store.graph_epoch += 1
    assert tracker.sync(store) == 5


# ---------------------------------------------------- incremental refresh
@pytest.mark.parametrize("diffusion,frontier", [("ic", "dense"),
                                                ("ic", "sparse"),
                                                ("lt", "sparse")])
def test_incremental_refresh_matches_cold_rebuild(graph, diffusion,
                                                  frontier):
    store = _stream_store(graph, diffusion=diffusion, frontier=frontier)
    store.visited_stack()
    tracker = DirtySlotTracker.for_store(store)
    rng = np.random.default_rng(21)
    delta = random_delta(store.graph, rng, num_deletes=4, num_inserts=4)
    v0 = store.version
    report = incremental_refresh(store, tracker, delta)
    assert store.version == (v0[0] + 1, v0[1], v0[2])
    assert report.graph_epoch == store.graph_epoch
    assert 0 < report.dirty_slots <= report.total_slots
    cold = cold_rebuild_batches(store)
    for got, want in zip(store.batches, cold):
        np.testing.assert_array_equal(np.asarray(got.visited),
                                      np.asarray(want.visited))
        assert got.fused_edge_visits == want.fused_edge_visits
        assert got.unfused_edge_visits == want.unfused_edge_visits
    # The in-place stack followed the donated scatter.
    np.testing.assert_array_equal(
        np.asarray(store.visited_stack()),
        np.stack([np.asarray(b.visited) for b in cold]))


def test_clean_slots_are_not_resampled(graph):
    store = _stream_store(graph, frontier="sparse", batches=8)
    tracker = DirtySlotTracker.for_store(store)
    rng = np.random.default_rng(31)
    delta = random_delta(store.graph, rng, num_deletes=2, num_inserts=0,
                         dst_rows=np.arange(64))
    before = list(store.batches)
    plan = plan_refresh(store, tracker, delta)
    apply_plan(store, plan)
    assert plan.dirty_slots, "a live-edge delete must dirty someone"
    for i, b in enumerate(before):
        if i not in plan.dirty_slots:
            assert store.batches[i] is b, \
                "clean slots must keep their batch OBJECT (no resample)"
    cold = cold_rebuild_batches(store)
    for got, want in zip(store.batches, cold):
        np.testing.assert_array_equal(np.asarray(got.visited),
                                      np.asarray(want.visited))
        assert got.fused_edge_visits == want.fused_edge_visits


# ------------------------------------- values-only frontier-index patch
def test_patch_frontier_index_matches_fresh_build(graph):
    from repro.core import sparse
    g_rev0 = csr.transpose(graph)
    fidx = sparse.build_frontier_index(g_rev0, tile_rows=64)
    rng = np.random.default_rng(71)
    delta = random_delta(graph, rng, num_deletes=6, num_inserts=0)
    g_rev2, applied = apply_delta(g_rev0, delta.reversed())
    blocks = touched_row_blocks(applied.touched_rows, 64)
    assert len(blocks), "a live-edge delete must touch a row block"
    patched = sparse.patch_frontier_index(fidx, g_rev2, blocks)
    fresh = sparse.build_frontier_index(g_rev2, tile_rows=64)
    for name in ("blk_src", "blk_dst", "blk_prob", "blk_eid", "blk_valid",
                 "blk_rowblock"):
        np.testing.assert_array_equal(
            np.asarray(getattr(patched, name)),
            np.asarray(getattr(fresh, name)), err_msg=name)
    assert (patched.num_blocks, patched.edge_block, patched.tile_rows) == \
        (fresh.num_blocks, fresh.edge_block, fresh.tile_rows)


def test_values_only_delta_patches_sampler_in_place(graph):
    """Dense, and data_parallel on a one-device mesh, whose shards run the
    dense sampler's block program on its patched data: a rebind keeps the
    module-level compiled program (one cache entry per statics)."""
    from repro.launch.mesh import make_mesh
    from repro.sampling import sampler as sampler_mod
    for mesh in (None, make_mesh((1,), ("data",))):
        store = _stream_store(graph, frontier="sparse", batches=3, mesh=mesh)
        s0 = store.sampler
        programs = len(sampler_mod._DP_BLOCK_FNS)
        tracker = DirtySlotTracker.for_store(store)
        rng = np.random.default_rng(73)
        incremental_refresh(store, tracker,
                            random_delta(store.graph, rng, num_deletes=3,
                                         num_inserts=0))
        assert store.sampler is s0, \
            "a tombstone-only delta must patch the frontier index in place"
        (sa, da), = _absent_pairs(store.graph, 1, seed=73)
        incremental_refresh(store, tracker,
                            EdgeDelta.inserts([sa], [da], [0.05]))
        assert store.sampler is not s0, \
            "an appending insert changes the edge layout → full rebuild"
        assert len(sampler_mod._DP_BLOCK_FNS) == programs
        for got, want in zip(store.batches, cold_rebuild_batches(store)):
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(want.visited))


# ------------------------------------------------------------- compaction
def test_compact_graph_drops_tombstones_bit_for_bit(graph):
    rng = np.random.default_rng(81)
    delta = random_delta(graph, rng, num_deletes=8, num_inserts=0)
    g1, _ = apply_delta(graph, delta)
    assert tombstone_fraction(graph) == 0.0
    assert tombstone_fraction(g1) == pytest.approx(8 / g1.num_edges)
    g2, g_rev2 = compact_graph(g1)
    assert g2.num_edges == g1.num_edges - 8
    assert tombstone_fraction(g2) == 0.0

    def live_edges(g, sel):
        e = g.num_edges
        s, d, p = (np.asarray(a)[:e][sel]
                   for a in (g.src, g.dst, g.prob))
        order = np.lexsort((d, s))
        return s[order], d[order], p[order]

    e1 = g1.num_edges
    live = np.asarray(g1.prob)[:e1] > 0
    # Live weights carry over bit-for-bit (no union-merge round-trip).
    for a, b in zip(live_edges(g1, live), live_edges(g2, slice(None))):
        np.testing.assert_array_equal(a, b)
    _assert_graph_identical(g_rev2, csr.transpose(g2))


def test_compact_store_matches_cold_build_on_compacted_graph(graph):
    store = _stream_store(graph, frontier="sparse", batches=4)
    tracker = DirtySlotTracker.for_store(store)
    rng = np.random.default_rng(83)
    incremental_refresh(store, tracker,
                        random_delta(store.graph, rng, num_deletes=6,
                                     num_inserts=2))
    frac = tombstone_fraction(store.graph)
    assert frac > 0
    reclaimed = compact_store(store)
    assert reclaimed == pytest.approx(frac)
    assert tombstone_fraction(store.graph) == 0.0
    cold = cold_rebuild_batches(store)
    for got, want in zip(store.batches, cold):
        np.testing.assert_array_equal(np.asarray(got.visited),
                                      np.asarray(want.visited))
        assert got.fused_edge_visits == want.fused_edge_visits
    np.testing.assert_array_equal(
        np.asarray(store.visited_stack()),
        np.stack([np.asarray(b.visited) for b in cold]))


def test_tier_maybe_compact_policy_and_counter(graph):
    store = _stream_store(graph, frontier="sparse", batches=3)
    with ServingTier.build(store, replicas=2, quota_qps=None,
                           default_deadline=0.05) as tier:
        rng = np.random.default_rng(91)
        tier.apply_delta("ops", random_delta(store.graph, rng,
                                             num_deletes=5, num_inserts=0))
        r0 = tier.group.replicas[0].store
        frac = tombstone_fraction(r0.graph)
        assert frac > 0
        assert not tier.maybe_compact(threshold=0.5), \
            "below threshold → no rebuild"
        assert tier.maybe_compact(threshold=0.0)
        assert tombstone_fraction(r0.graph) == 0.0
        assert not tier.maybe_compact(threshold=0.0), \
            "a freshly compacted graph has nothing to reclaim"
        assert tier.group.consistent()
        cold = cold_rebuild_batches(r0)
        for got, want in zip(r0.batches, cold):
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(want.visited))
        snap = tier.snapshot()
        assert snap["stream"]["compactions"] == 1
        assert snap["stream"]["compacted_fraction"]["count"] == 1
        # Queries keep flowing on the renumbered edge ids.
        tier.gather([tier.submit_sigma("ops", [3, 17, 29])])


# ------------------------------------------------- version + persistence
def test_graph_epoch_in_version_clone_and_snapshot(graph, tmp_path):
    store = _stream_store(graph, batches=3)
    tracker = DirtySlotTracker.for_store(store)
    rng = np.random.default_rng(41)
    incremental_refresh(store, tracker,
                        random_delta(store.graph, rng, num_deletes=2,
                                     num_inserts=2))
    assert store.version[0] == 1
    assert store.clone().version == store.version

    store.save(str(tmp_path))
    back = SketchStore.restore(str(tmp_path), store.graph, store.config,
                               g_rev=store.g_rev)
    assert back.version == store.version
    for got, want in zip(back.batches, store.batches):
        np.testing.assert_array_equal(np.asarray(got.visited),
                                      np.asarray(want.visited))


def test_restore_of_pre_streaming_snapshot_defaults_graph_epoch(
        graph, tmp_path, monkeypatch):
    store = _stream_store(graph, batches=2)
    store.graph_epoch = 7
    orig_tree = SketchStore._tree

    def legacy_tree(self):
        tree = orig_tree(self)
        tree["counters"] = tree["counters"][:4]   # pre-streaming format
        return tree

    monkeypatch.setattr(SketchStore, "_tree", legacy_tree)
    store.save(str(tmp_path))
    monkeypatch.undo()
    back = SketchStore.restore(str(tmp_path), graph, store.config)
    assert back.graph_epoch == 0
    assert back.version == (0, store.epoch, len(store.batches))


# ------------------------------------------------------------------ tier
def test_tier_apply_delta_end_to_end(graph):
    store = _stream_store(graph, frontier="sparse", batches=4)
    with ServingTier.build(store, replicas=2, quota_qps=None,
                           default_deadline=0.05) as tier:
        pre = [tier.submit_sigma("ops", [3, 17, 29])]
        tier.gather(pre)
        rng = np.random.default_rng(51)
        delta = random_delta(store.graph, rng, num_deletes=3,
                             num_inserts=3)
        report = tier.apply_delta("ops", delta)
        assert report.inserted == 3 and report.deleted == 3
        versions = {r.version for r in tier.group.replicas}
        assert len(versions) == 1 and next(iter(versions))[0] == 1
        assert tier.group.consistent()
        # Replicas swept atomically under one plan → still bit-identical
        # to a cold rebuild on the mutated pair.
        r0 = tier.group.replicas[0].store
        cold = cold_rebuild_batches(r0)
        for got, want in zip(r0.batches, cold):
            np.testing.assert_array_equal(np.asarray(got.visited),
                                          np.asarray(want.visited))
        # Pre-delta futures can never mix with post-delta ones.
        post = [tier.submit_sigma("ops", [3, 17, 29])]
        with pytest.raises(EpochMixError):
            tier.gather(pre + post)
        tier.gather(post)

        tier.set_quota("vandal", rate=0.01, burst=1)
        tier.apply_delta("vandal", EdgeDelta.deletes([], []))
        with pytest.raises(ShedError):
            tier.apply_delta("vandal", EdgeDelta.deletes([], []))

        snap = tier.snapshot()
        assert snap["stream"]["deltas_applied"] == 2
        assert snap["stream"]["tracker"]["slots"] == 4
        assert snap["stream"]["tracker"]["deltas_seen"] == 2
        assert snap["stream"]["refresh_s"]["count"] == 2
