"""The unified `Sampler` facade — one traversal-spec entry point over every
(diffusion × backend) combination.

All backends honor one RNG contract, owned here: batch ``b`` under
``master_seed`` draws its roots from ``rrr.batch_starts`` and its counter
seed from ``rrr.batch_seed``, so a given ``(master_seed, batch_index)`` is
**bit-identical across every backend that supports the diffusion** — dense,
tiled, Pallas-kernel and shard_map data-parallel runs all reproduce the
same ``(V, W)`` visited mask.  That invariant is what lets a sketch pool be
built under one backend, extended under another, and served from any mesh
shape without changing a single answer.

Backends:

* ``dense``          — CSR edge-centric sweep (`core.traversal.run_fused` /
                       `core.lt.run_fused_lt`), one batch per call on the
                       default device.
* ``tiled``          — block-sparse tile expansion, pure-jnp oracle
                       (`core.tiled_traversal.run_fused_tiled`; LT via
                       `run_fused_lt_tiled`).
* ``kernel``         — same tile layout through the Pallas kernels
                       (``fused_expand`` for IC, ``lt_select_expand`` for
                       LT).
* ``data_parallel``  — batch *blocks* over a mesh axis via ``shard_map``:
                       each shard traverses its own contiguous slice of the
                       block with per-batch RNG streams, on its own device
                       — pool builds parallelize across the mesh instead of
                       staging one batch at a time through the default
                       device (the ROADMAP's distributed-sampling item).
* ``graph_parallel`` — the graph itself partitioned: destination rows shard
                       over ``spec.model_axis`` (1-D tile partition, cached
                       on the sampler), batch blocks over ``spec.mesh_axis``
                       — so graphs bigger than one device's memory sample
                       at all, and sample parallelism still composes on the
                       same 2-D (data × model) mesh.  Per-level collectives
                       (frontier all-gather + termination psum) name only
                       the model axis.

LT diffusion: the facade owns live-edge weight normalization
(`lt.normalize_lt_weights`, idempotent) on the reversed graph, so consumers
can hand any IC-weighted graph to an LT sampler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lt, rrr, tiled_traversal, tiles
from repro.graph import csr
from repro.sampling.spec import SamplerSpec

__all__ = ["Sampler", "make_sampler"]


class Sampler:
    """Backend-agnostic sampling handle bound to one (graph, spec) pair.

    ``sample(batch_index)`` returns one `rrr.RRRBatch`;
    ``sample_many(batch_indices)`` a list of them (backends may batch the
    work); ``sample_stacked(batch_indices)`` the stacked ``(B, V, W)``
    visited masks (sharded over the mesh for the data_parallel backend).
    """

    def __init__(self, g: csr.Graph | None, spec: SamplerSpec, *,
                 g_rev: csr.Graph | None = None):
        if g is None and g_rev is None:
            raise ValueError("need g or g_rev")
        self.graph = g
        self.spec = spec
        g_rev = g_rev if g_rev is not None else csr.transpose(g)
        if spec.diffusion == "lt":
            # Idempotent: an already-normalized graph passes through.
            g_rev = lt.normalize_lt_weights(g_rev)
        self.g_rev = g_rev

    # ------------------------------------------------------------ RNG
    def batch_starts(self, batch_index: int) -> jnp.ndarray:
        """(num_colors,) roots — the shared cross-backend derivation."""
        return rrr.batch_starts(self.g_rev.num_vertices, self.spec.num_colors,
                                self.spec.master_seed, batch_index,
                                sort=self.spec.sort_starts)

    def batch_seed(self, batch_index: int) -> jnp.ndarray:
        return rrr.batch_seed(self.spec.master_seed, batch_index)

    def _dst_segments(self):
        """`traversal.dst_segments` of the reversed graph, built once per
        sampler (a function of the edge destinations alone, so it also
        survives values-only rebinds) and handed to every dense-path
        program — which then contains no sort."""
        if getattr(self, "_segments", None) is None:
            from repro.core import traversal
            self._segments = traversal.dst_segments(self.g_rev.dst,
                                                    self.g_rev.num_vertices)
        return self._segments

    # ------------------------------------------------------- sampling
    def sample(self, batch_index: int) -> rrr.RRRBatch:
        raise NotImplementedError

    def sample_many(self, batch_indices) -> list[rrr.RRRBatch]:
        return [self.sample(int(b)) for b in batch_indices]

    def sample_stacked(self, batch_indices) -> jnp.ndarray:
        """(B, V, W) stacked visited masks for the given batch indices."""
        return rrr.stack_visited(self.sample_many(batch_indices))

    # ------------------------------------------------------- rebinding
    def rebind(self, g: csr.Graph, g_rev: csr.Graph,
               touched_row_blocks=None) -> "Sampler":
        """Sampler for the delta-mutated ``(g, g_rev)`` pair under the SAME
        spec (and mesh, for mesh backends) — the `repro.stream` hook.

        The default is a full rebuild.  Backends with expensive host-side
        graph indexes override this with a values-only fast path: when the
        delta kept the edge arrays' layout (tombstone / resurrect / LT
        renorm — `_same_edge_layout`), they patch probabilities in place,
        confined to ``touched_row_blocks`` where an index is row-tiled,
        and return ``self``.  Either way the result is bit-identical to a
        fresh ``make_sampler`` on the new graphs.
        """
        return make_sampler(g, self.spec, getattr(self, "mesh", None),
                            g_rev=g_rev)

    def _try_patch_fidx(self, g, g_rev, touched_row_blocks) -> bool:
        """Shared sparse-frontier fast path: patch the cached
        `FrontierIndex` (and LT prefixes) in place when the delta is
        values-only and names its touched row blocks.  True on success."""
        spec = self.spec
        if (spec.frontier != "sparse" or touched_row_blocks is None
                or getattr(self, "_fidx", None) is None):
            return False
        if spec.diffusion == "lt":
            g_rev = lt.normalize_lt_weights(g_rev)   # idempotent
        if not _same_edge_layout(self.g_rev, g_rev):
            return False
        from repro.core import sparse
        self.graph = g
        self.g_rev = g_rev
        cb = None
        if spec.diffusion == "lt":
            self._cb = jnp.asarray(lt.selection_cum_before(self.g_rev))
            cb = np.asarray(self._cb)
        self._fidx = sparse.patch_frontier_index(
            self._fidx, self.g_rev, touched_row_blocks, cb=cb)
        return True

    # -------------------------------------------- sparse-frontier shared
    def _sparse_index(self, cb=None):
        """(FrontierIndex, bucket ladder) for ``spec.frontier == "sparse"``
        — ONE construction path for every backend that compacts edge
        blocks (tile_rows follows ``spec.tile_size``, capacity follows
        ``spec.frontier_capacity``).  ``cb`` attaches the LT
        selection-CDF prefixes."""
        from repro.core import sparse
        fidx = sparse.build_frontier_index(
            self.g_rev, tile_rows=self.spec.tile_size, cb=cb)
        return fidx, sparse.bucket_ladder(fidx.num_blocks,
                                          self.spec.frontier_capacity)

    # ------------------------------------------------- mesh-backend shared
    def _block_inputs(self, idx: list[int], shards: int):
        """(padded_len, starts (Bp, C), seeds (Bp,)) for a block padded to a
        multiple of ``shards`` with repeats of the last index (identical
        work, result dropped).  Roots come from the EXACT scalar
        ``jax.random.key(...)`` path the dense backend uses — the
        cross-backend bit-identity contract — so they are derived per batch
        and stacked ((B, C) ints, cheap next to the (B, V, W) traversal);
        seeds are pure uint32 arithmetic and vectorize host-side."""
        padded = -(-len(idx) // shards) * shards
        full = idx + [idx[-1]] * (padded - len(idx))
        starts = jnp.stack([self.batch_starts(b) for b in full])
        seeds = jnp.asarray(rrr.batch_seeds(self.spec.master_seed, full))
        return padded, starts, seeds


def _same_edge_layout(a: csr.Graph, b: csr.Graph) -> bool:
    """True when ``b`` kept ``a``'s exact edge-array layout (same shapes,
    same (src, dst) at every slot) — i.e. the mutation only changed
    probabilities in place, so per-position structures (tile slots, edge
    blocks, RNG edge ids) carry over unchanged."""
    return (a.num_edges == b.num_edges
            and a.padded_edges == b.padded_edges
            and np.array_equal(np.asarray(a.src), np.asarray(b.src))
            and np.array_equal(np.asarray(a.dst), np.asarray(b.dst)))


class DenseSampler(Sampler):
    """CSR edge-centric path — IC and LT.

    ``spec.frontier == "sparse"`` swaps the per-level edge sweep for the
    `core.sparse` active-tile compaction engine (edge blocks grouped by
    source row-block, gathered per level through a capacity-bucket
    ladder) — bit-identical masks AND work counters, per-level cost
    proportional to the live frontier instead of E.

    ``sample_many`` fuses the whole block into ONE dispatch (``lax.map``
    over batches inside one jit — `traversal.run_fused_block` /
    `sparse.sparse_block` / `lt.run_fused_lt_block`), so pool builds and
    refreshes stop paying per-batch dispatch.  IC blocks keep real
    edge-visit totals; LT carries the usual -1 sentinel.
    """

    def __init__(self, g, spec, *, g_rev=None):
        super().__init__(g, spec, g_rev=g_rev)
        self._fidx = None
        self._ladder = None
        self._cb = None

    # ----------------------------------------------------- lazy indexes
    def _lt_cb(self):
        if self._cb is None:
            self._cb = jnp.asarray(lt.selection_cum_before(self.g_rev))
        return self._cb

    def _frontier_index(self):
        if self._fidx is None:
            cb = (np.asarray(self._lt_cb())
                  if self.spec.diffusion == "lt" else None)
            self._fidx, self._ladder = self._sparse_index(cb)
        return self._fidx

    # -------------------------------------------------------- sampling
    def sample(self, batch_index: int) -> rrr.RRRBatch:
        if self.spec.frontier == "sparse":
            from repro.core import sparse
            fidx = self._frontier_index()
            starts = self.batch_starts(batch_index)
            seed = self.batch_seed(batch_index)
            if self.spec.diffusion == "lt":
                visited = sparse.run_fused_lt_sparse(
                    fidx, starts, self.spec.num_colors, seed,
                    max_levels=self.spec.max_iters, ladder=self._ladder)
                return rrr.RRRBatch(visited, np.asarray(starts),
                                    int(batch_index), -1, -1)
            res = sparse.run_fused_sparse(
                fidx, starts, self.spec.num_colors, seed,
                max_levels=self.spec.max_iters, ladder=self._ladder)
            return rrr.RRRBatch(
                res.visited, np.asarray(starts), int(batch_index),
                int(res.stats.fused_edge_visits.sum()),
                int(res.stats.unfused_edge_visits.sum()))
        return self.sample_many([batch_index])[0]

    def sample_many(self, batch_indices) -> list[rrr.RRRBatch]:
        idx = [int(b) for b in batch_indices]
        if not idx or (len(idx) == 1 and self.spec.frontier == "sparse"):
            return [self.sample(b) for b in idx]
        starts = jnp.stack([self.batch_starts(b) for b in idx])
        seeds = jnp.asarray(rrr.batch_seeds(self.spec.master_seed, idx))
        spec = self.spec
        if spec.frontier == "sparse":
            from repro.core import sparse
            fidx = self._frontier_index()
            vis, fused, unfused = sparse.sparse_block(
                fidx, starts, seeds, spec.num_colors, spec.max_iters,
                self._ladder, diffusion=spec.diffusion)
        elif spec.diffusion == "lt":
            vis = lt.run_fused_lt_block(self.g_rev, self._lt_cb(), starts,
                                        seeds, spec.num_colors,
                                        max_levels=spec.max_iters,
                                        segments=self._dst_segments())
            fused = unfused = np.full(len(idx), -1)
        else:
            from repro.core import traversal
            vis, fused, unfused = traversal.run_fused_block(
                self.g_rev, starts, seeds, spec.num_colors,
                max_levels=spec.max_iters, segments=self._dst_segments())
        roots = np.asarray(starts)
        return [rrr.RRRBatch(vis[i], roots[i], b, int(fused[i]),
                             int(unfused[i]))
                for i, b in enumerate(idx)]

    def rebind(self, g, g_rev, touched_row_blocks=None):
        if self._try_patch_fidx(g, g_rev, touched_row_blocks):
            return self
        return make_sampler(g, self.spec, g_rev=g_rev)


def _tile_graph(g_rev: csr.Graph, spec: SamplerSpec) -> tiles.TiledGraph:
    """Tile layout of the reversed graph, with the shared dedupe diagnosis
    (tile-layout backends need parallel edges merged)."""
    try:
        return tiles.from_graph(g_rev, tile_size=spec.tile_size)
    except ValueError as e:
        raise ValueError(
            f"the {spec.backend!r} backend needs a dedupe-clean graph "
            "(build it with csr.from_edges(..., dedupe=True)); "
            f"tiling failed with: {e}") from e


class TiledSampler(Sampler):
    """Block-sparse tile path (jnp oracle or Pallas kernel).

    The tile layout is built once per sampler from the reversed graph; the
    counter RNG is keyed by *CSR edge id* (IC) / global destination vertex
    (LT selection), so results stay bit-identical to the dense path.
    Requires a parallel-edge-free graph
    (``csr.from_edges(..., dedupe=True)``).

    ``spec.frontier == "sparse"`` compacts each level's expansion to the
    tiles with an active source block (`tiled_traversal` sparse legs) —
    the Pallas kernel grid then iterates exactly the compacted tile list.
    """

    def __init__(self, g, spec, *, g_rev=None):
        super().__init__(g, spec, g_rev=g_rev)
        self.tg_rev = _tile_graph(self.g_rev, spec)
        # LT carries the selection-CDF prefixes alongside the tiles (the
        # per-graph host precompute, done once like the layout itself).
        self._cb_tiles = (jnp.asarray(tiles.edge_values_to_tiles(
            self.tg_rev, lt.selection_cum_before(self.g_rev)))
            if spec.diffusion == "lt" else None)
        if spec.frontier == "sparse":
            from repro.core import sparse
            self._ladder = sparse.bucket_ladder(self.tg_rev.num_tiles,
                                                spec.frontier_capacity)
        # Grid-work observability (benchmarks' active_grid_frac column):
        # per-sample totals from the last `sample()` call.
        self.last_levels = 0
        self.last_grid_steps = 0

    def sample(self, batch_index: int) -> rrr.RRRBatch:
        spec = self.spec
        starts = self.batch_starts(batch_index)
        seed = self.batch_seed(batch_index)
        from repro.kernels import ops
        kw = dict(max_levels=spec.max_iters,
                  use_kernel=(spec.backend == "kernel"),
                  interpret=ops._interpret(), frontier=spec.frontier,
                  ladder=self._ladder if spec.frontier == "sparse" else None)
        if spec.diffusion == "lt":
            visited, levels, gs = tiled_traversal.run_fused_lt_tiled(
                self.tg_rev, self._cb_tiles, starts, spec.num_colors,
                seed, **kw)
        else:
            visited, levels, gs = tiled_traversal.run_fused_tiled(
                self.tg_rev, starts, spec.num_colors, seed, **kw)
        self.last_levels = int(levels)
        self.last_grid_steps = int(gs)
        return rrr.RRRBatch(visited, np.asarray(starts),
                            int(batch_index), -1, -1)


class _BlockSampler(Sampler):
    """Shared block protocol of the mesh backends: subclasses implement
    ``_block(idx) -> (visited, roots)`` — visited ``(B, Vp≥V, W)`` sharded
    on the subclass's mesh layout (row padding still attached for the
    graph-parallel case), roots ``(B, C)`` host numpy."""

    def _block(self, idx: list[int]):
        raise NotImplementedError

    def sample_stacked(self, batch_indices) -> jnp.ndarray:
        """(B, V, W) visited for the block, mesh-sharded; any row padding
        trimmed (an exact-fit block keeps its sharded layout untouched)."""
        idx = [int(b) for b in batch_indices]
        v = self.g_rev.num_vertices
        if not idx:
            return jnp.zeros((0, v, _num_words(self.spec.num_colors)),
                             jnp.uint32)
        vis = self._block(idx)[0]
        return vis if vis.shape[1] == v else vis[:, :v]

    def sample_many(self, batch_indices) -> list[rrr.RRRBatch]:
        """Block-sample, then host-stage `RRRBatch`es (each device
        contributes only its own slice of the block — the full block never
        transits a single device).  Edge-visit stats carry the -1 "not
        instrumented" sentinel, like the tiled and LT paths."""
        idx = [int(b) for b in batch_indices]
        if not idx:
            return []
        vis_sharded, roots = self._block(idx)
        vis = np.asarray(jax.device_get(vis_sharded))
        vis = vis[:, : self.g_rev.num_vertices]     # no-op when unpadded
        return [rrr.RRRBatch(vis[i], roots[i], b, -1, -1)
                for i, b in enumerate(idx)]


_DP_BLOCK_FNS: dict = {}


def _data_parallel_block_fn(mesh, axis: str, spec: SamplerSpec, ladder):
    """jit(shard_map) block traversal for the data_parallel backend.

    Cached at MODULE level on (mesh, statics), with the graph / frontier
    index passed as a traced ARGUMENT rather than baked into the closure
    as a trace-time constant — so rebinding a sampler to a mutated graph
    of the same shape (the `repro.stream` delta path builds one per
    delta) reuses the compiled program instead of recompiling it, and an
    incremental refresh stays churn-priced.  jit retraces per input
    shape, so one entry serves every padded block size and graph shape.
    """
    key = (mesh, axis, spec.diffusion, spec.frontier, spec.num_colors,
           spec.max_iters, ladder)
    fn = _DP_BLOCK_FNS.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        from repro.distributed.traversal import run_batch

        def one(data, starts, seed):
            if spec.frontier == "sparse":
                # The sparse engine is fully traced (capacity-bucket
                # conds are shard-local — no collectives), so it drops
                # straight into the shard_map body; fidx rides along
                # replicated like the graph.
                from repro.core import sparse
                (fidx,) = data
                if spec.diffusion == "lt":
                    return sparse.run_fused_lt_sparse(
                        fidx, starts, spec.num_colors, seed,
                        max_levels=spec.max_iters, ladder=ladder)
                return sparse.run_fused_sparse(
                    fidx, starts, spec.num_colors, seed,
                    max_levels=spec.max_iters, ladder=ladder).visited
            if spec.diffusion == "lt":
                g, cb, segs = data
                sel = lt.selection_mask_from_cb(g, cb, spec.num_colors,
                                                seed)
                return lt.lt_traversal_program(g, sel, starts,
                                               spec.num_colors,
                                               spec.max_iters, segs)
            g, segs = data
            return run_batch(g, starts, seed, spec.num_colors,
                             max_levels=spec.max_iters, segments=segs)

        def body(data, starts_local, seeds_local):
            # Sequential over the shard's local slice: one (V, W)
            # transient at a time per device, parallel across shards.
            return jax.lax.map(lambda a: one(data, *a),
                               (starts_local, seeds_local))

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(), P(axis), P(axis)),
                                   out_specs=P(axis), check_vma=False))
        _DP_BLOCK_FNS[key] = fn
    return fn


class DataParallelSampler(_BlockSampler):
    """Batch blocks over a mesh axis via ``shard_map`` — IC and LT.

    A block of B batch indices is padded to the shard count and sharded
    ``P(axis)`` over its leading dim; each shard runs a sequential
    ``lax.map`` of full traversals over its local slice (its own devices,
    its own RNG streams — zero collectives).  Slot blocks land exactly
    where `ShardedSketchStore` shards them, so pool builds and refreshes
    parallelize across the mesh with no default-device staging.
    """

    def __init__(self, g, spec, mesh, *, g_rev=None):
        super().__init__(g, spec, g_rev=g_rev)
        if mesh is None:
            raise ValueError("data_parallel backend needs a mesh")
        if spec.mesh_axis not in mesh.axis_names:
            raise ValueError(f"axis {spec.mesh_axis!r} not in mesh "
                             f"{mesh.axis_names}")
        self.mesh = mesh
        self.axis = spec.mesh_axis
        self._cb = (jnp.asarray(lt.selection_cum_before(self.g_rev))
                    if spec.diffusion == "lt" else None)
        if spec.frontier == "sparse":
            self._fidx, self._ladder = self._sparse_index(
                None if self._cb is None else np.asarray(self._cb))
        else:
            self._fidx = self._ladder = None

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    # ----------------------------------------------------- block program
    def _block_data(self):
        """The graph-dependent pytree the block program takes as a traced
        INPUT — what a streaming update swaps out under the cached
        program (`repro.stream` rebinds samplers per delta)."""
        if self.spec.frontier == "sparse":
            return (self._fidx,)
        if self.spec.diffusion == "lt":
            return (self.g_rev, self._cb, self._dst_segments())
        return (self.g_rev, self._dst_segments())

    def _block(self, idx: list[int]):
        """(visited, roots) for one padded block: visited (B, V, W) sharded
        ``P(axis)``, roots (B, C) host numpy — starts are derived once and
        shared by the traversal and the returned `RRRBatch` roots."""
        padded, starts, seeds = self._block_inputs(idx, self.num_shards)
        fn = _data_parallel_block_fn(self.mesh, self.axis, self.spec,
                                     self._ladder)
        vis = fn(self._block_data(), starts, seeds)
        # Slicing a sharded array re-gathers; keep the P(axis) layout when
        # the block divides evenly (the pool-build case).
        if padded != len(idx):
            vis = vis[: len(idx)]
        return vis, np.asarray(starts)[: len(idx)]

    def sample(self, batch_index: int) -> rrr.RRRBatch:
        """Single batch: go through the dense path — padding a 1-batch
        block to the shard count would traverse the same batch on every
        shard for one kept result.  Bit-identical by the facade contract."""
        if not hasattr(self, "_dense"):
            self._dense = DenseSampler(self.graph,
                                       self.spec.replace(backend="dense"),
                                       g_rev=self.g_rev)
        return self._dense.sample(batch_index)

    def rebind(self, g, g_rev, touched_row_blocks=None):
        if self._try_patch_fidx(g, g_rev, touched_row_blocks):
            # The lazily built single-batch helper binds the old graph.
            self.__dict__.pop("_dense", None)
            return self
        return make_sampler(g, self.spec, self.mesh, g_rev=g_rev)


def _gp_use_kernel() -> bool:
    """Whether the graph_parallel backend's per-shard tile expansion runs
    the Pallas kernels (the default on a TPU backend) or the jnp oracle
    (the default elsewhere, where the kernels would run interpreted).
    ``REPRO_GP_KERNEL=1``/``0`` overrides.  An env var rather than a
    `SamplerSpec` field because it does not change a single output bit —
    it selects an execution engine for the same partitioned layout, like
    ``interpret`` — so specs embedded in pool manifests stay portable
    across machines with and without kernel support."""
    default = "1" if jax.default_backend() == "tpu" else "0"
    return os.environ.get("REPRO_GP_KERNEL", default) == "1"


class GraphParallelSampler(_BlockSampler):
    """Graph rows sharded over ``spec.model_axis``, batch blocks over
    ``spec.mesh_axis`` — the 2-D (data × model) composition for graphs
    bigger than one device's memory.  IC and LT.

    The destination-row partition (`graph.partition.partition` of the tile
    layout, plus the LT selection-CDF tiles) is computed ONCE here and
    cached for the sampler's lifetime; every block reuses it.  Each device
    persistently holds only its row slice of the tile stacks and, during a
    block, its (batch slice × row slice) of the visited masks; the full
    (V, W) mask of a batch only materializes when a consumer asks for it
    (`sample_many` host-stages, which is exactly where `ShardedSketchStore`
    wants the mask anyway).
    """

    def __init__(self, g, spec, mesh, *, g_rev=None):
        super().__init__(g, spec, g_rev=g_rev)
        if mesh is None:
            raise ValueError("graph_parallel backend needs a mesh")
        for ax, role in ((spec.mesh_axis, "mesh_axis (batches)"),
                         (spec.model_axis, "model_axis (graph rows)")):
            if ax not in mesh.axis_names:
                raise ValueError(f"{role} {ax!r} not in mesh "
                                 f"{mesh.axis_names}")
        from repro.graph import partition as part_lib

        self.mesh = mesh
        self.data_axis = spec.mesh_axis
        self.model_axis = spec.model_axis
        tg = _tile_graph(self.g_rev, spec)
        # Partition ONCE; cached — the whole point of binding a sampler.
        self.ptg = part_lib.partition(tg, int(mesh.shape[spec.model_axis]))
        self._cb_tiles = None
        if spec.diffusion == "lt":
            cb = tiles.edge_values_to_tiles(
                tg, lt.selection_cum_before(self.g_rev))
            self._cb_tiles = jnp.asarray(part_lib.partition_tile_values(
                tg, self.ptg.num_shards, cb))
        # Rebind fast path: the tile layout and shard assignment are pure
        # functions of (src, dst, tile_size), so cache the CSR-edge →
        # flat-tile-slot map and the per-shard tile index lists — a
        # values-only delta then re-derives the prob/CDF stacks by direct
        # scatter + gather with NO re-sort / re-partition.
        self._slot_of_eid, self._num_tiles = tiles.edge_slot_map(
            self.g_rev, spec.tile_size)
        shard_of, _, self._tiles_per_shard = part_lib._assignment(
            tg, self.ptg.num_shards)
        self._shard_tiles = [np.flatnonzero(shard_of == s)
                             for s in range(self.ptg.num_shards)]
        # Per-batch per-level words moved over the model axis by the most
        # recent `_block` call — (B, max_iters) host int32, the traffic
        # observable `bench_pool_build` records.
        self.last_gather_words = None

    @property
    def data_shards(self) -> int:
        return int(self.mesh.shape[self.data_axis])

    def _block_fn(self):
        # Module-level cache keyed on (mesh, spec knobs, partition
        # statics) — a dict hit after the first build, shared across
        # rebound samplers so streaming deltas never re-trace.
        from repro.distributed.traversal import graph_parallel_block
        from repro.kernels import ops
        return graph_parallel_block(
            self.ptg, self.mesh, data_axis=self.data_axis,
            model_axis=self.model_axis,
            num_colors=self.spec.num_colors,
            max_levels=self.spec.max_iters,
            diffusion=self.spec.diffusion,
            frontier=self.spec.frontier,
            gather_capacity=self.spec.frontier_capacity,
            use_kernel=_gp_use_kernel(), interpret=ops._interpret())

    def _block(self, idx: list[int]):
        """(visited (B, Vp, W) sharded P(data, model), roots (B, C) numpy)
        for one padded block — row padding still attached."""
        padded, starts, seeds = self._block_inputs(idx, self.data_shards)
        args = ((self.ptg, self._cb_tiles, starts, seeds)
                if self.spec.diffusion == "lt"
                else (self.ptg, starts, seeds))
        vis, words = self._block_fn()(*args)
        self.last_gather_words = np.asarray(jax.device_get(words))[: len(idx)]
        if padded != len(idx):
            vis = vis[: len(idx)]
        return vis, np.asarray(starts)[: len(idx)]

    def _partition_edge_values(self, values: np.ndarray) -> np.ndarray:
        """Per-CSR-edge ``values`` → the ``(S, ntₘ, T, T)`` stacked layout,
        through the cached slot map + shard assignment (no sorting)."""
        t = self.spec.tile_size
        flat = np.zeros(self._num_tiles * t * t, values.dtype)
        flat[self._slot_of_eid] = values[: self.g_rev.num_edges]
        tiles_v = flat.reshape(self._num_tiles, t, t)
        out = np.zeros((self.ptg.num_shards, self._tiles_per_shard, t, t),
                       values.dtype)
        for s, tidx in enumerate(self._shard_tiles):
            if len(tidx):
                out[s, : len(tidx)] = tiles_v[tidx]
        return out

    def rebind(self, g, g_rev, touched_row_blocks=None):
        """Values-only deltas swap the prob (and LT CDF) tile stacks under
        the cached partition layout and compiled block program; structural
        deltas fall back to a full rebuild."""
        import dataclasses as _dc

        g_rev_n = (lt.normalize_lt_weights(g_rev)
                   if self.spec.diffusion == "lt" else g_rev)
        if not _same_edge_layout(self.g_rev, g_rev_n):
            return make_sampler(g, self.spec, self.mesh, g_rev=g_rev)
        self.graph = g
        self.g_rev = g_rev_n
        prob = np.asarray(self.g_rev.prob)
        self.ptg = _dc.replace(
            self.ptg,
            prob=jnp.asarray(self._partition_edge_values(
                prob.astype(np.float32))))
        if self.spec.diffusion == "lt":
            # Fresh-build parity: `edge_values_to_tiles` masks slots by
            # prob > 0, so a resurrected tombstone's CDF value must land
            # and a fresh tombstone's must zero out.
            cb = np.where(prob[: self.g_rev.num_edges] > 0,
                          np.asarray(lt.selection_cum_before(self.g_rev),
                                     np.float32)[: self.g_rev.num_edges],
                          np.float32(0))
            self._cb_tiles = jnp.asarray(self._partition_edge_values(cb))
        return self

    def sample(self, batch_index: int) -> rrr.RRRBatch:
        """Single batch through the SAME row-partitioned program (padding
        replicates the batch across data shards — wasteful but the graph
        never has to fit on one device, which is the backend's contract)."""
        return self.sample_many([int(batch_index)])[0]


def _num_words(num_colors: int) -> int:
    return -(-num_colors // 32)


def make_sampler(g: csr.Graph | None, spec: SamplerSpec, mesh=None, *,
                 g_rev: csr.Graph | None = None) -> Sampler:
    """Build the `Sampler` for ``spec``.

    ``g_rev``: prebuilt transpose(g) (skips one reversal; for LT it may be
    raw or already LT-normalized — normalization is idempotent).  ``mesh``
    is required by (and only used by) the ``data_parallel`` and
    ``graph_parallel`` backends.
    """
    if spec.backend == "graph_parallel":
        return GraphParallelSampler(g, spec, mesh, g_rev=g_rev)
    if spec.backend == "data_parallel":
        return DataParallelSampler(g, spec, mesh, g_rev=g_rev)
    if spec.backend in ("tiled", "kernel"):
        return TiledSampler(g, spec, g_rev=g_rev)
    return DenseSampler(g, spec, g_rev=g_rev)
