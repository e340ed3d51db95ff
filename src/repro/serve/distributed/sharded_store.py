"""Mesh-sharded RRR sketch pool: each device owns a disjoint slice of slots.

`ShardedSketchStore` extends the single-device `SketchStore` with a device
*placement* policy — nothing about sampling changes.  Slot ``i`` always
holds the batch drawn at the store's i-th stream allocation (the same
``next_batch_index`` bookkeeping as the base class), so a 1-device pool and
an N-device pool are **bit-identical per slot**; the mesh only decides
which device materializes slot ``i``.  That invariant is what makes the
distributed query engine's answers bit-for-bit equal to single-device ones.

Layout: the stacked ``(B, V, W)`` mask is zero-padded to a multiple of the
mesh axis size and placed with ``NamedSharding(mesh, P(axis))`` — shard
``s`` owns the contiguous slot block ``[s·Bp/S, (s+1)·Bp/S)``.  Pad slots
are all-zero masks; the query engine zeroes their active-mask rows so they
contribute nothing to any reduction.

Budget: ``PoolConfig.memory_budget_mb`` is **per shard** here — an N-shard
pool admits N× the batches of a 1-device pool under the same per-device
budget, which is the point of sharding.  To make that true on a real pod,
the pool never materializes on one device: each sampled mask is staged to
host memory, and ``visited_stack`` assembles the sharded stack from
per-device blocks (`jax.make_array_from_single_device_arrays`), so device
residency is exactly one slot block per shard.  Sampling distributes too:
with ``PoolConfig.spec.backend == "data_parallel"`` every ``ensure`` /
``refresh`` traverses its whole block of new batch indices in ONE
shard_map program — each shard computes its own contiguous slice with
per-batch RNG streams on its own devices, so pool builds parallelize
across the mesh instead of staging one batch at a time through the
default device (other backends keep the sequential default-device path).
With ``backend == "graph_parallel"`` the GRAPH is partitioned too: on a
2-D (data × model) mesh each device persistently holds only its
destination-row slice of the adjacency tiles, batches shard over ``data``
and every per-level collective (frontier all-gather) names only ``model``
— graphs bigger than one device's memory build pools at all, and the
resulting slots are still bit-identical to a 1-device dense pool.

When the mesh carries the spec's ``model_axis`` (size > 1), the pool's
VERTEX rows shard over it too: ``visited_stack`` pads V to a multiple of
M and places ``(Bp, Vp, W)`` with ``P(axis, model_axis)``, so each device
persistently holds only the V/M row slice of its slot block — the
serving-side completion of the 2-D story (the sampler already row-shards
the GRAPH; now the pool it builds is row-sharded at rest too).  The
distributed query engine reduces coverage locally and merges with one
psum over data and one over model (`DistributedQueryEngine`), still
bit-identical to the 1-device engine.  Host-staged batches stay full-V,
so snapshots remain mesh-shape-free and restore onto any D×M layout.

Refresh reuses the pool allocation: the base class's donated-buffer slot
scatter (`sketch_store._set_slots`) rewrites only the refreshed slots of
the sharded stack in place — untouched shards' blocks never move, and the
whole pool is never re-staged from host, so a refresh no longer costs as
much as a build.

Persistence: snapshots are written through the same manifest format as the
base class, with the shard layout recorded in the manifest's ``extra``
metadata.  Because leaves are *global* (slot-ordered) arrays, a snapshot
saved under one mesh shape restores under any other — restore simply
re-slots batches onto the new mesh's contiguous blocks.  A plain
`SketchStore` can restore a sharded snapshot (and vice versa); the formats
are identical up to ``extra``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import manager
from repro.core import rrr
from repro.graph import csr
from repro.sampling import SamplerSpec
from repro.serve.influence.sketch_store import PoolConfig, SketchStore


def _host_batch(b: rrr.RRRBatch) -> rrr.RRRBatch:
    """Stage a batch's mask to host memory (no-op if already there)."""
    return dataclasses.replace(b, visited=np.asarray(b.visited))


class ShardedSketchStore(SketchStore):
    """Epoch-tagged sketch pool with slots sharded over one mesh axis."""

    # Restored masks stay on host (see base class) — device residency is
    # only the per-shard blocks assembled by ``visited_stack``.
    _mask_array = staticmethod(np.asarray)

    def __init__(self, g: csr.Graph, config: PoolConfig | None = None,
                 mesh: Mesh | None = None, *, axis: str = "data",
                 g_rev: csr.Graph | None = None):
        if mesh is None:
            raise ValueError("ShardedSketchStore needs a mesh; use "
                             "SketchStore for single-device pools")
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh {mesh.axis_names}")
        # Set before super().__init__: the base constructor builds the
        # sampler through ``_make_sampler``, which reads the mesh.
        self.mesh = mesh
        self.axis = axis
        super().__init__(g, config, g_rev=g_rev)

    def _make_sampler(self, g: csr.Graph, spec, g_rev):
        """Back the sampler with the store's mesh — a ``data_parallel``
        spec builds each shard's slot block on that shard's own devices; a
        ``graph_parallel`` spec additionally row-partitions the graph over
        the spec's ``model_axis`` (batch blocks follow the store's slot
        axis, so slots land exactly where ``visited_stack`` shards them)."""
        from repro import sampling
        if spec.backend in ("data_parallel", "graph_parallel") \
                and spec.mesh_axis != self.axis:
            spec = spec.replace(mesh_axis=self.axis)
        return sampling.make_sampler(g, spec, mesh=self.mesh, g_rev=g_rev)

    # ------------------------------------------------------------- layout
    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def row_axis(self) -> str | None:
        """Mesh axis the pool's VERTEX rows shard over (the spec's
        ``model_axis``), or None when the mesh doesn't carry it / it has
        size 1 — then every device holds full-V rows as before."""
        ax = self.config.spec.model_axis
        if ax in self.mesh.axis_names and int(self.mesh.shape[ax]) > 1:
            return ax
        return None

    @property
    def row_shards(self) -> int:
        ax = self.row_axis
        return int(self.mesh.shape[ax]) if ax is not None else 1

    @property
    def padded_vertices(self) -> int:
        """Vertex count rounded up to a multiple of the row-shard count —
        the stack's second dim (== V when rows are unsharded)."""
        m = self.row_shards
        return -(-self.graph.num_vertices // m) * m

    @property
    def capacity(self) -> int:
        """Per-shard memory budget × shard count (≥ 1, like the base).

        With row sharding each device holds only V/M rows per local slot,
        so the per-device budget admits M× the batches — the 2-D story's
        memory win, priced into admission."""
        cap = self.config.max_batches
        if self.config.memory_budget_mb is not None:
            per_slot = -(-self.bytes_per_batch // self.row_shards)
            per_shard = int(self.config.memory_budget_mb * 2 ** 20
                            // per_slot)
            cap = min(cap, per_shard * self.num_shards)
        return max(cap, 1)

    @property
    def padded_batches(self) -> int:
        """Slot count rounded up to a multiple of the shard count."""
        s = self.num_shards
        return -(-len(self.batches) // s) * s

    def shard_layout(self) -> list[int]:
        """slot → owning shard (contiguous blocks over the padded slots)."""
        per = self.padded_batches // self.num_shards
        return [i // per for i in range(len(self.batches))]

    # ----------------------------------------------------------- sampling
    def _sample_block(self, batch_indices: list[int]) -> list[rrr.RRRBatch]:
        # Stage each mask to host: persistent device residency must be
        # only the sharded stack (one slot block per shard), or the
        # sampling device would accumulate the whole pool and void the
        # per-shard budget.  With the ``data_parallel`` backend the block
        # is traversed in ONE shard_map program (each shard computes its
        # own contiguous slice on its own devices — the same contiguous
        # layout ``visited_stack`` shards to) and arrives host-staged
        # already; other backends run per batch on the default device.
        return [_host_batch(b) for b in super()._sample_block(batch_indices)]

    def _clone_empty(self) -> "ShardedSketchStore":
        return type(self)(self.graph, self.config, self.mesh, axis=self.axis,
                          g_rev=self.g_rev)

    def _extend_stack(self, new_batches) -> None:
        # Growth can change ``padded_batches`` and every shard's block
        # boundaries — drop the cache and let ``visited_stack`` reassemble
        # from the host-staged batches (placement only; no resampling).
        self._stack = None

    def _truncate_stack(self, keep: int) -> None:
        self._stack = None

    # -------------------------------------------------------------- stack
    def visited_stack(self) -> jnp.ndarray:
        """(Bp, Vp, W) stack, slot dim zero-padded to ``padded_batches``
        and sharded ``P(axis)``; with row sharding the vertex dim is ALSO
        padded to ``padded_vertices`` and sharded ``P(axis, row_axis)``
        (cached per store version).

        Assembled from per-device blocks — each device receives exactly
        its own (slot block × row slice), so the full stack never
        materializes on any single device and per-device visited-row
        memory is V/M under row sharding.  Host-staged batches stay
        full-V: the row slicing is pure placement, which is what lets a
        snapshot restore onto ANY D×M mesh shape.  (Single-process meshes
        only for now; a multi-host pod would filter to addressable
        devices.)

        Offline IMM slices a prefix of this (``[:want]``); slicing a
        sharded array is fine — XLA re-gathers as needed.
        """
        if not self.batches:
            raise ValueError("empty pool — call ensure() first")
        if self._stack is None:
            bp, per = self.padded_batches, self.padded_batches // self.num_shards
            v, w = np.asarray(self.batches[0].visited).shape
            vp = self.padded_vertices
            vloc = vp // self.row_shards
            shape = (bp, vp, w)
            sharding = NamedSharding(self.mesh, P(self.axis, self.row_axis))
            blocks: dict[tuple[int, int], np.ndarray] = {}

            def block(lo: int, rlo: int) -> np.ndarray:
                if (lo, rlo) not in blocks:
                    rows = []
                    for b in self.batches[lo:lo + per]:
                        r = np.asarray(b.visited)[rlo:rlo + vloc]
                        if r.shape[0] < vloc:    # vertex pad, last shard
                            r = np.pad(r, ((0, vloc - r.shape[0]), (0, 0)))
                        rows.append(r)
                    rows += [np.zeros((vloc, w), rows[0].dtype
                                      if rows else np.uint32)
                             ] * (per - len(rows))
                    blocks[(lo, rlo)] = np.stack(rows)
                return blocks[(lo, rlo)]

            arrays = [
                jax.device_put(block(idx[0].start or 0, idx[1].start or 0),
                               dev)
                for dev, idx in sharding.devices_indices_map(shape).items()]
            self._stack = jax.make_array_from_single_device_arrays(
                shape, sharding, arrays)
        return self._stack

    def _update_stack(self, slots, new_batches) -> None:
        # The base scatter stacks full-V masks; a row-sharded stack is
        # padded to Vp rows — pad the refreshed masks to match before the
        # donated `_set_slots` scatter (which preserves the 2-D placement:
        # each device rewrites only its own row slice of the touched
        # slots).
        if self._stack is None:
            return
        vp = self._stack.shape[1]
        masks = jnp.stack([jnp.asarray(b.visited) for b in new_batches])
        if masks.shape[1] < vp:
            masks = jnp.pad(masks,
                            ((0, 0), (0, vp - masks.shape[1]), (0, 0)))
        from repro.serve.influence.sketch_store import _set_slots
        self._stack = _set_slots(self._stack,
                                 jnp.asarray(slots, jnp.int32), masks)

    # -------------------------------------------------------- persistence
    def _manifest_extra(self) -> dict:
        """Shard layout + the `SamplerSpec` (base class) in one ``extra``.

        ``mesh_shape`` records the FULL (data × model) layout the pool was
        built under — with a ``graph_parallel`` spec that is the row
        partition too, which restore validates against the new mesh.
        ``row_layout`` records the vertex-row sharding the stack served
        under (axis, shard count, padded vertex dim): because the saved
        leaves are full-V host arrays, the layout is metadata, not a
        constraint — restore re-slices rows onto ANY new D×M shape."""
        return {**super()._manifest_extra(),
                "kind": "sharded_sketch_pool",
                "mesh_axis": self.axis,
                "num_shards": self.num_shards,
                "mesh_shape": {str(a): int(self.mesh.shape[a])
                               for a in self.mesh.axis_names},
                "shard_layout": self.shard_layout(),
                "row_layout": {"axis": self.row_axis,
                               "shards": self.row_shards,
                               "padded_vertices": self.padded_vertices}}

    @staticmethod
    def saved_layout(directory: str, step: int | None = None) -> dict:
        """The ``extra`` metadata a snapshot was written under (empty dict
        for snapshots from a plain `SketchStore`)."""
        return manager.read_manifest(directory, step).get("extra", {})

    @classmethod
    def restore(cls, directory: str, g: csr.Graph,
                config: PoolConfig | None = None,
                mesh: Mesh | None = None, *, axis: str = "data",
                step: int | None = None,
                g_rev: csr.Graph | None = None) -> "ShardedSketchStore":
        """Rebuild a bit-identical pool, re-slotted onto ``mesh``.

        The new mesh may have any shape along the slot axis AND the row
        axis — the snapshot's slot-ordered, full-V global arrays are
        simply re-sliced into the new mesh's contiguous (slot block × row
        slice) blocks: a pool saved under a 2×4 mesh restores onto 4×2,
        8×1, or a single device with identical query answers (the
        recorded ``shard_layout`` / ``row_layout`` of the *saving* mesh
        are metadata, not constraints).  Masks load straight from disk to
        host (``_restored_fields`` with host placement), so restore never
        transits the pool through a single device.

        With no ``config``, the snapshot's recorded `SamplerSpec` is
        adopted wholesale — a pool built graph-parallel (because the graph
        exceeds one device) restores with a graph-parallel sampler, never
        silently falling back to a dense refresh path.  An explicit config
        still overrides (backends are interchangeable bit-for-bit, so
        re-backending a pool on restore is a legitimate choice).

        Refused layouts: a ``graph_parallel`` restore spec needs the new
        mesh to carry its model axis (future ``refresh`` calls must be
        able to row-partition the graph), and — via the base class — a
        diffusion mismatch with the snapshot always raises.
        """
        step, manifest = cls._resolve_snapshot(directory, step)
        extra = manifest.get("extra", {})
        if config is None:
            saved_spec = extra.get("sampler_spec")
            config = PoolConfig(
                spec=SamplerSpec.from_manifest(saved_spec)) \
                if saved_spec else PoolConfig()
        spec = config.spec
        if spec.backend == "graph_parallel" and (
                mesh is None or spec.model_axis not in mesh.axis_names):
            raise ValueError(
                f"layout mismatch: a graph_parallel pool needs a mesh with "
                f"model axis {spec.model_axis!r} to refresh, but the "
                f"restore mesh has axes "
                f"{mesh.axis_names if mesh is not None else ()} (snapshot "
                f"was written under mesh_shape "
                f"{extra.get('mesh_shape')}) — restore onto a "
                "(data × model) mesh or with a non-graph_parallel spec")
        config, epoch, nbi, batches, epochs, gepoch = cls._restored_fields(
            directory, config, step, manifest=manifest)
        store = cls(g, config, mesh, axis=axis, g_rev=g_rev)
        store.epoch = epoch
        store.graph_epoch = gepoch
        store.next_batch_index = nbi
        store.batches = batches
        store.batch_epochs = epochs
        return store
