"""Persistent pool of fused-BPT RRR sketch batches.

The store owns a device-resident collection of columnar ``(V, W)`` RRR
bitmask batches (`core.rrr.RRRBatch`) sampled on the reversed graph, under a
device-memory budget.  It implements the sketch-pool protocol that
``core.imm.run_imm`` / ``estimate_theta`` consume (``num_colors``,
``master_seed``, ``ensure``), so offline IMM and the online
`engine.QueryEngine` share one sampled asset.

Sampling routes through the `repro.sampling` facade: ``PoolConfig.spec`` is
a typed, frozen `SamplerSpec` (diffusion × backend + knobs) and the store
builds one `Sampler` from it — the same spec serves IC and LT pools, dense
and tiled/kernel expansion, and (in the sharded subclass) shard_map
data-parallel and graph-parallel pool builds.  (The deprecated untyped
``sample_kw`` dict, which warned since the Sampler-API PR, is gone — pass
``spec=SamplerSpec(...)``.)

Freshness is tracked per batch with an **epoch** tag: ``refresh()`` bumps
the store epoch and resamples the oldest batches with brand-new batch
indices (hence new RNG streams — never a repeat of a retired sample);
``shrink()`` bumps it too, so ``version`` (``(epoch, count)``, the
result-cache key) is never re-issued by a shrink→grow cycle.  Any
mutation changes ``version``.

Persistence rides the checkpoint manifest format (`checkpoint.manager`):
``save()`` writes an atomic ``step_<N>/{manifest.json, leaf_*.npy}``
snapshot of the pool tensors + counters, with the `SamplerSpec` recorded in
the manifest ``extra``; ``SketchStore.restore`` rebuilds a bit-identical
pool (uint32 masks round-trip exactly through ``.npy``) and REFUSES a
diffusion mismatch — a pool sampled under IC is never silently served as
LT or vice versa.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import manager
from repro.core import bitmask, rrr
from repro.graph import csr
from repro.sampling import SamplerSpec, resolve_spec


@dataclasses.dataclass(frozen=True)
class PoolConfig:
    """Sizing + sampling knobs for a sketch pool.  Frozen AND fully
    immutable (every field hashable), so a config can key jit caches.

    ``memory_budget_mb`` (when set) caps ``max_batches`` by the device bytes
    of one ``(V, W)`` uint32 batch — the pool never allocates past it.

    ``spec`` types the sampling configuration; after ``__post_init__`` it is
    always a resolved `SamplerSpec` (the default is dense IC built from
    ``num_colors``/``master_seed``, which default to 64/0 when unset).
    When an explicit spec is given, ``num_colors``/``master_seed`` are
    adopted from it, and an explicitly-set value that disagrees with the
    spec raises (``sampling.resolve_spec`` — the ``None`` field defaults
    make "explicitly set" detectable).
    """
    num_colors: int | None = None
    max_batches: int = 64
    memory_budget_mb: float | None = None
    master_seed: int | None = None
    spec: SamplerSpec | None = None

    def __post_init__(self):
        spec = resolve_spec(self.spec,
                            num_colors=self.num_colors,
                            master_seed=self.master_seed)
        object.__setattr__(self, "num_colors", spec.num_colors)
        object.__setattr__(self, "master_seed", spec.master_seed)
        object.__setattr__(self, "spec", spec)

    def with_master_seed(self, master_seed: int) -> "PoolConfig":
        """Config with ``master_seed`` replaced consistently in the spec
        too (restore adopts a snapshot's seed this way)."""
        return dataclasses.replace(
            self, master_seed=master_seed,
            spec=self.spec.replace(master_seed=master_seed))


@partial(jax.jit, donate_argnums=(0,))
def _set_slots(stack: jnp.ndarray, slots: jnp.ndarray,
               masks: jnp.ndarray) -> jnp.ndarray:
    """``stack[slots] = masks`` with the stack buffer DONATED — a refresh
    rewrites the touched slots in the existing pool allocation instead of
    re-staging the whole ``(B, V, W)`` stack (sharded stacks keep their
    sharding: the scatter only writes the owning shards' slot blocks)."""
    return stack.at[slots].set(masks)


class SketchStore:
    """Epoch-tagged, budgeted, persistable pool of RRR sketch batches."""

    # Where a restored mask lives.  The sharded subclass stages masks to
    # host (its device residency is the assembled per-shard stack, so a
    # restore must never transit the whole pool through one device).
    _mask_array = staticmethod(jnp.asarray)

    def __init__(self, g: csr.Graph, config: PoolConfig | None = None, *,
                 g_rev: csr.Graph | None = None):
        self.graph = g
        self.config = config if config is not None else PoolConfig()
        self.sampler = self._make_sampler(g, self.config.spec, g_rev)
        # The sampler owns graph reversal (and LT weight normalization).
        self.g_rev = self.sampler.g_rev
        self.epoch = 0
        self.graph_epoch = 0
        self.next_batch_index = 0
        self.batches: list[rrr.RRRBatch] = []
        self.batch_epochs: list[int] = []
        self._stack: jnp.ndarray | None = None

    def _make_sampler(self, g: csr.Graph, spec: SamplerSpec,
                      g_rev: csr.Graph | None):
        """Subclass hook — the sharded store passes its mesh here."""
        from repro import sampling
        return sampling.make_sampler(g, spec, g_rev=g_rev)

    # ------------------------------------------------------------- sizing
    @property
    def spec(self) -> SamplerSpec:
        return self.config.spec

    @property
    def num_colors(self) -> int:
        return self.config.num_colors

    @property
    def master_seed(self) -> int:
        return self.config.master_seed

    @property
    def bytes_per_batch(self) -> int:
        w = bitmask.num_words(self.config.num_colors)
        return self.graph.num_vertices * w * 4

    @property
    def capacity(self) -> int:
        """Max batches the budget admits (≥ 1 so the pool is never unusable)."""
        cap = self.config.max_batches
        if self.config.memory_budget_mb is not None:
            cap = min(cap, int(self.config.memory_budget_mb * 2 ** 20
                               // self.bytes_per_batch))
        return max(cap, 1)

    @property
    def num_samples(self) -> int:
        return len(self.batches) * self.config.num_colors

    @property
    def version(self) -> tuple[int, int, int]:
        """Cache key: changes on a graph delta, on refresh, AND on pool
        growth.  The leading graph-epoch component makes results computed
        on different topologies un-mixable (`router.EpochMixError`) and
        un-cacheable across a `repro.stream` delta even though slot count
        and refresh epoch look unchanged."""
        return (self.graph_epoch, self.epoch, len(self.batches))

    # ----------------------------------------------------------- sampling
    def _sample_block(self, batch_indices: list[int]) -> list[rrr.RRRBatch]:
        """Sample a block of batch indices through the store's sampler —
        ONE facade call, so block-capable backends (data_parallel) build
        every slot in parallel instead of one batch at a time."""
        return self.sampler.sample_many(batch_indices)

    def _take_indices(self, count: int) -> list[int]:
        """Allocate ``count`` never-before-used batch indices (RNG streams)."""
        idx = list(range(self.next_batch_index, self.next_batch_index + count))
        self.next_batch_index += count
        return idx

    def ensure(self, num_batches: int) -> list[rrr.RRRBatch]:
        """Grow the pool to ≥ ``num_batches`` (clamped to capacity).

        Sketch-pool protocol entry point for ``core.imm``; returns the live
        batch list (callers must not mutate it).
        """
        want = min(num_batches, self.capacity)
        missing = want - len(self.batches)
        if missing > 0:
            new = self._sample_block(self._take_indices(missing))
            for b in new:
                self.batches.append(b)
                self.batch_epochs.append(self.epoch)
            self._extend_stack(new)
        return self.batches

    def shrink(self, num_batches: int) -> list[int]:
        """Drop the highest slots down to ``num_batches`` (floor 1); returns
        the dropped slots.  The slot *prefix* is kept, so offline IMM's
        first-⌈θ/colors⌉-slots selection stays meaningful and replicas that
        apply the same shrink stay bit-identical.  The cached stack is
        sliced in place (no resample, no host re-staging).

        A shrink that drops anything bumps the store epoch: ``version`` is
        ``(epoch, count)`` and a later grow back to the same count samples
        NEW batch indices into the re-added slots, so without the bump a
        shrink→grow cycle would re-issue a previously-seen version and
        epoch-keyed result caches would serve stale answers against the
        new pool contents (the autoscaler's normal oscillation pattern).
        Within one epoch the count only grows, so ``(epoch, count)`` can
        never repeat.
        """
        keep = max(1, min(int(num_batches), len(self.batches)))
        dropped = list(range(keep, len(self.batches)))
        if not dropped:
            return dropped
        self.epoch += 1
        self.batches = self.batches[:keep]
        self.batch_epochs = self.batch_epochs[:keep]
        self._truncate_stack(keep)
        return dropped

    def clone(self) -> "SketchStore":
        """A replica pool sharing this store's (immutable) batches.

        The clone has its own sampler, stack cache, and counters, so later
        ``ensure``/``refresh``/``shrink`` on either store are independent —
        but because slot ``i`` is a pure function of ``(graph, master_seed,
        batch_index)`` and both stores continue from the same
        ``next_batch_index``, applying the *same* mutation sequence to every
        clone keeps them bit-identical (the serving tier's replica-group
        invariant).  No resampling: batch masks are shared references
        (RRR batches are never mutated in place).
        """
        c = self._clone_empty()
        c.epoch = self.epoch
        c.graph_epoch = self.graph_epoch
        c.next_batch_index = self.next_batch_index
        c.batches = list(self.batches)
        c.batch_epochs = list(self.batch_epochs)
        return c

    def _clone_empty(self) -> "SketchStore":
        """Subclass hook: a fresh store with this store's graph + config
        (the sharded subclass threads its mesh through)."""
        return type(self)(self.graph, self.config, g_rev=self.g_rev)

    def _extend_stack(self, new_batches: list[rrr.RRRBatch]) -> None:
        """Append newly-sampled slots to the cached stack without
        re-staging the existing allocation (a tier scale-up event must not
        cold-rebuild the pool).  No-op while the stack is unbuilt."""
        if self._stack is None:
            return
        masks = jnp.stack([jnp.asarray(b.visited) for b in new_batches])
        self._stack = jnp.concatenate([self._stack, masks])

    def _truncate_stack(self, keep: int) -> None:
        """Slice the cached stack to the kept slot prefix (device-side)."""
        if self._stack is not None:
            self._stack = self._stack[:keep]

    def visited_stack(self) -> jnp.ndarray:
        """(B, V, W) stacked masks for the query engine (cached per version)."""
        if not self.batches:
            raise ValueError("empty pool — call ensure() first")
        if self._stack is None:
            self._stack = rrr.stack_visited(self.batches)
        return self._stack

    # ------------------------------------------------------------ refresh
    def _update_stack(self, slots: list[int],
                      new_batches: list[rrr.RRRBatch]) -> None:
        """Write refreshed slots into the cached stack IN PLACE (donated
        buffer — `_set_slots`).  A refresh never changes the pool's shape,
        so the existing ``(B, V, W)`` allocation (and, in the sharded
        subclass, its per-device placement) is reused; only the touched
        slots transit a device.  No-op while the stack is unbuilt (lazy).

        Donation contract: the previously-returned ``visited_stack()``
        array object is consumed — consumers must re-fetch per query (the
        query engines already do).
        """
        if self._stack is None:
            return
        with obs.span("store.set_slots", slots=len(slots)):
            masks = jnp.stack([jnp.asarray(b.visited) for b in new_batches])
            self._stack = _set_slots(self._stack,
                                     jnp.asarray(slots, jnp.int32), masks)

    def refresh(self, fraction: float = 0.25) -> list[int]:
        """Resample the oldest-epoch batches with fresh RNG streams.

        Bumps the store epoch, then replaces ``ceil(fraction · B)`` batches
        (oldest epoch tag first, lowest slot on ties) with new samples drawn
        at never-before-used batch indices.  Returns the replaced slots.
        The cached visited stack is updated in place (`_update_stack`) —
        a refresh reuses the pool allocation instead of re-staging it.
        Runs under the host span ``repro:store.refresh`` (`obs.span`).
        """
        if not self.batches:
            return []
        self.epoch += 1
        with obs.span("store.refresh", epoch=self.epoch,
                      first_batch=self.next_batch_index):
            count = min(len(self.batches),
                        max(1, math.ceil(fraction * len(self.batches))))
            order = sorted(range(len(self.batches)),
                           key=lambda i: (self.batch_epochs[i], i))
            slots = order[:count]
            new = self._sample_block(self._take_indices(count))
            for i, b in zip(slots, new):
                self.batches[i] = b
                self.batch_epochs[i] = self.epoch
            self._update_stack(slots, new)
        return slots

    # ---------------------------------------------------- streaming deltas
    def apply_graph_update(self, g: csr.Graph, g_rev: csr.Graph,
                           touched_row_blocks=None) -> None:
        """Swap in a mutated graph pair (`repro.stream.apply_delta` output)
        and bump the graph epoch.

        For the streaming path the graphs are delta-applied descendants of
        the current pair — CSR edge ids stable, the reversed graph
        maintained by applying the reversed delta (NOT `csr.transpose`,
        which renumbers).  The sampler is REBOUND (`Sampler.rebind`): a
        values-only delta that names its ``touched_row_blocks`` patches
        the sampler's per-graph indexes in place (churn-priced), anything
        structural rebuilds them.  Existing batches keep their recorded
        RNG streams, so `resample_slots` can re-derive any slot on the new
        topology while clean slots stay bit-identical.

        The other caller is `stream.compact` — a rebuilt (renumbered!)
        graph pair is fine too because rebind detects the structural
        change and rebuilds, but then EVERY slot must be resampled (edge
        ids moved, so every slot's bits are suspect), which the compaction
        path does.

        ``g_rev`` must already carry the LT normalization invariant when
        the pool is LT (`stream.apply_delta(..., lt_normalized=True)`
        maintains it): the sampler re-runs `lt.normalize_lt_weights`,
        which is idempotent — order-preserving and a no-op on normalized
        weights — so the ids AND bits both survive.
        """
        self.graph = g
        self.sampler = self.sampler.rebind(g, g_rev, touched_row_blocks)
        self.g_rev = self.sampler.g_rev
        self.graph_epoch += 1

    def resample_slots(self, slots: list[int]) -> list[rrr.RRRBatch]:
        """Re-derive the given slots from their RECORDED RNG streams on
        the current graph (the incremental-refresh write path).

        Unlike `refresh` this allocates no new batch indices and bumps no
        epoch — slot ``i`` stays the pure function ``(graph, master_seed,
        batch_index_i)``, so after a graph delta the resampled slots match
        a cold rebuild of the same indices bit-for-bit, and replicas that
        apply the same delta + resample stay identical.  The cached stack
        is updated in place through the donated `_set_slots` scatter.
        """
        if not slots:
            return []
        new = self._sample_block([self.batches[i].batch_index
                                  for i in slots])
        for i, b in zip(slots, new):
            self.batches[i] = b
        self._update_stack(slots, new)
        return new

    # -------------------------------------------------------- persistence
    def _tree(self) -> dict[str, Any]:
        return {
            "visited": np.stack([np.asarray(b.visited) for b in self.batches]),
            "roots": np.stack([b.roots for b in self.batches]),
            "batch_indices": np.asarray(
                [b.batch_index for b in self.batches], np.int64),
            "batch_epochs": np.asarray(self.batch_epochs, np.int64),
            "edge_visits": np.asarray(
                [[b.fused_edge_visits, b.unfused_edge_visits]
                 for b in self.batches], np.int64),
            "counters": np.asarray(
                [self.epoch, self.next_batch_index,
                 self.config.master_seed, self.config.num_colors,
                 self.graph_epoch], np.int64),
        }

    def _manifest_extra(self) -> dict:
        """Manifest ``extra`` metadata — the `SamplerSpec` always rides
        along so restore can refuse a diffusion mismatch."""
        return {"kind": "sketch_pool",
                "sampler_spec": self.config.spec.to_manifest()}

    def save(self, directory: str, *, keep: int = 3) -> None:
        """Atomic manifest snapshot; step number = store epoch."""
        manager.save(directory, self.epoch, self._tree(), keep=keep,
                     extra=self._manifest_extra())

    @classmethod
    def _resolve_snapshot(cls, directory: str, step: int | None):
        """(step, manifest) of the latest (or given) snapshot — read ONCE;
        restore paths that need the manifest early pass it back down."""
        step = step if step is not None else manager.latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no sketch-pool snapshot in {directory}")
        return step, manager.read_manifest(directory, step)

    @classmethod
    def _restored_fields(cls, directory: str, config: PoolConfig,
                         step: int | None, manifest: dict | None = None):
        """(config, epoch, next_batch_index, batches, batch_epochs,
        graph_epoch) of a snapshot.  Leaves load as host numpy; each mask
        is placed via ``cls._mask_array``, so the whole pool never
        transits one device unless the subclass wants it to."""
        if manifest is None:
            step, manifest = cls._resolve_snapshot(directory, step)
        saved_spec = manifest.get("extra", {}).get("sampler_spec")
        if saved_spec is not None:
            saved = SamplerSpec.from_manifest(saved_spec)
            if saved.diffusion != config.spec.diffusion:
                raise ValueError(
                    f"snapshot was sampled under diffusion "
                    f"{saved.diffusion!r} but the restore config requests "
                    f"{config.spec.diffusion!r} — an IC pool must never be "
                    "silently served as LT (or vice versa); restore with a "
                    "matching SamplerSpec")
        target = {e["path"]: np.zeros(e["shape"], manager._np_dtype(e["dtype"]))
                  for e in manifest["leaves"]}
        tree, _ = manager.restore(directory, target, step, as_numpy=True)
        counters = np.asarray(tree["counters"])
        if int(counters[3]) != config.num_colors:
            raise ValueError(f"snapshot colors {int(counters[3])} != "
                             f"config {config.num_colors}")
        config = config.with_master_seed(int(counters[2]))
        visited = np.asarray(tree["visited"])
        roots = np.asarray(tree["roots"])
        indices = np.asarray(tree["batch_indices"])
        visits = np.asarray(tree["edge_visits"])
        batches = [
            rrr.RRRBatch(cls._mask_array(visited[i]), roots[i],
                         int(indices[i]), (int(visits[i, 0]),
                                           int(visits[i, 1]), -1, -1))
            for i in range(visited.shape[0])]
        epochs = [int(e) for e in np.asarray(tree["batch_epochs"])]
        # Pre-streaming snapshots carry 4 counters (no graph epoch): 0.
        graph_epoch = int(counters[4]) if counters.shape[0] > 4 else 0
        return (config, int(counters[0]), int(counters[1]), batches, epochs,
                graph_epoch)

    @classmethod
    def restore(cls, directory: str, g: csr.Graph,
                config: PoolConfig | None = None, *,
                step: int | None = None,
                g_rev: csr.Graph | None = None) -> "SketchStore":
        """Rebuild a bit-identical pool from the latest (or given) snapshot."""
        config, epoch, nbi, batches, epochs, gepoch = cls._restored_fields(
            directory, config if config is not None else PoolConfig(), step)
        store = cls(g, config, g_rev=g_rev)
        store.epoch = epoch
        store.graph_epoch = gepoch
        store.next_batch_index = nbi
        store.batches = batches
        store.batch_epochs = epochs
        return store
