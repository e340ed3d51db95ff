"""Distributed influence-query engine: local coverage + one collective.

Same query API as `repro.serve.influence.engine.QueryEngine` (so
`MicroBatcher` / `AsyncFrontEnd` drive either engine unchanged), but the
pool's slot dim is sharded over a mesh axis and every program runs under
``shard_map``:

* each device reduces coverage over **its local batches** with the shared
  count programs (`kernels.ops.cover_counts` / jnp popcounts);
* **one ``lax.psum``** merges the per-shard partial counts — the ButterFly
  BFS lesson: engineer exactly one deliberate collective per reduction;
* greedy selection (`core.imm.greedy_extend_program`) argmaxes on the
  *merged, replicated* counts, so every shard picks the same seed with no
  second collective, and each updates only its local active-mask slice.

When the store also shards VERTEX rows over the mesh's model axis
(`ShardedSketchStore.row_shards` > 1 — each device holds only its V/M row
slice of every local slot), the same programs run 2-D: per-vertex gain
counts are computed over the local row slice, embedded at the shard's row
offset, and merged with a psum over **data and model together** (disjoint
offsets make the sum exact); selected/seed visited rows come back through
one model-axis psum (rows are disjointly owned, so the integer sum IS the
row); and reductions over model-replicated state (the active mask, the
merged covered mask) name the data axis only.  The greedy argmax still
runs on merged, replicated counts — the vertex padding rows carry all-zero
masks and can never outscore a real vertex.

All reductions are integer, so the N-shard answer is **bit-identical** to
the 1-device `QueryEngine` on the same pool — asserted by
``tests/serve_distributed_check.py`` (including D×M row-sharded meshes).

Gain counts go through the Pallas coverage kernel, as in `QueryEngine`
(compiled on a TPU, interpreted elsewhere); ``use_kernel=False`` selects
the jnp popcounts, which give identical integer counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import imm
from repro.serve.distributed import sharded_store as store_lib
from repro.serve.influence import engine as engine_lib


class DistributedQueryEngine:
    """Static-shape shard_map query programs bound to one sharded store."""

    def __init__(self, store: store_lib.ShardedSketchStore, *,
                 query_slots: int = 8, max_seeds: int = 8,
                 use_kernel: bool = True):
        self.store = store
        self.query_slots = query_slots
        self.max_seeds = max_seeds
        self.use_kernel = use_kernel
        self._greedy_fns: dict[int, object] = {}
        self._sigma_fn = None
        self._marginal_fn = None

    @property
    def _n(self) -> int:
        return self.store.graph.num_vertices

    @property
    def _theta(self) -> int:
        return self.store.num_samples

    def _psum(self):
        return functools.partial(jax.lax.psum, axis_name=self.store.axis)

    def _row_layout(self):
        """``(row_axis, M, Vp, V_loc)`` — the pool's vertex-row sharding
        (``row_axis`` is None / M == 1 when rows are replicated)."""
        m = self.store.row_shards
        vp = self.store.padded_vertices
        return self.store.row_axis, m, vp, vp // m

    @staticmethod
    def _row_hooks(vis, row_axis: str, vp: int, vloc: int):
        """Trace-time helpers for a row-sharded ``vis`` (B_loc, V_loc, W).

        ``take(flat_global_ids) -> (B, n, W)`` — each shard contributes the
        rows it owns (others zero), one psum over ``row_axis`` merges; row
        ownership is disjoint so the integer sum IS the exact row, and the
        result is replicated across model shards.  ``embed(local_counts)``
        places a shard's (V_loc,) partial at its row offset in the global
        (Vp,) vector, so a psum over (data, model) yields exact merged
        counts — pad rows have all-zero masks, hence zero counts, and can
        never win the greedy argmax over a real vertex (ties break low).
        """
        off = jax.lax.axis_index(row_axis) * vloc
        psum_row = functools.partial(jax.lax.psum, axis_name=row_axis)

        def take(flat):
            loc = jnp.clip(flat - off, 0, vloc - 1)
            rows = jnp.take(vis, loc, axis=1)           # (B, n, W)
            ok = (flat >= off) & (flat < off + vloc)
            return psum_row(jnp.where(ok[None, :, None], rows,
                                      jnp.uint32(0)))

        def embed(counts):
            return jax.lax.dynamic_update_slice(
                jnp.zeros((vp,), counts.dtype), counts, (off,))

        return take, embed

    # ------------------------------------------------------ sharded state
    def _initial_active(self) -> jnp.ndarray:
        """(Bp, W) all-uncovered mask, pad slots zeroed, sharded P(axis).

        Zeroing pad rows keeps them out of every popcount: a pad slot has a
        zero visited mask AND a zero active mask, so it adds nothing to
        gain counts or to the uncovered total.
        """
        bp = self.store.padded_batches
        act = imm.initial_active(bp, self.store.num_colors)
        valid = (jnp.arange(bp) < len(self.store.batches))[:, None]
        act = jnp.where(valid, act, jnp.uint32(0))
        return jax.device_put(
            act, NamedSharding(self.store.mesh, P(self.store.axis)))

    # ----------------------------------------------------------- programs
    def _greedy(self, k: int):
        """jit(shard_map) greedy program for a fixed k (cached)."""
        fn = self._greedy_fns.get(k)
        if fn is None:
            axis, use_kernel = self.store.axis, self.use_kernel
            psum = self._psum()
            row_axis, m, vp, vloc = self._row_layout()

            if m > 1:
                # Row-sharded pool: local gains embedded at the shard's
                # row offset, ONE psum over (data × model) merges them
                # (disjoint offsets ⇒ exact), the argmax runs on the
                # replicated merged (Vp,) counts — same seed on every
                # shard, no second collective — and the winner's visited
                # row comes back via one model-axis psum.  The active
                # mask is replicated across model shards, so the
                # uncovered popcount reduces over data only.
                merge = functools.partial(jax.lax.psum,
                                          axis_name=(axis, row_axis))

                def body(vis, act):
                    take, embed = self._row_hooks(vis, row_axis, vp, vloc)
                    return imm.greedy_extend_program(
                        vis, act, k, use_kernel, all_reduce=merge,
                        embed_counts=embed,
                        fetch_row=lambda sel: take(sel[None])[:, 0, :],
                        final_reduce=psum)

                in_vis = P(axis, row_axis)
            else:
                def body(vis, act):
                    return imm.greedy_extend_program(vis, act, k, use_kernel,
                                                     all_reduce=psum)

                in_vis = P(axis)

            fn = jax.jit(jax.shard_map(
                body, mesh=self.store.mesh,
                in_specs=(in_vis, P(axis)),
                out_specs=(P(), P(axis), P()), check_vma=False))
            self._greedy_fns[k] = fn
        return fn

    def _sigma(self):
        if self._sigma_fn is None:
            axis, nc = self.store.axis, self.store.num_colors
            psum = self._psum()
            row_axis, m, vp, vloc = self._row_layout()

            if m > 1:
                # Seed rows merge over model (disjoint ownership), the
                # covered mask is then model-replicated, so the count
                # reduction names the data axis only.
                def body(vis, seeds, mask):
                    take, _ = self._row_hooks(vis, row_axis, vp, vloc)
                    return engine_lib.sigma_counts_program(
                        vis, seeds, mask, nc, all_reduce=psum,
                        take_rows=take)

                in_vis = P(axis, row_axis)
            else:
                def body(vis, seeds, mask):
                    return engine_lib.sigma_counts_program(
                        vis, seeds, mask, nc, all_reduce=psum)

                in_vis = P(axis)

            self._sigma_fn = jax.jit(jax.shard_map(
                body, mesh=self.store.mesh,
                in_specs=(in_vis, P(), P()), out_specs=P(),
                check_vma=False))
        return self._sigma_fn

    def _marginal(self):
        if self._marginal_fn is None:
            axis, nc = self.store.axis, self.store.num_colors
            use_kernel, psum = self.use_kernel, self._psum()
            row_axis, m, vp, vloc = self._row_layout()

            if m > 1:
                merge = functools.partial(jax.lax.psum,
                                          axis_name=(axis, row_axis))

                def body(vis, seeds, mask):
                    take, embed = self._row_hooks(vis, row_axis, vp, vloc)
                    return engine_lib.marginal_counts_program(
                        vis, seeds, mask, nc, use_kernel, all_reduce=merge,
                        take_rows=take, embed_counts=embed)

                in_vis = P(axis, row_axis)
            else:
                def body(vis, seeds, mask):
                    return engine_lib.marginal_counts_program(
                        vis, seeds, mask, nc, use_kernel, all_reduce=psum)

                in_vis = P(axis)

            self._marginal_fn = jax.jit(jax.shard_map(
                body, mesh=self.store.mesh,
                in_specs=(in_vis, P(), P()), out_specs=P(),
                check_vma=False))
        return self._marginal_fn

    # -------------------------------------------------------------- top-k
    def top_k(self, k: int) -> tuple[np.ndarray, float]:
        """Greedy seed selection over the sharded pool: one program, one
        psum per greedy round."""
        seeds, _, uncovered = self._greedy(k)(self.store.visited_stack(),
                                              self._initial_active())
        theta = self._theta
        cov = (theta - int(uncovered)) / theta
        return engine_lib._frozen(np.asarray(seeds)), cov * self._n

    # --------------------------------------------------------------- σ(S)
    def sigma_padded(self, seeds: jnp.ndarray,
                     mask: jnp.ndarray) -> np.ndarray:
        counts = self._sigma()(self.store.visited_stack(), seeds, mask)
        return engine_lib._frozen(
            np.asarray(counts, np.float64) * self._n / self._theta)

    def sigma(self, seed_sets) -> np.ndarray:
        seeds, mask = engine_lib.pad_queries(seed_sets, self.query_slots,
                                             self.max_seeds)
        return self.sigma_padded(seeds, mask)[:len(seed_sets)]

    # ----------------------------------------------------- marginal gains
    def marginal_padded(self, excl_seeds: jnp.ndarray,
                        excl_mask: jnp.ndarray) -> np.ndarray:
        counts = self._marginal()(self.store.visited_stack(), excl_seeds,
                                  excl_mask)
        # Row-sharded pools count over (Q, Vp) — drop the vertex padding
        # (no-op when the stack carries exactly V rows).
        return engine_lib._frozen(
            np.asarray(counts, np.float64)[:, :self._n]
            * self._n / self._theta)

    def marginal_gains(self, exclude) -> np.ndarray:
        seeds, mask = engine_lib.pad_queries([exclude], self.query_slots,
                                             self.max_seeds)
        return self.marginal_padded(seeds, mask)[0]

    def best_extension(self, exclude, num: int = 1) -> np.ndarray:
        """Resume greedy selection after ``exclude`` — exact marginal-gain
        argmax through the same one-collective greedy program."""
        visited = self.store.visited_stack()
        active = self._initial_active()
        for s in exclude:
            active = active & ~visited[:, int(s), :]
        seeds, _, _ = self._greedy(num)(visited, active)
        return np.asarray(seeds)
