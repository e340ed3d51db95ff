"""Random Reverse Reachable (RRR) set sampling (paper §2, Def. 2).

An RRR set for a uniformly-random root v is the visited set of a *reverse*
probabilistic BFS from v (Def. 2: traverse G with every edge flipped).  The
fused algorithm samples ``num_colors`` RRR sets per batch: color c's RRR set
is bit c of the visited mask — the (V, W) bitmask IS the RRR collection in
columnar form, which is exactly what greedy max-cover wants (DESIGN.md §2).

Batches are the unit of distribution and fault tolerance: batch ``b`` is a
pure function of ``(graph, master_seed, b)``, so a re-executed batch (lost
node, straggler reissue) reproduces the identical samples.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask, traversal
from repro.graph import csr


NOT_COUNTED = (-1, -1, -1, -1)


@dataclasses.dataclass(frozen=True)
class RRRBatch:
    """One fused batch of ``num_colors`` RRR sets.

    ``counts`` holds the traversal counters (``fused_edge_visits``,
    ``unfused_edge_visits``, ``levels``, ``truncated_colors``), or a
    callable that returns them when first read, so that a block's
    counters can stay on the device until then.  They are -1 on paths
    that do not count them: the edge visits on the tiled, kernel,
    data_parallel and graph_parallel paths (the dense and sparse CSR
    sweeps, IC and LT, track them); ``levels`` (levels the traversal ran) and ``truncated_colors``
    (colors still on the frontier when ``max_iters`` stopped it; 0 when
    the frontier emptied) on the data_parallel and graph_parallel paths
    and on restored snapshots.  The tiled and kernel paths count
    ``levels`` only."""
    visited: jnp.ndarray        # (V, W) uint32; column c = RRR set c
    roots: np.ndarray           # (num_colors,) root vertex per color
    batch_index: int
    counts: tuple[int, int, int, int] | Callable[[], tuple] = NOT_COUNTED

    def _count(self, k: int) -> int:
        counts = self.counts() if callable(self.counts) else self.counts
        return counts[k]

    @property
    def fused_edge_visits(self) -> int:
        return self._count(0)

    @property
    def unfused_edge_visits(self) -> int:
        return self._count(1)

    @property
    def levels(self) -> int:
        return self._count(2)

    @property
    def truncated_colors(self) -> int:
        return self._count(3)


def stats_counts(stats: traversal.TraversalStats) -> tuple:
    """`RRRBatch.counts` of one traversal's `TraversalStats`."""
    return (int(stats.fused_edge_visits.sum()),
            int(stats.unfused_edge_visits.sum()), int(stats.levels_run),
            int(stats.truncated_colors))


def batch_seeds(master_seed: int, batch_indices) -> np.ndarray:
    """(B,) uint32 counter seeds — host-side, one value per batch index.
    THE stream derivation (single source of truth for every backend)."""
    return np.asarray(
        [(master_seed * 0x9E3779B9 + int(b) * 0x85EBCA6B) & 0xFFFFFFFF
         for b in batch_indices], np.uint32)


def batch_seed(master_seed: int, batch_index: int) -> jnp.ndarray:
    """Distinct, reproducible RNG stream per batch (idempotent re-issue)."""
    return jnp.uint32(batch_seeds(master_seed, [batch_index])[0])


def batch_starts(num_vertices: int, num_colors: int, master_seed: int,
                 batch_index: int, sort: bool = False) -> jnp.ndarray:
    """The (num_colors,) root vertices of batch ``batch_index`` — THE
    start-derivation every sampling backend shares, so a given
    ``(master_seed, batch_index)`` reproduces identical roots everywhere."""
    key = jax.random.key(master_seed * 1_000_003 + batch_index)
    return traversal.random_starts(key, num_vertices, num_colors, sort=sort)


def sample_batch(g_rev: csr.Graph, num_colors: int, master_seed: int,
                 batch_index: int, *, sort_starts: bool = False,
                 max_levels: int = 64,
                 model: str = "ic") -> RRRBatch:
    """Sample one fused batch of RRR sets on the REVERSED graph ``g_rev``.

    NOTE: this per-batch path is the reference the `repro.sampling`
    facade's block programs are tested against — new code goes through
    ``repro.sampling.make_sampler`` (a CI grep guard enforces that nothing
    outside ``repro/sampling/`` calls this).

    ``model``: "ic" (Independent Cascade, the paper's evaluation model) or
    "lt" (Linear Threshold via live-edge selection, each frontier vertex
    picking among its own reversed-CSR row — its forward in-edges; g_rev
    must carry LT-normalized weights, see core/lt.normalize_lt_weights).
    """
    seed = batch_seed(master_seed, batch_index)
    roots = batch_starts(g_rev.num_vertices, num_colors, master_seed,
                         batch_index, sort=sort_starts)
    if model == "lt":
        from repro.core import lt
        visited = lt.run_fused_lt(g_rev, roots, num_colors, seed,
                                  max_levels=max_levels)
        return RRRBatch(visited, np.asarray(roots), batch_index)
    res = traversal.run_fused(g_rev, roots, num_colors, seed,
                              max_levels=max_levels)
    return RRRBatch(res.visited, np.asarray(roots), batch_index,
                    stats_counts(res.stats))


def sample_collection(g: csr.Graph, theta: int,
                      num_colors: int | None = None,
                      master_seed: int | None = None, *, spec=None,
                      mesh=None) -> list[RRRBatch]:
    """θ RRR sets as ⌈θ/num_colors⌉ fused batches on transpose(g).

    Routed through the `repro.sampling` facade (``sampling.resolve_spec``
    policy: explicit num_colors/master_seed that disagree with ``spec``
    raise); ``mesh`` backs the ``data_parallel`` backend.
    """
    from repro import sampling

    spec = sampling.resolve_spec(spec, num_colors=num_colors,
                                 master_seed=master_seed)
    sampler = sampling.make_sampler(g, spec, mesh=mesh)
    n_batches = -(-theta // spec.num_colors)
    return sampler.sample_many(range(n_batches))


def stack_visited(batches: list[RRRBatch]) -> jnp.ndarray:
    """(B, V, W) stacked visited masks for seed selection."""
    return jnp.stack([b.visited for b in batches])
