"""Graph data of a configuration (host numpy).

A configuration's graph is a Graph500 Kronecker graph, generated as the
Graph500 specification (3.0, section 3) states it, so that nothing has to
be downloaded: ``N = 2**scale`` vertices and ``M = edgefactor * N``
generated tuples, each tuple placed by ``scale`` recursive quadrant
choices with the initiator probabilities A = 0.57, B = 0.19, C = 0.19
(D = 0.05), then every vertex label mapped through one random
permutation.  Graph500's graph is undirected, so each tuple {i, j} gives
both i -> j and j -> i; self-loops are dropped and parallel edges merged,
as the specification allows.  (Its shuffle of the tuple order is left out:
the edges are sorted and merged here, so the order cannot matter.)
In-degree equals degree and reaches thousands at the hubs.

The probabilities are assigned afterwards, by the configuration's rule:

* ``weighted_cascade`` — p(u, v) = 1 / in-degree(v), IMM's standard
  (Tang, Shi, Xiao, SIGMOD 2015), rounded once to float32;
* ``uniform`` — p drawn uniformly on [0, 1) per edge, float32.

`Edges` is the data that both the program (through ``csr.from_edges``)
and the plain reference read: unique (src, dst) pairs sorted by
``(src, dst)``, with one float32 probability each.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Edges:
    num_vertices: int
    src: np.ndarray     # (E,) int32, sorted by (src, dst), no duplicates
    dst: np.ndarray     # (E,) int32
    prob: np.ndarray    # (E,) float32

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


def kronecker_tuples(scale: int, edgefactor: int, *, a: float, b: float,
                     c: float, seed: int):
    """(i, j) int64 of the Graph500 Kronecker generator: ``edgefactor *
    2**scale`` tuples, vertex labels permuted."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        i_bit = rng.random(m, dtype=np.float32) > ab
        thresh = np.where(i_bit, np.float32(c_norm), np.float32(a_norm))
        j_bit = rng.random(m, dtype=np.float32) > thresh
        i |= i_bit.astype(np.int64) << bit
        j |= j_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[i], perm[j]


def make_edges(graph: dict) -> Edges:
    """The configuration's graph (its ``graph`` entry): Kronecker tuples
    from the entry's ``seed``, both directions, self-loops dropped,
    parallel edges merged, probabilities by rule."""
    if graph["generator"] != "graph500_kronecker":
        raise ValueError(f"unknown generator {graph['generator']!r}")
    scale = int(graph["scale"])
    n = 1 << scale
    gseed = int(graph["seed"])
    i, j = kronecker_tuples(scale, int(graph["edgefactor"]),
                            a=float(graph["a"]), b=float(graph["b"]),
                            c=float(graph["c"]), seed=gseed)
    keep = i != j
    i, j = i[keep], j[keep]
    key = np.unique(np.concatenate([i * n + j, j * n + i]))
    src = (key >> scale).astype(np.int32)        # sorted by (src, dst)
    dst = (key & (n - 1)).astype(np.int32)
    rule = graph["probability"]
    if rule == "weighted_cascade":
        indeg = np.bincount(dst, minlength=n)
        prob = np.float32(1.0) / indeg[dst].astype(np.float32)
    elif rule == "uniform":
        rng = np.random.default_rng(gseed + 1)
        prob = rng.random(len(src), dtype=np.float32)
    else:
        raise ValueError(f"unknown probability rule {rule!r}")
    return Edges(n, src, dst, prob.astype(np.float32))
