"""Plain reference of Linear Threshold RRR sampling (numpy, one color at a
time).

What a batch of LT RRR sets is, stated without the program's code
(Kempe, Kleinberg, Tardos, KDD 2003: LT equals a live-edge graph in which
every vertex keeps at most one of its in-edges):

* Weights.  Vertex x's in-edges u -> x of the data are listed in
  ascending order of the source u (parallel edges in edge-list order).
  With p the data's float32 probabilities, S = the float64 sum of x's p,
  left to right, and each weight is w = p * (1 / max(1, S)), computed in
  float64 and rounded once to float32.
* CDF.  In that order, the i-th in-edge's prefix is cb_i, summed left
  to right in float32: cb_0 = 0, cb_{i+1} = float32(cb_i + w_i).  Its
  interval is [cb_i, cb_{i+1}), so the intervals tile and no number lies
  in two of them.
* Batch ``b`` under master seed ``m`` has 64 colors.  Color ``c`` starts
  at root ``roots[c]`` and draws with the stream seed
  ``(m * 0x9E3779B9 + b * 0x85EBCA6B) mod 2**32`` (`rrr.roots`,
  `rrr.stream_seed`).
* Pick.  For color ``c``, vertex x draws u = uniform(hash(seed, 0x17, x,
  c)) (`rrr.hash4`, `rrr.uniform`) and keeps the in-edge whose interval
  holds u, or none when no interval does (u at or past the weights' sum).
* The set.  The RRR set of a root is every vertex that reaches it over
  kept edges.  Read from the root it is a walk: the next vertex is the
  source of the current vertex's kept in-edge, and the walk stops when
  the current vertex keeps none or the next vertex is already in the set.
  No level cap.  The depth is the number of vertices on the walk, the
  root included.

`InGraph` holds each vertex's in-edges; `walk` runs some colors of one
batch; `compare_sets` checks a program's sets against it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference.rrr import hash4, unpack_colors, uniform

SELECT = 0x17            # the hash's second counter for an LT pick
F32 = np.float32


@dataclasses.dataclass
class InGraph:
    num_vertices: int
    indptr: np.ndarray   # (V+1,) int64: in-edges of x at [indptr[x], [x+1])
    src: np.ndarray      # (E,) int32 source of each in-edge, ascending
    prob: np.ndarray     # (E,) float32
    _cdf: dict = dataclasses.field(default_factory=dict, repr=False)

    @classmethod
    def from_edges(cls, edges) -> "InGraph":
        order = np.lexsort((np.arange(edges.num_edges), edges.src,
                            edges.dst))                 # by (dst, src)
        indptr = np.zeros(edges.num_vertices + 1, np.int64)
        np.cumsum(np.bincount(edges.dst, minlength=edges.num_vertices),
                  out=indptr[1:])
        return cls(edges.num_vertices, indptr,
                   edges.src[order].astype(np.int32),
                   edges.prob[order].astype(F32))

    def intervals(self, x: int):
        """(sources, lo, hi) of x's in-edges: the float32 intervals."""
        got = self._cdf.get(x)
        if got is None:
            a, b = self.indptr[x], self.indptr[x + 1]
            p = self.prob[a:b].astype(np.float64)
            total = np.add.accumulate(p)[-1] if b > a else 0.0
            w = (p * (1.0 / max(total, 1.0))).astype(F32)
            bounds = np.add.accumulate(np.concatenate([[F32(0)], w]),
                                       dtype=F32)
            got = self._cdf[x] = (self.src[a:b], bounds[:-1], bounds[1:])
        return got

    def pick(self, x: int, color: int, seed: int):
        """The source of x's kept in-edge for ``color``, or None."""
        src, lo, hi = self.intervals(x)
        u = uniform(hash4(seed, SELECT, np.uint32(x), color))
        kept = src[(lo <= u) & (u < hi)]
        assert len(kept) <= 1, f"vertex {x} keeps {len(kept)} in-edges"
        return int(kept[0]) if len(kept) else None


def _walk_one(g: InGraph, root: int, color: int, seed: int):
    in_set = {root}
    x = root
    while (x := g.pick(x, color, seed)) is not None and x not in in_set:
        in_set.add(x)
    return in_set, len(in_set)


def walk(g: InGraph, batch_roots, colors, seed: int):
    """RRR sets of ``colors`` of one batch: ((len(colors), V) bool, (len,)
    depths)."""
    sets = np.zeros((len(colors), g.num_vertices), bool)
    depths = np.zeros(len(colors), np.int64)
    for i, c in enumerate(colors):
        members, depths[i] = _walk_one(g, int(batch_roots[c]), int(c), seed)
        sets[i, list(members)] = True
    return sets, depths


def compare_sets(g: InGraph, items) -> dict:
    """Compare every program set with the reference.  ``items`` yields
    (mask (V, W) uint32, roots, color, stream seed)."""
    compared = differ = deepest = steps = 0
    for mask, batch_roots, color, seed in items:
        want, depth = walk(g, batch_roots, [color], seed)
        differ += int((want != unpack_colors(mask, [color])).any())
        deepest = max(deepest, int(depth[0]))
        steps += int(depth[0])
        compared += 1
    return {"compared": compared, "differing": differ, "deepest": deepest,
            "steps": steps}
