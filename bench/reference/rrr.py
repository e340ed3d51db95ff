"""Plain reference of fused-BPT RRR sampling (numpy, one color at a time).

What a batch of RRR sets is, stated without the program's code:

* The reversed graph lists every edge u -> v of the data as v -> u.  Its
  edges are numbered in ``(v, u)`` order: that number is the edge's RNG
  counter.
* Batch ``b`` under master seed ``m`` has 64 colors.  Color ``c`` starts
  at root ``roots[c]`` (`roots`) and draws with the 32-bit stream seed
  ``(m * 0x9E3779B9 + b * 0x85EBCA6B) mod 2**32``.
* Level-synchronous reverse cascade of one color: the frontier folds into
  the visited set; then an edge x -> y of the reversed graph with x on the
  frontier fires when ``uniform(hash(seed, level, edge, c)) < p``, and y
  joins the next frontier when it has not been visited.  The cascade runs
  until the frontier is empty — no level cap.  The RRR set is the visited
  set.
* ``hash`` is murmur3's fmix32 finalizer folded over the four counters,
  ``uniform`` the top 24 bits over 2**24 in float32.

`ReverseGraph` holds the reversed CSR; `cascade` runs some colors of one
batch and returns their sets and depths; `compare_sets` checks a program's
sets against it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

M1, M2, GOLDEN = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9
U32 = np.uint32


def _mix32(x):
    x = x ^ (x >> U32(16))
    x = x * U32(M1)
    x = x ^ (x >> U32(13))
    x = x * U32(M2)
    return x ^ (x >> U32(16))


def _absorb(h, x):
    x = np.asarray(x, U32)
    return _mix32(h ^ (x + U32(GOLDEN) + (h << U32(6)) + (h >> U32(2))))


def hash4(seed, level, edge, word):
    """uint32 hash of the four counters (vectorized over ``edge``/``word``)."""
    with np.errstate(over="ignore"):
        h = np.asarray(seed, U32) * U32(GOLDEN)
        h = _absorb(h, np.asarray(level, U32))
        h = _absorb(h, edge)
        return _absorb(h, word)


def uniform(bits):
    return (bits >> U32(8)).astype(np.float32) * np.float32(1.0 / (1 << 24))


def stream_seed(master_seed: int, batch_index: int) -> int:
    return (int(master_seed) * 0x9E3779B9
            + int(batch_index) * 0x85EBCA6B) & 0xFFFFFFFF


def roots(num_vertices: int, num_colors: int, master_seed: int,
          batch_index: int) -> np.ndarray:
    """(num_colors,) roots: ``num_colors`` draws, uniform over the
    vertices, of JAX's threefry stream keyed by
    ``master_seed * 1_000_003 + batch_index``."""
    import jax
    key = jax.random.key(int(master_seed) * 1_000_003 + int(batch_index))
    return np.asarray(jax.random.randint(key, (num_colors,), 0,
                                         num_vertices, np.int32))


@dataclasses.dataclass(frozen=True)
class ReverseGraph:
    num_vertices: int
    indptr: np.ndarray   # (V+1,) int64 rows of the reversed graph
    dst: np.ndarray      # (E,) int32 head of reversed edge e
    prob: np.ndarray     # (E,) float32

    @classmethod
    def from_edges(cls, edges) -> "ReverseGraph":
        order = np.lexsort((edges.src, edges.dst))      # by (dst, src)
        rsrc = edges.dst[order]
        indptr = np.zeros(edges.num_vertices + 1, np.int64)
        np.cumsum(np.bincount(rsrc, minlength=edges.num_vertices),
                  out=indptr[1:])
        return cls(edges.num_vertices, indptr,
                   edges.src[order].astype(np.int32),
                   edges.prob[order].astype(np.float32))


def _cascade_one(g: ReverseGraph, root: int, color: int, seed: int):
    v = g.num_vertices
    visited = np.zeros(v, bool)
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size:
        visited[frontier] = True
        lo, hi = g.indptr[frontier], g.indptr[frontier + 1]
        cnt = hi - lo
        total = int(cnt.sum())
        if total:
            first = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
            eid = first + np.arange(total, dtype=np.int64)
            bits = hash4(seed, level, eid.astype(U32), color)
            fired = eid[uniform(bits) < g.prob[eid]]
            heads = np.unique(g.dst[fired])
            frontier = heads[~visited[heads]].astype(np.int64)
        else:
            frontier = frontier[:0]
        level += 1
    return visited, level


def cascade(g: ReverseGraph, batch_roots, colors, seed: int):
    """RRR sets of ``colors`` of one batch: ((len(colors), V) bool, (len,)
    depths — levels run until the color's frontier emptied)."""
    sets = np.zeros((len(colors), g.num_vertices), bool)
    depths = np.zeros(len(colors), np.int64)
    for i, c in enumerate(colors):
        sets[i], depths[i] = _cascade_one(g, int(batch_roots[c]), int(c),
                                          seed)
    return sets, depths


def unpack_colors(mask: np.ndarray, colors) -> np.ndarray:
    """(len(colors), V) bool columns of a (V, W) uint32 packed mask."""
    colors = np.asarray(colors, np.int64)
    words = mask[:, colors // 32]                       # (V, n)
    return ((words >> (colors % 32).astype(U32)) & U32(1)).T.astype(bool)


def compare_sets(g: ReverseGraph, items, *, edge_budget: int,
                 min_sets: int) -> dict:
    """Compare program sets with the reference, in the order given, until
    the reference has expanded ``edge_budget`` edges and compared at least
    ``min_sets`` sets.  ``items`` yields (mask (V, W) uint32, roots,
    color, stream seed)."""
    outdeg = np.diff(g.indptr)
    compared = differ = spent = deepest = 0
    for mask, batch_roots, color, seed in items:
        if compared >= min_sets and spent >= edge_budget:
            break
        want, depth = cascade(g, batch_roots, [color], seed)
        differ += int((want != unpack_colors(mask, [color])).any())
        spent += int(outdeg[want[0]].sum())
        deepest = max(deepest, int(depth[0]))
        compared += 1
    return {"compared": compared, "differing": differ,
            "edges_expanded": spent, "deepest": deepest}
