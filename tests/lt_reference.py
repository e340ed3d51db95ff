"""Check sampled LT RRR sets against the plain reference
`bench/reference/lt.py`, which states the live-edge rule without the
program's code."""
from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import lt as ref_lt  # noqa: E402
from bench.reference import rrr as ref_rrr  # noqa: E402


def random_edges(num_vertices: int, num_edges: int, seed: int):
    """Seeded random directed graph, self-loops and parallel edges dropped,
    with asymmetric float32 weights on [0.05, 1): p(u, v) and p(v, u) are
    drawn apart.  The forward data both the program and the reference
    read."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_vertices, num_edges)
    dst = rng.integers(0, num_vertices, num_edges)
    keep = src != dst
    key = np.unique(src[keep] * num_vertices + dst[keep])
    src = (key // num_vertices).astype(np.int32)
    dst = (key % num_vertices).astype(np.int32)
    prob = rng.uniform(0.05, 1.0, len(src)).astype(np.float32)
    return SimpleNamespace(num_vertices=num_vertices, num_edges=len(src),
                           src=src, dst=dst, prob=prob)


def edges_of(g) -> SimpleNamespace:
    """The forward edge data of a `csr.Graph`."""
    e = g.num_edges
    return SimpleNamespace(
        num_vertices=g.num_vertices, num_edges=e,
        src=np.asarray(g.src)[:e], dst=np.asarray(g.dst)[:e],
        prob=np.asarray(g.prob)[:e].astype(np.float32))


def differing_sets(edges, batches, master_seed: int) -> int:
    """Sets of ``batches`` (`rrr.RRRBatch`) that differ from the
    reference's, over every color; the roots must be the reference's."""
    g = ref_lt.InGraph.from_edges(edges)
    differ = 0
    for b in batches:
        roots = ref_rrr.roots(edges.num_vertices, len(b.roots), master_seed,
                              b.batch_index)
        np.testing.assert_array_equal(np.asarray(b.roots), roots)
        want, _ = ref_lt.walk(g, roots, range(len(roots)),
                              ref_rrr.stream_seed(master_seed,
                                                  b.batch_index))
        got = ref_rrr.unpack_colors(np.asarray(b.visited), range(len(roots)))
        differ += int((want != got).any(axis=1).sum())
    return differ
