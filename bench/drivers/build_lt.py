"""Closed-loop pool builds under Linear Threshold: `build.Driver`'s loop,
checked against the plain LT reference.

Traffic keys (``bench/traffic/<name>.json`` with ``"kind": "build_lt"``):
``pool_batches``, ``refresh_fraction``, ``master_seed`` and
``check_refreshes`` as `bench/drivers/build.py` reads them.  There is no
check budget: every set the window and the seed's calls produced is
compared with `bench/reference/lt.py`, since a walk costs the reference a
few hundred steps at most.

After the window has closed, never inside it (reading a block's counters
waits for the device), the window's batches' ``levels``,
``truncated_colors`` and fused edge visits are summed into
``run.counters``, with the edges one dense level sweeps, for the
per-layer readers ``lt_*``.
"""
from __future__ import annotations

import gc

import numpy as np

from bench import harness
from bench.drivers import build
from bench.reference import lt as ref_lt
from bench.reference import rrr as ref


class Driver(build.Driver):

    def _count_window(self) -> None:
        c = self.run.counters
        batches = self.produced
        c["levels"] = sum(int(b.levels) for b in batches)
        c["truncated_colors"] = sum(int(b.truncated_colors) for b in batches)
        visits = [int(b.fused_edge_visits) for b in batches]
        c["fused_edge_visits"] = (sum(visits) if min(visits, default=-1) >= 0
                                  else None)
        c["edges_per_level"] = int(self.store.g_rev.padded_edges)
        harness.log("window counters", {
            k: c[k] for k in ("levels", "truncated_colors",
                              "fused_edge_visits", "edges_per_level")})

    def check(self) -> dict:
        self._count_window()
        run, colors = self.run, self.colors
        seeded = self._seed_calls()
        streams = [(self.produced, self.first_window_index),
                   (seeded, build.check_start(run.seed))]
        out_of_sequence = 0
        for batches, start in streams:
            got = [int(b.batch_index) for b in batches]
            out_of_sequence += sum(
                a != b for a, b in zip(got, range(start, start + len(got))))
        # Host copies of every batch, then the program's state goes before
        # the reference runs.
        items = [(np.asarray(b.visited), np.asarray(b.roots),
                  int(b.batch_index))
                 for batches, _ in streams for b in batches]
        edges = self.edges
        self.produced = self.store = None
        gc.collect()

        g = ref_lt.InGraph.from_edges(edges)
        roots_differ = 0
        compared = []
        for mask, roots, index in items:
            want = ref.roots(g.num_vertices, colors, self.master_seed, index)
            roots_differ += int(np.sum(want != roots))
            seed = ref.stream_seed(self.master_seed, index)
            compared.extend((mask, want, c, seed) for c in range(colors))
        sets = ref_lt.compare_sets(g, compared)
        harness.log("reference", dict(
            sets, sets_produced=len(items) * colors,
            window_sets=len(streams[0][0]) * colors,
            seed_sets=len(streams[1][0]) * colors,
            seed_first_index=streams[1][1],
            max_iters=int(run.config["max_iters"])))
        differ = sets["differing"]
        return {"attempted": len(items) * colors, "failed": differ,
                "checks": [
                    ("sets_differing", differ, 0),
                    ("roots_differing", roots_differ, 0),
                    ("batches_out_of_sequence", out_of_sequence, 0),
                ]}
