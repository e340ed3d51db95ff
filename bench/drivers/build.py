"""Closed-loop pool builds: one caller refreshes the whole pool, back to back.

Traffic keys (``bench/traffic/<name>.json`` with ``"kind": "build"``):

* ``pool_batches`` — 64-color batches the pool holds; a call with
  ``refresh_fraction`` 1.0 samples that many fresh;
* ``refresh_fraction`` — share of the pool each call resamples (1.0: all);
* ``master_seed`` — the sampling stream of the window.  A dense batch
  runs as many levels as its deepest set, and that depth swings by ~40%
  from batch to batch, so a stream drawn from the run's seed would change
  the work of a window by ±10% (PERF.md).  The window therefore replays
  one fixed stream, the same batches for every seed;
* ``check_refreshes`` — after the window has closed, the same store makes
  this many more calls of the window's own ``refresh``, at fresh batch
  indices drawn from the run's seed (`check_start`), so that every seed
  checks a stream of its own;
* ``check_edge_budget`` / ``check_min_sets`` — how many sets the reference
  checks: in an order drawn from the seed that alternates between the
  seed's own sets and the window's, until it has expanded this many edges,
  and never fewer than this many sets (nor more than ``MAX_COMPARED``).

The window drives ``SketchStore.refresh`` → ``repro.sampling`` →
``core.traversal`` / ``core.rng``.
``rrr_sets_per_s`` is the RRR sets of every call started inside
``--seconds``, over the span from the window's start to the end of the
last of them.  Every set the window and the seed's calls produced must be
its batch's reference set: the batch indices must be the fresh ones in
sequence, each root the reference's root, each set the reference cascade
with no level cap.
"""
from __future__ import annotations

import gc
import numpy as np

from bench import graphs, harness
from bench.reference import rrr as ref

MAX_COMPARED = 8192      # sets the reference may check in one run


def check_start(seed: int) -> int:
    """First batch index of the seed's own calls: drawn from the seed,
    a multiple of 2**10 in [2**20, 2**30), far from the window's
    indices."""
    return int(np.random.default_rng(seed).integers(2 ** 10, 2 ** 20)) << 10


class Driver:
    def __init__(self, run):
        self.run = run
        cfg, tr = run.config, run.traffic
        if int(cfg["layout"]["chips"]) != 1:
            raise ValueError("the build driver drives one chip; a mesh "
                             "layout needs ShardedSketchStore wired in")
        self.pool = int(tr["pool_batches"])
        self.colors = int(cfg["num_colors"])
        self.master_seed = int(tr["master_seed"])

    # ------------------------------------------------------------ set-up
    def _program_probs(self, prob: np.ndarray) -> np.ndarray:
        """The probabilities the program is given: the data's float32, or
        rounded to bfloat16 for the lower-precision control."""
        if self.run.control == "bf16":
            import ml_dtypes
            return prob.astype(ml_dtypes.bfloat16).astype(np.float32)
        return prob

    def setup(self) -> None:
        run, cfg = self.run, self.run.config
        jax = run.jax
        from repro.graph import csr
        from repro.sampling import SamplerSpec
        from repro.serve.influence import PoolConfig, SketchStore

        with run.span("data"):
            self.edges = graphs.make_edges(cfg["graph"])
            e = self.edges
            g = csr.from_edges(e.src, e.dst, self._program_probs(e.prob),
                               e.num_vertices)
        control = run.control or ""
        max_iters = (int(control[3:]) if control.startswith("cap")
                     else int(cfg["max_iters"]))
        spec = SamplerSpec(diffusion=cfg["diffusion"],
                           backend=cfg["backend"],
                           num_colors=self.colors,
                           master_seed=self.master_seed,
                           max_iters=max_iters, frontier=cfg["frontier"])
        self.store = SketchStore(g, PoolConfig(max_batches=self.pool,
                                               spec=spec))
        with run.span("sampling.ensure"):
            self.store.ensure(self.pool)
            jax.block_until_ready(self.store.visited_stack())
        # One refresh: the window's exact programs, warm.
        self._refresh()
        self.first_window_index = self.store.next_batch_index

    def _refresh(self):
        with self.run.span("sampling.refresh"):
            slots = self.store.refresh(float(self.run.traffic[
                "refresh_fraction"]))
            self.run.jax.block_until_ready(self.store.visited_stack())
        return slots

    # ------------------------------------------------------------ window
    def window(self) -> dict:
        produced, calls = [], 0
        t0 = harness.now()
        while harness.now() - t0 < self.run.seconds:
            slots = self._refresh()
            produced.extend(self.store.batches[i] for i in slots)
            calls += 1
        span = harness.now() - t0
        self.produced = produced
        sets = len(produced) * self.colors
        self.run.counters.update(batches=len(produced), calls=calls,
                                 window_s=span, sets=sets)
        return {"rrr_sets_per_s": sets / span}

    def log_window(self, **info) -> None:
        c = self.run.counters
        harness.log("window", dict(info, batches=c["batches"],
                                   calls=c["calls"],
                                   window_s=round(c["window_s"], 4),
                                   pool_batches=self.pool))

    # ------------------------------------------------------------- check
    def _seed_calls(self) -> list:
        """The window's own ``refresh``, ``check_refreshes`` more times,
        from the seed's first batch index."""
        self.store.next_batch_index = check_start(self.run.seed)
        out = []
        for _ in range(int(self.run.traffic["check_refreshes"])):
            out.extend(self.store.batches[i] for i in self._refresh())
        return out

    def check(self) -> dict:
        run, colors = self.run, self.colors
        seeded = self._seed_calls()
        streams = [(self.produced, self.first_window_index),
                   (seeded, check_start(run.seed))]
        out_of_sequence = 0
        for batches, start in streams:
            got = [int(b.batch_index) for b in batches]
            out_of_sequence += sum(
                a != b for a, b in zip(got, range(start, start + len(got))))
        # Sets in an order drawn from the seed, alternating between the
        # seed's own calls and the window's.
        rng = np.random.default_rng(run.seed)
        orders = [[(k, *divmod(int(j), colors)) for j in
                   rng.permutation(len(b) * colors)]
                  for k, (b, _) in enumerate(streams)]
        merged = [x for pair in zip(*orders) for x in pair]
        longer = max(orders, key=len)
        merged += longer[min(map(len, orders)):]
        picks = merged[:MAX_COMPARED]
        # Host copies of what the program produced, then its state goes
        # before the reference runs.
        keys = {(k, i) for k, i, _ in picks}
        batch = {key: streams[key[0]][0][key[1]] for key in keys}
        masks = {key: np.asarray(b.visited) for key, b in batch.items()}
        roots = {key: np.asarray(b.roots) for key, b in batch.items()}
        index = {key: int(b.batch_index) for key, b in batch.items()}
        total = sum(len(b) for b, _ in streams) * colors
        edges = self.edges
        self.produced = self.store = batch = None
        gc.collect()

        g = ref.ReverseGraph.from_edges(edges)
        ref_roots, roots_differ = {}, 0
        for key in masks:
            ref_roots[key] = ref.roots(g.num_vertices, colors,
                                       self.master_seed, index[key])
            roots_differ += int(np.sum(ref_roots[key] != roots[key]))
        sets = ref.compare_sets(g, (
            (masks[k, i], ref_roots[k, i], c,
             ref.stream_seed(self.master_seed, index[k, i]))
            for k, i, c in picks),
            edge_budget=int(run.traffic["check_edge_budget"]),
            min_sets=int(run.traffic["check_min_sets"]))
        harness.log("reference", dict(
            sets, sets_produced=total,
            window_sets=len(streams[0][0]) * colors,
            seed_sets=len(streams[1][0]) * colors,
            seed_first_index=streams[1][1],
            max_iters=int(run.config["max_iters"])))
        differ = sets["differing"]
        return {"attempted": total, "failed": differ, "checks": [
            ("sets_differing", differ, 0),
            ("roots_differing", roots_differ, 0),
            ("batches_out_of_sequence", out_of_sequence, 0),
        ]}
