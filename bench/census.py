"""Depth census of RRR sets: how many levels a reverse cascade runs.

The level cap of a configuration (``max_iters``) has to exceed the depth
of every RRR set the benchmark will ever sample, or the program cuts sets
short without saying so and the comparison with the reference fails.
This script measures the depth distribution with the plain reference on
the configuration's own graph, so the cap in each configuration file is
set from data:

    python bench/census.py --config rmat-ic-wc --seed 0 --batches 50
    python bench/census.py --config rmat-ic-uni --seed 0 --batches 4 --colors 8
    python bench/census.py --config rmat-ic-wc --seed 0 --lt --walks 2000

``--seed`` is the master seed of the sampled stream; the graph is the
configuration's own.  IC: every color of ``--batches`` batches (64 colors each), through
`reference.rrr.cascade`; besides each set's depth it prints the deepest
color of each batch, which is what one fused batch runs.  ``--colors``
samples fewer colors per batch (for the uniform regime, where each set is
most of the graph).  ``--lt``: Linear Threshold with the in-weights
normalized to sum to one per vertex, under which an RRR set is a reverse
random walk that stops at a vertex it has visited; the walk's length is
its depth.  Prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import graphs  # noqa: E402
from bench.reference import rrr  # noqa: E402


def _quantiles(x) -> dict:
    x = np.asarray(x)
    return {"n": int(x.size), "mean": float(x.mean()),
            "p50": float(np.percentile(x, 50)),
            "p99": float(np.percentile(x, 99)), "max": int(x.max())}


def ic_census(cfg: dict, seed: int, batches: int, colors: int) -> dict:
    edges = graphs.make_edges(cfg["graph"])
    g = rrr.ReverseGraph.from_edges(edges)
    c = cfg["num_colors"]
    depths, sizes, batch_max = [], [], []
    rng = np.random.default_rng(seed)
    for b in range(batches):
        roots = rrr.roots(g.num_vertices, c, seed, b)
        pick = (np.arange(c) if colors >= c
                else np.sort(rng.choice(c, colors, replace=False)))
        sets, d = rrr.cascade(g, roots, pick, rrr.stream_seed(seed, b))
        depths.extend(d.tolist())
        sizes.extend(sets.sum(1).tolist())
        batch_max.append(int(d.max()))
    cap = cfg["max_iters"]
    return {"config": cfg["name"], "model": "ic", "seed": seed,
            "vertices": edges.num_vertices, "edges": edges.num_edges,
            "depth": _quantiles(depths),
            "share_deeper_than_64": float(np.mean(np.asarray(depths) > 64)),
            "deeper_than_cap": int(np.sum(np.asarray(depths) > cap)),
            "max_iters": cap,
            "batch_max_depth": _quantiles(batch_max),
            "set_size": _quantiles(sizes)}


def lt_census(cfg: dict, seed: int, walks: int) -> dict:
    edges = graphs.make_edges(cfg["graph"])
    g = rrr.ReverseGraph.from_edges(edges)
    w = g.prob.astype(np.float64)
    deg = np.diff(g.indptr)
    row = np.repeat(np.arange(g.num_vertices), deg)
    tot = np.bincount(row, w, minlength=g.num_vertices)
    cum = np.cumsum(w / np.maximum(tot[row], 1e-300))
    base = np.concatenate([[0.0], cum])[g.indptr[:-1]]
    rng = np.random.default_rng(seed)
    lengths = []
    for _ in range(walks):
        x = int(rng.integers(g.num_vertices))
        seen = {x}
        while True:
            lo, hi = g.indptr[x], g.indptr[x + 1]
            if hi == lo:
                break
            e = lo + int(np.searchsorted(cum[lo:hi] - base[x],
                                         rng.random(), side="right"))
            x = int(g.dst[min(e, hi - 1)])
            if x in seen:
                break
            seen.add(x)
        lengths.append(len(seen))
    return {"config": cfg["name"], "model": "lt", "seed": seed,
            "vertices": edges.num_vertices, "edges": edges.num_edges,
            "depth": _quantiles(lengths),
            "share_deeper_than_64": float(np.mean(np.asarray(lengths) > 64))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--colors", type=int, default=64)
    ap.add_argument("--lt", action="store_true")
    ap.add_argument("--walks", type=int, default=500)
    args = ap.parse_args(argv)
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{args.config}.json").read_text())
    t0 = time.perf_counter()
    out = (lt_census(cfg, args.seed, args.walks) if args.lt
           else ic_census(cfg, args.seed, args.batches, args.colors))
    out["seconds"] = round(time.perf_counter() - t0, 1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
