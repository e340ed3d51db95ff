"""Production training launcher: ``--arch`` selects any assigned config;
mesh shape adapts to the available devices (on a real pod the runtime
provides them; on CPU pass --smoke for a reduced config).

    python -m repro.launch.train --arch llama3.2-3b --smoke --steps 50
    python -m repro.launch.train --arch nemotron-4-340b \
        --mesh 16x16 --steps 1000 --checkpoint-dir /ckpts/nemotron
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import registry
from repro.distributed import sharding_rules as rules
from repro.launch.mesh import make_mesh
from repro.models.config import SHAPES
from repro.train import loop


def parse_mesh(spec: str | None):
    if spec is None:
        n = len(jax.devices())
        return make_mesh((n,), ("data",))
    dims = tuple(int(x) for x in spec.split("x"))
    axes = {1: ("data",), 2: ("data", "model"),
            3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, axes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-scale)")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 or 2x16x16")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    cfg = registry.smoke(args.arch) if args.smoke else registry.get(args.arch)
    shp = SHAPES[args.shape]
    batch = args.batch or (8 if args.smoke else shp.global_batch)
    seq = args.seq_len or (64 if args.smoke else shp.seq_len)

    mesh = parse_mesh(args.mesh)
    rules.set_mesh(mesh if np.prod(list(mesh.shape.values())) > 1 else None)
    try:
        res = loop.train(cfg, batch=batch, seq_len=seq, steps=args.steps,
                         lr=args.lr, checkpoint_dir=args.checkpoint_dir,
                         ckpt_every=args.ckpt_every,
                         num_microbatches=args.microbatches)
        print(f"[launch.train] done: loss {res.losses[0]:.3f} → "
              f"{res.losses[-1]:.3f} over {res.steps_run} steps")
    finally:
        rules.set_mesh(None)


if __name__ == "__main__":
    main()
