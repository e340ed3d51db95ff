"""Launch-layer checks under 8 forced host devices (subprocess twin of
tests/test_launch.py): the REAL lower_cell code path at reduced scale for
every kind (train/prefill/decode) and family, plus sharding-rule sanity."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses      # noqa: E402

import jax              # noqa: E402
import numpy as np      # noqa: E402

from repro.configs import registry                      # noqa: E402
from repro.distributed import sharding_rules as rules   # noqa: E402
from repro.launch import dryrun, specs                  # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402
from repro.models.config import ShapeConfig             # noqa: E402


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    shapes = {
        "train": ShapeConfig("t", "train", 64, 8),
        "prefill": ShapeConfig("p", "prefill", 64, 4),
        "decode": ShapeConfig("d", "decode", 64, 8),
    }
    # one arch per family keeps runtime sane; all 10 are covered at full
    # scale by the real dry-run sweep.
    archs = ["llama3.2-3b", "deepseek-v3-671b", "zamba2-2.7b",
             "mamba2-1.3b", "musicgen-medium"]
    for arch in archs:
        cfg = dataclasses.replace(
            registry.smoke(arch), num_patches=0, attn_block_q=32,
            attn_block_k=32, ssm_chunk=32)
        for kind, shape in shapes.items():
            rec = dryrun.lower_cell(arch, kind, multi_pod=False, cfg=cfg,
                                    mesh=mesh, shape=shape)
            assert rec["status"] == "ok", (arch, kind, rec.get("error"),
                                           rec.get("traceback", "")[-500:])
            assert rec["flops_per_device"] > 0, (arch, kind)
            rt = rec["roofline"]
            assert rt["compute_s"] >= 0 and rt["memory_s"] > 0
            print(f"OK lower {arch} {kind} dom={rt['dominant']}")

    # sharding rules: every param leaf gets a valid sharding on this mesh
    cfg = registry.smoke("qwen1.5-110b")
    p_shapes = specs.param_specs(cfg)
    sh = rules.param_shardings(mesh, p_shapes)
    n_sharded = 0
    for leaf_shape, leaf_sh in zip(jax.tree.leaves(p_shapes),
                                   jax.tree.leaves(sh)):
        spec = leaf_sh.spec
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            size = np.prod([mesh.shape[a] for a in
                            (ax if isinstance(ax, tuple) else (ax,))])
            assert leaf_shape.shape[dim] % size == 0, (leaf_shape, spec)
            n_sharded += 1
    assert n_sharded > 0
    print(f"OK sharding_rules ({n_sharded} sharded dims)")


if __name__ == "__main__":
    main()
