"""Drive a whole run of a cell on the CPU at a tiny size, skipping the
harness's look for a chip.  The benchmark's own tests use it:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import copy
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_SCALE = 10            # Kronecker scale: 1,024 vertices, ~25,000 edges


def rehearse(cell_name: str, *, seed: int = 11, seconds: float = 1.0,
             traffic: dict | None = None, control: str | None = None,
             scale: int = TINY_SCALE):
    """(result line, run) of one run of ``cell_name`` on the default
    devices, with the configuration's graph cut to Kronecker ``scale``
    and the given overrides merged into its traffic."""
    import jax
    from bench import run as run_mod

    bench, cell, cfg, tr = harness.load_cell(cell_name)
    cfg = copy.deepcopy(cfg)
    cfg["graph"]["scale"] = scale
    tr = dict(tr, **(traffic or {}))
    devices = jax.devices()[:int(cell["chips"])]
    run = harness.Run(cell=cell, config=cfg, traffic=tr, seed=seed,
                      seconds=seconds, trace=False, jax=jax,
                      devices=devices, t_start=harness.now())
    run.control = control
    return run_mod.execute(run, bench), run
