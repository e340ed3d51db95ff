"""Int8-compressed DP training: converges like the exact step (subprocess,
8 forced devices)."""
import os
import pathlib
import subprocess
import sys

import pytest

_SRC = str(pathlib.Path(__file__).parents[1] / "src")

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import registry
from repro.data.pipeline import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import model
from repro.optim import adamw
from repro.train.dp_step import make_dp_train_step

cfg = registry.smoke("llama3.2-3b")
mesh = make_mesh((8,), ("data",))
data = SyntheticLM(cfg, 16, 32, seed=4)

def run(compressed):
    params = model.init_params(jax.random.key(0), cfg)
    opt = adamw.init(params)
    step, init_res = make_dp_train_step(cfg, lambda s: 1e-3, mesh,
                                        compressed=compressed)
    err = init_res(params)
    losses = []
    for s in range(25):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
        params, opt, err, m = step(params, opt, err, b)
        losses.append(float(m["loss"]))
    return losses

exact = run(False)
comp = run(True)
print("exact first/last:", exact[0], exact[-1])
print("comp  first/last:", comp[0], comp[-1])
assert comp[-1] < comp[0] - 0.4, "compressed run must learn"
assert abs(comp[-1] - exact[-1]) < 0.25, (comp[-1], exact[-1])
print("OK dp_compression")
"""


@pytest.mark.slow
def test_compressed_dp_converges_like_exact():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          timeout=1800)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-2500:]
    assert "OK dp_compression" in proc.stdout
