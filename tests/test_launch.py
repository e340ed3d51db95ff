"""Launch layer: HLO cost parser units + the real lower_cell path in a
subprocess (8 forced devices; see tests/launch_check.py)."""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as ha

_SCRIPT = pathlib.Path(__file__).parent / "launch_check.py"
_SRC = str(pathlib.Path(__file__).parents[1] / "src")


# -------------------------------------------------------------- HLO parser
def _xla_flops(comp) -> float:
    """``compiled.cost_analysis()`` returns a dict on some jax versions and
    a one-element list of dicts on others — normalize."""
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca["flops"])


def test_parser_matches_xla_loop_free():
    def f(a, b):
        return a @ b
    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 32), jnp.float32),
                            jax.ShapeDtypeStruct((32, 128), jnp.float32)
                            ).compile()
    got = ha.full_cost(comp.as_text())
    assert got["flops"] == 2 * 64 * 32 * 128
    assert got["flops"] == _xla_flops(comp)


def test_parser_weights_scan_loops():
    def g(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=12)
        return out
    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(g).lower(s, s).compile()
    got = ha.full_cost(comp.as_text())
    assert got["flops"] == 12 * 2 * 64**3, \
        "scan body must be weighted by trip count"
    # XLA's own analysis counts the body once — we must exceed it
    assert got["flops"] > _xla_flops(comp) * 10


def test_parser_nested_scans():
    def g(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        out, _ = jax.lax.scan(outer, x, None, length=5)
        return out
    s = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    comp = jax.jit(g).lower(s, s).compile()
    got = ha.full_cost(comp.as_text())
    assert got["flops"] == 15 * 2 * 32**3


def test_shape_bytes_tuple_and_layout():
    assert ha._shape_bytes("f32[2,3]{1,0}") == 24
    assert ha._shape_bytes("(s32[], bf16[4,4]{1,0}, pred[8])") == 4 + 32 + 8
    assert ha._shape_bytes("(f32[2], /*index=5*/f32[2])") == 16


def test_collectives_counted(tmp_path):
    """all-reduce on a 2-device mesh appears in the collective accounting
    with the 2× ring factor."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch import hlo_analysis as ha
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,), ("d",))
def f(x):
    return jax.lax.psum(x, "d")
fn = jax.shard_map(f, mesh=mesh, in_specs=(P("d"),), out_specs=P(),
                   check_vma=False)
comp = jax.jit(fn).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile()
c = ha.full_cost(comp.as_text())["collective"]
assert c["op_counts"].get("all-reduce", 0) >= 1, c
assert c["per_device_bytes"] >= 2 * 4 * 128 * 4, c
print("OK collective")
"""
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK collective" in proc.stdout


# ------------------------------------------------------------- lower_cell
@pytest.mark.slow
def test_lower_cell_all_kinds_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, str(_SCRIPT)],
                          capture_output=True, text=True, env=env,
                          timeout=1800)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert "OK sharding_rules" in proc.stdout
    assert proc.stdout.count("OK lower") == 15, proc.stdout
