"""Runtime plumbing: accelerator config, compile cache, meshes, the
launcher's in-process smoke, reproducible datasets, one process per chip."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro.launch import accel
from repro.launch.mesh import make_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _env(**updates):
    env = dict(os.environ,
               PYTHONPATH=str(ROOT / "src") + os.pathsep + str(ROOT))
    env.update(updates)
    return env


@pytest.fixture
def restore_cache_config():
    """`accel.configure` writes JAX's cache directory setting: put it back."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_configure_adds_no_flags_and_forces_no_cpu_without_platform(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    cfg = accel.configure(host_devices=4)
    assert cfg["xla_flags_added"] == [] and not cfg["host_devices_forced"]
    assert "XLA_FLAGS" not in os.environ
    assert "JAX_PLATFORMS" not in os.environ


def test_configure_flags_are_accepted_by_xla():
    """Every flag `configure` sets under JAX_PLATFORMS=cpu parses: the
    process reaches its first op with the forced devices."""
    code = ("from repro.launch import accel; "
            "cfg = accel.configure(host_devices=3); "
            "import jax, jax.numpy as jnp; "
            "jnp.ones(3).block_until_ready(); "
            "print(cfg['xla_flags_added'], jax.device_count())")
    env = _env(JAX_PLATFORMS="cpu", XLA_FLAGS="")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "3"


def test_compile_cache_honours_env(monkeypatch, restore_cache_config,
                                   tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert accel.configure_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = accel.configure_compilation_cache()
    assert got == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert accel.configure_compilation_cache() == got      # never moves


@pytest.mark.parametrize("explicit_devices", [False, True])
def test_mesh_axes_are_auto(explicit_devices):
    devices = jax.devices()[:1] if explicit_devices else None
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices)
    assert mesh.axis_names == ("data", "model")
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)


def test_launcher_smoke_in_process(capsys, tmp_path, restore_cache_config):
    from repro.launch import serve_influence
    serve_influence.main(["--smoke", "--n", "200", "--batches", "4",
                          "--ckpt-dir", str(tmp_path)])
    assert "[smoke] PASS" in capsys.readouterr().out


def test_table1_clone_is_the_same_graph_in_every_process():
    code = ("import hashlib, numpy as np; "
            "from repro.graph import datasets; "
            "g = datasets.table1_clone('web-Google', scale=0.003); "
            "print(hashlib.sha256(np.asarray(g.src).tobytes() "
            "+ np.asarray(g.dst).tobytes() "
            "+ np.asarray(g.prob).tobytes()).hexdigest())")
    digests = set()
    for hash_seed in ("1", "2"):
        out = subprocess.run([sys.executable, "-c", code],
                             env=_env(JAX_PLATFORMS="cpu",
                                      PYTHONHASHSEED=hash_seed),
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        digests.add(out.stdout.strip())
    assert len(digests) == 1


def test_benchmark_parent_imports_no_jax():
    code = ("import sys; from benchmarks import run; "
            "names = [s[0] for s in run._sections()]; "
            "assert 'jax' not in sys.modules, 'parent imported jax'; "
            "print(len(names))")
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]
    assert int(out.stdout) > 0


@pytest.mark.parametrize("alone", [False, True],
                         ids=["checkout_without_tpu", "script_alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    if alone:
        (tmp_path / "chip_smoke.py").write_text(script.read_text())
        script = tmp_path / "chip_smoke.py"
    out = subprocess.run(
        [sys.executable, str(script)], cwd=tmp_path, capture_output=True,
        text=True, timeout=120,
        env=_env(JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
                 PYTHONPATH=""))
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
