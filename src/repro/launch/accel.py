"""Accelerator runtime configuration — the ONE place that sets the XLA
flags and JAX settings every launcher and benchmark needs, before jax's
backend materializes.

Two concerns live here:

* **Host-device shims.**  CI and ``--smoke`` runs exercise the mesh
  backends on forced host CPU devices
  (``--xla_force_host_platform_device_count``).  That happens only where
  the run explicitly asked for the CPU (``JAX_PLATFORMS=cpu``): anywhere
  else the mesh is built from the real devices, so a multi-chip path never
  runs on fake CPU devices by accident.
* **Persistent compilation cache.**  Where ``JAX_COMPILATION_CACHE_DIR`` is
  set, JAX reads it itself and nothing here overrides it.  Otherwise the
  cache goes to ``<checkout>/.jax_cache`` — a fixed path, because the path
  is part of the cache key.

Everything here must run before the first jax device query or op (module
imports are safe — the backend materializes lazily).  ``LIBTPU_INIT_ARGS``
is never touched.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/src/repro/launch/accel.py → <checkout>/.jax_cache
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def _flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def append_xla_flags(flags) -> list[str]:
    """Append ``flags`` to ``XLA_FLAGS`` (idempotent per flag NAME: a flag
    the user already set — either value — is left alone).  Returns the
    flags actually added."""
    current = os.environ.get("XLA_FLAGS", "")
    added = [f for f in flags if _flag_name(f) not in current]
    if added:
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [current] + added))
    return added


def set_host_device_count(n: int) -> bool:
    """Force ``n`` host CPU devices (the multi-device smoke/CI trick).

    Only where ``JAX_PLATFORMS`` is explicitly ``cpu``; otherwise — and
    when ``n <= 1`` — a no-op returning False.  Never sets
    ``JAX_PLATFORMS`` itself.  Must run before the jax backend materializes
    (first device query), like everything here.
    """
    if n <= 1 or os.environ.get("JAX_PLATFORMS") != "cpu":
        return False
    return bool(append_xla_flags(
        [f"--xla_force_host_platform_device_count={n}"]))


def configure_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory
    unless ``JAX_COMPILATION_CACHE_DIR`` already names one.  Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def configure(*, host_devices: int = 1) -> dict:
    """Apply the standard accelerator configuration.

    ``host_devices > 1`` forces that many host CPU devices under
    ``JAX_PLATFORMS=cpu`` (smoke/CI meshes).  Returns
    ``{"xla_flags_added": [...], "host_devices_forced": bool,
    "compilation_cache_dir": str}`` for launcher logs and worker-env
    propagation.
    """
    forced = set_host_device_count(host_devices)
    added = ([f"--xla_force_host_platform_device_count={host_devices}"]
             if forced else [])
    return {"xla_flags_added": added, "host_devices_forced": forced,
            "compilation_cache_dir": configure_compilation_cache()}
