"""What every cell shares: finding its files, the chip check, the clock,
host spans, the trace around the window, and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``.  Everything
that belongs to one configuration, traffic mix or per-layer metric is a
file of its own, found by name:

* ``bench/configs/<config>.json`` — the deployment (graph, diffusion,
  level cap, colors, chip layout, guarantees);
* ``bench/traffic/<traffic>.json`` — the traffic mix; its ``kind`` names
  the general driver in ``bench/drivers/<kind>.py`` that reads it;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric, with a
  ``read(run)`` that returns the value or ``None`` when it finds nothing.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = ROOT / ".bench_work"          # traces; listed in .gitignore
SPAN_PREFIX = "bench:"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, config, traffic) for workload ``name``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def cell_metrics(bench: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics with
    ``--trace 0``, its per-layer metrics with ``--trace 1``."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def configure_jax_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed ``<checkout>/.jax_cache`` (the path is
    part of the cache key).  Must run before JAX is imported."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    # Every program, however quick to compile, so that a second run of a
    # cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(jax, chips: int):
    """The cell's devices; `NoChip` without a TPU or with too few."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


class Clock:
    """Compile seconds and persistent-cache hits, from JAX's own events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Run:
    """One run of one cell: its files, seed, device, spans and counters."""

    def __init__(self, *, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, jax, devices,
                 t_start: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = int(seed), seconds, trace
        self.jax, self.devices = jax, list(devices)
        self.t_start = t_start
        self.clock = Clock(jax)
        self.counters: dict = {}     # the driver's counts, for the readers
        self.trace_summary = None    # trace.Summary of a traced window
        self.device_kind = self.devices[0].device_kind
        self.control = None          # bench/control.py sets it

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around a call into one layer, written into the
        profiler's trace (``bench:<name>``) so that idle gaps on the
        device can be attributed to what the host was doing."""
        with self.jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield

    def memory_peak_bytes(self) -> int | None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    @contextlib.contextmanager
    def traced(self):
        """Trace the window when ``--trace 1``; reduce it on exit."""
        if not self.trace:
            yield
            return
        from bench import trace as trace_lib
        out = WORK / "trace"
        shutil.rmtree(out, ignore_errors=True)
        out.mkdir(parents=True)
        self.jax.profiler.start_trace(str(out))
        try:
            with self.span("window"):
                yield
        finally:
            self.jax.profiler.stop_trace()
        self.trace_summary = trace_lib.summarize(
            trace_lib.load(trace_lib.find_xplane(out)),
            window_span=SPAN_PREFIX + "window", span_prefix=SPAN_PREFIX,
            devices=len(self.devices))
        shutil.rmtree(out, ignore_errors=True)


def read_per_layer(run: Run, metrics: list[dict]) -> dict:
    """Each per-layer metric from its reader; a reader that finds nothing
    returns ``None`` and the metric is left out."""
    out = {}
    for m in metrics:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, run: Run, peak: int | None,
                checks: list[tuple[str, float, float]]) -> dict:
    dev = run.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(run.devices), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    s = run.trace_summary
    if s is not None:
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        line["breakdown"] = {"device_ops": s.top_ops(10),
                             "idle_gaps": s.top_gaps(10)}
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in checks}
    return line


def now() -> float:
    return time.perf_counter()


def log(*parts) -> None:
    print("[bench]", *parts, file=sys.stderr, flush=True)
