"""Benchmark driver: one bench per paper table/figure + kernel micros +
the roofline table from dry-run records.  ``python -m benchmarks.run``.

Sizes are scaled for CPU wall-clock sanity; every bench accepts kwargs for
full-size runs on real hardware.

Every section runs in a child process of its own (``python -m
benchmarks.run --section I``) and this parent never imports JAX: an
accelerator belongs to one process at a time, and several benches start
JAX children themselves.  Sections marked as forced-host-device runs are
CPU rehearsals of a mesh; they are skipped, saying so, unless
``JAX_PLATFORMS=cpu``.  Any failed section makes the run exit non-zero.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time


def _sections():
    """(name, forced_host_devices, fn) — ``fn`` imports its bench lazily,
    so listing the sections imports no JAX."""
    def bench(module: str):
        import importlib
        return importlib.import_module(f"benchmarks.{module}")

    return [
        ("Fig4 work savings / occupancy", False,
         lambda: bench("bench_work_savings").run(
             n=1200, degrees=(4, 11), colors=(32, 64),
             probs=(0.1, 0.3), seeds=(0,))),
        ("Fig5 reordering", False,
         lambda: bench("bench_reorder").run(n=2000)),
        ("Fig7/8 fused vs unfused", False,
         lambda: bench("bench_fused_vs_unfused").run(
             n=1500, colors=(8, 32), probs=(0.1, 0.2))),
        ("Fig9 frontier profile", False,
         lambda: bench("bench_frontier_profile").run(
             n=2000, colors=(1, 32), probs=(0.2,))),
        ("kernel micros", False, lambda: bench("bench_kernels").run()),
        ("scatter_or_words packed fast path", False,
         lambda: bench("bench_scatter_words").run(
             rows=1 << 12, counts=(1 << 8, 1 << 11))),
        ("Butterfly frontier exchange vs flat all-gather "
         "(8 forced CPU devices)", True,
         lambda: bench("bench_butterfly_exchange").run(
             rows=1 << 11, shard_counts=(8, 6),
             active_words=(64, 256), iters=5)),
        ("IMM end-to-end", False,
         lambda: bench("bench_imm").run(theta_cap=2048)),
        ("Online serving: throughput vs pool size", False,
         lambda: bench("bench_serve_influence").run(
             n=1000, pool_sizes=(2, 4, 8))),
        ("Distributed serving: shards × deadline (8 forced CPU devices)",
         True,
         lambda: bench("bench_distributed_serve").run(
             n=600, batches=8, shard_counts=(1, 4, 8),
             deadlines_ms=(5, 25), clients=32)),
        ("Serving tier SLO: open-loop load vs replicas × quota", False,
         lambda: bench("bench_serve_load").run(
             n=400, batches=4, arrivals=120, offered_qps=60.0)),
        ("Streaming deltas: incremental vs cold refresh × churn "
         "(8 forced CPU devices)", True,
         lambda: bench("bench_stream_updates").run(
             sweeps=bench("bench_stream_updates").standard_sweeps(
                 churn_n=3000, scale_ns=(3000,), batches=8))),
        ("Roofline table (from dry-run records)", False,
         lambda: bench("roofline").table()),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", type=int, default=None,
                    help="run only section I, in this process")
    args = ap.parse_args(argv)
    sections = _sections()
    if args.section is not None:
        sections[args.section][2]()
        return 0

    t0 = time.time()
    on_cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    failed = []
    for i, (name, forced_host, _) in enumerate(sections):
        print(f"\n===== {name} =====", flush=True)
        if forced_host and not on_cpu:
            print("skipped: a forced-host-device CPU rehearsal, run only "
                  "under JAX_PLATFORMS=cpu")
            continue
        rc = subprocess.run([sys.executable, "-m", "benchmarks.run",
                             "--section", str(i)]).returncode
        if rc != 0:
            print(f"BENCH-ERROR {name}: exit code {rc}")
            failed.append(name)
    print(f"\n[benchmarks] total {time.time() - t0:.1f}s, "
          f"{len(failed)} of {len(sections)} sections failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
