#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload rmat-ic-wc.build --seed 7 --seconds 20 --trace 0

(``python -m bench.run`` takes the same arguments.)  The cell, its
configuration and its traffic are found by name from ``BENCHMARK.json``
and the files under ``bench/``.  Set-up (data from the seed, pool, warm-up
of every shape the window uses) is timed as ``setup_s``; then the window
runs for ``--seconds``; then what the window produced is compared with the
plain reference under ``bench/reference/``.  With ``--trace 1`` the window
is traced and the cell's per-layer metrics are read from the trace and the
driver's counters instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), and last ``checks``, each compared number beside its
limit, which also end standard error.  Without a TPU, with fewer chips
than the cell asks for, or outside a checkout that holds the program, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None, control: str | None = None) -> int:
    """``control`` (`bench/control.py`): run the cell's lower-precision
    control in the program's place instead of the program as configured."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from bench import harness
    try:
        bench, cell, config, traffic = harness.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.configure_jax_cache()
    import jax
    try:
        devices = harness.require_chips(jax, int(cell["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    run = harness.Run(cell=cell, config=config, traffic=traffic,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), jax=jax, devices=devices,
                      t_start=T_START)
    run.control = control
    line = execute(run, bench)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def execute(run, bench: dict) -> dict:
    """Set-up, window, check: the whole of one run after the chip check
    (the CPU rehearsals call this directly)."""
    from bench import harness
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{run.traffic['kind']}.py").Driver(run)
    driver.setup()
    setup_s = harness.now() - run.t_start
    compiles_before = run.clock.compiles
    with run.traced():
        e2e = driver.window()
    compiled_in_window = run.clock.compiles - compiles_before
    peak = run.memory_peak_bytes()
    driver.log_window(setup_s=setup_s, compile_s=run.clock.compile_s,
                      cache_hits=run.clock.cache_hits,
                      compiled_in_window=compiled_in_window)
    outcome = driver.check()
    wanted = harness.cell_metrics(bench, run.cell["name"], run.trace)
    if run.trace:
        metrics = harness.read_per_layer(run, wanted)
    else:
        e2e = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in wanted if m["name"] in e2e}
    checks = outcome["checks"]
    correct = all(v <= lim for _, v, lim in checks)
    return harness.result_line(
        correct=correct, attempted=outcome["attempted"],
        failed=outcome["failed"], metrics=metrics, run=run, peak=peak,
        checks=checks)


if __name__ == "__main__":
    sys.exit(main())
