#!/usr/bin/env python3
"""Record the TPU trace with named scopes and program spans that
``test_scopes.py`` reads.

    python3 bench/tests/record_scoped_trace.py bench/tests/data/v5e_scoped.xplane.pb

On one TPU chip, inside the host spans ``bench:window`` and
``bench:sampling.refresh``: a jitted ``scoped_step`` that gathers rows
under ``jax.named_scope("bpt.gather")`` and counts them under
``bpt.count`` three times in a ``while`` loop, and 10 ms of host sleep;
then, inside the program's own span ``repro:store.set_slots``
(`repro.obs.span`), ``scoped_step``, 20 ms of host sleep and
``scoped_step`` again.  Copies the ``.xplane.pb`` to the given path and
prints what the test expects to find.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: no TPU", file=sys.stderr)
        return 1
    from repro import obs

    @jax.jit
    def scoped_step(table, idx):
        def body(c):
            i, acc = c
            with jax.named_scope("bpt.gather"):
                rows = table[(idx + i) % table.shape[0]]
            with jax.named_scope("bpt.count"):
                acc = acc + jnp.sum(rows, dtype=jnp.uint32)
            return i + 1, acc
        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (jnp.int32(0), jnp.uint32(0)))[1]

    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.integers(0, 2**32, (1 << 16, 2),
                                     dtype=np.uint32))
    idx = jnp.asarray(rng.integers(0, 1 << 16, 1 << 20, dtype=np.int32))
    jax.block_until_ready(scoped_step(table, idx))
    work = ROOT / ".bench_work"
    with tempfile.TemporaryDirectory(dir=work if work.is_dir()
                                     else None) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench:window"):
            with jax.profiler.TraceAnnotation("bench:sampling.refresh"):
                jax.block_until_ready(scoped_step(table, idx))
                time.sleep(0.01)
                with obs.span("store.set_slots", slots=1):
                    jax.block_until_ready(scoped_step(table, idx))
                    time.sleep(0.02)
                    jax.block_until_ready(scoped_step(table, idx))
        jax.profiler.stop_trace()
        src = sorted(pathlib.Path(d).rglob("*.xplane.pb"))[-1]
        pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out)
    from bench import scopes, trace
    planes = scopes.load(out)
    dev = [p for p in planes if trace.DEVICE_PLANE.match(p.name)][0]
    for line in (trace.OPS_LINE, trace.MODULES_LINE):
        print(line, sorted({(e.name[:60], e.tf_op)
                            for e in dev.lines.get(line, [])}))
    summary = trace.summarize(trace.load(out), window_span="bench:window",
                              span_prefix="bench:", devices=1)
    print(json.dumps(scopes.by_scope(summary, scopes.name_stacks(planes),
                                     program="scoped_step")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
