#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/v5e_small.xplane.pb

On one TPU chip, inside a host span ``bench:window``: a jitted matrix
product under the span ``bench:matmul``; then 30 ms of host sleep and the
coverage kernel under ``bench:coverage`` (so the device idles ~30 ms
inside that span).  Copies the ``.xplane.pb`` to the given path and prints
what the test expects to find.  Exits non-zero without a TPU.
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
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    from repro.kernels import coverage

    mm = jax.jit(lambda a: a @ a)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    vis = jnp.asarray(np.random.default_rng(0).integers(
        0, 2**32, (4, 100_000, 2), dtype=np.uint32))
    act = jnp.full((4, 2), 0xFFFFFFFF, jnp.uint32)
    cover = jax.jit(jax.vmap(lambda v, m: coverage.cover_counts(
        v, m, interpret=False)))
    jax.block_until_ready((mm(a), cover(vis, act)))
    with tempfile.TemporaryDirectory(dir=ROOT / ".bench_work"
                                     if (ROOT / ".bench_work").is_dir()
                                     else None) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench:window"):
            with jax.profiler.TraceAnnotation("bench:matmul"):
                jax.block_until_ready(mm(a))
            with jax.profiler.TraceAnnotation("bench:coverage"):
                time.sleep(0.03)
                jax.block_until_ready(cover(vis, act))
        jax.profiler.stop_trace()
        src = sorted(pathlib.Path(d).rglob("*.xplane.pb"))[-1]
        pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, out)
    from bench import trace
    planes = trace.load(out)
    print(json.dumps({p.name: {k: len(v) for k, v in p.lines.items()}
                      for p in planes}))
    dev = [p for p in planes if trace.DEVICE_PLANE.match(p.name)][0]
    for line in (trace.OPS_LINE, trace.MODULES_LINE):
        print(line, sorted({e.name for e in dev.lines.get(line, [])}))
    s = trace.summarize(planes, window_span="bench:window",
                        span_prefix="bench:", devices=1)
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "programs": s.programs, "top_ops": s.top_ops(10),
                      "gaps": s.top_gaps(5)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
