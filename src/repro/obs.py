"""Host spans of the sampling path on the profiler's clock.

`span` writes a host span ``repro:<name>`` into a running profiler trace
through `jax.profiler.TraceAnnotation`, so it lands on the same host plane
and clock as the device planes, and an idle stretch of the device can be
put down to what the host was doing.  With no trace running a span costs
TraceAnnotation's check for an active profiler.  Spans go on host paths
only, never inside a traced program or a per-level loop.
"""
from __future__ import annotations

import jax

SPAN_PREFIX = "repro:"


def span(name: str, **args):
    """Context manager: the host span ``repro:<name>``, with ``args`` as
    its metadata."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
