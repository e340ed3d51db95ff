"""The reader of the device's idle time between programs,
``host_gap_ms_per_refresh``, after a CPU rehearsal of each build cell."""
from __future__ import annotations

import types

import pytest

from bench import harness
from bench.tests.rehearsal import rehearse

BUILD = {"pool_batches": 2}


def _reader():
    return harness.load_module(
        harness.BENCH / "metrics" / "host_gap_ms_per_refresh.py")


@pytest.fixture(scope="module", params=["rmat-ic-wc.build",
                                        "rmat-ic-uni.build"])
def rehearsed(request):
    line, run = rehearse(request.param, traffic=BUILD)
    assert line["correct"], line["checks"]
    return run


def test_window_less_every_program_per_call(rehearsed):
    run = rehearsed
    run.trace_summary = types.SimpleNamespace(
        window_s=2.0,
        programs={"jit_run_fused_block": 1.5, "jit__set_slots": 0.25})
    assert _reader().read(run) == pytest.approx(
        (2.0 - 1.5 - 0.25) / run.counters["calls"] * 1e3)


def test_nothing_without_a_trace(rehearsed):
    run = rehearsed
    run.trace_summary = None
    assert _reader().read(run) is None
    run.trace_summary = types.SimpleNamespace(window_s=2.0, programs={})
    assert _reader().read(run) is None
