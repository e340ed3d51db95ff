"""The trace reduction: busy union, per-program time, kernel time by name
and idle-gap attribution, on hand-made planes and on a small trace
recorded on a TPU v5e chip by ``record_trace.py``."""
from __future__ import annotations

import pathlib

import pytest

from bench import roofline, trace
from bench.trace import Event, Plane

RECORDED = pathlib.Path(__file__).parent / "data" / "v5e_small.xplane.pb"
MS = 1e6
# The recorded trace's Pallas kernel: the custom call of the program's
# coverage kernel, ``kernels.coverage.cover_counts``.
COVERAGE_KERNEL = r"cover_counts.* custom-call "


def _planes():
    host = Plane("/host:CPU", {"python": [
        Event("bench:window", 0, 100 * MS),
        Event("bench:sampling.refresh", 0, 60 * MS),
        Event("bench:client", 60 * MS, 40 * MS),
        Event("other", 0, 100 * MS)]})
    ops = [Event("fusion.1", 10 * MS, 20 * MS),      # 10–30
           Event("gather.2", 30 * MS, 10 * MS),      # 30–40
           Event("coverage_kernel", 70 * MS, 10 * MS),
           Event("fusion.1", 95 * MS, 10 * MS)]      # runs past the window
    mods = [Event("jit_run_fused_block(12)", 10 * MS, 30 * MS),
            Event("jit_greedy(3)", 70 * MS, 10 * MS)]
    dev0 = Plane("/device:TPU:0", {"XLA Ops": ops, "XLA Modules": mods})
    dev1 = Plane("/device:TPU:1", {"XLA Ops": [Event("fusion.1", 0,
                                                      100 * MS)]})
    return [host, dev0, dev1]


def _summary(devices=1):
    return trace.summarize(_planes(), window_span="bench:window",
                           span_prefix="bench:", devices=devices)


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3),
                                                              (5, 10)]


def test_busy_is_the_union_clipped_to_the_window():
    s = _summary()
    assert s.window_s == pytest.approx(0.1)
    # 10–40 (two ops back to back), 70–80, 95–100 (clipped)
    assert s.busy_s == pytest.approx(0.045)
    assert s.idle_share == pytest.approx(0.55)


def test_busy_is_averaged_over_the_cells_devices():
    s = _summary(devices=2)
    assert s.busy_by_device == pytest.approx([0.045, 0.1])
    assert s.busy_s == pytest.approx(0.0725)


def test_program_time_by_name_without_id():
    s = _summary()
    assert s.program_seconds("run_fused_block") == (pytest.approx(0.03), 1)
    assert s.programs == pytest.approx({"jit_run_fused_block": 0.03,
                                        "jit_greedy": 0.01})


def test_kernel_time_by_name():
    s = _summary()
    assert s.op_seconds_runs("coverage") == (pytest.approx(0.01), 1)
    assert s.op_seconds_runs("fusion") == (pytest.approx(0.025), 2)


def test_gaps_go_to_the_span_that_covers_them():
    s = _summary()
    # Idle 0–10 and 40–70 (20 ms of it under the refresh span, 10 under
    # the client's: the refresh has more), 80–95 under the client's.
    got = sorted((name, round(sec, 6)) for name, sec in s.gaps)
    assert got == [("client", 0.015), ("sampling.refresh", 0.01),
                   ("sampling.refresh", 0.03)]
    assert s.top_gaps(1) == [["sampling.refresh", pytest.approx(0.03)]]


def test_a_gap_outside_every_span_is_named_so():
    assert trace.attribute(0, 10, []) == trace.NO_SPAN
    assert trace.attribute(0, 10, [("a", 20, 30)]) == trace.NO_SPAN


def test_recorded_v5e_trace():
    """record_trace.py: a matmul under bench:matmul, then 30 ms of host
    sleep and the coverage kernel under bench:coverage."""
    s = trace.summarize(trace.load(RECORDED), window_span="bench:window",
                        span_prefix="bench:", devices=1)
    assert 0 < s.busy_s < s.window_s
    seconds, runs = s.op_seconds_runs(COVERAGE_KERNEL)
    assert runs == 1 and 0 < seconds < s.busy_s
    assert sum(s.ops.values()) == pytest.approx(s.busy_s, rel=1e-3)
    assert s.programs and all("(" not in k for k in s.programs)
    slept = [sec for name, sec in s.gaps if name == "coverage"]
    assert max(slept) >= 0.025


def test_self_time_of_nested_ops():
    outer = Event("while.1", 0, 100)
    inner = [Event("fusion.2", 10, 30), Event("fusion.3", 50, 20)]
    own = {e.name: t for e, t in trace.self_times([outer] + inner)}
    assert own == {"while.1": 50, "fusion.2": 30, "fusion.3": 20}


def test_op_names_from_hlo_text():
    text = ("%fusion.41 = u32[4973649,2]{0,1:T(2,128)S(1)} fusion(u32[875713"
            ",2]{0,1:T(2,128)} %get-tuple-element.706), kind=kLoop")
    assert trace.op_name(text) == "fusion.41 fusion u32[4973649,2]"
    assert trace.op_name("dot_general.1") == "dot_general.1"


def test_peaks_of_the_chip():
    """v5e's published peaks; an unknown device is an error."""
    assert roofline.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary", "hbm_bytes_per_s")
