"""Device time by named scope: the ``tf_op`` name stacks `bench.scopes`
decodes from the ``XSpace`` protobuf, applied to the op self time
`trace.summarize` collects, and the idle split between and inside
programs — on hand-made planes and on two small traces recorded on a TPU
v5e chip (``record_trace.py`` and ``record_scoped_trace.py``)."""
from __future__ import annotations

import pathlib

import pytest

from bench import scopes, trace
from bench.scopes import ScopedEvent as Ev
from bench.trace import Plane

DATA = pathlib.Path(__file__).parent / "data"
SMALL = DATA / "v5e_small.xplane.pb"
SCOPED = DATA / "v5e_scoped.xplane.pb"
MS = 1e6
STACK = "jit(run_fused_block)/while/body/closed_call/while/body/"


def _planes():
    host = Plane("/host:CPU", {"python": [
        Ev("bench:window", 0, 100 * MS),
        Ev("bench:sampling.refresh", 0, 100 * MS),
        Ev("repro:store.set_slots", 40 * MS, 35 * MS)]})
    ops = [Ev("fusion.1", 10 * MS, 10 * MS, STACK + "bpt.gather/gather"),
           Ev("fusion.2", 25 * MS, 5 * MS,
              STACK + "bpt.or_scan/while/body/or"),
           Ev("fusion.3", 30 * MS, 4 * MS, STACK + "bpt.rng/bpt.count/sum"),
           Ev("fusion.6", 34 * MS, 2 * MS, STACK + "any_set/reduce"),
           Ev("copy.4", 36 * MS, 4 * MS),
           Ev("scatter.5", 75 * MS, 10 * MS, "jit(_set_slots)/scatter")]
    mods = [Ev("jit_run_fused_block(3)", 10 * MS, 30 * MS),
            Ev("jit__set_slots(4)", 75 * MS, 12 * MS)]
    return [host, Plane("/device:TPU:0", {"XLA Ops": ops,
                                          "XLA Modules": mods})]


def _summary(planes):
    return trace.summarize(planes, window_span="bench:window",
                           span_prefix="bench:", devices=1)


def test_scope_is_the_innermost_bpt_segment():
    assert scopes.scope_of(STACK + "bpt.rng/bpt.count/sum") == "bpt.count"
    assert scopes.scope_of("jit(f)/reshape:") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def test_scope_self_time_inside_the_program():
    planes = _planes()
    got = scopes.by_scope(_summary(planes), scopes.name_stacks(planes))
    assert got["scopes"] == pytest.approx({"bpt.gather": 0.010,
                                           "bpt.or_scan": 0.005,
                                           "bpt.count": 0.004})
    assert got["program_s"] == pytest.approx(0.030)
    assert got["program_runs"] == 1
    # The program's time outside every scope: its unscoped op, its
    # stackless copy and the 5 ms it was idle.
    assert got["unscoped"] == pytest.approx(0.011)
    assert got["unscoped_ops"] == pytest.approx({"fusion.6": 0.002})
    assert got["no_stack"] == pytest.approx({"copy.4": 0.004})
    assert got["scoped_share"] == pytest.approx(19 / 30)


def test_one_name_two_stacks_is_left_unscoped():
    planes = _planes()
    dev = planes[1]
    dev.lines["XLA Ops"].append(
        Ev("fusion.1", 60 * MS, 1 * MS, "jit(other)/gather"))
    assert scopes.name_stacks(planes)["fusion.1"] == ""


def test_idle_between_and_inside_programs():
    split = scopes.idle_split(_summary(_planes()))
    # Busy 10–40 less the 20–25 hole, and 75–85; programs 10–40, 75–87.
    assert split["busy_s"] == pytest.approx(0.035)
    assert split["between_programs_s"] == pytest.approx(0.058)
    assert split["in_programs_s"] == pytest.approx(0.007)
    assert split["between_programs_s"] + split["in_programs_s"] == \
        pytest.approx(split["idle_s"])


def test_loader_matches_jaxs_reader_on_a_recorded_trace():
    """Every event of the small v5e trace, as `trace.load` reads it; the
    matrix product's fusion carries its name stack."""
    ours, theirs = scopes.load(SMALL), trace.load(SMALL)
    assert [p.name for p in ours] == [p.name for p in theirs]
    for a, b in zip(ours, theirs):
        assert set(a.lines) == set(b.lines)
        for line in a.lines:
            assert [(e.name, e.start_ns, e.dur_ns) for e in a.lines[line]] \
                == [(e.name, e.start_ns, e.dur_ns) for e in b.lines[line]]
    dev = next(p for p in ours if trace.DEVICE_PLANE.match(p.name))
    tf_ops = {e.tf_op for e in dev.lines["XLA Ops"]}
    assert "jit(<lambda>)/dot_general:" in tf_ops
    assert "" in tf_ops                     # a compiler-inserted copy


def test_recorded_scoped_trace():
    """record_scoped_trace.py: a gather under bpt.gather and a sum under
    bpt.count in a while loop, run three times, the last two inside the
    program's span repro:store.set_slots, which lands on the host plane
    inside bench:sampling.refresh."""
    planes = scopes.load(SCOPED)
    dev = next(p for p in planes if trace.DEVICE_PLANE.match(p.name))
    assert any("bpt.gather" in e.tf_op for e in dev.lines["XLA Ops"])
    summary = _summary(trace.load(SCOPED))
    got = scopes.by_scope(summary, scopes.name_stacks(planes),
                          program="scoped_step")
    assert got["program_runs"] == 3
    assert got["scopes"].get("bpt.gather", 0) > 0
    assert got["scopes"].get("bpt.count", 0) > 0
    assert 0.9 < got["scoped_share"] <= 1 + 1e-6
    host = [(e.name, e.start_ns, e.end_ns) for p in planes
            if not trace.DEVICE_PLANE.match(p.name)
            for evs in p.lines.values() for e in evs
            if e.name.startswith(("bench:", "repro:"))]
    (_, s, e), = [h for h in host if h[0] == "repro:store.set_slots"]
    (_, rs, re_), = [h for h in host if h[0] == "bench:sampling.refresh"]
    assert rs <= s < e <= re_ and e - s >= 20 * MS
    split = scopes.idle_split(summary)
    assert split["between_programs_s"] >= 0.03
