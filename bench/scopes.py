#!/usr/bin/env python3
"""Device time of a program by named scope, from a profiler trace
(``.xplane.pb``).

    python3 bench/scopes.py --workload rmat-ic-wc.build --seed 7 --seconds 30
    python3 bench/scopes.py --trace-file run.xplane.pb

The first form runs the cell with ``--trace 1`` through `bench/run.py`
itself (its result line comes first), keeps the window's trace under
``.bench_work/scopes/`` and sums the traversal counters of the batches
the window sampled; the second reduces a trace already recorded.  Either
prints one JSON object last.

`bench/trace.py` reads a trace through `jax.profiler.ProfileData`, which
does not show an event's metadata.  `load` decodes the ``XSpace``
protobuf itself: every event as `trace.load` gives it, and for each
device op its ``tf_op`` stat, the JAX name stack of the op (a fusion
carries its root's).  `by_scope` then splits the op self time that
`trace.summarize` collects by the innermost ``bpt.*`` segment of each
op's name stack (`core.traversal._expand`'s ``jax.named_scope``s).
Compiler-inserted copies carry no name stack at all; they and the
program's ops outside every scope are listed by op name.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.trace import Plane  # noqa: E402

SCOPE_PREFIX = "bpt."
UNSCOPED = "unscoped"
WINDOW_SPAN, SPAN_PREFIX = "bench:window", "bench:"


@dataclasses.dataclass(frozen=True)
class ScopedEvent(trace.Event):
    tf_op: str = ""


def _xspace_class():
    """The message class of the fields of ``XSpace`` (tsl's
    ``xplane.proto``) that this module reads, built from a descriptor so
    that no generated code is needed."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, f64 = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    text, raw, sub = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields, into=None):
        m = (fd.message_type if into is None else into).add(name=name)
        for fname, number, kind, label, *ref in fields:
            f = m.field.add(name=fname, number=number, type=kind, label=label)
            if ref:
                f.type_name = ".bench_xplane." + ref[0]
        return m

    message("XStat", [
        ("metadata_id", 1, i64, one), ("double_value", 2, f64, one),
        ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
        ("str_value", 5, text, one), ("bytes_value", 6, raw, one),
        ("ref_value", 7, u64, one)])
    message("XEvent", [("metadata_id", 1, i64, one),
                       ("offset_ps", 2, i64, one),
                       ("duration_ps", 3, i64, one)])
    message("XLine", [("name", 2, text, one), ("timestamp_ns", 3, i64, one),
                      ("events", 4, sub, many, "XEvent")])
    message("XEventMetadata", [("name", 2, text, one),
                               ("stats", 5, sub, many, "XStat")])
    message("XStatMetadata", [("name", 2, text, one)])
    # A map field's entry type is named after the field.
    plane = message("XPlane", [
        ("name", 2, text, one), ("lines", 3, sub, many, "XLine"),
        ("event_metadata", 4, sub, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, sub, many, "XPlane.StatMetadataEntry")])
    for value in ("XEventMetadata", "XStatMetadata"):
        e = message(value[1:] + "Entry", [("key", 1, i64, one),
                                          ("value", 2, sub, one, value)],
                    into=plane.nested_type)
        e.options.map_entry = True
    message("XSpace", [("planes", 1, sub, many, "XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def load(path) -> list[Plane]:
    """The trace's planes, lines and events with the times and names
    `trace.load` gives them, each event a `ScopedEvent` carrying its
    metadata's ``tf_op`` ("" where it has none)."""
    space = _xspace_class()()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    planes = []
    for p in space.planes:
        stat_names = {k: v.name for k, v in p.stat_metadata.items()}
        meta = {}
        for k, m in p.event_metadata.items():
            tf_op = ""
            for st in m.stats:
                if stat_names.get(st.metadata_id) == "tf_op":
                    tf_op = st.str_value or stat_names.get(st.ref_value, "")
            meta[k] = (m.name, tf_op)
        lines = collections.defaultdict(list)
        for line in p.lines:
            for ev in line.events:
                name, tf_op = meta.get(ev.metadata_id, ("", ""))
                lines[line.name].append(ScopedEvent(
                    name, float(line.timestamp_ns + ev.offset_ps // 1000),
                    float(ev.duration_ps // 1000), tf_op))
        planes.append(Plane(p.name, dict(lines)))
    return planes


def scope_of(tf_op: str) -> str:
    """The innermost ``bpt.*`` segment of a name stack, else `UNSCOPED`."""
    segments = [s for s in tf_op.split("/") if s.startswith(SCOPE_PREFIX)]
    return segments[-1] if segments else UNSCOPED


def name_stacks(planes) -> dict:
    """Op name (as `trace.summarize` names it) → the op's name stack, over
    the device planes' ops; "" where an op has none, or where ops of one
    name carry different stacks."""
    seen = collections.defaultdict(set)
    for p in planes:
        if trace.DEVICE_PLANE.match(p.name):
            for ev in p.lines.get(trace.OPS_LINE, []):
                seen[trace.op_name(ev.name)].add(ev.tf_op)
    return {k: next(iter(v)) if len(v) == 1 else "" for k, v in seen.items()}


def by_scope(summary: trace.Summary, stacks: dict,
             program: str = "run_fused_block") -> dict:
    """The op self seconds of ``summary`` (a `trace.Summary`) inside
    ``program``, by scope.  An op is the program's when its name stack
    starts at ``jit(<program>)``; ops with no stack are listed under
    ``no_stack`` (compiler copies, of any program).  ``unscoped`` is the
    program's device time less its scoped op time."""
    root = f"jit({program})"
    scopes, no_stack, unscoped_ops = (collections.Counter() for _ in range(3))
    for name, sec in summary.ops.items():
        stack = stacks.get(name, "")
        if not stack:
            no_stack[name] += sec
        elif stack.split("/")[0] == root:
            scope = scope_of(stack)
            if scope == UNSCOPED:
                unscoped_ops[name] += sec
            else:
                scopes[scope] += sec
    program_s, runs = summary.program_seconds(program)
    scoped = sum(scopes.values())
    return {"program_s": program_s, "program_runs": runs,
            "scopes": dict(sorted(scopes.items())),
            UNSCOPED: program_s - scoped,
            "scoped_share": scoped / program_s if program_s else None,
            "unscoped_ops": dict(unscoped_ops.most_common()),
            "no_stack": dict(no_stack.most_common())}


def idle_split(summary: trace.Summary) -> dict:
    """The window's idle seconds between programs (the host's) and inside
    them (the device's own), from the program intervals."""
    in_programs = sum(summary.programs.values())
    return {"window_s": summary.window_s, "busy_s": summary.busy_s,
            "idle_s": summary.window_s - summary.busy_s,
            "between_programs_s": summary.window_s - in_programs,
            "in_programs_s": in_programs - summary.busy_s}


def _run_cell(args) -> tuple[pathlib.Path, dict]:
    """Run the cell through `bench.run.main` with ``--trace 1``; keep the
    window's trace, and sum the counters of the batches sampled while it
    was recorded."""
    from bench import harness, run
    sys.path.insert(0, str(ROOT / "src"))
    from repro.serve.influence import SketchStore

    keep = harness.WORK / "scopes" / f"{args.workload}.xplane.pb"
    keep.parent.mkdir(parents=True, exist_ok=True)
    batches, tracing = [], [False]
    load_trace, traced = trace.load, harness.Run.traced
    sample_block = SketchStore._sample_block

    def load_and_keep(path):
        shutil.copy(path, keep)
        return load_trace(path)

    def traced_and_flagged(self):
        tracing[0] = True
        with traced(self):
            yield
        tracing[0] = False

    def sample_and_count(self, idx):
        out = sample_block(self, idx)
        if tracing[0]:
            batches.extend(out)
        return out

    trace.load = load_and_keep
    harness.Run.traced = contextlib.contextmanager(traced_and_flagged)
    SketchStore._sample_block = sample_and_count
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        trace.load, harness.Run.traced = load_trace, traced
        SketchStore._sample_block = sample_block
    if rc:
        raise SystemExit(rc)
    got = {"batches": len(batches)}
    for key in ("levels", "truncated_colors", "fused_edge_visits"):
        values = [getattr(b, key, -1) for b in batches]
        if values and min(values) >= 0:      # counted on every batch
            got[key] = sum(values)
    return keep, got


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-file")
    ap.add_argument("--program", default="run_fused_block")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.trace_file):
        ap.error("give --workload or --trace-file")
    path, got = (_run_cell(args) if args.workload
                 else (pathlib.Path(args.trace_file), {}))
    summary = trace.summarize(trace.load(path), window_span=WINDOW_SPAN,
                              span_prefix=SPAN_PREFIX, devices=1)
    line = dict(by_scope(summary, name_stacks(load(path)), args.program),
                **idle_split(summary), trace=str(path), counts=got)
    if got.get("levels"):
        line["ms_per_level"] = {
            k: v / got["levels"] * 1e3
            for k, v in dict(line["scopes"], unscoped=line[UNSCOPED],
                             program=line["program_s"]).items()}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
