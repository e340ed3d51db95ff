"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

* **busy** — the union of the intervals in which an operation ran on a
  device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), clipped to
  the traced window, averaged over the cell's devices;
* **per program** — device seconds of each XLA program (line
  ``XLA Modules``), by program name with its ``(id)`` suffix dropped, and
  how many times it ran;
* **per operation** — device self seconds by operation (a ``while`` holds
  the ops of its body on the same line, so each op counts only the time
  none of its children ran), named ``<instruction> <kind> <shape>`` from
  the HLO text the TPU trace gives; a Pallas kernel's instruction carries
  its kernel's name;
* **idle gaps** — the stretches of the window in which a device ran
  nothing, each attributed to the harness's host span (``bench:<layer>``,
  written with ``jax.profiler.TraceAnnotation``) that overlaps it most.

The window is the host span ``bench:window``.  Times are seconds.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "outside any bench span"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict      # line name -> [Event]


def find_xplane(directory) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path) -> list[Plane]:
    """The trace's planes, lines and events, read with JAX's own reader."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    planes = []
    for p in data.planes:
        lines = collections.defaultdict(list)
        for line in p.lines:
            for ev in line.events:
                lines[line.name].append(
                    Event(ev.name, float(ev.start_ns), float(ev.duration_ns)))
        planes.append(Plane(p.name, dict(lines)))
    return planes


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _program_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


_HLO = re.compile(r"^%?([\w.\-]+) = (\(.*?\)|[\w]+\[[\d,]*\])\S* ([\w\-]+)\(")


def op_name(text: str) -> str:
    """``fusion.41 fusion u32[4973649,2]`` from an op's HLO text; the text
    itself where it does not parse."""
    m = _HLO.match(text)
    if not m:
        return text
    shape = m.group(2) if not m.group(2).startswith("(") else "(tuple)"
    return f"{m.group(1)} {m.group(3)} {shape}"


def self_times(events) -> list[tuple[Event, float]]:
    """Each event with its duration less the time of the events nested
    in it (same line, inside its interval)."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    own = {id(e): e.dur_ns for e in order}
    stack: list[Event] = []
    for ev in order:
        while stack and stack[-1].end_ns <= ev.start_ns:
            stack.pop()
        if stack:
            own[id(stack[-1])] -= min(ev.end_ns, stack[-1].end_ns) \
                - ev.start_ns
        stack.append(ev)
    return [(e, own[id(e)]) for e in order]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                    # mean over the cell's devices
    devices: int
    ops: dict                        # op name -> self seconds, per device
    op_runs: dict                    # op name -> runs, mean per device
    programs: dict                   # program name -> seconds, per device
    program_runs: dict               # program name -> runs, per device
    gaps: list                       # [(span label, seconds)], all devices
    busy_by_device: list = dataclasses.field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int) -> list:
        return [[k, v] for k, v in sorted(self.ops.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> list:
        return [[k, v] for k, v in sorted(self.gaps,
                                          key=lambda kv: -kv[1])[:n]]

    def op_seconds_runs(self, pattern: str) -> tuple[float, float]:
        """(seconds, runs) of every op whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hit = [k for k in self.ops if rx.search(k)]
        return (sum(self.ops[k] for k in hit),
                sum(self.op_runs[k] for k in hit))

    def program_seconds(self, pattern: str) -> tuple[float, float]:
        """(seconds, runs) of every program whose name matches."""
        rx = re.compile(pattern)
        hit = [k for k in self.programs if rx.search(k)]
        return (sum(self.programs[k] for k in hit),
                sum(self.program_runs[k] for k in hit))


def summarize(planes: list[Plane], *, window_span: str, span_prefix: str,
              devices: int) -> Summary:
    spans = [(e.name[len(span_prefix):], e.start_ns, e.end_ns)
             for p in planes if not DEVICE_PLANE.match(p.name)
             for evs in p.lines.values() for e in evs
             if e.name.startswith(span_prefix)]
    windows = [(s, e) for n, s, e in spans if span_prefix + n == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in the trace")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    layer_spans = [(n, s, e) for n, s, e in spans
                   if span_prefix + n != window_span]
    dev_planes = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                        key=lambda p: p.name)[:devices]
    if not dev_planes:
        raise ValueError("no TPU device plane in the trace")
    ops = collections.Counter()
    op_runs = collections.Counter()
    programs = collections.Counter()
    runs = collections.Counter()
    gaps, busy = [], []
    for p in dev_planes:
        spans_on = []
        for ev, own in self_times(p.lines.get(OPS_LINE, [])):
            s, e = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if e > s:
                name = op_name(ev.name)
                ops[name] += own * (e - s) / ev.dur_ns / 1e9
                op_runs[name] += 1
                spans_on.append((s, e))
        merged = union(spans_on)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for ev in p.lines.get(MODULES_LINE, []):
            s, e = _clip(ev.start_ns, ev.end_ns, lo, hi)
            if e > s:
                name = _program_name(ev.name)
                programs[name] += (e - s) / 1e9
                runs[name] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps.append((attribute(gs, ge, layer_spans), (ge - gs) / 1e9))
    n = len(dev_planes)
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n, devices=n,
        ops={k: v / n for k, v in ops.items()},
        op_runs={k: v / n for k, v in op_runs.items()},
        programs={k: v / n for k, v in programs.items()},
        program_runs={k: v / n for k, v in runs.items()},
        gaps=gaps, busy_by_device=busy)


def attribute(gs: float, ge: float, spans) -> str:
    """The host span that overlaps the gap [gs, ge) most; the innermost
    (shortest) one on a tie."""
    best, best_key = NO_SPAN, (0.0, 0.0)
    for name, s, e in spans:
        overlap = min(e, ge) - max(s, gs)
        if overlap > 0:
            key = (overlap, -(e - s))
            if key > best_key:
                best, best_key = name, key
    return best
