"""Reduction of a profiler trace of the measured window to numbers.

Device planes are ``/device:<platform>:<n>``; their ``XLA Ops`` line holds
one event per operation run.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation``s, named ``bench.*``, on a host thread.
The window is the span from the first ``bench.call`` start to the last
``bench.call`` end, on the trace's own clock.

* busy     the union of the operation intervals inside the window, per
           chip, averaged over the chips the cell uses;
* idle     the window less busy;
* gaps     the intervals of the first chip with no operation running,
           each named after the innermost host span over its midpoint;
* ops      device self time per operation name, summed over the window:
           an operation's time less that of the operations it contains
           (a ``while`` holds its body's operations on the same line).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
CALL_SPAN = "bench.call"


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    """The parts of a trace the reduction reads."""

    device_ops: list[list[Event]]  # per chip, the XLA Ops line
    host_spans: list[Event]  # bench.* spans and the host events beside them


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    device_ops: list[tuple[str, float]]  # largest first
    idle_gaps: list[tuple[str, float]]  # longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _short(op_name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return op_name.split(" = ", 1)[0].lstrip("%").strip()


def load(path: str, chips: int) -> Trace:
    """Reads the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(files[-1])
    devices: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and ":CUSTOM:" not in plane.name:
            try:
                n = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[n] = [Event(_short(e.name), e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                       for e in line.events]
                if any(e.name == CALL_SPAN for e in evs):
                    host.extend(evs)
    return Trace(device_ops=[devices.get(i, []) for i in range(chips)],
                 host_spans=host)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def self_times(events: list[Event]) -> list[tuple[str, float, float, float]]:
    """(name, start, end, self seconds) of nested events: each event's
    duration less the part of it that the events starting inside it cover."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][2] <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e.end, stack[-1][2]) - e.start
        stack.append([e.name, e.start, e.end, e.end - e.start])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def _innermost(spans: list[Event], t: float) -> str:
    best = None
    for sp in spans:
        if sp.start <= t <= sp.end and (best is None
                                        or sp.end - sp.start < best.end - best.start):
            best = sp
    return best.name if best is not None else "no host span"


def reduce(tr: Trace, *, top: int = 10) -> Reduced:
    calls = [e for e in tr.host_spans if e.name == CALL_SPAN]
    if not calls:
        raise ValueError("the trace holds no bench.call span")
    lo = min(e.start for e in calls)
    hi = max(e.end for e in calls)
    busy, per_op = [], defaultdict(float)
    for ops in tr.device_ops:
        iv = union(_clip([(e.start, e.end) for e in ops], lo, hi))
        busy.append(sum(e - s for s, e in iv))
        for name, s, f, own in self_times(ops):
            if s >= lo and f <= hi:
                per_op[name] += own
    first = union(_clip([(e.start, e.end) for e in tr.device_ops[0]], lo, hi))
    edges = [lo] + [t for iv in first for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = sorted(((_innermost(tr.host_spans, (s + e) / 2), e - s)
                    for s, e in gaps), key=lambda g: -g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return Reduced(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                   device_ops=ops[:top], idle_gaps=named[:top])
