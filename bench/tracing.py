"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

The reduction reads planes, lines and events with a name, a start and a
duration in nanoseconds, as ``jax.profiler.ProfileData`` gives them, so
the tests can hand it a synthetic trace.  Device operations are the
events on the "XLA Ops" line of each ``/device:`` plane; program
executions are those on its "XLA Modules" line.  Host spans are the
benchmark's own ``TraceAnnotation`` events, named ``bench.<what>``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def mentions(self, needle: str) -> bool:
        return needle in self.name or any(needle in str(v)
                                          for _, v in self.stats)


@dataclasses.dataclass
class Trace:
    """What the reduction reads: per device, its ops and program runs,
    and the host spans."""
    device_ops: Dict[str, List[Event]]
    device_modules: Dict[str, List[Event]]
    spans: List[Event]


def from_profile(planes) -> Trace:
    """Collect the events of ``ProfileData.planes`` (or a stand-in)."""
    ops: Dict[str, List[Event]] = {}
    mods: Dict[str, List[Event]] = {}
    spans: List[Event] = []

    def ev(e):
        stats = tuple((str(k), str(v)) for k, v in
                      (getattr(e, "stats", None) or ()))
        return Event(str(e.name), float(e.start_ns), float(e.duration_ns),
                     stats)

    for plane in planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        ev(e) for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.setdefault(plane.name, []).extend(
                        ev(e) for e in line.events)
        else:
            for line in plane.lines:
                spans.extend(ev(e) for e in line.events
                             if str(e.name).startswith(SPAN_PREFIX))
    return Trace(ops, mods, spans)


def load(log_dir: Path) -> Trace:
    """The newest trace under a ``jax.profiler.start_trace`` directory."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return from_profile(ProfileData.from_file(files[-1]).planes)


def clip(intervals: Iterable[Tuple[float, float]], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    out = []
    for a, b in intervals:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge overlapping intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(ops: Sequence[Event], t0: float, t1: float) -> float:
    return sum(b - a for a, b in
               union(clip(((e.start_ns, e.end_ns) for e in ops), t0, t1)))


def idle_gaps(ops: Sequence[Event], t0: float, t1: float
              ) -> List[Tuple[float, float]]:
    """Intervals of [t0, t1] in which no device operation ran."""
    gaps = []
    cur = t0
    for a, b in union(clip(((e.start_ns, e.end_ns) for e in ops), t0, t1)):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        gaps.append((cur, t1))
    return gaps


class GapLabeller:
    """Names an idle gap by the host span that covers most of it (the
    window span itself does not count).  A gap can only overlap spans
    that start less than the longest span's length before it, so each
    look is a bisection and a short scan."""

    def __init__(self, spans: Sequence[Event]):
        self.spans = sorted((s for s in spans if s.name != WINDOW_SPAN),
                            key=lambda s: s.start_ns)
        self.starts = [s.start_ns for s in self.spans]
        self.longest = max((s.dur_ns for s in self.spans), default=0.0)

    def __call__(self, gap: Tuple[float, float]) -> str:
        best, best_ns = "host outside any span", 0.0
        i = bisect.bisect_left(self.starts, gap[0] - self.longest)
        while i < len(self.spans) and self.starts[i] < gap[1]:
            s = self.spans[i]
            ov = min(gap[1], s.end_ns) - max(gap[0], s.start_ns)
            if ov > best_ns:
                best, best_ns = s.name, ov
            i += 1
        return best


def window(trace: Trace) -> Tuple[float, float]:
    """The traced slice: the benchmark's window span."""
    w = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if not w:
        raise ValueError("the trace holds no bench.window span")
    return w[0].start_ns, w[0].end_ns


def kernel_ns(ops: Sequence[Event], needles: Sequence[str], t0: float,
              t1: float) -> float:
    """Device time of the ops that mention any of ``needles``."""
    return sum(b - a for e in ops if any(e.mentions(n) for n in needles)
               for a, b in clip([(e.start_ns, e.end_ns)], t0, t1))


def module_runs(modules: Sequence[Event], ops: Sequence[Event],
                needles: Sequence[str], t0: float, t1: float
                ) -> List[float]:
    """Durations of the program executions, wholly inside [t0, t1],
    during which an op that mentions any of ``needles`` started: a
    jitted ``functools.partial`` has no name of its own, its ops do."""
    marks = sorted(e.start_ns for e in ops
                   if any(e.mentions(n) for n in needles))
    out = []
    for m in modules:
        if m.start_ns < t0 or m.end_ns > t1:
            continue
        i = bisect.bisect_left(marks, m.start_ns)
        if i < len(marks) and marks[i] <= m.end_ns:
            out.append(m.dur_ns)
    return out


def short_name(op: str) -> str:
    """``%concatenate.1 = bf16[...] concatenate(...)`` -> ``concatenate``."""
    head = op.split(" = ", 1)[0].lstrip("%")
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


@dataclasses.dataclass
class Summary:
    window_ns: float
    busy_ns: float          # averaged over the devices
    n_devices: int
    top_ops: List[Tuple[str, float]]     # device time by op name
    top_gaps: List[Tuple[str, float]]    # idle time by host span


def summarize(trace: Trace, top: int = 10) -> Summary:
    t0, t1 = window(trace)
    devices = sorted(trace.device_ops)
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy = [busy_ns(trace.device_ops[d], t0, t1) for d in devices]
    by_op: Dict[str, float] = defaultdict(float)
    by_label: Dict[str, float] = defaultdict(float)
    label = GapLabeller(trace.spans)
    for d in devices:
        for e in trace.device_ops[d]:
            ns = sum(b - a for a, b in clip([(e.start_ns, e.end_ns)],
                                            t0, t1))
            if ns:
                by_op[short_name(e.name)] += ns / len(devices)
        for g in idle_gaps(trace.device_ops[d], t0, t1):
            by_label[label(g)] += (g[1] - g[0]) \
                / len(devices)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return Summary(window_ns=t1 - t0, busy_ns=sum(busy) / len(busy),
                   n_devices=len(devices), top_ops=ops, top_gaps=gaps)
