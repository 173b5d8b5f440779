"""The program's own spans and counters, read from the run's trace.

The serving loop opens spans on the profiler's clock, named
``serve.*``, ``kv.*`` and ``tier.*``.  ``serve.iteration``,
``kv.gather`` and ``serve.decode`` carry the cumulative counters
(``tokens_out``, ``prefill_tokens``, ``kv_h2d_bytes``,
``kv_d2h_bytes``, ``kv_h2d_puts``, ``kv_d2h_puts``) as stats, taken
when the span opens; the decode program runs as ``serve_decode`` with
its ops under the scopes ``attention``, ``kv_write``, ``mlp`` and
``head``.  ``bench/tracing.py`` keeps only the benchmark's ``bench.*``
spans, so this module reads the newest trace under
``harness.TRACE_DIR`` again, once per file, and hands it to a metric
reader only when its ``bench.window`` is the run's own: a stale or
foreign trace is never read.  Only iterations that lie wholly inside
the window count.  A program without these spans (an older commit)
gives the readers nothing to read, and they return None.

    python3 -m bench.program_trace <trace dir>

prints the traced slice's device idle time by the innermost program
span open during it, the decode program's device time by scope, and
the readings of the metrics that read this trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import sys
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import tracing
from bench.tracing import Event

PREFIXES = ("serve.", "kv.", "tier.")
ITERATION = "serve.iteration"
DECODE_PROGRAM = "serve_decode"
SCOPES = ("attention", "kv_write", "mlp", "head")
OUTSIDE = "outside any program span"
METRICS = ("kv_pcie_bytes_per_tok", "kv_gather_h2d_gb_s",
           "kv_append_ms_per_step")


@dataclasses.dataclass
class ProgramTrace:
    """The window, the program's spans (by start, outer before inner),
    per device its ops and program runs, and per device each op name's
    source path (``tf_op``: the program's name and the scopes)."""
    window: Optional[Tuple[float, float]]
    spans: List[Event]
    device_ops: Dict[str, List[Event]]
    device_modules: Dict[str, List[Event]]
    op_paths: Dict[str, Dict[str, str]] = \
        dataclasses.field(default_factory=dict)

    def iterations(self) -> List[Event]:
        """The ``serve.iteration`` spans wholly inside the window."""
        t0, t1 = self.window
        return [s for s in self.spans if s.name == ITERATION
                and s.start_ns >= t0 and s.end_ns <= t1]

    def by_iteration(self) -> List[Tuple[Event, List[Event]]]:
        """Each iteration wholly inside the window, with the program
        spans that lie within it."""
        starts = [s.start_ns for s in self.spans]
        out = []
        for it in self.iterations():
            inner = []
            i = bisect.bisect_left(starts, it.start_ns)
            while i < len(self.spans) and starts[i] < it.end_ns:
                s = self.spans[i]
                if s is not it and s.end_ns <= it.end_ns:
                    inner.append(s)
                i += 1
            out.append((it, inner))
        return out


def stat(e: Event, key: str) -> int:
    """An integer arg of a program span."""
    return int(dict(e.stats)[key])


def _event(e) -> Event:
    return Event(str(e.name), float(e.start_ns), float(e.duration_ns),
                 tuple((str(k), str(v))
                       for k, v in (getattr(e, "stats", None) or ())))


def from_profile(planes) -> ProgramTrace:
    """Collect ``ProfileData.planes`` (or a stand-in): the program's
    spans and the window span from the host, ops and program runs from
    each device."""
    ops: Dict[str, List[Event]] = {}
    mods: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    window = None
    for plane in planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name == tracing.OPS_LINE:
                ops.setdefault(plane.name, []).extend(
                    _event(e) for e in line.events)
            elif on_device and line.name == tracing.MODULES_LINE:
                mods.setdefault(plane.name, []).extend(
                    _event(e) for e in line.events)
            elif not on_device:
                for e in line.events:
                    name = str(e.name)
                    if name.startswith(PREFIXES):
                        spans.append(_event(e))
                    elif name == tracing.WINDOW_SPAN and window is None:
                        w = _event(e)
                        window = (w.start_ns, w.end_ns)
    spans.sort(key=lambda s: (s.start_ns, -s.dur_ns))
    return ProgramTrace(window, spans, ops, mods)


def newest(log_dir: Path) -> Optional[str]:
    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile"
                                 / "*" / "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    trace = from_profile(ProfileData.from_file(path).planes)
    trace.op_paths = op_paths(Path(path).read_bytes())
    return trace


# ``ProfileData`` gives an event's own stats but not those of its
# metadata, where a device op keeps its source path (the ``tf_op`` stat:
# the jit name and the named scopes).  The few fields needed are read from the
# XSpace protobuf's wire format (tsl/profiler/protobuf/xplane.proto):
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name =
# 2, .display_name = 4, .stats = 5; XStatMetadata.name = 2;
# XStat.metadata_id = 1, .str_value = 5, .ref_value = 7.
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) for each field of the message in
    buf[i:end]: an int for a varint, a (start, end) span for a
    length-delimited field, None for a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unexpected wire type {wire} in the trace")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_paths(buf: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each op name's ``tf_op`` source path (a string
    value, or a reference to a stat name that holds it)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry.get(2, (0, 0))))
                stat_names[entry.get(1, 0)] = _text(buf, meta[2]) \
                    if 2 in meta else ""
        if not name.startswith("/device:"):
            continue
        tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
        ops = out.setdefault(name, {})
        for span in events:
            entry = dict(_fields(buf, *span))
            names, path = [], None
            for g, v in _fields(buf, *entry.get(2, (0, 0))):
                if g in (2, 4):
                    names.append(_text(buf, v))
                elif g == 5:
                    st = dict(_fields(buf, *v))
                    if st.get(1, 0) not in tf_op:
                        continue
                    if 5 in st:
                        path = _text(buf, st[5])
                    elif 7 in st:
                        path = stat_names.get(st[7], "")
            if path is not None:
                ops.update((n, path) for n in names if n)
    return out


@functools.lru_cache(maxsize=1)
def _load_once(path: str, mtime_ns: int) -> ProgramTrace:
    return load(path)


def of(run) -> Optional[ProgramTrace]:
    """The run's program trace: the newest trace under
    ``harness.TRACE_DIR``, if its window is the run's own."""
    from bench import harness
    if run.trace is None:
        return None
    path = newest(harness.TRACE_DIR)
    if path is None:
        return None
    trace = _load_once(path, os.stat(path).st_mtime_ns)
    try:
        own = tracing.window(run.trace)
    except ValueError:
        return None
    return trace if trace.window == own else None


# ---------------------------------------------------------------------- #
# attribution                                                            #
# ---------------------------------------------------------------------- #
def leaf_segments(events: Sequence[Event], t0: float, t1: float,
                  label: Callable[[Event], str] = lambda e: e.name
                  ) -> List[Tuple[float, float, str]]:
    """[t0, t1] cut into pieces, each labelled by the innermost of the
    nested ``events`` open over it (``OUTSIDE`` where none is): the
    self time of each event, laid out in time."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []        # (end, label), open events
    cur = t0

    def emit(upto: float) -> None:
        nonlocal cur
        if upto > cur:
            out.append((cur, upto, stack[-1][1] if stack else OUTSIDE))
            cur = upto

    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(a)
        stack.append((b, label(e)))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(t1)
    return out


def overlap_by_label(intervals: Sequence[Tuple[float, float]],
                     segments: Sequence[Tuple[float, float, str]]
                     ) -> Dict[str, float]:
    """Nanoseconds of the sorted, disjoint ``intervals`` that fall in
    each label's sorted, disjoint ``segments``."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in intervals:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            lo, hi = max(a, segments[k][0]), min(b, segments[k][1])
            if hi > lo:
                out[segments[k][2]] += hi - lo
            k += 1
    return out


def idle_by_leaf_span(trace: ProgramTrace) -> Dict[str, float]:
    """Device idle seconds in the window by the innermost program span
    open on the host at the time, averaged over the devices."""
    t0, t1 = trace.window
    segs = leaf_segments(trace.spans, t0, t1)
    out: Dict[str, float] = defaultdict(float)
    for ops in trace.device_ops.values():
        for k, ns in overlap_by_label(tracing.idle_gaps(ops, t0, t1),
                                      segs).items():
            out[k] += ns / 1e9 / len(trace.device_ops)
    return dict(out)


def scope_of(path: str) -> str:
    """The decode scope an op's source path names (its first segment
    that is one), or ``other``."""
    for part in path.replace("(", "/").replace(")", "/").split("/"):
        if part in SCOPES:
            return part
    return "other"


def decode_time_by_scope(trace: ProgramTrace) -> Tuple[int, Dict[str, float]]:
    """Runs of the decode program wholly inside the window, and the
    device seconds of their ops by scope (each op's self time: a
    ``while`` is charged only for what its body's ops leave over),
    averaged over the devices."""
    t0, t1 = trace.window
    runs = 0
    out: Dict[str, float] = defaultdict(float)
    for dev, mods in trace.device_modules.items():
        spans = [(m.start_ns, m.end_ns) for m in mods
                 if DECODE_PROGRAM in m.name
                 and m.start_ns >= t0 and m.end_ns <= t1]
        runs += len(spans)
        starts = [a for a, _ in spans]
        ops = []
        for op in trace.device_ops.get(dev, ()):
            i = bisect.bisect_right(starts, op.start_ns) - 1
            if i >= 0 and op.start_ns < spans[i][1]:
                ops.append(op)
        paths = trace.op_paths.get(dev, {})
        label = lambda op: scope_of(paths.get(op.name, ""))  # noqa: E731
        for a, b, scope in leaf_segments(ops, t0, t1, label):
            if scope != OUTSIDE:
                out[scope] += (b - a) / 1e9 / len(trace.device_modules)
    return runs // max(len(trace.device_modules), 1), dict(out)


# ---------------------------------------------------------------------- #
# report                                                                 #
# ---------------------------------------------------------------------- #
def report(trace: ProgramTrace) -> List[str]:
    from bench import harness
    t0, t1 = trace.window
    its = trace.by_iteration()
    decodes = sum(1 for _, inner in its
                  if any(s.name == "serve.decode" for s in inner))
    lines = [f"window {(t1 - t0) / 1e9:.3f} s; {len(its)} iterations "
             f"wholly inside, {decodes} with a decode"]
    if trace.device_ops:
        idle = idle_by_leaf_span(trace)
        total = sum(idle.values())
        lines.append(f"device idle {total:.3f} s by innermost program "
                     f"span (s, share of idle):")
        for k, s in sorted(idle.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {k:28s} {s:9.3f}  {100 * s / total:6.2f} %")
        runs, scopes = decode_time_by_scope(trace)
        busy = sum(scopes.values())
        lines.append(f"{DECODE_PROGRAM}: {runs} runs, {busy:.3f} device s "
                     f"by scope (s, ms a run):")
        for k, s in sorted(scopes.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {k:28s} {s:9.3f}  {1e3 * s / max(runs, 1):8.3f}")
    for name in METRICS:
        v = harness.load_reader(name).value(trace)
        lines.append(f"{name} {v!r}")
    return lines


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m bench.program_trace <trace dir>",
              file=sys.stderr)
        return 2
    path = newest(Path(argv[0]))
    if path is None:
        print(f"no trace under {argv[0]}", file=sys.stderr)
        return 1
    trace = load(path)
    if trace.window is None:
        print(f"{path} holds no {tracing.WINDOW_SPAN} span",
              file=sys.stderr)
        return 1
    print(path)
    print("\n".join(report(trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
