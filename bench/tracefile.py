"""Read a JAX profiler trace into plain event lists, and the reductions
every per-layer metric shares: the union of busy intervals, idle gaps,
and the host activity that each gap falls in.

Times are in seconds on the profiler's common clock: the device planes'
events and the host's TraceMe spans line up (checked on a v5e: each
``bench.fit`` span contains its fit's device programs).
"""
from __future__ import annotations

import collections
import bisect
import dataclasses
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
FIT_SPAN = "bench.fit"         # the benchmark's span around each fit
# Ops that only contain other ops: their interval is not work.
CONTAINER = re.compile(r"^%?(while|conditional|call)[.\s=]")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]       # device id -> XLA ops
    modules: dict[int, list[Event]]   # device id -> XLA programs
    host: list[Event]                 # spans of the thread that fits

    @property
    def fits(self) -> list[Event]:
        return sorted((e for e in self.host if e.name == FIT_SPAN),
                      key=lambda e: e.start)

    @property
    def window(self) -> tuple[float, float]:
        fits = self.fits
        if not fits:
            raise ValueError(f"trace holds no {FIT_SPAN!r} span")
        return fits[0].start, max(e.end for e in fits)


def op_name(name: str) -> str:
    """'%fused_stats.13 = (f32[...]) custom-call(...)' -> 'fused_stats.13'."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def load(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir`` as a ``Trace``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: dict[int, list[Event]] = collections.defaultdict(list)
    modules: dict[int, list[Event]] = collections.defaultdict(list)
    host: list[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = (ops if line.name == OPS_LINE else modules)[int(m[1])]
            elif plane.name == HOST_PLANE:
                dest = []
            else:
                continue
            dest.extend(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events)
            # The host thread that calls into JAX is the one that holds
            # the benchmark's spans.
            if not m and any(e.name == FIT_SPAN for e in dest):
                host.extend(dest)
    return Trace(dict(ops), dict(modules), host)


def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi], sorted."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    out: list[list[float]] = []
    for a, b in iv:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def work(events) -> list[Event]:
    return [e for e in events if not CONTAINER.match(op_name(e.name))]


def busy_seconds(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(work(events), lo, hi))


def busy_in(events, intervals) -> list[float]:
    """``busy_seconds(events, a, b)`` for each (a, b) of ``intervals``,
    from one union of the events' intervals: each interval's sum is of
    the union's pieces clipped to it, the same pieces in the same order,
    so the same floats, without a pass over every event per interval."""
    if not intervals:
        return []
    union = merged(work(events), min(a for a, _ in intervals),
                   max(b for _, b in intervals))
    ends = [y for _, y in union]
    out = []
    for a, b in intervals:
        total, i = 0, bisect.bisect_right(ends, a)
        while i < len(union) and union[i][0] < b:
            x, y = max(union[i][0], a), min(union[i][1], b)
            if y > x:
                total += y - x
            i += 1
        out.append(total)
    return out


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The intervals of [lo, hi] in which no op ran."""
    gaps, t = [], lo
    for a, b in merged(work(events), lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def gap_breakdown(trace: Trace, device: int = 0, top: int = 10):
    """Idle seconds of one device in the window, summed by the host span
    the host was in at each gap's midpoint t: the innermost span with
    start <= t < end, of equal durations the first in ``trace.host``, or
    "outside any span"; the ``top`` largest.

    One sweep over the spans sorted by start and the gaps in time order:
    a heap holds the spans begun by t, keyed by (duration, list index),
    and a span that has ended is dropped once it comes to the top."""
    lo, hi = trace.window
    host = trace.host
    order = sorted(range(len(host)), key=lambda i: host[i].start)
    begun: list[tuple[float, int]] = []
    j = 0
    by = collections.Counter()
    for a, b in idle_gaps(trace.ops.get(device, []), lo, hi):
        t = 0.5 * (a + b)
        while j < len(order) and host[order[j]].start <= t:
            heapq.heappush(begun, (host[order[j]].dur, order[j]))
            j += 1
        while begun and host[begun[0][1]].end <= t:
            heapq.heappop(begun)
        by[host[begun[0][1]].name if begun else "outside any span"] += b - a
    return [[k, v] for k, v in by.most_common(top)]


def op_breakdown(trace: Trace, top: int = 10):
    """Device seconds per op (instruction name) in the window, averaged
    over devices; containers left out."""
    lo, hi = trace.window
    by = collections.Counter()
    n = max(len(trace.ops), 1)
    for evs in trace.ops.values():
        for e in work(evs):
            if e.end > lo and e.start < hi:
                by[op_name(e.name)] += e.dur / n
    return [[k, v] for k, v in by.most_common(top)]
