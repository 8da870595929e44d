"""Reduction of a profiler trace to device times.

`read_xplane` flattens the `.xplane.pb` the JAX profiler writes into
events (plane, line, name, start_ns, dur_ns). The reductions work on
that list alone, so they can be checked on a recorded trace:

  * device busy time: the union of the op intervals on a device plane;
  * idle share: 1 - busy / window;
  * device time by program (the "XLA Modules" line: one event per
    execution of a compiled program, named after the jitted function)
    and by op or kernel (the "XLA Ops" line), matched by substring;
  * the longest idle gaps, each named by the benchmark's host span
    (`bench.*`) that covers its middle.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
MODULES = "XLA Modules"
OPS = "XLA Ops"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_xplane(path: str) -> List[Event]:
    """Device planes' module and op lines, and the host's bench spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in pd.planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if on_device and line.name not in (MODULES, OPS):
                continue
            for e in line.events:
                if not on_device and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)})


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge [start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_intervals(events: Iterable[Event], plane: str,
                   line: str = OPS,
                   clip: Optional[Tuple[float, float]] = None
                   ) -> List[Tuple[float, float]]:
    """Union of the plane's op intervals, cut to `clip` when given."""
    lo, hi = clip if clip is not None else (-float("inf"), float("inf"))
    return union((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                 if e.plane == plane and e.line == line
                 and e.end_ns > lo and e.start_ns < hi)


def busy_seconds(events: Sequence[Event],
                 clip: Optional[Tuple[float, float]] = None) -> float:
    """Busy seconds averaged over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = sum(sum(e - s for s, e in busy_intervals(events, p, clip=clip))
                for p in planes)
    return total / len(planes) * 1e-9


def idle_share(busy_s: float, window_s: float) -> float:
    return 1.0 - busy_s / window_s


def time_by_name(events: Iterable[Event], line: str,
                 needles: Sequence[str]) -> float:
    """Seconds summed over device events on `line` whose name contains
    any of `needles`, averaged over the device planes."""
    events = list(events)
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = sum(e.dur_ns for e in events
                if e.plane.startswith(DEVICE_PREFIX) and e.line == line
                and any(n in e.name for n in needles))
    return total / len(planes) * 1e-9


SKEW_NS = 2e6    # device clock may read up to ~1 ms behind the host's


def time_in_spans(events: Sequence[Event], span: str,
                  exclude: Sequence[str] = ()) -> Tuple[float, int]:
    """(seconds, count) of the device programs ("XLA Modules" events)
    that started inside a host span named `span`, leaving out programs
    whose name contains any of `exclude`. Attributes programs to the host
    phase that dispatched them where their names say nothing (a jitted
    `functools.partial` is named `jit__unknown`). A program counts when
    it starts inside the span, allowing SKEW_NS for the two clocks.
    Averaged over planes."""
    spans = sorted((e.start_ns, e.end_ns) for e in events
                   if e.name == span and not e.plane.startswith(DEVICE_PREFIX))
    planes = device_planes(events)
    if not spans or not planes:
        return 0.0, 0
    starts = [s - SKEW_NS for s, _ in spans]
    import bisect
    total, n = 0.0, 0
    for e in events:
        if (not e.plane.startswith(DEVICE_PREFIX) or e.line != MODULES
                or any(x in e.name for x in exclude)):
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.start_ns < spans[i][1]:
            total += e.dur_ns
            n += 1
    return total / len(planes) * 1e-9, n // len(planes)


def short_name(name: str) -> str:
    """An op's HLO text cut to its instruction name ("%fusion.12")."""
    return name.split(" = ", 1)[0]


CONTROL_FLOW = ("%while", "%conditional", "%call")


def count_by_name(events: Iterable[Event], line: str,
                  needles: Sequence[str]) -> int:
    return sum(1 for e in events
               if e.plane.startswith(DEVICE_PREFIX) and e.line == line
               and any(n in e.name for n in needles))


def top_ops(events: Iterable[Event], n: int = 10,
            line: str = OPS) -> List[List]:
    """[[name, seconds], ...] of the device ops (or, with line=MODULES,
    programs) that took most time, summed over executions and planes.
    Ops are named by their HLO instruction; loops and calls, which
    contain other ops, are left out."""
    tot: Dict[str, float] = {}
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line == line:
            name = short_name(e.name)
            if name.startswith(CONTROL_FLOW):    # they contain other ops
                continue
            tot[name] = tot.get(name, 0.0) + e.dur_ns
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def idle_gaps(events: Sequence[Event], lo_ns: float, hi_ns: float,
              n: int = 10) -> List[List]:
    """[[what the host was doing, seconds], ...]: the n longest gaps in
    the first device's busy time within [lo_ns, hi_ns], each named by
    the innermost bench span covering its middle ("host" if none)."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0], clip=(lo_ns, hi_ns))
    gaps, cur = [], lo_ns
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, hi_ns)))
        cur = max(cur, e)
        if cur >= hi_ns:
            break
    if cur < hi_ns:
        gaps.append((cur, hi_ns))
    spans = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        name = min(cover, key=lambda sp: sp.dur_ns).name if cover else "host"
        out.append([name, (e - s) * 1e-9])
    return out


def window_ns(events: Sequence[Event], span: str) -> Optional[Tuple[float, float]]:
    """[start, end) of the named host span, on the trace's clock."""
    for e in events:
        if e.name == span and not e.plane.startswith(DEVICE_PREFIX):
            return e.start_ns, e.end_ns
    return None
