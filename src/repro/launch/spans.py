"""Host spans of the serving loop, kept in memory and on the profiler's clock.

`ServeLoop` records a span at each of its layer boundaries (a scheduler
round, an admission, a decode block, a blocking device->host read) into
the one process-wide `RECORDER`. Each record holds its name, its id, the
id of the span that was open around it (its parent), the request it
belongs to where there is one, its start and end on `time.perf_counter`,
and a few attributes. Records go into a bounded ring; the oldest are
dropped first, and the ring counts them.

`span()` also enters `jax.profiler.TraceAnnotation`, so that while a
profiler session runs the span sits in the trace (`.xplane.pb`) beside
the device's events. Outside a session that costs a check and nothing
is written; the ring is always kept. `add()` records a span after the
fact, in memory only: the per-request spans, whose start (the arrival)
lies in the past.

The recorder takes one anchor pair (`perf_counter()`, `time.time_ns()`)
when it is made, so any record can be placed on a trace's clock:
`trace_ns(t, profile_start_time)` with the trace's
`profile_start_time` (its "Task Environment" plane).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional

import jax

CAPACITY = 1 << 15      # records kept; a busy round writes about a dozen


@dataclasses.dataclass
class Span:
    name: str
    sid: int
    parent: Optional[int]
    rid: Optional[int]
    t0: float
    t1: float
    attrs: Dict[str, Any]

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


class Recorder:
    """A bounded ring of finished spans, plus the stack of open ones per
    thread (which gives each new span its parent). Loops in several
    threads may share one recorder."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.anchor = (time.perf_counter(), time.time_ns())
        self.dropped = 0
        self.dropped_t1 = float("-inf")   # latest end among dropped records
        self._ring: Deque[Span] = deque()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()

    def _open(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, rec: Span) -> None:
        with self._lock:
            if len(self._ring) >= self.capacity:
                old = self._ring.popleft()
                self.dropped += 1
                self.dropped_t1 = max(self.dropped_t1, old.t1)
            self._ring.append(rec)

    @contextlib.contextmanager
    def span(self, name: str, rid: Optional[int] = None,
             **attrs) -> Iterator[Span]:
        """Records the `with` block as a span, child of the innermost
        open one. The yielded record takes attributes known only at
        exit (`rec.attrs[...] = ...`); those given here also go to the
        profiler trace."""
        stack = self._open()
        rec = Span(name, next(self._ids), stack[-1].sid if stack else None,
                   rid, 0.0, 0.0, attrs)
        stack.append(rec)
        with jax.profiler.TraceAnnotation(name, **attrs):
            rec.t0 = time.perf_counter()
            try:
                yield rec
            finally:
                rec.t1 = time.perf_counter()
                stack.pop()
                self._push(rec)

    def add(self, name: str, t0: float, t1: float,
            rid: Optional[int] = None, **attrs) -> None:
        """Records a span that has already happened (perf_counter stamps);
        in memory only, with no parent."""
        self._push(Span(name, next(self._ids), None, rid, t0, t1, attrs))

    def records(self) -> List[Span]:
        """The kept records, oldest finished first."""
        with self._lock:
            return list(self._ring)

    def trace_ns(self, t: float, profile_start_ns: int) -> float:
        """A perf_counter stamp on a profiler trace's clock: nanoseconds
        after the trace's `profile_start_time`."""
        perf, wall_ns = self.anchor
        return (t - perf) * 1e9 + wall_ns - profile_start_ns


RECORDER = Recorder()
span = RECORDER.span
add = RECORDER.add
