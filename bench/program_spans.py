"""The program's own spans (`repro.launch.spans`), cut to the traced
window, for the readers of per-layer metrics that rest on them.

The window runs from the first window block's start to the last one's
end (`ctx["blocks"]`, stamped on `time.perf_counter`, the clock the
spans use). Nothing is returned for a program that records no spans,
for a window without blocks, or where the ring dropped a record that
ended inside the window.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict, List, Optional, Tuple


def window(ctx: Dict[str, Any]) -> Optional[Tuple[float, float, List]]:
    """(start, end, the ring's records) of the traced window, or None."""
    try:
        spans = importlib.import_module("repro.launch.spans")
    except ImportError:           # a program without spans
        return None
    blocks = ctx["blocks"]
    if not blocks:
        return None
    lo, hi = blocks[0]["t0"], blocks[-1]["t1"]
    if spans.RECORDER.dropped_t1 >= lo:
        return None
    return lo, hi, spans.RECORDER.records()


def admitted_waits(recs, lo: float, hi: float) -> Dict[int, float]:
    """rid -> queue wait, over the requests whose `serve.request.queue`
    span starts in [lo, hi] and ends there with an admission (a span
    with an `outcome` belongs to a request never admitted)."""
    return {r.rid: r.dur for r in recs
            if r.name == "serve.request.queue" and "outcome" not in r.attrs
            and lo <= r.t0 and r.t1 <= hi}


def mean(xs: List[float]) -> Optional[float]:
    return sum(xs) / len(xs) if xs else None


def waits(recs, lo: float, hi: float) -> Dict[str, Tuple[float, float, int]]:
    """what -> (seconds, longest, count) of the `serve.wait` spans
    (blocking device->host reads) that start in [lo, hi]."""
    out: Dict[str, Tuple[float, float, int]] = {}
    for r in recs:
        if r.name == "serve.wait" and lo <= r.t0 <= hi:
            s, m, n = out.get(r.attrs.get("what", "?"), (0.0, 0.0, 0))
            out[r.attrs.get("what", "?")] = (s + r.dur, max(m, r.dur), n + 1)
    return out
