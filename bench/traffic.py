"""The one traffic generator: a workload file's parameters -> requests.

Every seed gets the same sizes and inter-arrival gaps, in the same
order: lengths are the quantiles of the stated distribution and gaps
the quantiles of an exponential, shuffled once by a fixed order seed.
The run's seed draws only the token ids (and the weights, elsewhere).
A window closes on whatever is in flight, so which lengths come last
decides how much work a window holds; with the order fixed, two seeds
offer the same work and the spread between them is the system's.

Workload parameters (`bench/workloads/<cell>.json`):

    "lead_s": L                                          seconds the traffic runs
                                                         before the window opens
                                                         (the harness asks for
                                                         L + window seconds)
    "arrivals": {"kind": "poisson", "rate_per_s": r}     open loop over the window
                {"kind": "onoff", "rate_per_s": r, "on_s": a, "off_s": b}
                                                         poisson at r while on
                {"kind": "backlog", "per_lane": k}       k x lanes queued at t=0
    "classes": [{"share": 1.0,
                 "prompt": {"dist": "loguniform"|"uniform", "min": lo, "max": hi},
                 "output": {...},
                 "shared_prefix": 0}]                    leading tokens common
                                                         to every request of the class
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """n integer lengths at the mid-quantiles of `dist`."""
    lo, hi = int(dist["min"]), int(dist["max"])
    u = (np.arange(n) + 0.5) / max(n, 1)
    kind = dist.get("dist", "loguniform")
    if kind == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.round(x), lo, hi).astype(np.int64)


ORDER_SEED = 0


def arrival_times(arr: Dict[str, Any], seconds: float, lanes: int,
                  rng: np.random.Generator) -> np.ndarray:
    kind = arr["kind"]
    if kind == "backlog":
        return np.zeros(int(arr["per_lane"]) * lanes)
    if kind not in ("poisson", "onoff"):
        raise ValueError(f"unknown arrival kind {kind!r}")
    rate = float(arr["rate_per_s"])
    if kind == "onoff":
        on, off = float(arr["on_s"]), float(arr["off_s"])
        span = seconds * on / (on + off)          # seconds of "on" time
    else:
        on, off, span = seconds, 0.0, seconds
    n = max(1, int(round(rate * span)))
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    t = span * np.cumsum(gaps) / (gaps.sum() * (n + 1) / n)  # last < span
    if kind == "onoff":                           # map on-time to wall time
        t = (t // on) * (on + off) + (t % on)
    return t


def generate(workload: Dict[str, Any], seed: int, seconds: float,
             vocab: int, lanes: int) -> List[Dict[str, Any]]:
    """Requests as dicts: prompt (int32 array), max_new, arrival (s)."""
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    times = arrival_times(workload["arrivals"], seconds, lanes, order)
    n = len(times)
    classes = workload["classes"]
    shares = np.array([c.get("share", 1.0) for c in classes], float)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[np.argmax(shares)] += n - counts.sum()
    reqs: List[Dict[str, Any]] = []
    for c, m in zip(classes, counts):
        if m == 0:
            continue
        plen = order.permutation(quantiles(c["prompt"], m))
        olen = order.permutation(quantiles(c["output"], m))
        shared = int(c.get("shared_prefix", 0))
        prefix = rng.integers(0, vocab, shared, dtype=np.int64)
        for p, o in zip(plen, olen):
            tail = rng.integers(0, vocab, max(int(p) - shared, 0),
                                dtype=np.int64)
            prompt = np.concatenate([prefix[:p], tail]).astype(np.int32)
            reqs.append({"prompt": prompt, "max_new": int(o)})
    slots = order.permutation(n)                  # interleave the classes
    return [dict(reqs[i], arrival=float(t)) for i, t in zip(slots, times)]
