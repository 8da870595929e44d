"""Request arithmetic: what a user of the server sees, from the host
records `ServeLoop` keeps per request (`RequestStats`).

Times are the loop's own run-relative `time.monotonic` stamps: arrival
is when the request was due (the schedule is submitted ahead, so a
stalled loop's lateness lands in the latency, not in the generator),
`t_first` when its first token reached the host, `t_done` when it
resolved. A request the window closed before its first token carries
`t_first == t_done`, the censored time to first token.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence

MISSING = ("failed", "rejected")       # count as missing every limit


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it. Defined for +inf entries."""
    if not values:
        return float("nan")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def ttft_s(stats: Iterable) -> List[float]:
    """Time to first token of every request; +inf for one that failed
    or was rejected; the censored value for one the window closed."""
    out = []
    for s in stats:
        out.append(math.inf if s.outcome in MISSING
                   else s.t_first - s.t_arrival)
    return out


def tpot_ms(stats: Iterable) -> List[float]:
    """Per request with two or more tokens: milliseconds per output
    token after the first."""
    return [(s.t_done - s.t_first) / (len(s.tokens) - 1) * 1e3
            for s in stats if len(s.tokens) >= 2]


def output_tokens(stats: Iterable) -> int:
    return sum(len(s.tokens) for s in stats)


def block_tokens(blocks: Iterable) -> int:
    """Tokens the recorded decode blocks emitted (the harness records,
    per block, (prompt length, tokens before, tokens emitted) per lane)."""
    return sum(n for b in blocks for _, _, n in b["lanes"])


def lane_occupancy_pct(tokens: int, decode_blocks: int, block: int,
                       lanes: int) -> float:
    """Decode-emitted tokens over the lane-steps the decode blocks ran."""
    steps = decode_blocks * block * lanes
    return 100.0 * tokens / steps if steps else float("nan")


def outcomes(stats: Iterable) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for s in stats:
        out[s.outcome] = out.get(s.outcome, 0) + 1
    return out
