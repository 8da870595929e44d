"""The trace reduction: busy union, idle share, device time by program
and by kernel, idle gaps named by the host span over them; on a
hand-made event list and on a small trace recorded on a TPU v5e."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
RECORDED = Path(__file__).parent / "data" / "small_trace.xplane.pb"


def ev(plane, line, name, start, dur):
    return tr.Event(plane, line, name, float(start), float(dur))


EVENTS = [
    ev(HOST, "python", "bench.window", 0, 1000),
    ev(HOST, "python", "bench.schedule", 100, 300),
    ev(DEV, tr.MODULES, "jit_decode_block_lanes(12)", 10, 90),
    ev(DEV, tr.OPS, "fusion.1", 10, 40),
    ev(DEV, tr.OPS, "ragged_decode_kernel", 40, 60),   # overlaps fusion.1
    ev(DEV, tr.MODULES, "jit_prefill_group(3)", 500, 200),
    ev(DEV, tr.OPS, "fusion.2", 500, 200),
    ev(DEV, tr.OPS, "ragged_decode_kernel", 950, 100),  # runs past the window
]


def test_union_merges_overlaps():
    assert tr.union([(0, 5), (3, 8), (10, 12), (12, 13)]) == [(0, 8), (10, 13)]


def test_busy_and_idle_share():
    # ops cover [10, 100) + [500, 700) + [950, 1050) -> 90 + 200 + 100
    assert tr.busy_seconds(EVENTS) == pytest.approx(390e-9)
    assert tr.busy_seconds(EVENTS, clip=(0, 1000)) == pytest.approx(340e-9)
    assert tr.idle_share(340e-9, 1000e-9) == pytest.approx(0.66)


def test_time_by_program_and_kernel():
    assert tr.time_by_name(EVENTS, tr.MODULES, ["decode_block_lanes"]) == \
        pytest.approx(90e-9)
    assert tr.time_by_name(EVENTS, tr.MODULES,
                           ["prefill_one", "prefill_group"]) == \
        pytest.approx(200e-9)
    assert tr.time_by_name(EVENTS, tr.OPS, ["ragged_decode"]) == \
        pytest.approx(160e-9)
    assert tr.count_by_name(EVENTS, tr.OPS, ["ragged_decode"]) == 2
    assert tr.top_ops(EVENTS, 2) == [["fusion.2", pytest.approx(200e-9)],
                                     ["ragged_decode_kernel",
                                      pytest.approx(160e-9)]]


def test_idle_gaps_named_by_host_span():
    gaps = tr.idle_gaps(EVENTS, 0, 1000, 3)
    # [100, 500) lies under bench.schedule's [100, 400) at its middle 300;
    # [700, 950) and [0, 10) under bench.window only
    assert gaps == [["bench.schedule", pytest.approx(400e-9)],
                    ["bench.window", pytest.approx(250e-9)],
                    ["bench.window", pytest.approx(10e-9)]]
    assert tr.window_ns(EVENTS, "bench.window") == (0.0, 1000.0)


def test_recorded_v5e_trace():
    """bench/tests/data/small_trace.xplane.pb: three calls of the ragged
    decode kernel (16 rows, 1088 slots) and three small matmul programs,
    each in a `bench.*` host span, under a `bench.window` span, recorded
    on one TPU v5 lite."""
    from jax.profiler import ProfileData
    events = tr.read_xplane(str(RECORDED))
    assert tr.device_planes(events) == [DEV]
    lo, hi = tr.window_ns(events, "bench.window")
    # independent count straight from the file: op intervals of the
    # device's "XLA Ops" line, merged by hand
    raw = []
    for plane in ProfileData.from_file(str(RECORDED)).planes:
        if plane.name == DEV:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    raw += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    raw.sort()
    busy, end = 0.0, -1.0
    for s, e in raw:
        s = max(s, end)
        if e > s:
            busy += e - s
            end = e
    assert tr.busy_seconds(events) == pytest.approx(busy * 1e-9)
    clipped = tr.busy_seconds(events, clip=(lo, hi))
    assert 0 < clipped <= (hi - lo) * 1e-9
    assert tr.count_by_name(events, tr.OPS, ["ragged_decode"]) == 3
    assert tr.count_by_name(events, tr.MODULES, ["mlp_step"]) == 3
    assert tr.time_by_name(events, tr.OPS, ["ragged_decode"]) > 0
    names = [n for n, _ in tr.idle_gaps(events, lo, hi, 10)]
    assert names and all(n.startswith("bench.") or n == "host"
                         for n in names)
