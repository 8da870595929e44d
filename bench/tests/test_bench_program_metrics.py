"""The readers of the metrics that rest on the program's own spans
(`sched.queue_wait_s`, `admit.first_token_s`, `host.round_self_ms`), on
hand-made records in a ring of their own and a window of block records;
and silent where the window has no blocks, the program records no
spans, the ring dropped a record inside the window, or nothing was
sampled."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from repro.launch import spans  # noqa: E402

NAMES = ("sched.queue_wait_s", "admit.first_token_s", "host.round_self_ms")
# the traced window: the first window block starts at 10, the last ends at 20
CTX = {"blocks": [{"t0": 10.0, "t1": 12.0, "lanes": []},
                  {"t0": 12.0, "t1": 20.0, "lanes": []}]}


def read(name, ctx=CTX):
    return spec.metric_reader(name)(ctx)


def request(rec, rid, arrival, admit=None, first=None, tokens=1,
            outcome="deadline"):
    if admit is None:
        rec.add("serve.request.queue", arrival, first, rid=rid,
                outcome=outcome)
        return
    rec.add("serve.request.queue", arrival, admit, rid=rid)
    rec.add("serve.request.first_token", admit, first, rid=rid,
            tokens=tokens)


def timed(recs, *times):
    """Sets each record's (start, end) by hand."""
    for r, (t0, t1) in zip(recs, times):
        r.t0, r.t1 = t0, t1


def round_with_block(rec, t, waits, block=True):
    """A serve.round at t = (start, end) with its serve.block and the
    serve.wait spans (start, end) under it."""
    with rec.span("serve.round", round=1) as rnd:
        with rec.span("serve.sweep") as sweep:
            pass
        if block:
            with rec.span("serve.block") as blk:
                made = []
                for _ in waits:
                    with rec.span("serve.wait", what="block") as w:
                        made.append(w)
    timed([rnd, sweep], t, (t[0], t[0]))
    if block:
        timed([blk] + made, (t[0] + 0.01, t[1] - 0.01), *waits)


@pytest.fixture
def ring(monkeypatch):
    rec = spans.Recorder()
    monkeypatch.setattr(spans, "RECORDER", rec)
    return rec


def test_queue_wait_and_first_token(ring):
    request(ring, 0, 10.5, 11.0, 12.0)               # counted in both
    request(ring, 1, 12.0, 13.0, 21.0)               # first token after close
    request(ring, 2, 13.0, 13.5, 13.5, tokens=0)     # resolved, no token
    request(ring, 3, 9.0, 10.5, 11.5)                # arrived before the window
    request(ring, 4, 19.0, 21.0, 22.0)               # admitted after the close
    request(ring, 5, 11.0, first=15.0)               # never admitted
    assert read("sched.queue_wait_s") == pytest.approx((0.5 + 1.0 + 0.5) / 3)
    assert read("admit.first_token_s") == pytest.approx(1.0)


def test_round_self_time(ring):
    # 2.0 s less 0.05 + 1.5 s of waits; 2.0 s less 1.4 s
    round_with_block(ring, (10.0, 12.0), [(10.15, 10.2), (10.3, 11.8)])
    round_with_block(ring, (12.0, 14.0), [(12.5, 13.9)])
    round_with_block(ring, (9.5, 11.0), [(9.6, 9.7)])   # starts before lo
    round_with_block(ring, (14.0, 15.0), [], block=False)  # no block
    assert read("host.round_self_ms") == pytest.approx((450.0 + 600.0) / 2)


def test_nested_waits_count_once(ring):
    with ring.span("serve.round") as rnd:
        with ring.span("serve.block") as blk:
            with ring.span("serve.admit") as adm:
                with ring.span("serve.wait", what="seed") as w1:
                    pass
            with ring.span("serve.wait", what="block") as w2:
                pass
    timed([rnd, blk, adm, w1, w2], (10.0, 11.0), (10.0, 11.0),
          (10.1, 10.3), (10.1, 10.2), (10.15, 10.9))
    assert read("host.round_self_ms") == pytest.approx(
        (1.0 - 0.8) * 1e3)          # waits cover [10.1, 10.9]


def test_silent_without_a_window_or_samples(ring):
    for name in NAMES:
        assert read(name) is None                      # nothing sampled
        assert read(name, {"blocks": []}) is None      # no window
    request(ring, 0, 10.5, 11.0, 12.0)
    round_with_block(ring, (10.0, 12.0), [(10.3, 11.8)])
    assert all(read(name) is not None for name in NAMES)


def test_silent_where_the_ring_dropped_inside_the_window(monkeypatch):
    rec = spans.Recorder(capacity=5)
    monkeypatch.setattr(spans, "RECORDER", rec)
    rec.add("serve.request.queue", 5.0, 9.0, rid=9, outcome="deadline")
    request(rec, 0, 10.5, 11.0, 12.0)
    request(rec, 1, 10.6, 11.0, 12.0)
    assert rec.dropped == 0 and read("sched.queue_wait_s") is not None
    rec.add("serve.request.queue", 10.2, 10.4, rid=7, outcome="deadline")
    assert rec.dropped_t1 == 9.0                       # before the window
    assert read("sched.queue_wait_s") is not None
    rec.add("serve.request.queue", 10.2, 10.4, rid=8, outcome="deadline")
    assert rec.dropped_t1 == 11.0                      # inside it
    for name in NAMES:
        assert read(name) is None


def test_silent_on_a_program_without_spans(ring, monkeypatch):
    request(ring, 0, 10.5, 11.0, 12.0)
    monkeypatch.setitem(sys.modules, "repro.launch.spans", None)
    for name in NAMES:
        assert read(name) is None


def test_host_timeline_names_the_stall(ring):
    """The run's log line that says where a window's host time went: the
    longest block and gap, and the blocking reads from the window's
    opening on, by what they wait for."""
    from bench import harness
    round_with_block(ring, (10.0, 12.0), [(10.15, 10.2), (10.3, 11.8)])
    round_with_block(ring, (12.0, 14.0), [(12.5, 13.9)])
    with ring.span("serve.wait", what="fill") as w:
        pass
    timed([w], (9.0, 9.9))                   # before the first block
    win = {"t_open": 8.5, "blocks": [
        {"t0": 10.0, "t1": 12.0, "lanes": []},
        {"t0": 12.0, "t1": 14.0, "lanes": []},
        {"t0": 15.5, "t1": 16.0, "lanes": []}]}
    line = harness.timeline(win)
    assert "first block 1.5000 s after the window opened" in line
    assert "longest (block, s) [(0, 2.0), (1, 2.0), (2, 0.5)]" in line
    assert "longest gaps before a block [(2, 1.5), (1, 0.0)]" in line
    assert "'block': (2.95, 1.5, 3)" in line and "'fill': (0.9, 0.9, 1)" in line
    assert harness.timeline({"t_open": 0.0, "blocks": []}) == \
        "no decode blocks in the window"
