"""The serving loop's spans (`launch/spans.py`): the tree a round records,
the per-request spans that add up to the time to first token, the
per-block record against what the benchmark derives from the loop, the
ring's bound, the spans' place on a profiler trace's clock, and the
decode block program's name."""
import glob
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import baselines
from repro.launch import serve, spans
from repro.launch.serve import Request, ServeLoop
from repro.models.transformer import Model

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.harness import watch_blocks  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

PRUNE = baselines.unicaim(heavy=48, reserve=16, select_k=16,
                          sink_tokens=2, recent_window=8)
# (prompt length, max_new, arrival, deadline_s): two lanes; the third
# request waits for a lane, the fourth expires waiting, the fifth arrives
# late
TRAFFIC = [(24, 6, 0.0, None), (32, 4, 0.0, None), (24, 3, 0.0, None),
           (40, 3, 0.0, 1e-3), (20, 2, 0.3, None)]
PER_REQUEST = ("serve.request.queue", "serve.request.first_token")


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, n), max_new=m,
                    arrival=a, deadline_s=d) for n, m, a, d in TRAFFIC]


@pytest.fixture(scope="module")
def served():
    cfg = reduced(get_config("granite-3-2b"))
    model = Model(cfg, PRUNE)
    params = model.init(jax.random.PRNGKey(0))
    loop = ServeLoop(model, params, lanes=2, eos=-1, block=2)
    handles = [loop.submit(r) for r in _requests(cfg)]
    blocks = watch_blocks(loop, lambda t: None)
    t_start = time.perf_counter()
    loop.run()
    recs = [r for r in spans.RECORDER.records() if r.t0 >= t_start]
    return {"cfg": cfg, "model": model, "params": params, "loop": loop,
            "handles": handles, "blocks": blocks, "recs": recs}


def test_round_span_tree(served):
    recs = served["recs"]
    by_id = {r.sid: r for r in recs}
    parent = {r.sid: by_id[r.parent].name for r in recs if r.parent}
    rounds = [r for r in recs if r.name == "serve.round"]
    assert [r.attrs["round"] for r in rounds] == list(
        range(1, len(rounds) + 1))
    for r in recs:
        if r.name in PER_REQUEST or r.name == "serve.round":
            assert r.parent is None, r
            continue
        up = by_id[r.parent]            # nested inside its parent's time
        assert up.t0 <= r.t0 <= r.t1 <= up.t1, (r, up)
    want = {"serve.sweep": {"serve.round"}, "serve.schedule": {"serve.round"},
            "serve.block": {"serve.round"}, "serve.admit": {"serve.schedule"},
            "serve.block.launch": {"serve.block"}}
    for r in recs:
        if r.name in want:
            assert parent[r.sid] in want[r.name], (r, parent[r.sid])
    waits = [r for r in recs if r.name == "serve.wait"]
    assert {r.attrs["what"] for r in waits} == {"fill", "block", "seed"}
    for r in waits:
        assert parent[r.sid] == {"block": "serve.block",
                                 "seed": "serve.admit"}.get(
            r.attrs["what"], parent[r.sid])
    schedules = [r for r in recs if r.name == "serve.schedule"]
    assert sum(r.attrs["admitted"] for r in schedules) == 4  # one expired
    admits = [r for r in recs if r.name == "serve.admit"]
    assert sum(r.attrs["group"] for r in admits) == 4
    assert {r.attrs["kind"] for r in admits} <= {"lane", "group"}
    for rnd in rounds:
        kids = [r.name for r in recs if r.parent == rnd.sid]
        assert kids.count("serve.sweep") == kids.count("serve.schedule") == 1
        assert kids.count("serve.block") <= 1
    for blk in (r for r in recs if r.name == "serve.block"):
        kids = [r for r in recs if r.parent == blk.sid]
        assert [r.name for r in kids].count("serve.block.launch") == 1
        assert [r.attrs.get("what") for r in kids].count("block") == 1
        assert blk.attrs["steps"] == 2 and blk.attrs["window"] > 0


def test_queue_plus_first_token_is_ttft(served):
    recs, loop = served["recs"], served["loop"]
    admits = {r.t0 for r in recs if r.name == "serve.admit"}
    expired = [h for h in served["handles"] if h.outcome == "deadline"]
    assert len(expired) == 1
    for h in served["handles"]:
        st = h.stats
        mine = {r.name: r for r in recs if r.rid == st.rid
                and r.name in PER_REQUEST}
        queue = mine["serve.request.queue"]
        assert queue.t0 == pytest.approx(loop._t0 + st.t_arrival, abs=1e-9)
        if h in expired:        # never admitted: waited to its resolution
            assert queue.attrs["outcome"] == "deadline"
            assert "serve.request.first_token" not in mine
            assert queue.t1 == pytest.approx(loop._t0 + st.t_done, abs=1e-9)
            continue
        first = mine["serve.request.first_token"]
        assert queue.t1 in admits and first.t0 == queue.t1
        assert first.attrs["tokens"] == len(st.tokens) > 0
        # the same stamps: equal up to float rounding of the sum
        assert queue.dur + first.dur == pytest.approx(st.ttft, abs=1e-9)


def test_block_record_matches_watch_blocks(served):
    records = [r for r in served["recs"] if r.name == "serve.block"]
    watched = served["blocks"]
    assert len(records) == len(watched) == \
        served["loop"].counters["decode_blocks"]
    for rec, b in zip(records, watched):
        assert b["t0"] <= rec.t0 <= rec.t1 <= b["t1"]
        assert rec.attrs["per_lane"] == b["lanes"]
        assert rec.attrs["lanes"] == len(b["lanes"])
        assert rec.attrs["tokens"] == sum(n for _, _, n in b["lanes"])
    assert sum(r.attrs["finished"] for r in records) == 4
    assert max(r.attrs["waiting"] for r in records) >= 1


def test_ring_bound_and_drop_count():
    rec = spans.Recorder(capacity=3)
    for i in range(5):
        rec.add("x", float(i), float(i) + 0.5, rid=i)
    assert [r.rid for r in rec.records()] == [2, 3, 4]
    assert rec.dropped == 2 and rec.dropped_t1 == 1.5
    with pytest.raises(RuntimeError):
        with rec.span("outer") as outer:
            with rec.span("inner", what="w") as inner:
                raise RuntimeError("closes both spans")
    assert [r.name for r in rec.records()] == ["x", "inner", "outer"]
    assert inner.parent == outer.sid and outer.parent is None
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert rec.dropped == 4


def test_threads_share_one_ring():
    """Spans from many threads at once: none lost from the count, and
    each thread's spans nest under its own parent."""
    import threading
    rec = spans.Recorder(capacity=500)
    threads, per = 16, 200
    crossed = []

    def work():
        for _ in range(per // 2):
            with rec.span("outer") as outer:
                with rec.span("inner") as inner:
                    pass
            if inner.parent != outer.sid:
                crossed.append((inner, outer))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in pool)
    assert not crossed
    kept = rec.records()
    assert len(kept) == 500 and rec.dropped == threads * per - 500
    assert len({r.sid for r in kept}) == 500


def test_spans_on_profiler_clock(served, tmp_path):
    """Every in-memory span of a served run sits in the profiler trace's
    host plane under its name, at the recorder's time mapped through the
    anchor, to within 0.1 ms."""
    from jax.profiler import ProfileData
    loop = ServeLoop(served["model"], served["params"], lanes=2, eos=-1,
                     block=2)
    for r in _requests(served["cfg"])[:3]:
        loop.submit(r)
    t_start = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop.run()
    finally:
        jax.profiler.stop_trace()
    recs = [r for r in spans.RECORDER.records()
            if r.t0 >= t_start and r.name not in PER_REQUEST]
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    pd = ProfileData.from_file(path)
    env = next(p for p in pd.planes if p.name == "Task Environment")
    start_ns = dict(env.stats)["profile_start_time"]
    on_trace = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        on_trace.setdefault(e.name, []).append(e.start_ns)
    names = sorted({r.name for r in recs})
    assert {"serve.round", "serve.block", "serve.wait"} <= set(names)
    assert names == sorted(on_trace)
    for name in names:
        mine = sorted(spans.RECORDER.trace_ns(r.t0, start_ns)
                      for r in recs if r.name == name)
        theirs = sorted(on_trace[name])
        assert len(mine) == len(theirs), name
        assert max(abs(a - b) for a, b in zip(mine, theirs)) < 1e5, name


def test_decode_block_program_is_named(served):
    loop = served["loop"]
    fn = serve._lanes_block_fn(serve._model_key(served["model"]), 2, None)
    text = fn.lower(*loop._block_args(np.zeros((2, 2), bool))).as_text()
    module = text.split("module @", 1)[1].split()[0]
    assert module == "jit_decode_block_lanes"
    # the benchmark's decode-step metrics leave these names out
    assert "prefill" not in module and "admit" not in module
