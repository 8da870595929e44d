"""Discovery by name, and the traffic generator's fixed work per seed."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec, traffic  # noqa: E402


def test_every_entry_has_its_file():
    bj = spec.benchmark(ROOT)
    for c in bj["configs"]:
        assert spec.config(c["name"])["name"] == c["name"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for w in bj["workloads"]:
        cell = spec.cell(w["name"], ROOT)
        assert cell["workload"]["config"] == w["config"]
        assert cell["config"]["name"] == w["config"]
        assert any(m["name"] == "setup_s" for m in cell["end_to_end"])
        assert cell["per_layer"]
        # a per-layer metric is read only in cells that report what it moves
        reported = {m["name"] for m in cell["end_to_end"]}
        for m in cell["per_layer"]:
            assert m["moves"] in reported, (w["name"], m["name"])
    for m in bj["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_cell_sees_only_its_metrics(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "workloads").mkdir()
    (tmp_path / "bench" / "configs" / "c.json").write_text(
        json.dumps({"name": "c"}))
    for w in ("a", "b"):
        (tmp_path / "bench" / "workloads" / f"{w}.json").write_text(
            json.dumps({"name": w, "config": "c"}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": w, "config": "c", "traffic": w}
                      for w in ("a", "b")],
        "end_to_end": [{"name": "x"}, {"name": "y", "workloads": ["a"]}],
        "per_layer": [{"name": "z", "workloads": ["b"]}]}))
    a, b = spec.cell("a", tmp_path), spec.cell("b", tmp_path)
    assert [m["name"] for m in a["end_to_end"]] == ["x", "y"]
    assert [m["name"] for m in b["end_to_end"]] == ["x"]
    assert a["per_layer"] == [] and [m["name"] for m in b["per_layer"]] == ["z"]


@pytest.mark.parametrize("out_max,fits", [(20, True), (21, False)])
def test_cell_fits_the_context(tmp_path, out_max, fits):
    """A cell whose longest prompt plus longest output passes its
    configuration's max_position_embeddings is refused."""
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "workloads").mkdir()
    (tmp_path / "bench" / "configs" / "c.json").write_text(json.dumps(
        {"name": "c", "model": {"max_position_embeddings": 100}}))
    length = {"dist": "uniform", "min": 10}
    (tmp_path / "bench" / "workloads" / "w.json").write_text(json.dumps(
        {"name": "w", "config": "c", "classes": [
            {"prompt": dict(length, max=80), "output": dict(length, max=5)},
            {"prompt": dict(length, max=40),
             "output": dict(length, max=out_max)}]}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "w", "config": "c", "traffic": "w"}],
        "end_to_end": [], "per_layer": []}))
    if fits:
        assert spec.cell("w", tmp_path)["workload"]["name"] == "w"
    else:
        with pytest.raises(ValueError, match="max_position_embeddings"):
            spec.cell("w", tmp_path)


def test_discovery_follows_the_file_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "x-1.json").write_text(json.dumps({"name": "x-1"}))
    (tmp_path / "metrics" / "a.b_c.py").write_text(
        "def read(ctx):\n    return ctx['v'] * 2\n")
    assert spec.config("x-1", tmp_path) == {"name": "x-1"}
    assert spec.metric_reader("a.b_c", tmp_path)({"v": 21}) == 42
    (tmp_path / "configs" / "y.json").write_text(json.dumps({"name": "z"}))
    try:
        spec.config("y", tmp_path)
    except ValueError:
        pass
    else:
        raise AssertionError("a file naming another config was accepted")


def test_readers_are_silent_without_data():
    empty = {"events": [], "blocks": [], "prompt_tokens": 0,
             "lanes": 4, "block": 8, "busy_s": 0.0, "window_s": 1.0,
             "model": spec.config("granite-3-2b")["model"],
             "prune": spec.config("granite-3-2b")["prune"],
             "peak": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    for m in spec.benchmark(ROOT)["per_layer"]:
        assert spec.metric_reader(m["name"])(empty) is None, m["name"]


def test_seeds_offer_the_same_work():
    """Two seeds: the same lengths and arrival times in the same order,
    other token ids; one seed twice: the same requests."""
    wl = spec.workload("granite-longdoc")
    a = traffic.generate(wl, 1, 40.0, 49155, 16)
    b = traffic.generate(wl, 2**33 + 5, 40.0, 49155, 16)
    assert len(a) == len(b) == round(wl["arrivals"]["rate_per_s"] * 40)
    assert [(len(r["prompt"]), r["max_new"], r["arrival"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"], r["arrival"]) for r in b]
    assert not all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert all(0 <= r["arrival"] < 40.0 for r in a)
    lo, hi = wl["classes"][0]["prompt"]["min"], wl["classes"][0]["prompt"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in a)
    # lengths are quantiles: every part of the range is offered
    lens = sorted(len(r["prompt"]) for r in a)
    assert lens[0] < 1.1 * lo and lens[-1] > 0.9 * hi
    again = traffic.generate(wl, 1, 40.0, 49155, 16)
    assert all((x["prompt"] == y["prompt"]).all() and x["arrival"] == y["arrival"]
               for x, y in zip(a, again))


def test_backlog_onoff_and_shared_prefix():
    wl = {"arrivals": {"kind": "backlog", "per_lane": 4},
          "classes": [{"share": 1.0,
                       "prompt": {"dist": "uniform", "min": 50, "max": 60},
                       "output": {"dist": "loguniform", "min": 2, "max": 9},
                       "shared_prefix": 40}]}
    reqs = traffic.generate(wl, 7, 10.0, 100, 8)
    assert len(reqs) == 32 and all(r["arrival"] == 0.0 for r in reqs)
    assert all((r["prompt"][:40] == reqs[0]["prompt"][:40]).all() for r in reqs)
    onoff = {"arrivals": {"kind": "onoff", "rate_per_s": 4.0, "on_s": 1.0,
                          "off_s": 3.0}, "classes": wl["classes"]}
    t = np.array([r["arrival"] for r in traffic.generate(onoff, 3, 20.0, 100, 8)])
    assert len(t) == 20 and ((t % 4.0) < 1.0).all() and (t < 20.0).all()


@pytest.mark.parametrize("name,reports,omits", [
    ("granite-longdoc",
     ["ttft_p90_s", "tpot_p90_ms", "setup_s", "decode_step.mfu",
      "ragged_decode_roofline"],
     ["output_tok_s", "sched.lane_occupancy"]),
    ("granite-gen",
     ["output_tok_s", "tpot_p90_ms", "setup_s", "decode_step.mfu",
      "ragged_decode_roofline", "sched.lane_occupancy", "decode_step.ms",
      "device.idle_share", "host.round_self_ms"],
     ["ttft_p90_s", "prefill.ms_per_ktok", "sched.queue_wait_s",
      "admit.first_token_s"]),
])
def test_cell_reports_its_metrics(name, reports, omits):
    """Tokens per second is judged only above the knee, where the engine
    sets it; below the knee it is the offered load."""
    cell = spec.cell(name, ROOT)
    names = {m["name"] for m in cell["end_to_end"] + cell["per_layer"]}
    assert set(reports) <= names and not set(omits) & names, names


def test_backlog_cell_offers_fixed_work():
    """granite-gen: 16 requests a lane, all queued at t=0, lengths inside
    their ranges and the context, the same lengths for every seed."""
    wl = spec.workload("granite-gen")
    ctx = spec.config("granite-3-2b")["model"]["max_position_embeddings"]
    a = traffic.generate(wl, 3, 51.0, 49155, 16)
    b = traffic.generate(wl, 2**31 + 77, 51.0, 49155, 16)
    assert len(a) == len(b) == 256
    assert all(r["arrival"] == 0.0 for r in a + b)
    c = wl["classes"][0]
    for r in a:
        assert c["prompt"]["min"] <= len(r["prompt"]) <= c["prompt"]["max"]
        assert c["output"]["min"] <= r["max_new"] <= c["output"]["max"]
        assert len(r["prompt"]) + r["max_new"] <= ctx
    assert [(len(r["prompt"]), r["max_new"]) for r in a] == \
        [(len(r["prompt"]), r["max_new"]) for r in b]
    assert not all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # more output queued than 16 lanes emit in a window at ~1,900 tok/s
    assert sum(r["max_new"] for r in a) > 1900 * 51
