"""Finds the benchmark's parts by name.

`BENCHMARK.json` at the checkout root lists configurations, cells
(`workloads`) and metrics. Everything that belongs to one of them lives
in a file of its own under `bench/`:

    bench/configs/<config>.json     model sizes, prune settings, serving options
    bench/workloads/<cell>.json     traffic parameters for the generator
    bench/metrics/<metric>.py       reader of one per-layer metric

A new cell, configuration or metric is a new file plus an entry in
`BENCHMARK.json`; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def config(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    spec = load_json(bench / "configs" / f"{name}.json")
    if spec.get("name") != name:
        raise ValueError(f"configs/{name}.json names {spec.get('name')!r}")
    return spec


def workload(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    spec = load_json(bench / "workloads" / f"{name}.json")
    if spec.get("name") != name:
        raise ValueError(f"workloads/{name}.json names {spec.get('name')!r}")
    return spec


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """`read(ctx) -> float | None` from bench/metrics/<name>.py."""
    path = bench / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell's entry in BENCHMARK.json joined with its files: the
    workload and config specs, and the metric entries it reports. A cell
    whose longest prompt plus longest output would pass its
    configuration's `max_position_embeddings` is refused."""
    bj = benchmark(root)
    entry = next((w for w in bj["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    bench = root / "bench"
    wl = workload(entry["traffic"], bench)
    cfg = config(entry["config"], bench)
    ctx = cfg.get("model", {}).get("max_position_embeddings")
    if ctx is not None and wl.get("classes"):
        longest = (max(int(c["prompt"]["max"]) for c in wl["classes"])
                   + max(int(c["output"]["max"]) for c in wl["classes"]))
        if longest > ctx:
            raise ValueError(
                f"cell {name!r}: the longest prompt plus the longest output "
                f"({longest}) exceed {entry['config']}'s "
                f"max_position_embeddings ({ctx})")

    def here(m):
        return name in m.get("workloads", [name])

    return {
        "entry": entry,
        "workload": wl,
        "config": cfg,
        "end_to_end": [m for m in bj["end_to_end"] if here(m)],
        "per_layer": [m for m in bj["per_layer"] if here(m)],
    }
