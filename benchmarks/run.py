"""Benchmark harness — one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (EXPERIMENTS.md indexes them).
  Table II → bench_aedp        Fig 10 → bench_footprint
  Fig 11  → bench_energy       Fig 12 → bench_latency
  Fig 13  → bench_accuracy     Fig 9  → bench_fidelity

A bench whose ``run()`` returns a dict additionally gets a
machine-readable ``BENCH_<name>.json`` written next to the cwd under
``--smoke`` (CI uploads these — the serving trajectory lives in
``BENCH_serve.json``: tok/s, p50/p99 ttft, prefill compile counts).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

BENCHES = ("aedp", "footprint", "energy", "latency", "fidelity",
           "accuracy", "needle", "serve")


SMOKE_BENCHES = ("aedp", "latency", "serve")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma list from {BENCHES}")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CI sweep: "
                         f"{SMOKE_BENCHES} with shrunk configs")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.smoke:
        from benchmarks import common
        common.set_smoke(True)
    wanted = (args.only.split(",") if args.only
              else list(SMOKE_BENCHES) if args.smoke else list(BENCHES))
    print("name,us_per_call,derived")
    for name in wanted:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        t0 = time.time()
        summary = mod.run()
        if args.smoke and isinstance(summary, dict) and summary:
            path = f"BENCH_{name}.json"
            with open(path, "w") as f:
                json.dump(summary, f, indent=2, sort_keys=True)
            print(f"wrote {path}", file=sys.stderr)
        print(f"bench_{name}_total,{(time.time() - t0) * 1e6:.0f},done",
              file=sys.stderr)
    if args.smoke:
        # cross-PR trajectory: committed baseline history + this run,
        # tabulated to stdout and plotted to BENCH_trajectory.{svg,png}
        # (CI uploads the pair with the BENCH_*.json artifacts)
        from benchmarks import trajectory
        print()
        trajectory.main(["--plot"])


if __name__ == "__main__":
    main()
