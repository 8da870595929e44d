"""The chip benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --sweep 0.2,0.4,0.8

Runs one cell of BENCHMARK.json on the chips of the machine it starts
on, and prints as its last line one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and
`compared` last). With `--trace 0` the metrics are the cell's
end-to-end metrics; with `--trace 1` its per-layer metrics, read from a
profiler trace of the window. It exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.

`--sweep` serves the cell's traffic at each listed arrival rate (one
window each, one process) and prints a line per rate: the knee sweep
that fixes a cell's rate. It prints no result line.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)

    from bench import harness, spec
    cell = spec.cell(args.workload, ROOT)
    import jax
    devices = jax.devices()
    chips = int(cell["entry"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"no TPU with {chips} chip(s): JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 3
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        harness.sweep(cell, args.seed, args.seconds, rates)
        return 0
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS, ROOT)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
