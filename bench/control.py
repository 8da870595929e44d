"""Readings that set a cell's correctness limits (not part of a run).

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 30
    python3 bench/control.py --workload <cell> --seeds 11,12,13 --prefill-parts

For each seed, in one process (set-up paid once): fresh weights from the
seed, the cell's own traffic through `ServeLoop` (lead-in and window as
a run serves them), then the numbers a run compares, read twice:

  program  as a run reads them: the first served tokens against the plain
           reference, and the lockstep decode step of the program against
           the reference at every layer;
  control  the plain reference in int8 (bench/reference.py) in the
           program's place: the token it puts first after each prompt,
           and its attention at every layer of the same lockstep step.

Each is judged against the cell's limits by the function a run uses,
so every line shows the program correct and the control not. The lower
reading of a limit is the largest program value over a dozen seeds or
more, the upper the smallest control value. The last line is JSON with
every row. `--prefill-parts` reads, without a window, every part of the
lockstep prefill (bench/harness.py PREFILL_PARTS) on the longest prompt
of each seed's traffic, for the program and the control.
"""
import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    from bench import harness, spec
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--prefill-parts", action="store_true",
                    help="read every part of the lockstep prefill only")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.prefill_parts:
        rows = harness.prefill_readings(cell, seeds)
    else:
        rows = harness.readings(cell, seeds, args.seconds)
    print(harness.finite_json({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
