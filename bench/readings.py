#!/usr/bin/env python3
"""Readings that set the output check's limits, for one cell, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \\
        --seconds 8 --control bfloat16 --out readings.jsonl

For each seed it makes a run of the cell at the cell's own load and sizes
(a short window: the readings need no more than the answers a run checks)
and writes one JSON line: the numbers the check compares for the program,
and for the control, the plain reference computed in the next precision
below the configuration's (``bfloat16`` for a float32 archive, ``int4``
for an int8 tier, each answer from the window of the version that served
it) and put in the program's place on the same sampled requests.  The lower
reading of a number is the largest the program gives; the upper, the
smallest the control gives.  The benchmark's own runs never run this.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", choices=("bfloat16", "int4"),
                    required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import runner, spec
    bench = spec.Bench(ROOT)
    cell = bench.cell(args.workload)
    t = T_START
    with open(args.out, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            out = runner.run_cell(bench, cell, seed, args.seconds, False, t,
                                  cache_dir=str(ROOT / ".jax_cache"),
                                  control=args.control)
            line = {"workload": cell.name, "seed": seed,
                    "correct": out["correct"],
                    "program": {k: v["value"] for k, v in out["check"].items()},
                    "control": out["control"], "window": out["window"]}
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
