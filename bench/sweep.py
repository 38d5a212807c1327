#!/usr/bin/env python3
"""Find the knee of one cell on the chip, in one process, and fix its rate.

    python3 bench/sweep.py --workload <cell> --seconds 12 --out sweep.json
    python3 bench/sweep.py --workload <cell> --apply sweep.json

The first form offers the cell's traffic at each rate of its traffic file's
``sweep.rates`` (the mean rate; an MMPP keeps its burst ratio) and records,
per rate, the requests answered inside the window per second, the backlog
(requests due a second or more before the close and still unanswered at
it), the p99 latency and how late the generator ran.  The knee is the
highest rate that, with every lower rate of the sweep, left a backlog of at
most 1% of the requests offered and a p99 under a second: above it the
queue grows, for good or for seconds at a time.  The second
form writes ``sweep.factor`` x knee into the traffic file as its rate.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BACKLOG_SHARE = 0.01
P99_LIMIT_MS = 1000.0


def knee(rows: list):
    """The highest rate sustained together with every lower one."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate"]):
        if (r["backlog_1s"] > BACKLOG_SHARE * r["offered"]
                or r["p99_ms"] > P99_LIMIT_MS):
            break
        best = r["rate"]
    return best


def scaled(arrivals: dict, rate: float) -> dict:
    from bench import traffic
    out = dict(arrivals)
    f = rate / traffic.mean_rate(arrivals)
    for k in ("rate", "rate_low", "rate_high"):
        if k in out:
            out[k] = out[k] * f
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out")
    ap.add_argument("--apply")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import runner, spec
    bench = spec.Bench(ROOT)
    cell = bench.cell(args.workload)
    path = bench.base / "traffic" / f"{cell.traffic['name']}.json"
    if args.apply:
        with open(args.apply) as f:
            found = knee(json.load(f)["rows"])
        with open(path) as f:
            doc = json.load(f)
        doc["arrivals"] = scaled(doc["arrivals"],
                                 round(doc["sweep"]["factor"] * found, 1))
        doc["sweep"]["knee"] = found
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"{path}: rate {doc['arrivals']}")
        return 0
    rows, t = [], T_START
    probe = copy.deepcopy(cell)
    probe.traffic["check"]["sample"] = 8
    # every rate's tail is wanted, so each run waits for all its answers
    probe.end_to_end = [{"name": "p99_ms", "unit": "ms"},
                        {"name": "setup_s", "unit": "s"}]
    for rate in cell.traffic["sweep"]["rates"]:
        probe.traffic["arrivals"] = scaled(cell.traffic["arrivals"],
                                           float(rate))
        out = runner.run_cell(bench, probe, args.seed, args.seconds,
                              False, t, cache_dir=str(ROOT / ".jax_cache"))
        w = out["window"]
        row = {"rate": rate, "answered_per_s": w["answered_in_window"]
               / args.seconds,
               "backlog_1s": w["backlog_1s"],
               "offered": w["offered"],
               "p99_ms": out["metrics"]["p99_ms"]["value"],
               "late_p50_ms": w["late_p50_ms"],
               "late_max_ms": w["late_max_ms"], "correct": out["correct"]}
        # a live deployment's ingest pump runs through every rate
        row.update({k: w[k] for k in ("ticks", "pump_errors") if k in w})
        rows.append(row)
        print(json.dumps(row), flush=True)
        t = time.monotonic()
    result = {"workload": cell.name, "seconds": args.seconds,
              "rows": rows, "knee": knee(rows)}
    print(json.dumps(result), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
