#!/usr/bin/env python3
"""Run one cell several times, one process per run, and report the spread
of every metric.

    python3 bench/spread.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--trace 0] [--out PATH]

Each seed is one run of ``bench/run.py`` in a fresh process, one after
another (this process never touches the device). The spread of a metric
is the distance between the first and third quartile, as
``statistics.quantiles(values, n=4)`` gives them, over the median. The
report (every run's result line, the end of its standard error, and the
spreads) is printed as JSON and written to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list[float]) -> float | None:
    """Interquartile distance over the median."""
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    runs = []
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds), "--trace", str(args.trace)],
            capture_output=True, text=True, cwd=os.path.dirname(HERE))
        lines = proc.stdout.strip().splitlines()
        try:
            result = json.loads(lines[-1]) if proc.returncode == 0 else None
        except (IndexError, json.JSONDecodeError):
            result = None
        runs.append({"seed": seed, "rc": proc.returncode,
                     "wall_s": time.monotonic() - t0, "result": result,
                     "stderr_tail": proc.stderr[-3000:]})
        print(json.dumps({"seed": seed, "rc": proc.returncode,
                          "wall_s": runs[-1]["wall_s"],
                          "result": result}), flush=True)
    values: dict = {}
    for r in runs:
        for name, m in ((r["result"] or {}).get("metrics") or {}).items():
            values.setdefault(name, []).append(m["value"])
    report = {"workload": args.workload, "seconds": args.seconds,
              "trace": args.trace, "runs": runs,
              "spread": {k: spread(v) for k, v in values.items()},
              "median": {k: statistics.median(v) for k, v in values.items()}}
    print(json.dumps({"spread": report["spread"],
                      "median": report["median"]}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0 if all(r["rc"] == 0 for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
