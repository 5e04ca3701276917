#!/usr/bin/env python3
"""Readings of the correctness check for its limits: the program as the
benchmark runs it, and the control, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--sweeps 2] [--dt-scale 8] [--out PATH.jsonl]

For each seed the script runs ``--sweeps`` sweeps of the cell at its own
size through the timed entry, then the same comparison with the plain
reference as a run makes, and prints every number it computes (not only
the limited ones) as one JSON line; ``--out`` gets the same lines with
each sampled row's readings of both sides. The control is the same program with
its time step ``--dt-scale`` times the planner's: a step coarser than the
shortest time scale a configuration has (the planner resolves it six
times over), which breaks the time-resolution guarantee of the
configuration and is the step that would tempt a change made for speed.
The limits in ``limits/<cell>.json`` are set from these readings: above
the largest sound reading, below the smallest control reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--sweeps", type=int, default=2)
    p.add_argument("--dt-scale", type=float, default=8.0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import check, entry, harness

    cell = harness.load_cell(args.workload)
    cfg, traffic, gen = cell["config"], cell["traffic"], cell["generator"]
    entry.enable_compile_cache()
    harness.check_devices(int(cell["cell"]["chips"]))
    tc = int(cfg["target_cs"])

    def control_sweep(cols, *, target_cs, **kw):
        dt = entry.planned_dt(cols, target_cs) * args.dt_scale
        return entry.run_sweep(cols, target_cs=target_cs, dt=dt, **kw)

    out = open(args.out, "w") if args.out else None
    plan = ([(int(s), "sound", entry.run_sweep)
             for s in args.seeds.split(",") if s]
            + [(int(s), "control", control_sweep)
               for s in args.control_seeds.split(",") if s])
    for seed, mode, fn in plan:
        t0 = time.monotonic()
        with harness.stdout_to_stderr():
            sweeps = [harness.one_sweep(gen, cfg, traffic, seed, k, fn)
                      for k in range(args.sweeps)]
            rows: list = []
            numbers = check.compare(sweeps, seed, cell["limits"], rows,
                                    reference=cell["reference"],
                                    root=cell["root"])
        row = {"workload": args.workload, "seed": seed, "mode": mode,
               "dt_scale": args.dt_scale if mode == "control" else 1.0,
               "sweeps": args.sweeps, "target_cs": tc,
               "seconds": time.monotonic() - t0, "numbers": numbers}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(dict(row, rows=rows)) + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
