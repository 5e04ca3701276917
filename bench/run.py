#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this host.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (the compile cache, the device check, one untimed warm-up sweep)
counts as ``setup_s``, from process start. Then sweeps run back to back
for ``--seconds`` (see ``bench/harness.py``); with ``--trace 0`` the last
line of standard output carries the cell's end-to-end metrics, with
``--trace 1`` the per-layer metrics read from the profiler's trace of the
window. Every run then checks the window's results against the plain
reference and prints each number compared beside its limit, as the last
lines of standard error and under ``check`` in the result line.

A host without a TPU, or with another number of chips than the cell asks
for, exits non-zero and prints no result.
"""

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness

    with harness.stdout_to_stderr():
        out = harness.run(args, _T0)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
