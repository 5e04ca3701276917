"""Workload x discipline x oracle diagram — which lock wins under which
workload.

Every workload row (``repro.core.policy.WORKLOAD_ROWS``: the paper's
constant uniform draws, bursty ON/OFF duty cycles, heterogeneous
per-thread CS/NCS scales, Poisson-like jittered arrivals) crossed with
every discipline-diagram variant (``DISCIPLINE_ROWS`` x ``ORACLE_ROWS``,
windowed-row pruning), on every random scenario of the adaptive-spin
design space — simulated by a SINGLE jit-compiled
:func:`repro.core.xdes.simulate_batch` program, sharded over all visible
devices (``shard_map`` over the config axis; the scenario count
auto-sizes to the device count).

This is the experiment behind the paper's robustness pitch: the winner
flips with workload shape, and the mutable lock's value is exactly that
it does not need to know the shape in advance (docs/workloads.md walks
through how to read the artifact).

Artifacts, also emitted by ``benchmarks/run.py``:

* ``reports/workload_diagram.json`` — full per-(workload, variant) stats
* ``reports/workload_phase_diagram.csv`` — which (discipline, oracle)
  wins per (workload x CS length x subscription) bucket
* ``reports/workload_phase_diagram.md`` — the same as a readable report

    PYTHONPATH=src python -m benchmarks.workload_diagram [--quick]
"""

from __future__ import annotations

import argparse
import json
import os

from benchmarks import sweep
from benchmarks.discipline_diagram import auto_scenarios


def write_phase_diagram(result: dict, reports_dir: str = "reports",
                        stem: str = "workload_phase_diagram"
                        ) -> tuple[str, str]:
    """Render the workload grid's phase diagram to ``<stem>.csv`` and
    ``<stem>.md`` under ``reports_dir``.  Returns the two paths."""
    os.makedirs(reports_dir, exist_ok=True)
    meta = result["meta"]
    variant_names = meta["variant_names"]

    csv_path = os.path.join(reports_dir, stem + ".csv")
    with open(csv_path, "w") as f:
        f.write("workload,cs,subscription,n,winner,win_share,"
                + ",".join(f"wins_{n}" for n in variant_names) + "\n")
        for cell in result["phase"]:
            f.write(f"{cell['workload']},{cell['cs']},{cell['sub']},"
                    f"{cell['n']},{cell['winner']},{cell['win_share']},"
                    + ",".join(str(cell["wins_by_variant"].get(n, 0))
                               for n in variant_names) + "\n")

    md_path = os.path.join(reports_dir, stem + ".md")
    with open(md_path, "w") as f:
        f.write("# Workload phase diagram — which lock wins under which "
                "workload\n\n")
        f.write(f"{meta['n_scenarios']} random scenarios x "
                f"{meta['n_workloads']} workload rows x "
                f"{meta['n_variants']} (discipline, oracle) variants = "
                f"{meta['n_configs']} configurations, one "
                f"{'sharded ' if meta['sharded'] else ''}batched xdes call "
                f"({meta['backend']} backend, {meta['n_devices']} "
                f"device(s), {meta['n_steps']} steps, {meta['wall_s']}s "
                f"wall).\n\nWorkload rows and how to read this page: "
                "docs/workloads.md; discipline rows: docs/disciplines.md; "
                "oracle families: docs/oracles.md.\n\n")
        f.write("## Discipline wins per workload (best variant per "
                "scenario)\n\n")
        disc_names = list(next(iter(result["workloads"].values())))
        f.write("| workload | " + " | ".join(disc_names)
                + " | top discipline |\n")
        f.write("|---" * (len(disc_names) + 2) + "|\n")
        for w, rows in result["workloads"].items():
            top = max(rows, key=lambda d: rows[d]["wins"])
            f.write(f"| {w} | "
                    + " | ".join(str(rows[d]["wins"]) for d in disc_names)
                    + f" | {top} |\n")
        f.write("\n## Phase diagram\n\nBuckets: workload row x CS length "
                "(short ≤ 10 µs < mid ≤ 100 µs < long) x subscription "
                "(threads vs cores).  The per-scenario best is taken "
                "within the workload, so winners are judged against the "
                "other locks under the same hold-time model.\n\n")
        f.write("| workload | CS | subscription | n | winning variant "
                "| win share |\n|---|---|---|---|---|---|\n")
        for cell in result["phase"]:
            f.write(f"| {cell['workload']} | {cell['cs']} | {cell['sub']} "
                    f"| {cell['n']} | {cell['winner']} "
                    f"| {cell['win_share']:.2f} |\n")
        f.write("\n## Variant detail (per workload)\n\n| workload "
                "| variant | wins | mean ratio | p10 ratio "
                "| spin CPU/CS (µs) |\n|---|---|---|---|---|---|\n")
        for v in sorted(result["variants"],
                        key=lambda v: (v["workload"],
                                       -v["mean_ratio_to_best"])):
            f.write(f"| {v['workload']} | {v['name']} | {v['wins']} "
                    f"| {v['mean_ratio_to_best']:.3f} "
                    f"| {v['p10_ratio_to_best']:.3f} "
                    f"| {v['mean_sync_cpu_per_cs_us']:.2f} |\n")
    return csv_path, md_path


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smoke-scale grid (<60 s on CPU)")
    ap.add_argument("--scenarios", type=int, default=None,
                    help="default: auto-sized to the device count "
                         "(100/device full, 12/device with --quick)")
    ap.add_argument("--target-cs", type=int, default=None,
                    help="default: 150 (40 with --quick)")
    ap.add_argument("--backend", choices=("ref", "pallas"), default="ref")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-shard", action="store_true",
                    help="disable the shard_map path even on multi-device "
                         "hosts")
    ap.add_argument("--stream", choices=("auto", "on", "off"),
                    default="auto",
                    help="run the grid chunk-by-chunk under a memory "
                         "budget (auto: stream at >= %d configs)"
                         % sweep.STREAM_AUTO)
    ap.add_argument("--mem-mb", type=float, default=None,
                    help="streaming memory budget in MiB (default: "
                         "REPRO_SWEEP_MEM_MB env, else device-derived)")
    ap.add_argument("--out", default="reports/workload_diagram.json")
    args = ap.parse_args(argv)

    from repro.configs.catalog import (LOCK_WORKLOADS,
                                       lock_workload_variants)

    n_variants = len(lock_workload_variants())
    base = 12 if args.quick else 100
    n_scenarios = args.scenarios or auto_scenarios(base, n_variants)
    result = sweep.workload_grid(
        n_scenarios=n_scenarios,
        target_cs=args.target_cs or (40 if args.quick else 150),
        backend=args.backend, seed=args.seed,
        workloads=LOCK_WORKLOADS,
        shard=False if args.no_shard else None,
        stream={"auto": None, "on": True, "off": False}[args.stream],
        mem_mb=args.mem_mb)

    out_dir = os.path.dirname(args.out) or "."
    os.makedirs(out_dir, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    csv_path, md_path = write_phase_diagram(result, out_dir)
    print(f"wrote {args.out}, {csv_path}, {md_path}")
    return result


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
