"""Benchmark orchestrator — one entry per paper artifact.

    PYTHONPATH=src python -m benchmarks.run [--full]

| benchmark  | paper artifact         | module                  |
|------------|------------------------|-------------------------|
| fig1       | Fig. 1 timelines       | benchmarks.lockbench    |
| fig3       | Fig. 3 lockbench grid  | benchmarks.lockbench (xdes; --engine des legacy) |
| sweep      | Fig. 3 grid + scenario | benchmarks.sweep (xdes) |
| phold      | Fig. 4 PHOLD/PDES      | benchmarks.phold        |
| sched      | §3 technique on TPU    | benchmarks.sched_bench  |
| oracle     | §5 oracle families     | benchmarks.oracle_ablation (xdes) |
| discipline | discipline x oracle map| benchmarks.discipline_diagram (sharded xdes) |
| workload   | workload x lock map    | benchmarks.workload_diagram (sharded xdes) |
| arrival    | open-loop traffic map  | benchmarks.arrival_diagram (sharded xdes) |
| fault      | fault x lock map       | benchmarks.fault_diagram (sharded xdes) |
| park       | park-cost x lock map   | benchmarks.park_diagram (sharded xdes) |
| perf       | engine perf trajectory | benchmarks.perf_bench   |
| fidelity   | dt-convergence study   | benchmarks.fidelity_study (xdes vs DES; not in --quick/--full, run on demand) |

Artifacts land in reports/* (JSON plus the oracle and discipline
phase-diagram CSV/markdown, and the measured perf trajectory —
``BENCH_xdes.json`` at the repo root is the committed perf BASELINE,
refreshed only by an explicit ``perf_bench --out BENCH_xdes.json``); a
summary CSV is printed at the end.  ``--quick`` runs the batched xdes sweep, the oracle-family grid,
the discipline/workload/arrival/fault diagrams and the perf
microbenchmark at smoke scale (~2-3 min) — the fast signal that the
simulation stack works end to end and hasn't slowed down.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sample counts (slower)")
    ap.add_argument("--quick", action="store_true",
                    help="batched-sweep smoke only (<60 s)")
    args = ap.parse_args(argv)
    os.makedirs("reports", exist_ok=True)
    t0 = time.time()
    summary: list[tuple[str, object]] = []

    if args.quick:
        print("=" * 72)
        print("[quick] batched xdes sweep smoke (fig3 grid + scenarios)")
        print("=" * 72)
        from benchmarks import sweep
        sw = sweep.main(["--quick"])
        for claim, ok in sw["fig3"]["claims"].items():
            summary.append((f"sweep.fig3.{claim}", ok))
        summary.append(("sweep.scenario.mutable.mean_ratio",
                        round(sw["scenario"]["mean_ratio_to_best"]
                              ["mutable"], 3)))
        print("\n" + "=" * 72)
        print("[quick] oracle-family grid smoke (phase-diagram report)")
        print("=" * 72)
        from benchmarks import oracle_ablation
        oa = oracle_ablation.main(["--quick"])
        for fam, row in oa["families"].items():
            summary.append((f"oracle.{fam}.best_tuned_ratio",
                            round(row["best_tuned_mean_ratio"], 3)))
        print("\n" + "=" * 72)
        print("[quick] discipline x oracle diagram smoke (sharded xdes)")
        print("=" * 72)
        from benchmarks import discipline_diagram
        dd = discipline_diagram.main(["--quick"])
        for disc, row in dd["disciplines"].items():
            summary.append((f"discipline.{disc}.wins", row["wins"]))
        print("\n" + "=" * 72)
        print("[quick] workload x discipline diagram smoke (sharded xdes)")
        print("=" * 72)
        from benchmarks import workload_diagram
        wd = workload_diagram.main(["--quick"])
        for w, rows in wd["workloads"].items():
            top = max(rows, key=lambda d: rows[d]["wins"])
            summary.append((f"workload.{w}.top", top))
        print("\n" + "=" * 72)
        print("[quick] arrival x discipline diagram smoke (open-loop xdes)")
        print("=" * 72)
        from benchmarks import arrival_diagram
        ad = arrival_diagram.main(["--quick"])
        for cell in ad["phase"]:
            summary.append(
                (f"arrival.{cell['arrival']}.rho{cell['rho']}.winner",
                 cell["winner"]))
        print("\n" + "=" * 72)
        print("[quick] fault x discipline diagram smoke (sharded xdes)")
        print("=" * 72)
        from benchmarks import fault_diagram
        fd = fault_diagram.main(["--quick"])
        for fl, rows in fd["faults"].items():
            top = max(rows, key=lambda d: rows[d]["wins"])
            summary.append((f"fault.{fl}.top", top))
        print("\n" + "=" * 72)
        print("[quick] park-cost x discipline diagram smoke (sharded xdes)")
        print("=" * 72)
        from benchmarks import park_diagram
        # 4 scenarios keep the park_cost=100 horizons (the slowest cells
        # in the whole quick path) inside the smoke budget
        pd = park_diagram.main(["--quick", "--scenarios", "4"])
        for p, rows in pd["park_costs"].items():
            top = max(rows, key=lambda d: rows[d]["wins"])
            summary.append((f"park.{p}.top", top))
        print("\n" + "=" * 72)
        print("[quick] xdes perf microbenchmark")
        print("=" * 72)
        from benchmarks import perf_bench
        # reports/ output: the repo-root BENCH_xdes.json is the committed
        # baseline the CI gate compares against — refresh it deliberately
        # via `perf_bench --full-size --out BENCH_xdes.json`.
        pb = perf_bench.main(["--quick",
                              "--out", "reports/bench_xdes_quick.json"])
        for name, x in pb["speedups"].items():
            summary.append((f"perf.{name}", x))
        print("\n" + "=" * 72)
        print(f"quick smoke done in {time.time()-t0:.0f}s — summary CSV")
        print("=" * 72)
        print("name,value")
        for k, v in summary:
            print(f"{k},{v}")
        return

    print("=" * 72)
    print("[1/12] lockbench fig1 (paper Fig. 1 timelines)")
    print("=" * 72)
    from benchmarks import lockbench
    f1 = lockbench.fig1()
    summary.append(("fig1.spin.makespan_slots",
                    f1["ttas"]["makespan_slots"]))
    summary.append(("fig1.sleep.makespan_slots",
                    f1["sleep"]["makespan_slots"]))
    summary.append(("fig1.mutable.makespan_slots",
                    f1["mutable"]["makespan_slots"]))

    print("\n" + "=" * 72)
    print("[2/12] lockbench fig3 (paper Fig. 3 grid, batched xdes engine)")
    print("=" * 72)
    f3 = lockbench.fig3(target_cs=400 if args.full else 200)
    for regime, data in f3.items():
        for lock in ("mutable", "pt-exp"):
            summary.append((f"fig3.{regime}.{lock}.ratio",
                            round(data["summary"][lock]["ratio_to_opt"], 3)))
    with open("reports/lockbench.json", "w") as f:
        json.dump({"fig1": f1, "fig3": f3}, f, indent=1)

    print("\n" + "=" * 72)
    print("[3/12] batched xdes sweep (fig3 grid + 1000-config scenarios)")
    print("=" * 72)
    from benchmarks import sweep
    sw = sweep.main(["--target-cs", "250" if args.full else "150"])
    for claim, ok in sw["fig3"]["claims"].items():
        summary.append((f"sweep.fig3.{claim}", ok))
    for lock, r in sw["scenario"]["mean_ratio_to_best"].items():
        summary.append((f"sweep.scenario.{lock}.mean_ratio", round(r, 3)))

    print("\n" + "=" * 72)
    print("[4/12] PHOLD on share-everything PDES (paper Fig. 4)")
    print("=" * 72)
    from benchmarks import phold
    ph = phold.run_phold(n_events=3000 if args.full else 1500)
    with open("reports/phold.json", "w") as f:
        json.dump(ph, f, indent=1)
    for g, rows in ph.items():
        for tc, locks in rows.items():
            summary.append((f"phold.{g}.t{tc}.mutable.speedup",
                            locks["mutable"]["speedup"]))

    print("\n" + "=" * 72)
    print("[5/12] serving-window scheduler (the technique on TPU batches)")
    print("=" * 72)
    from benchmarks import sched_bench
    sb = sched_bench.main(["--requests", "400" if args.full else "250"])
    for pol, agg in sb.items():
        summary.append((f"sched.{pol}.late_handoff_rate",
                        round(agg["late_handoff_rate"], 3)))
        summary.append((f"sched.{pol}.avg_standby",
                        round(agg["avg_standby"], 2)))

    print("\n" + "=" * 72)
    print("[6/12] oracle-family grid (paper §5 future work, batched xdes)")
    print("=" * 72)
    from benchmarks import oracle_ablation
    oa = oracle_ablation.main(
        ["--scenarios", "200" if args.full else "100",
         "--target-cs", "150" if args.full else "100"])
    for fam, row in oa["families"].items():
        summary.append((f"oracle.{fam}.wins", row["wins"]))
        summary.append((f"oracle.{fam}.best_tuned_ratio",
                        round(row["best_tuned_mean_ratio"], 3)))

    print("\n" + "=" * 72)
    print("[7/12] discipline x oracle diagram (sharded batched xdes)")
    print("=" * 72)
    from benchmarks import discipline_diagram
    dd = discipline_diagram.main(
        [] if args.full else ["--scenarios", "100", "--target-cs", "100"])
    for disc, row in dd["disciplines"].items():
        summary.append((f"discipline.{disc}.wins", row["wins"]))
        summary.append((f"discipline.{disc}.best_variant_ratio",
                        round(row["best_variant_mean_ratio"], 3)))

    print("\n" + "=" * 72)
    print("[8/12] workload x discipline diagram (sharded batched xdes)")
    print("=" * 72)
    from benchmarks import workload_diagram
    wd = workload_diagram.main(
        [] if args.full else ["--scenarios", "50", "--target-cs", "100"])
    for w, rows in wd["workloads"].items():
        top = max(rows, key=lambda d: rows[d]["wins"])
        summary.append((f"workload.{w}.top", top))
        summary.append((f"workload.{w}.mutable.best_ratio",
                        round(rows["mutable"]["best_variant_mean_ratio"],
                              3)))

    print("\n" + "=" * 72)
    print("[9/12] arrival x discipline diagram (open-loop sharded xdes)")
    print("=" * 72)
    from benchmarks import arrival_diagram
    ad = arrival_diagram.main(
        [] if args.full else ["--scenarios", "25", "--target-cs", "100"])
    for cell in ad["phase"]:
        summary.append(
            (f"arrival.{cell['arrival']}.rho{cell['rho']}.winner",
             cell["winner"]))
        summary.append(
            (f"arrival.{cell['arrival']}.rho{cell['rho']}.slo_frac",
             round(cell["mean_slo_frac"], 3)))

    print("\n" + "=" * 72)
    print("[10/12] fault x discipline diagram (sharded batched xdes)")
    print("=" * 72)
    from benchmarks import fault_diagram
    fd = fault_diagram.main(
        [] if args.full else ["--scenarios", "50", "--target-cs", "100"])
    for fl, rows in fd["faults"].items():
        top = max(rows, key=lambda d: rows[d]["wins"])
        summary.append((f"fault.{fl}.top", top))
        ret = rows["sleep"]["mean_retained_vs_none"]
        summary.append((f"fault.{fl}.sleep.retained",
                        None if ret is None else round(ret, 3)))

    print("\n" + "=" * 72)
    print("[11/12] park-cost x discipline diagram (sharded batched xdes)")
    print("=" * 72)
    from benchmarks import park_diagram
    pkd = park_diagram.main(
        [] if args.full else ["--scenarios", "25", "--target-cs", "100"])
    for p, rows in pkd["park_costs"].items():
        top = max(rows, key=lambda d: rows[d]["wins"])
        summary.append((f"park.{p}.top", top))
        ret = rows["sleep"]["mean_retained_vs_unit"]
        summary.append((f"park.{p}.sleep.retained",
                        None if ret is None else round(ret, 3)))

    print("\n" + "=" * 72)
    print("[12/12] xdes perf microbenchmark (reports/bench_xdes.json)")
    print("=" * 72)
    from benchmarks import perf_bench
    pb = perf_bench.main(["--full-size"] if args.full else [])
    with open("reports/perf_bench.md", "w") as f:
        f.write(perf_bench.summarize(pb) + "\n")
    for name, x in pb["speedups"].items():
        summary.append((f"perf.{name}", x))

    print("\n" + "=" * 72)
    print(f"benchmark suite done in {time.time()-t0:.0f}s — summary CSV")
    print("=" * 72)
    print("name,value")
    for k, v in summary:
        print(f"{k},{v}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
