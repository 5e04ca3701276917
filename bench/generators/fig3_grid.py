"""Sweeps over the paper's Fig. 3 grid: four CS/NCS regimes x five locks
x eight thread counts on a 20-core machine, with seed replicas.

The grid's constants and row order copy ``repro.configs.catalog``'s
``lock_fig3_grid``, and :func:`fig3_columns` gives exactly the RAW
columns that ``repro.core.policy.config_columns`` makes of that grid
(``bench/tests`` pins both). The replicas' simulation seeds are drawn
from the sweep's index alone and a run's ``--seed`` orders them: the
seeds set when the slowest configuration reaches ``target_cs``, and
seeds drawn per run moved a run's rate by about 1% on a TPU v5e; with
one set per sweep index every run offers the same work. The host
reduction copies ``benchmarks/sweep.py``'s ``fig3_batched``: per
(regime, lock, threads) mean throughput and spin CPU per critical
section over the replicas.
"""

from __future__ import annotations

import numpy as np

from bench import entry

# -- copies of repro.configs.catalog --------------------------------------------
LOCK_SHORT = (0.0, 3.7e-6)        # paper §4: uniform [0, 3.7) us
LOCK_LONG = (0.0, 366e-6)         # uniform [0, 366) us
LOCK_WAKE = 8e-6
LOCK_CORES = 20
LOCK_THREADS = (2, 4, 8, 12, 16, 20, 26, 32)
LOCK_DISCIPLINES = ("ttas", "mcs", "sleep", "adaptive", "mutable")
LOCK_REGIMES = {
    "cs_short_ncs_short": (LOCK_SHORT, LOCK_SHORT),
    "cs_long_ncs_short": (LOCK_LONG, LOCK_SHORT),
    "cs_short_ncs_long": (LOCK_SHORT, LOCK_LONG),
    "cs_long_ncs_long": (LOCK_LONG, LOCK_LONG),
}


def fig3_columns(seeds, regimes=LOCK_REGIMES, locks=LOCK_DISCIPLINES,
                 threads=LOCK_THREADS, cores=LOCK_CORES,
                 wake=LOCK_WAKE) -> dict:
    """The Fig. 3 grid as RAW columns, rows regime-major, then lock, then
    thread count, then seed (every other field at its SimConfig
    default)."""
    seeds = np.asarray(seeds, np.int64)
    R, L, T, N = len(regimes), len(locks), len(threads), len(seeds)
    n = R * L * T * N
    cs = np.asarray([c for c, _ in regimes.values()], np.float64)
    ncs = np.asarray([c for _, c in regimes.values()], np.float64)
    per = lambda a: np.repeat(a, L * T * N, axis=0)  # noqa: E731
    return {
        "lock": np.tile(np.repeat(np.asarray(
            [entry.POLICY_IDS[x] for x in locks], np.int32), T * N), R),
        "threads": np.tile(np.repeat(np.asarray(threads, np.int32), N),
                           R * L),
        "cores": np.full(n, cores, np.int32),
        "cs_lo": per(cs[:, 0]), "cs_hi": per(cs[:, 1]),
        "ncs_lo": per(ncs[:, 0]), "ncs_hi": per(ncs[:, 1]),
        "wake_latency": np.full(n, wake, np.float64),
        "alpha": np.full(n, np.nan, np.float64),
        "sws_init": np.ones(n, np.int32),
        "sws_max": np.full(n, -1, np.int32),
        "k": np.full(n, 10, np.int32),
        "spin_budget": np.full(n, entry.DEFAULT_SPIN_BUDGET, np.float64),
        "seed": np.tile(seeds, R * L * T).astype(np.uint32),
        "oracle": np.full(n, entry.ORACLE_IDS["paper"], np.int32),
        "workload": np.full(n, entry.WORKLOAD_IDS["constant"], np.int32),
        "wl_period": np.full(n, 1e-4, np.float64),
        "wl_duty": np.full(n, 0.25, np.float64),
        "wl_burst": np.full(n, 8.0, np.float64),
        "wl_spread": np.full(n, 4.0, np.float64),
        "arrival_phase": np.zeros(n, np.float64),
        "arrival": np.full(n, entry.ARRIVAL_IDS["closed"], np.int32),
        "arrival_rate": np.zeros(n, np.float64),
        "queue_cap": np.full(n, entry.QUEUE_MAX, np.int32),
        "slo": np.full(n, 1e-3, np.float64),
        "tie_break": np.zeros(n, np.int32),
        "fault": np.zeros(n, np.int32),
        "fault_rate": np.zeros(n, np.float64),
        "fault_scale": np.full(n, 5e-5, np.float64),
        "park_cost": np.ones(n, np.float64),
    }


# -- the generator ------------------------------------------------------------------
def sweep(config: dict, traffic: dict, seed: int, k: int) -> dict:
    """Sweep ``k`` of a run with ``--seed seed``: the whole grid with
    ``replicas`` seeded replicas of every cell, in the run's order."""
    pool = np.random.default_rng([int(k)]).integers(
        0, 2**32, int(traffic["replicas"]), dtype=np.int64)
    seeds = np.random.default_rng([int(seed), int(k)]).permutation(pool)
    cols = fig3_columns(seeds)
    n = len(cols["lock"])
    lock = np.repeat(np.asarray(LOCK_DISCIPLINES),
                     len(LOCK_THREADS) * len(seeds))
    lock = np.tile(lock, len(LOCK_REGIMES))
    names = {"lock": lock, "oracle": np.full(n, "paper"),
             "arrival": np.full(n, "closed"),
             "workload": np.full(n, "constant"), "fault": np.full(n, "none")}
    alpha = np.asarray([entry.DEFAULT_ALPHA[x] for x in lock], np.float64)
    return {"cols": cols, "names": names, "alpha": alpha, "reduce": None,
            "replicas": len(seeds)}


def diagram(sw: dict, res) -> dict:
    """Per (regime, lock, threads) mean throughput and spin CPU per CS
    over the replicas, and each lock's mean ratio to the best lock."""
    shape = (len(LOCK_REGIMES), len(LOCK_DISCIPLINES), len(LOCK_THREADS),
             sw["replicas"])
    completed = np.asarray(res.completed, np.float64)
    thr = (completed / np.maximum(np.asarray(res.t_end, np.float64),
                                  1e-30)).reshape(shape).mean(-1)
    cpu = (np.asarray(res.spin_cpu, np.float64)
           / np.maximum(completed, 1)).reshape(shape).mean(-1)
    ratio = thr / np.maximum(thr.max(axis=1, keepdims=True), 1e-30)
    return {regime: {lock: {"ratio_to_best": float(ratio[ri, li].mean()),
                            "spin_cpu_per_cs": float(cpu[ri, li].mean())}
                     for li, lock in enumerate(LOCK_DISCIPLINES)}
            for ri, regime in enumerate(LOCK_REGIMES)}
