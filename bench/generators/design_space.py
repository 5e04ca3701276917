"""Sweeps over the design space of random machines and workloads.

The scenario draw, the product with the (discipline, oracle) variants and
the open-loop arrival columns are copies of the repository's
``repro.configs.catalog`` generators (``sample_scenarios``,
``sample_scenario_columns``, ``_product_columns``,
``lock_discipline_variants``, ``lock_arrival_variants`` and the body of
``lock_arrival_columns``), and the phase-diagram arithmetic copies
``benchmarks/sweep.py`` (``_scenario_feats``, ``_phase_cells``). ``bench/tests`` pins the copies to the originals. They
take only registry ids and the default contention coefficients from the
program, through :mod:`bench.entry`.

Every sweep of a cell runs the same scenario pool, the configuration's
``pool_seed`` draw of ``sample_scenarios``: a run's ``--seed`` and the
sweep's index choose the order of the scenarios and the simulation seed
of each, so every seed offers the same amount of work.
"""

from __future__ import annotations

import numpy as np

from bench import entry


# -- copies of repro.configs.catalog -------------------------------------------
def sample_scenarios(n_scenarios: int, seed: int = 0) -> list[dict]:
    """Draw ``n_scenarios`` random machines/workloads: CS/NCS lengths
    log-uniform across the paper's two regimes, wake latency from
    fast-futex to slow-scheduler, contention from none to 4x the paper's
    default, over- as well as under-subscribed machines."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_scenarios):
        out.append(dict(
            threads=int(rng.integers(2, 33)),
            cores=int(rng.integers(2, 33)),
            cs_hi=float(np.exp(rng.uniform(np.log(1e-6), np.log(4e-4)))),
            ncs_hi=float(np.exp(rng.uniform(np.log(1e-6), np.log(4e-4)))),
            wake=float(np.exp(rng.uniform(np.log(2e-6), np.log(5e-5)))),
            contention=float(rng.uniform(0.0, 4.0)),
            seed=i,
        ))
    return out


def sample_scenario_columns(n_scenarios: int, seed: int = 0) -> dict:
    """:func:`sample_scenarios` packed as (S,) column arrays."""
    sc = sample_scenarios(n_scenarios, seed)
    return {k: np.asarray([s[k] for s in sc],
                          np.int64 if k in ("threads", "cores", "seed")
                          else np.float64)
            for k in ("threads", "cores", "cs_hi", "ncs_hi", "wake",
                      "contention", "seed")}


def lock_discipline_variants(disciplines, oracles) -> list[dict]:
    """The ``(discipline, oracle)`` variant axis: only windowed
    disciplines read the oracle column, so the others appear once."""
    out = []
    for d in disciplines:
        fams = oracles if entry.windowed(d) else oracles[:1]
        for o in fams:
            out.append(dict(lock=d, oracle=o))
    return out


def lock_arrival_variants(arrivals, rhos, disciplines, oracles) -> list[dict]:
    """The ``(arrival, rho, discipline, oracle)`` variant axis,
    arrival-major then rho."""
    return [dict(arrival=a, rho=r, **v)
            for a in arrivals
            for r in rhos
            for v in lock_discipline_variants(disciplines, oracles)]


def _product_columns(sc: dict, variants: list[dict],
                     wl: dict | None = None) -> dict:
    """Scenario-major x variant-minor product as RAW columns, with
    ``alpha = contention x DEFAULT_ALPHA[lock]`` per row."""
    S, V = len(sc["seed"]), len(variants)
    rep = lambda a, dt: np.repeat(np.asarray(a, dt), V)  # noqa: E731
    tile = lambda a: np.tile(a, S)  # noqa: E731
    lock_names = [v.get("lock", "mutable") for v in variants]
    wl = wl or {}
    wlcol = lambda key, dflt: (rep(wl[key], np.float64) if key in wl  # noqa
                               else np.full(S * V, dflt, np.float64))
    return {
        "lock": tile(np.asarray([entry.POLICY_IDS[n] for n in lock_names],
                                np.int32)),
        "threads": rep(sc["threads"], np.int32),
        "cores": rep(sc["cores"], np.int32),
        "cs_lo": np.zeros(S * V, np.float64),
        "cs_hi": rep(sc["cs_hi"], np.float64),
        "ncs_lo": np.zeros(S * V, np.float64),
        "ncs_hi": rep(sc["ncs_hi"], np.float64),
        "wake_latency": rep(sc["wake"], np.float64),
        "alpha": rep(sc["contention"], np.float64)
        * tile(np.asarray([entry.DEFAULT_ALPHA[n] for n in lock_names],
                          np.float64)),
        "sws_init": np.ones(S * V, np.int32),
        "sws_max": tile(np.asarray(
            [-1 if v.get("sws_max") is None else v["sws_max"]
             for v in variants], np.int32)),
        "k": tile(np.asarray([v.get("k", 10) for v in variants],
                             np.int32)),
        "spin_budget": np.full(S * V, entry.DEFAULT_SPIN_BUDGET, np.float64),
        "seed": rep(sc["seed"], np.uint32),
        "oracle": tile(np.asarray(
            [entry.ORACLE_IDS[v.get("oracle", "paper")] for v in variants],
            np.int32)),
        "workload": tile(np.asarray(
            [entry.WORKLOAD_IDS[v.get("workload", "constant")]
             for v in variants], np.int32)),
        "wl_period": wlcol("wl_period", 1e-4),
        "wl_duty": wlcol("wl_duty", 0.25),
        "wl_burst": wlcol("wl_burst", 8.0),
        "wl_spread": wlcol("wl_spread", 4.0),
        "arrival_phase": np.zeros(S * V, np.float64),
    }


def arrival_columns(sc: dict, variants: list[dict]) -> dict:
    """The body of ``catalog.lock_arrival_columns`` after its scenario
    draw: burst-gate knobs, the closed-form capacity each ``rho`` scales,
    the queue cap and the SLO as columns."""
    S = len(sc["seed"])
    V = len(variants)
    wl = dict(wl_period=16.0 * (sc["cs_hi"] + sc["ncs_hi"]),
              wl_duty=np.full(S, 0.25), wl_burst=np.full(S, 8.0),
              wl_spread=np.full(S, 4.0))
    cols = _product_columns(sc, variants, wl)
    mean_cs = 0.5 * sc["cs_hi"]
    mean_round = 0.5 * (sc["cs_hi"] + sc["ncs_hi"])
    eff = np.minimum(sc["threads"], sc["cores"]).astype(np.float64)
    cap = np.minimum(1.0 / np.maximum(mean_cs, 1e-12),
                     eff / np.maximum(mean_round, 1e-12))
    cols["arrival"] = np.tile(np.asarray(
        [entry.ARRIVAL_IDS[v["arrival"]] for v in variants], np.int32), S)
    cols["arrival_rate"] = (
        np.tile(np.asarray([v["rho"] for v in variants], np.float64), S)
        * np.repeat(cap, V))
    cols["queue_cap"] = np.full(S * V, entry.QUEUE_MAX, np.int32)
    cols["slo"] = np.repeat(4.0 * (sc["cs_hi"] + sc["ncs_hi"]), V)
    cols["tie_break"] = np.zeros(S * V, np.int32)
    return cols


# -- copies of benchmarks/sweep.py ------------------------------------------------
def _scenario_feats(sc_cols: dict) -> list[dict]:
    """Coarse workload features per scenario: the phase-diagram axes."""
    return [{
        "cs": "short" if cs <= 1e-5 else "mid" if cs <= 1e-4 else "long",
        "sub": "under" if th <= co else "over",
        "wake": "fast" if wk <= 1e-5 else "slow",
    } for th, co, cs, wk in zip(sc_cols["threads"], sc_cols["cores"],
                                sc_cols["cs_hi"], sc_cols["wake"])]


def _phase_cells(keys: list[tuple]) -> tuple[list[tuple], np.ndarray]:
    """Order the distinct phase-cell keys and map each reduction group to
    its cell id."""
    uniq = sorted(set(keys))
    kid = {k: i for i, k in enumerate(uniq)}
    return uniq, np.asarray([kid[k] for k in keys], np.int32)


# -- the generator ------------------------------------------------------------------
def sweep(config: dict, traffic: dict, seed: int, k: int) -> dict:
    """Sweep ``k`` of a run with ``--seed seed``: the pool's scenarios in
    a seeded order with seeded simulation seeds, times the variants."""
    S = int(traffic["scenarios"])
    pool = sample_scenario_columns(S, int(config["pool_seed"]))
    rng = np.random.default_rng([int(seed), int(k)])
    order = rng.permutation(S)
    sc = {key: v[order] for key, v in pool.items()}
    sc["seed"] = rng.integers(0, 2**32, S, dtype=np.int64)
    disc = lock_discipline_variants(config["disciplines"], config["oracles"])
    arrivals = traffic.get("arrivals", [])
    if arrivals:
        rhos = traffic["rhos"]
        variants = lock_arrival_variants(arrivals, rhos,
                                         config["disciplines"],
                                         config["oracles"])
        cols = arrival_columns(sc, variants)
        n_cells = len(arrivals) * len(rhos)
        cell_ids = np.tile(np.arange(n_cells, dtype=np.int32), S)
        cells = [f"{a}/rho={r}" for a in arrivals for r in rhos]
    else:
        variants = disc
        cols = _product_columns(sc, variants)
        feats = _scenario_feats(sc)
        uniq, cell_ids = _phase_cells(
            [(f["cs"], f["sub"], f["wake"]) for f in feats])
        n_cells = len(uniq)
        cells = ["/".join(u) for u in uniq]
    S_V = S * len(variants)
    names = {
        "lock": np.tile(np.asarray([v["lock"] for v in variants]), S),
        "oracle": np.tile(np.asarray([v["oracle"] for v in variants]), S),
        "arrival": np.tile(np.asarray([v.get("arrival", "closed")
                                       for v in variants]), S),
        "workload": np.full(S_V, "constant"),
        "fault": np.full(S_V, "none"),
    }
    return {"cols": cols, "names": names, "alpha": cols["alpha"],
            "variants": [entry_name(v) for v in disc],
            "reduce": {"group": len(disc), "cell_ids": cell_ids,
                       "n_cells": n_cells},
            "cells": cells}


def entry_name(v: dict) -> str:
    """Display name of a (discipline, oracle) variant."""
    return f"{v['lock']}/{v['oracle']}" if entry.windowed(v["lock"]) \
        else v["lock"]


def latency_percentiles(hist, q: float) -> np.ndarray:
    """Per-config latency quantile from the 64-bin, 2-per-octave latency
    histogram starting at 1e-7 s (a copy of
    ``repro.core.policy.latency_percentiles``): the geometric midpoint of
    the bin holding the quantile, NaN where nothing departed."""
    hist = np.asarray(hist, np.int64)
    edges = 1e-7 * 2.0 ** (np.arange(hist.shape[-1] + 1, dtype=np.float64)
                           / 2)
    mids = np.sqrt(edges[:-1] * edges[1:])
    tot = hist.sum(axis=-1)
    cum = np.cumsum(hist, axis=-1)
    target = np.ceil(q * np.maximum(tot, 1)).astype(np.int64)[..., None]
    idx = np.argmax(cum >= target, axis=-1)
    return np.where(tot > 0, mids[idx], np.nan)


def diagram(sw: dict, res) -> dict:
    """The phase diagram users read, from one sweep's results: per cell,
    the variant that wins most often, and per variant the mean ratio of
    its throughput to the best of its scenario (and, open loop, the median
    p95 latency over scenarios)."""
    V = sw["reduce"]["group"]
    thr = np.asarray(res.completed, np.float64) / np.maximum(
        np.asarray(res.t_end, np.float64), 1e-30)
    thr = thr.reshape(-1, V)
    ratio = thr / np.maximum(thr.max(axis=1, keepdims=True), 1e-30)
    wins = np.asarray(res.wins)
    out = {"winner": {c: sw["variants"][int(np.argmax(wins[i]))]
                      for i, c in enumerate(sw["cells"])},
           "mean_ratio_to_best": dict(zip(sw["variants"],
                                          ratio.mean(axis=0).tolist()))}
    if res.lat_hist is not None:
        p95 = latency_percentiles(res.lat_hist, 0.95).reshape(-1, V)
        out["median_p95_s"] = dict(zip(sw["variants"],
                                       np.nanmedian(p95, axis=0).tolist()))
    return out
