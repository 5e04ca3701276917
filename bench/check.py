"""How ``correct`` is decided: the timed sweeps' own results against the
plain reference, and the program's counts against the guarantees its
configuration states.

* ``sample`` configurations are drawn from the seed out of every sweep
  of the window, plus the one that simulated the longest time. The
  configuration's reference (``reference/<name>.py``, its ``run_spec``;
  :mod:`bench.reference.lockdes`, event by event on the host, where the
  configuration names none) runs each to ``reference_cs`` critical
  sections; an open-loop configuration runs for as long as the program
  simulated it, its ``steps_run`` times the time step its configuration
  states (the reference checks the program's answer for the length the
  program served, as a served model's reference scores the tokens the
  model served).
* For each quantity -- ``thr`` (completed critical sections per
  simulated second), ``spin`` (spin CPU as a share of the machine's CPU
  time), ``wake`` (wake-ups per critical section) and, on open-loop rows,
  ``lat`` (mean request latency) and ``p95`` (95th percentile latency,
  both sides read in the program's histogram bins) -- a row's gap is
  ``min(|ln(program / reference)|, 1)`` (``spin``: the absolute
  difference of the shares) and its signed error the same unclipped in
  sign. ``<q>_gap`` and ``<q>_bias`` are the mean gap and the size of the
  mean signed error over all sampled rows; ``<q>_gap_max`` and
  ``<q>_bias_max`` the largest of these over the disciplines, so that
  one discipline's wrong answers cannot hide among the others'.
* ``t_end_mismatch``: configurations of every sweep whose simulated time
  is not ``steps_run`` times the stated time step, ``min(mean CS, wake
  latency x park cost) / 6`` in float32. Exact.
* ``conservation``: open-loop configurations of every sweep whose
  request counts do not add up: ``arrived = departed + shed +
  in_flight``, ``departed = completed =`` the latencies recorded, and
  ``0 <= in_flight <= queue_cap + threads``. Exact.
* ``wins_mismatch``: the on-device ``CellReduce`` win table against the
  same table counted on the host from the program's per-config columns:
  groups whose winner differs, less the groups whose best two variants
  tie to within float32 rounding. Exact.

Each limited number is printed with its limit; the run is correct when
every one is within its limit.
"""

from __future__ import annotations

import math
import multiprocessing as mp
from collections import defaultdict

import numpy as np

from bench.harness import ROOT, load_module

#: Values the reference takes for RAW columns a generator leaves out (the
#: program's defaults for them).
_DEFAULTS = {"arrival_rate": 0.0, "queue_cap": 128, "slo": 1e-3,
             "fault_rate": 0.0, "fault_scale": 5e-5, "park_cost": 1.0}
_SPEC_COLS = ("threads", "cores", "cs_lo", "cs_hi", "ncs_lo", "ncs_hi",
              "wake_latency", "sws_init", "sws_max", "k", "spin_budget",
              "wl_period", "wl_duty", "wl_burst", "wl_spread",
              "arrival_phase", "arrival_rate", "queue_cap", "slo",
              "fault_rate", "fault_scale", "park_cost")
#: Relative gap under which two float32 throughputs count as tied.
TIE_RTOL = 1e-6
#: The latency histogram's bins: ``LAT_NBINS`` bins, ``LAT_PER_OCTAVE`` per
#: factor of two from ``LAT_BIN0`` seconds; the end bins absorb what lies
#: outside.
LAT_NBINS, LAT_PER_OCTAVE, LAT_BIN0 = 64, 2, 1e-7
#: ``(name, row key, kind, open-loop rows only)`` of each quantity
#: compared: ``log`` compares ``ln(program / reference)``, ``abs`` the
#: difference.
QUANTITIES = (("thr", "throughput", "log", False),
              ("spin", "spin_share", "abs", False),
              ("wake", "wakes_per_cs", "log", False),
              ("lat", "mean_latency", "log", True),
              ("p95", "p95_latency", "log", True))


def _col(cols: dict, name: str, n: int) -> np.ndarray:
    return (np.asarray(cols[name], np.float64) if name in cols
            else np.full(n, _DEFAULTS[name], np.float64))


def stated_dt(cols: dict) -> np.ndarray:
    """The time step each configuration states, as the program holds it
    (float32): ``min(mean CS, wake latency x park cost) / 6``."""
    n = len(cols["cs_hi"])
    cs = (np.asarray(cols["cs_lo"], np.float64)
          + np.asarray(cols["cs_hi"], np.float64)) / 2.0
    wake = (np.asarray(cols["wake_latency"], np.float64)
            * _col(cols, "park_cost", n))
    return (np.minimum(np.maximum(cs, 1e-8), np.maximum(wake, 1e-8))
            / 6.0).astype(np.float32)


def reference_spec(sw: dict, res, i: int, seed: int, dt) -> dict:
    """Row ``i`` of a sweep as the reference takes it: by name and value,
    with its own simulation seed. An open-loop row also takes the length
    the program simulated, its step count times the stated time step
    ``dt``: its queue, and so its latency, depends on how long arrivals
    ran. Columns the sweep names under ``reference_cols`` come by name
    too, one value per row: a vector row as a list."""
    cols = sw["cols"]
    spec = {c: (cols[c][i].item() if c in cols else _DEFAULTS[c])
            for c in _SPEC_COLS}
    spec.update({c: np.asarray(cols[c])[i].tolist()
                 for c in sw.get("reference_cols", ())})
    spec.update({k: str(v[i]) for k, v in sw["names"].items()})
    spec["alpha"] = float(sw["alpha"][i])
    spec["seed"] = int(seed)
    if spec["arrival"] != "closed":
        spec["horizon"] = float(np.float32(res.steps_run[i]) * dt[i])
    return spec


def sample_rows(sweeps: list, n: int, seed: int) -> list:
    """``n`` (sweep, row) pairs drawn from the seed out of every sweep,
    plus the row that simulated the longest."""
    offs = np.cumsum([0] + [len(s["res"].completed) for s in sweeps])
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    flat = rng.choice(offs[-1], size=min(n, int(offs[-1])),
                      replace=False).tolist()
    t_end = np.concatenate([np.asarray(s["res"].t_end, np.float64)
                            for s in sweeps])
    flat.append(int(np.argmax(t_end)))
    pairs = set()
    for f in flat:
        j = int(np.searchsorted(offs, f, side="right") - 1)
        pairs.add((j, int(f - offs[j])))
    return sorted(pairs)


def _run_one(args):
    root, reference, spec, target = args
    return load_module("reference", reference, root).run_spec(spec, target)


def run_reference(specs: list[dict], target_cs: int, workers: int,
                  reference: str, root: str) -> list[dict]:
    """The reference ``reference/<reference>.py`` under ``root`` over
    every spec, in ``workers`` fresh processes that import nothing of the
    program and never touch the device."""
    ctx = mp.get_context("spawn")
    with ctx.Pool(workers) as pool:
        return pool.map(_run_one, [(root, reference, s, target_cs)
                                   for s in specs], chunksize=1)


def bin_mid(latency: float) -> float:
    """The geometric midpoint of the histogram bin holding ``latency``;
    NaN for NaN."""
    if not math.isfinite(latency):
        return float("nan")
    b = math.floor(LAT_PER_OCTAVE * math.log2(max(latency, 1e-300)
                                              / LAT_BIN0))
    return _mid(min(max(b, 0), LAT_NBINS - 1))


def _mid(b: int) -> float:
    return LAT_BIN0 * 2.0 ** ((b + 0.5) / LAT_PER_OCTAVE)


def hist_quantile(hist, q: float) -> float:
    """Nearest-rank quantile of one configuration's latency histogram, as
    its bin's midpoint; NaN where nothing departed."""
    hist = np.asarray(hist, np.int64)
    tot = int(hist.sum())
    if tot <= 0:
        return float("nan")
    rank = math.ceil(q * tot)
    return _mid(int(np.argmax(np.cumsum(hist) >= rank)))


def _shares(spin_cpu, t_end, threads, cores) -> float:
    eff = min(int(threads), int(cores))
    return (float(spin_cpu) / (float(t_end) * eff) if t_end > 0
            else float("nan"))


def program_row(sw: dict, res, i: int) -> dict:
    """One configuration's answer as the program gave it."""
    cols = sw["cols"]
    completed = float(res.completed[i])
    t_end = float(res.t_end[i])
    row = {"throughput": completed / t_end if t_end > 0 else 0.0,
           "spin_share": _shares(res.spin_cpu[i], t_end, cols["threads"][i],
                                 cols["cores"][i]),
           "wakes_per_cs": float(res.wake_count[i]) / max(completed, 1.0)}
    if res.lat_hist is not None:
        dep = float(res.departed[i])
        row["mean_latency"] = (float(res.lat_sum[i]) / dep if dep > 0
                               else float("nan"))
        row["p95_latency"] = hist_quantile(res.lat_hist[i], 0.95)
    return row


def reference_row(ref: dict, spec: dict) -> dict:
    """The reference's answer for the same configuration, read as
    :func:`program_row` reads the program's."""
    return {"throughput": float(ref["throughput"]),
            "spin_share": _shares(ref["spin_cpu"], ref["t_end"],
                                  spec["threads"], spec["cores"]),
            "wakes_per_cs": float(ref["wake_count"])
            / max(float(ref["completed"]), 1.0),
            "mean_latency": float(ref["mean_latency"]),
            "p95_latency": bin_mid(float(ref["p95_latency"]))}


def _good(x: float) -> bool:
    return x > 0 and math.isfinite(x)


def row_error(prog: float, ref: float, kind: str) -> tuple[float, float]:
    """``(gap, signed error)`` of one row. ``log``: ``ln(prog / ref)``
    clipped to [-1, 1], 0 where both sides are equal or neither is a
    positive finite number, -1 (1) where only the reference's (program's)
    is. ``abs``: ``prog - ref`` clipped likewise, 1 where either is not
    finite."""
    if kind == "abs":
        if not (math.isfinite(prog) and math.isfinite(ref)):
            return 1.0, 1.0
        d = max(-1.0, min(1.0, prog - ref))
        return abs(d), d
    if prog == ref or not (_good(prog) or _good(ref)):
        return 0.0, 0.0
    if not _good(prog):
        return 1.0, -1.0
    if not _good(ref):
        return 1.0, 1.0
    d = max(-1.0, min(1.0, math.log(prog / ref)))
    return abs(d), d


def gaps(prog_rows: list[dict], ref_rows: list[dict], locks: list[str],
         open_loop: list[bool]) -> dict:
    """Per quantity, over all sampled rows and per discipline: the mean
    gap (``*_gap``, ``*_gap_max``) and the size of the mean signed error
    (``*_bias``, ``*_bias_max``), which averages single configurations'
    sampling noise away and keeps a systematic error."""
    out = {}
    for name, key, kind, only_open in QUANTITIES:
        per: dict = defaultdict(list)
        for p, r, lock, o in zip(prog_rows, ref_rows, locks, open_loop):
            if o or not only_open:
                per[lock].append(row_error(p[key], r[key], kind))
        if not per:
            continue
        every = [e for rows in per.values() for e in rows]
        out[name + "_gap"] = float(np.mean([g for g, _ in every]))
        out[name + "_bias"] = float(abs(np.mean([s for _, s in every])))
        out[name + "_gap_max"] = float(max(
            np.mean([g for g, _ in rows]) for rows in per.values()))
        out[name + "_bias_max"] = float(max(
            abs(np.mean([s for _, s in rows])) for rows in per.values()))
    return out


def t_end_mismatch(sw: dict, res) -> int:
    """Configurations whose simulated time is not their step count times
    the stated time step."""
    want = np.asarray(res.steps_run).astype(np.float32) * stated_dt(sw["cols"])
    got = np.asarray(res.t_end, np.float32)
    ok = np.abs(got - want) <= 1e-6 * np.abs(want)
    return int(np.sum(~ok))


def conservation(sw: dict, res) -> int:
    """Open-loop configurations whose request counts do not add up (0 for
    a closed-loop sweep)."""
    if res.lat_hist is None:
        return 0
    arr = np.asarray(res.arrived, np.int64)
    dep = np.asarray(res.departed, np.int64)
    shed = np.asarray(res.shed, np.int64)
    infl = np.asarray(res.in_flight, np.int64)
    cap = (np.asarray(sw["cols"]["queue_cap"], np.int64)
           + np.asarray(sw["cols"]["threads"], np.int64))
    ok = ((arr == dep + shed + infl)
          & (dep == np.asarray(res.completed, np.int64))
          & (dep == np.asarray(res.lat_hist, np.int64).sum(axis=1))
          & (infl >= 0) & (infl <= cap))
    return int(np.sum(~ok))


def wins_mismatch(sw: dict, res) -> int:
    """Groups whose on-device winner differs from the host's, less the
    host's float32 near-ties."""
    red = sw["reduce"]
    group, n_cells = red["group"], red["n_cells"]
    thr = (np.asarray(res.completed).astype(np.float32)
           / np.maximum(np.asarray(res.t_end, np.float32),
                        np.float32(1e-30))).reshape(-1, group)
    host = np.zeros((n_cells, group), np.int64)
    np.add.at(host, (np.asarray(red["cell_ids"]), thr.argmax(axis=1)), 1)
    top2 = np.sort(thr, axis=1)[:, -2:] if group > 1 else None
    ties = 0 if top2 is None else int(np.sum(
        top2[:, 1] - top2[:, 0] <= TIE_RTOL * np.abs(top2[:, 1])))
    moved = int(np.abs(host - np.asarray(res.wins, np.int64)).sum()) // 2
    return max(0, moved - ties)


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limited number against its limit: ``(correct, {name:
    {value, limit}})``. A limit without a number is a fault of the
    harness."""
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"limits {sorted(missing)} have no number")
    table = {k: {"value": numbers[k], "limit": limits[k]}
             for k in sorted(limits)}
    ok = all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
             for k in limits)
    return ok, table


def compare(sweeps: list, seed: int, params: dict,
            rows: list | None = None, *, reference: str = "lockdes",
            root: str = ROOT) -> dict:
    """All numbers of a run: the sampled comparison with the reference
    ``reference/<reference>.py`` under ``root``, the exact checks over
    every configuration and, where the cell reduces on device, the win
    tables of every sweep. ``rows``, where given, gets each sampled row's
    readings of both sides."""
    pairs = sample_rows(sweeps, int(params["sample"]), seed)
    dts = [stated_dt(s["sw"]["cols"]) for s in sweeps]
    rng = np.random.default_rng([int(seed), 0x5EED])
    ref_seeds = rng.integers(0, 2**32, len(pairs), dtype=np.int64)
    specs = [reference_spec(sweeps[j]["sw"], sweeps[j]["res"], i, s, dts[j])
             for (j, i), s in zip(pairs, ref_seeds)]
    ref = run_reference(specs, int(params["reference_cs"]),
                        int(params["workers"]), reference, root)
    prog = [program_row(sweeps[j]["sw"], sweeps[j]["res"], i)
            for j, i in pairs]
    refr = [reference_row(r, s) for r, s in zip(ref, specs)]
    locks = [s["lock"] for s in specs]
    numbers = gaps(prog, refr, locks, [s["arrival"] != "closed"
                                       for s in specs])
    numbers["t_end_mismatch"] = float(sum(
        t_end_mismatch(s["sw"], s["res"]) for s in sweeps))
    if sweeps[0]["res"].lat_hist is not None:
        numbers["conservation"] = float(sum(
            conservation(s["sw"], s["res"]) for s in sweeps))
    if sweeps[0]["sw"]["reduce"] is not None:
        numbers["wins_mismatch"] = float(sum(
            wins_mismatch(s["sw"], s["res"]) for s in sweeps))
    if rows is not None:
        rows.extend({"lock": lk, "arrival": s["arrival"],
                     "rate": s["arrival_rate"], "threads": s["threads"],
                     "program": p, "reference": r}
                    for lk, s, p, r in zip(locks, specs, prog, refr))
    return numbers
