"""``correct`` comes out false for the control and for each fault the
cells can have, through the rest of a real run (the look for a chip
skipped, tiny sizes on the CPU). The limits are the cells' own."""

import argparse
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import check, entry, harness


def _cell(name, scenarios=2):
    cell = harness.load_cell(name)
    key = "scenarios" if "scenarios" in cell["traffic"] else "replicas"
    cell["traffic"][key] = scenarios
    cell["limits"]["sample"] = 32
    cell["limits"]["workers"] = 2
    return cell


def _run(name, run_sweep, seed=2**31 + 99):
    args = argparse.Namespace(workload=name, seed=seed, seconds=0.0,
                              trace=0)
    return harness.run(args, time.monotonic(), require_chip=False,
                       run_sweep=run_sweep, cell=_cell(name))


def _state_unchanged(cols, **kw):
    res = entry.run_sweep(cols, **kw)
    res.completed[:] = 0
    res.steps_run[:] = 0
    return res


def _half_left_out(cols, **kw):
    res = entry.run_sweep(cols, **kw)
    n = len(res.completed) // 2
    for f in ("completed", "t_end", "spin_cpu", "wake_count"):
        getattr(res, f)[n:] = getattr(res, f)[:len(res.completed) - n]
    return res


def _answer_altered(cols, **kw):
    res = entry.run_sweep(cols, **kw)
    res.completed[:] = res.completed * 3 // 2
    return res


def _control(cols, *, target_cs, **kw):
    dt = entry.planned_dt(cols, target_cs) * 8.0
    return entry.run_sweep(cols, target_cs=target_cs, dt=dt, **kw)


def _rows_of(cols, lock):
    return np.asarray(cols["lock"]) == entry.POLICY_IDS[lock]


def _one_discipline_altered(cols, **kw):
    """One discipline's answers twice too high, the others' sound: 1/15
    of the design-space rows, 1/5 of Fig. 3's."""
    res = entry.run_sweep(cols, **kw)
    rows = _rows_of(cols, "adaptive")
    res.completed[rows] = res.completed[rows] * 2
    if res.lat_hist is not None:
        res.departed[rows] = res.completed[rows]
        res.arrived[rows] = (res.departed[rows] + res.shed[rows]
                             + res.in_flight[rows])
        res.lat_hist[rows, 0] += res.departed[rows] - res.lat_hist[
            rows].sum(axis=1)
    return res


def _one_discipline_latency_altered(cols, **kw):
    """One discipline's request latencies one and a half times too long,
    every count left consistent."""
    res = entry.run_sweep(cols, **kw)
    rows = _rows_of(cols, "adaptive")
    res.lat_sum[rows] = res.lat_sum[rows] * 1.5
    res.lat_hist[rows] = np.roll(res.lat_hist[rows], 1, axis=1)
    return res


CELLS = ["design_space.closed", "fig3_paper.closed", "design_space.open"]
FAULTS = {"state_unchanged": _state_unchanged,
          "half_left_out": _half_left_out,
          "answer_altered": _answer_altered,
          "control_dt_x8": _control}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(name, fault):
    out = _run(name, FAULTS[fault])
    assert out["correct"] is False, out["check"]


@pytest.mark.parametrize("name", CELLS)
def test_one_discipline_altered_fails_its_own_number(name):
    # 1/15 (1/5) of the rows off by ln(2): the pooled bias hardly moves,
    # the discipline's own does
    out = _run(name, _one_discipline_altered)
    assert out["correct"] is False, out["check"]
    row = out["check"]["thr_bias_max"]
    assert row["value"] > row["limit"], out["check"]


def test_one_discipline_latency_altered_shows_in_its_own_number():
    # read, not limited: its limit waits for readings on the chip (PERF.md)
    cell = _cell("design_space.open")
    cfg, traffic, gen = cell["config"], cell["traffic"], cell["generator"]
    seed = 2**31 + 99

    def numbers(fn):
        sweeps = [harness.one_sweep(gen, cfg, traffic, seed, 0, fn)]
        return check.compare(sweeps, seed, cell["limits"])

    sound, bad = numbers(entry.run_sweep), numbers(
        _one_discipline_latency_altered)
    assert bad["conservation"] == 0
    assert bad["lat_bias_max"] - sound["lat_bias_max"] > 0.25
    assert bad["lat_bias"] - sound["lat_bias"] < 0.1


def test_row_errors_clip():
    log = lambda p, r: check.row_error(p, r, "log")  # noqa: E731
    assert log(1.0, 1.0) == (0.0, 0.0)
    assert log(0.0, 1.0) == (1.0, -1.0)
    assert log(float("nan"), 1.0) == (1.0, -1.0)
    assert log(100.0, 1.0) == (1.0, 1.0)
    assert log(2.0, 1.0) == pytest.approx((np.log(2.0), np.log(2.0)))
    assert log(0.0, 0.0) == (0.0, 0.0)
    assert check.row_error(0.3, 0.1, "abs") == pytest.approx((0.2, 0.2))


def test_one_discipline_off_shows_in_its_own_numbers():
    # nine disciplines agree exactly; the tenth reads twice the reference
    locks = [f"d{k}" for k in range(10) for _ in range(10)]
    ref = [{"throughput": 1.0, "spin_share": 0.0, "wakes_per_cs": 0.0}
           for _ in locks]
    prog = [dict(r, throughput=2.0 if lk == "d9" else 1.0)
            for r, lk in zip(ref, locks)]
    g = check.gaps(prog, ref, locks, [False] * len(locks))
    assert g["thr_bias"] == pytest.approx(np.log(2.0) / 10)
    assert g["thr_bias_max"] == pytest.approx(np.log(2.0))
    assert g["thr_gap_max"] == pytest.approx(np.log(2.0))
    assert g["spin_gap_max"] == 0.0 and "lat_gap" not in g


def test_sampled_rows_hold_the_longest():
    sweeps = [{"res": SimpleNamespace(completed=np.zeros(100),
                                      t_end=np.arange(100.0) * (k + 1))}
              for k in range(2)]
    pairs = check.sample_rows(sweeps, 8, seed=5)
    assert (1, 99) in pairs and len(pairs) in (8, 9)
    assert pairs == check.sample_rows(sweeps, 8, seed=5)


def test_verdict_needs_every_limited_number():
    ok, table = check.verdict({"a": 0.1, "b": 3.0}, {"a": 0.2})
    assert ok and list(table) == ["a"]
    assert not check.verdict({"a": float("nan")}, {"a": 0.2})[0]
    with pytest.raises(KeyError):
        check.verdict({"a": 0.1}, {"b": 0.2})


def test_histogram_and_exact_p95_read_the_same_bin():
    rng = np.random.default_rng(3)
    lat = np.exp(rng.uniform(np.log(2e-7), np.log(1e-2), 997))
    bins = np.clip(np.floor(2 * np.log2(lat / 1e-7)), 0, 63).astype(int)
    hist = np.bincount(bins, minlength=64)
    exact = np.sort(lat)[int(np.ceil(0.95 * len(lat))) - 1]
    assert check.hist_quantile(hist, 0.95) == check.bin_mid(exact)
    assert np.isnan(check.hist_quantile(np.zeros(64), 0.95))
