"""The window rule, the metric arithmetic, and the data-driven layout:
every cell resolves to its files, and a new cell is files plus one
entry."""

import json
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness

ROOT = harness.ROOT


def _fake_res(completed, steps=100):
    completed = np.asarray(completed, np.int32)
    return SimpleNamespace(completed=completed,
                           t_end=np.ones(len(completed), np.float32),
                           steps_run=np.full(len(completed), steps, np.int32),
                           failures=[])


class _Gen:
    def sweep(self, cfg, traffic, seed, k):
        return {"cols": {"lock": np.zeros(10)}, "reduce": None}

    def diagram(self, sw, res):
        return {}


def test_window_closes_on_the_first_sweep_ending_past_the_length():
    ticks = iter([0.0, 3.0, 6.0, 9.5, 12.0, 99.0])
    calls = []

    def run_sweep(cols, **kw):
        calls.append(1)
        return _fake_res([5] * 10)

    win = harness.window(_Gen(), {"target_cs": 5}, {"max_threads": 4},
                         seed=1, seconds=10.0, run_sweep=run_sweep,
                         clock=lambda: next(ticks))
    assert len(calls) == 4 and len(win["sweeps"]) == 4
    assert win["window_s"] == 12.0 and win["attempted"] == 40
    assert harness.configs_per_s(win) == pytest.approx(40 / 12.0)


def test_a_sweep_that_raises_counts_as_failed():
    ticks = iter([0.0, 1.0, 2.0, 11.0])
    n = iter(range(10))

    def run_sweep(cols, **kw):
        if next(n) == 1:
            raise RuntimeError("device lost")
        return _fake_res([5] * 10)

    win = harness.window(_Gen(), {"target_cs": 5}, {"max_threads": 4},
                         seed=1, seconds=10.0, run_sweep=run_sweep,
                         clock=lambda: next(ticks))
    assert win["attempted"] == 30 and win["failed"] == 10
    assert len(win["sweeps"]) == 2


def test_useful_share_and_ns_per_step_arithmetic():
    sweeps = [{"res": _fake_res([50, 200, 10], steps=1000)},
              {"res": _fake_res([100], steps=1000)}]
    rec = {"trace": {"busy_ns_total": 4e6, "n_devices": 1,
                     "window_ns": 1e7, "busy_ns": 4e6},
           "sweeps": sweeps, "target_cs": 50}
    assert harness.metric_reader("useful_cs_share")(rec) == pytest.approx(
        100.0 * (50 + 50 + 10 + 50) / 360)
    assert harness.metric_reader("device_ns_per_config_step")(rec) \
        == pytest.approx(4e6 / 4000)
    assert harness.metric_reader("device_idle_share")(rec) \
        == pytest.approx(60.0)
    assert harness.metric_reader("device_ns_per_config_step")(
        dict(rec, trace=None)) is None


def test_every_cell_resolves_to_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert hasattr(cell["generator"], "sweep")
        assert hasattr(cell["generator"], "diagram")
        assert {m["name"] for m in cell["end_to_end"]} \
            == {"setup_s", "configs_per_s"}
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(harness.metric_reader(m["name"]))
        assert cell["limits"]["limits"]


def test_sweep_sizes_are_the_configurations_own():
    # a cut of scale stated in a configuration file is the size its
    # cells' generators make
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        sizes = cell["config"].get("sweep_configs", {})
        if w["traffic"] in sizes:
            sw = cell["generator"].sweep(cell["config"], cell["traffic"],
                                         2**31 + 5, 0)
            assert len(sw["cols"]["lock"]) == sizes[w["traffic"]]


def test_a_new_cell_is_files_plus_one_entry(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (root / "bench" / "traffic" / "closed_64.json").write_text(json.dumps(
        {"scenarios": 64, "max_threads": 32, "why": "a smaller sweep"}))
    (root / "bench" / "limits" / "design_space.closed64.json").write_text(
        (root / "bench" / "limits" / "design_space.closed.json").read_text())
    bench["workloads"].append(
        {"name": "design_space.closed64", "config": "design_space",
         "traffic": "closed_64", "chips": 1, "why": "a smaller sweep"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("design_space.closed64", root=str(root))
    assert cell["traffic"]["scenarios"] == 64
    sw = cell["generator"].sweep(cell["config"], cell["traffic"], 3, 0)
    assert len(sw["cols"]["lock"]) == 64 * 15


def test_benchmark_json_keeps_to_its_shape():
    import re

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(name.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.exists(os.path.join(ROOT, c["file"]))
