"""A configuration that brings its own generator and plain reference is
files plus entries: in a copy of the checkout, a new configuration whose
sweeps hand the reference a vector column and the program a static
keyword runs through the rest of a real run (the look for a chip skipped,
a stand-in program entry, tiny sizes on the CPU) with no file that was
already there edited. Today's configurations, which name no reference
and whose sweeps carry neither key, reach the reference and the program
exactly as before."""

import argparse
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import check, entry, harness

ROOT = harness.ROOT
SEED = 2**31 + 17

GENERATOR = '''"""The design space with a per-row stripe distribution for the
reference and a stripe count for the program."""

import numpy as np

from bench.generators import design_space as base

diagram = base.diagram


def sweep(config, traffic, seed, k):
    sw = base.sweep(config, traffic, seed, k)
    n = len(sw["cols"]["lock"])
    i = np.arange(n, dtype=np.float64)
    sw["cols"]["stripes"] = np.stack(
        [i, 1.0 / (i + 3.0), np.full(n, 0.1), (i * 7.0) % 16.0], axis=1)
    sw["reference_cols"] = ["stripes"]
    sw["program"] = {"stripes": int(config["stripes"])}
    return sw
'''

REFERENCE = '''"""The plain reference, noting what reaches it."""

import json
import os

from bench.reference import lockdes

SEEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "seen.jsonl")


def run_spec(spec, target_cs):
    with open(SEEN, "a") as f:
        f.write(json.dumps({"stripes": spec["stripes"],
                            "threads": spec["threads"],
                            "cs_hi": spec["cs_hi"],
                            "lock": spec["lock"]}) + "\\n")
    out = lockdes.run_spec(spec, target_cs)
    return dict(out, throughput=SCALE * out["throughput"])
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the checkout with two configurations added: ``striped``
    (its reference mirrors ``lockdes``) and ``striped_double`` (its
    reference doubles throughput), one cell each."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = json.load(open(os.path.join(ROOT, "bench", "configs",
                                       "design_space.json")))
    (root / "bench" / "generators" / "striped.py").write_text(GENERATOR)
    limits = (root / "bench" / "limits" / "design_space.closed.json"
              ).read_text()
    for name, scale in (("striped", 1.0), ("striped_double", 2.0)):
        (root / "bench" / "reference" / f"{name}_ref.py").write_text(
            REFERENCE.replace("SCALE", repr(scale)))
        cfg = dict(base, name=name, generator="striped",
                   reference=f"{name}_ref", stripes=16)
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        (root / "bench" / "limits" / f"{name}.closed.json").write_text(
            limits)
        bench["configs"].append(
            {"name": name, "source": "a test's copy of design_space",
             "file": f"bench/configs/{name}.json", "reduced": [],
             "why": "a configuration with its own reference"})
        bench["workloads"].append(
            {"name": f"{name}.closed", "config": name,
             "traffic": "closed_512", "chips": 1,
             "why": "a configuration with its own reference"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p: p.read_bytes() for p in before}
    assert after == before          # nothing that was there is edited
    return root


def _run(root, name):
    cell = harness.load_cell(name, root=str(root))
    cell["traffic"]["scenarios"] = 4
    cell["limits"].update(sample=24, workers=2)
    seen = root / "bench" / "reference" / "seen.jsonl"
    seen.unlink(missing_ok=True)
    calls = []

    def run_sweep(cols, *, stripes, **kw):
        calls.append(dict(kw, stripes=stripes))
        return entry.run_sweep(cols, **kw)

    args = argparse.Namespace(workload=name, seed=SEED, seconds=0.0,
                              trace=0)
    out = harness.run(args, time.monotonic(), require_chip=False,
                      run_sweep=run_sweep, cell=cell)
    rows = [json.loads(line) for line in seen.read_text().splitlines()]
    return cell, out, calls, rows


@pytest.fixture(scope="module")
def mirrored(checkout):
    return _run(checkout, "striped.closed")


def test_the_reference_is_the_configurations_own(checkout):
    cell = harness.load_cell("striped.closed", root=str(checkout))
    assert cell["reference"] == "striped_ref"
    assert cell["root"] == str(checkout)
    assert os.path.dirname(cell["generator"].__file__) \
        == str(checkout / "bench" / "generators")


def test_the_vector_reaches_the_reference_row_by_row(checkout, mirrored):
    cell, out, calls, rows = mirrored
    sw = cell["generator"].sweep(cell["config"], cell["traffic"], SEED, 0)
    cols = sw["cols"]
    assert len(rows) >= 24
    for row in rows:
        i = int(row["stripes"][0])
        assert row["stripes"] == cols["stripes"][i].tolist()
        assert row["threads"] == int(cols["threads"][i])
        assert row["cs_hi"] == float(cols["cs_hi"][i])
        assert row["lock"] == str(sw["names"]["lock"][i])


def test_the_keyword_reaches_the_program(mirrored):
    _, out, calls, _ = mirrored
    assert len(calls) == 2          # the warm-up and the window's sweep
    assert all(c["stripes"] == 16 for c in calls)
    assert all(set(c) == {"target_cs", "max_threads", "reduce", "stripes"}
               for c in calls)


def test_a_mirroring_reference_is_correct(mirrored):
    _, out, _, _ = mirrored
    assert out["attempted"] == 4 * 15 and out["failed"] == 0
    assert out["correct"] is True, out["check"]


def test_a_reference_that_doubles_throughput_is_not_correct(checkout):
    _, out, _, rows = _run(checkout, "striped_double.closed")
    assert rows
    assert out["correct"] is False, out["check"]
    assert out["check"]["thr_bias"]["value"] > 0.5


# -- today's configurations ------------------------------------------------
CELLS = ["design_space.closed", "fig3_paper.closed", "design_space.open",
         "design_space.closed.4chip"]
#: The keys ``reference_spec`` has always built.
TODAYS_KEYS = sorted([
    "threads", "cores", "cs_lo", "cs_hi", "ncs_lo", "ncs_hi",
    "wake_latency", "sws_init", "sws_max", "k", "spin_budget", "wl_period",
    "wl_duty", "wl_burst", "wl_spread", "arrival_phase", "arrival_rate",
    "queue_cap", "slo", "fault_rate", "fault_scale", "park_cost", "lock",
    "oracle", "arrival", "workload", "fault", "alpha", "seed"])


def _small(name):
    cell = harness.load_cell(name)
    key = "scenarios" if "scenarios" in cell["traffic"] else "replicas"
    cell["traffic"][key] = 2
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_todays_cells_reach_the_reference_as_before(name):
    cell = _small(name)
    assert "reference" not in cell["config"]
    assert cell["reference"] == "lockdes"
    sw = cell["generator"].sweep(cell["config"], cell["traffic"], SEED, 0)
    assert "reference_cols" not in sw and "program" not in sw
    n = len(sw["cols"]["lock"])
    res = argparse.Namespace(steps_run=np.full(n, 100, np.int32))
    dt = check.stated_dt(sw["cols"])
    for i in range(n):
        spec = check.reference_spec(sw, res, i, SEED, dt)
        extra = ["horizon"] if spec["arrival"] != "closed" else []
        assert sorted(spec) == sorted(TODAYS_KEYS + extra)
        assert all(not isinstance(v, list) for v in spec.values())


class _Stop(Exception):
    pass


@pytest.mark.parametrize("name", CELLS)
def test_todays_cells_call_the_program_as_before(name):
    cell = _small(name)
    seen = {}

    def run_sweep(cols, **kw):
        seen.update(kw)
        raise _Stop

    with pytest.raises(_Stop):
        harness.one_sweep(cell["generator"], cell["config"],
                          cell["traffic"], SEED, 0, run_sweep)
    assert set(seen) == {"target_cs", "max_threads", "reduce"}
    assert seen["target_cs"] == int(cell["config"]["target_cs"])
    assert seen["max_threads"] == int(cell["traffic"]["max_threads"])
