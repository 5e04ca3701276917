"""The sharded path a four-chip host takes, rehearsed on four virtual CPU
devices with the closed design-space cell: the program shards the sweep
by default, the run comes out correct on four devices, and each fault of
the timed path (``test_check``'s) makes it come out not correct."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FAULTS = ["state_unchanged", "half_left_out", "answer_altered",
          "control_dt_x8"]

SCRIPT = r"""
import argparse, json, sys, time
sys.path[:0] = [{root!r}]
from bench import entry, harness
from bench.tests import test_check as t
if __name__ == "__main__":
    paths = dict(sound=entry.run_sweep, state_unchanged=t._state_unchanged,
                 half_left_out=t._half_left_out,
                 answer_altered=t._answer_altered, control_dt_x8=t._control)
    for mode, fn in paths.items():
        cell = harness.load_cell("design_space.closed")
        cell["traffic"]["scenarios"] = 4
        cell["limits"].update(sample=24, workers=2)
        args = argparse.Namespace(workload="design_space.closed",
                                  seed=2**31 + 17, seconds=0.0, trace=0)
        out = harness.run(args, time.monotonic(), require_chip=False,
                          run_sweep=fn, cell=cell)
        print(json.dumps(dict(out, mode=mode)), flush=True)
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    script = tmp_path_factory.mktemp("four") / "four.py"
    script.write_text(SCRIPT.format(root=ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    outs = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    return {o["mode"]: o for o in outs}


def test_sharded_cell_on_four_virtual_devices(runs):
    out = runs["sound"]
    assert out["device"]["count"] == 4
    assert out["attempted"] == 4 * 15 and out["failed"] == 0
    assert out["correct"], out["check"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_sharded_path_is_not_correct(runs, fault):
    assert runs[fault]["correct"] is False, runs[fault]["check"]
