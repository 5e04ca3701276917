"""The four-chip design-space cell rehearsed on four virtual CPU devices
at 4 scenarios: the program shards its one chunk over the four devices by
default, and the run, from its own cell's files, comes out correct."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = r"""
import argparse, json, sys, time
sys.path[:0] = [{root!r}]
from bench import harness
if __name__ == "__main__":
    cell = harness.load_cell("design_space.closed.4chip")
    assert cell["cell"]["chips"] == 4
    cell["traffic"]["scenarios"] = 4
    cell["limits"].update(sample=24, workers=2)
    args = argparse.Namespace(workload="design_space.closed.4chip",
                              seed=2**31 + 17, seconds=0.0, trace=0)
    out = harness.run(args, time.monotonic(), require_chip=False, cell=cell)
    print(json.dumps(out), flush=True)
"""


def test_four_chip_cell_on_four_virtual_devices(tmp_path):
    script = tmp_path / "four.py"
    script.write_text(SCRIPT.format(root=ROOT))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["attempted"] == 4 * 15 and out["failed"] == 0
    assert out["correct"], out["check"]
    assert set(out["check"]) == {"t_end_mismatch", "thr_bias",
                                 "thr_bias_max", "thr_gap", "wins_mismatch"}
