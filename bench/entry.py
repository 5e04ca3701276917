"""The benchmark's one door into the program.

Every name the benchmark takes from ``repro`` is imported here and
nowhere else, so a rename in the program costs one edit of this file.
The benchmark takes the system under test (the streamed sweep and its
on-device phase-diagram reduction), the compile-cache placement, and the
registry ids and default contention coefficients that turn a
configuration's names into the program's input columns. In a directory
that holds only the benchmark the import fails, and so does the run.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.policy import (ARRIVAL_IDS, DEFAULT_ALPHA,  # noqa: E402
                               DEFAULT_SPIN_BUDGET, ORACLE_IDS, POLICY_IDS,
                               POLICY_ROW, QUEUE_MAX, WORKLOAD_IDS)
from repro.core.stream import CellReduce, sweep_stream  # noqa: E402
from repro.core.xdes import plan_schedule_columns  # noqa: E402

__all__ = ["ARRIVAL_IDS", "DEFAULT_ALPHA", "DEFAULT_SPIN_BUDGET",
           "ORACLE_IDS", "POLICY_IDS", "QUEUE_MAX", "WORKLOAD_IDS",
           "enable_compile_cache", "planned_dt", "run_sweep", "windowed"]


def windowed(lock: str) -> bool:
    """Whether a discipline reads the oracle column (runs the window)."""
    return bool(POLICY_ROW[POLICY_IDS[lock]].windowed)


def planned_dt(cols: dict, target_cs: int):
    """The per-config time step the program's planner picks."""
    return plan_schedule_columns(cols, target_cs)[0]


def run_sweep(cols: dict, *, target_cs: int, max_threads: int,
              reduce: dict | None = None, dt=None, **program):
    """The entry the window drives: ``sweep_stream`` over RAW columns at
    the program's default backend, sharding, chunking and memory budget.
    ``reduce`` (``group``, ``cell_ids``, ``n_cells``) asks for the
    on-device win-count table. ``dt`` overrides the planner's time step,
    which only the control of the correctness check does. ``program``
    holds the static keyword arguments a generator's sweep names, passed
    to ``sweep_stream`` as they are."""
    red = None if reduce is None else CellReduce(**reduce)
    return sweep_stream(cols, target_cs=target_cs, max_threads=max_threads,
                        reduce=red, dt=dt, **program)
