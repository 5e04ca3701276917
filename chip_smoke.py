#!/usr/bin/env python3
"""Chip smoke test: drive the batched lock simulator once on a TPU.

Runs the main path through the entry points users call
(``xdes.simulate_batch``, ``benchmarks/sweep.py``'s grids and the
streamed sweep behind them) at the size users run it, with the compiled
Pallas kernel, and checks what comes out:

  (a) the device is a TPU, Pallas is not in interpret mode, and the
      lowered rollout holds the fused kernel (``tpu_custom_call``);
  (b) the closed-loop discipline grid, ~30k configs, through
      ``backend="pallas"`` and ``backend="ref"``: every per-config result
      bit-identical; then its phase diagram through ``discipline_grid``;
  (c) the open-loop arrival grid through ``pallas``;
  (d) a streamed discipline grid of >= 50k configs (chunk planner and the
      on-device ``CellReduce``): no quarantined config, no OOM halving;
  (e) Fig. 3 cells against the exact event-driven DES on the host, in
      the bands ``tests/test_xdes.py`` uses.

``--four-chips`` runs only the sharded path: phase (b)'s configs sharded
over four devices against the same configs unsharded, bit-identical.

Lines starting with ``info:`` are informational (sizes, seconds,
device); none is a metric.  The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  Any failure, or a host without a TPU, exits non-zero with no
such line.

    python chip_smoke.py [--four-chips] [--report PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

#: Phase (b): scenarios of the closed discipline grid (x 15 variants).
GRID_SCENARIOS = 2000
#: Phase (c): scenarios of the open-loop arrival grid (its one-device
#: default in benchmarks/arrival_diagram.py).
ARRIVAL_SCENARIOS = 50
#: Phase (d): scenarios of the streamed grid: 4096 x 15 = 61,440 configs,
#: which fill the chunk planner's padded shape exactly.
STREAM_SCENARIOS = 4096
#: Critical sections per config in (b)-(d).  The grids' own default is
#: 150; at 150, (b)'s pallas run alone took 517 s on one TPU v5e and (d)
#: runs twice its configs, so the horizon is cut to fit the run's 1,200 s
#: limit (configs are not cut).
TARGET_CS = 50
#: Phase (e): Fig. 3 cells checked against the DES, and the bands of
#: tests/test_xdes.py::test_agrees_with_event_driven_des_on_trends.
FIG3_TARGET_CS = 120
DES_TARGET_CS = 800
DES_CELLS = (("cs_short_ncs_short", "ttas", 20),
             ("cs_short_ncs_short", "sleep", 20),
             ("cs_long_ncs_short", "mutable", 20))
DES_BAND = (0.7, 1.4)

#: Per-config BatchResult fields compared bit for bit.
RESULT_FIELDS = ("dt", "t_end", "steps_run", "completed", "spin_cpu",
                 "wake_count", "final_sws", "completed_per_thread")


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


def first_difference(a, b, fields=RESULT_FIELDS) -> str | None:
    """``None`` when every field of two results is bit-identical, else
    the first differing config and field, and how many configs differ in
    each differing field."""
    import numpy as np

    first, counts = None, []
    for f in fields:
        x, y = getattr(a, f), getattr(b, f)
        if x is None and y is None:
            continue
        x, y = np.ascontiguousarray(x), np.ascontiguousarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return f"field {f}: {x.dtype}{x.shape} vs {y.dtype}{y.shape}"
        bits_x = x.view(np.uint8).reshape(len(x), -1)
        bits_y = y.view(np.uint8).reshape(len(y), -1)
        bad = np.nonzero((bits_x != bits_y).any(axis=1))[0]
        if bad.size:
            i = int(bad[0])
            first = first or f"config {i} field {f}: {x[i]!r} vs {y[i]!r}"
            counts.append(f"{f} {bad.size}")
    if first is None:
        return None
    return f"{first} (configs differing per field: {', '.join(counts)})"


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def check_device(n_chips: int) -> dict:
    """Phase (a), first half: a TPU is attached and Pallas compiles."""
    import jax

    from repro.kernels.pallas_compat import default_interpret

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: jax found {dev.platform} "
                           f"({dev.device_kind})")
    if len(devs) < n_chips:
        raise RuntimeError(f"need {n_chips} chips, jax found {len(devs)}")
    if default_interpret():
        raise RuntimeError("Pallas resolved to interpret mode on a TPU")
    info(f"device {dev.platform} {dev.device_kind} x{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def check_lowering(configs) -> None:
    """Phase (a), second half: the pallas rollout holds the kernel."""
    import numpy as np

    from repro.core import policy as P
    from repro.core import xdes

    arrs = P.encode_configs(configs)
    arrs["dt"] = xdes.plan_schedule(configs)[0]
    T = int(arrs["threads"].max())
    text = xdes._simulate_dyn.lower(
        arrs, np.int32(64), T=T, backend="pallas", rollout="blocked",
        block_steps=xdes.DEFAULT_BLOCK_STEPS, target_cs=np.int32(0),
        early_exit=False, keep_per_thread=True, open_loop=False).as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("lowered pallas rollout has no tpu_custom_call")
    info("lowered pallas rollout contains tpu_custom_call")


def phase_grid(n_scenarios: int, target_cs: int) -> dict:
    """Phase (b): pallas == ref on every config, then the phase diagram."""
    import numpy as np

    from benchmarks import sweep
    from repro.configs.catalog import lock_discipline_sweep
    from repro.core import xdes

    configs = lock_discipline_sweep(n_scenarios=n_scenarios)
    check_lowering(configs[:64])
    pal, t_pal = timed(xdes.simulate_batch, configs, target_cs=target_cs,
                       backend="pallas")
    pal.validate("chip_smoke pallas")
    info(f"(b) pallas: {len(configs)} configs x {int(pal.steps_run.max())}"
         f" steps, {t_pal:.1f} s incl. compile")
    ref, t_ref = timed(xdes.simulate_batch, configs, target_cs=target_cs,
                       backend="ref")
    info(f"(b) ref: {len(configs)} configs, {t_ref:.1f} s incl. compile")
    diff = first_difference(pal, ref)
    if diff:
        raise RuntimeError(f"pallas and ref differ on the chip: {diff}")
    info("(b) pallas == ref bit-identical on every per-config field")
    # the diagram through the grid entry point, on the faster backend:
    # its per-variant wins must be those of the pallas results above
    grid, t_grid = timed(sweep.discipline_grid, n_scenarios=n_scenarios,
                         target_cs=target_cs, backend="ref", stream=False,
                         verbose=False)
    V = len(grid["variants"])
    win_v = np.bincount(pal.throughput.reshape(n_scenarios, V)
                        .argmax(axis=1), minlength=V)
    if [v["wins"] for v in grid["variants"]] != win_v.tolist():
        raise RuntimeError("discipline_grid wins differ from the pallas "
                           "results")
    info(f"(b) discipline_grid: {len(grid['phase'])} phase cells, wins "
         f"match the pallas results, {t_grid:.1f} s (executable reused)")
    return {"configs": len(configs), "steps": int(pal.steps_run.max()),
            "pallas_s": t_pal, "ref_s": t_ref, "grid_s": t_grid}


def phase_arrival(n_scenarios: int, target_cs: int) -> dict:
    """Phase (c): the open-loop arrival grid through the pallas kernel."""
    from benchmarks import sweep

    grid, t = timed(sweep.arrival_grid, n_scenarios=n_scenarios,
                    target_cs=target_cs, backend="pallas", stream=False,
                    verbose=False)
    meta = grid["meta"]
    if not grid["phase"] or sum(c["n"] for c in grid["phase"]) \
            != meta["n_configs"] // meta["n_variants"]:
        raise RuntimeError("arrival grid phase cells do not cover it")
    info(f"(c) arrival_grid: {meta['n_configs']} open-loop configs x "
         f"{meta['n_steps']} steps, {t:.1f} s incl. compile")
    return {"configs": meta["n_configs"], "s": t}


def phase_stream(n_scenarios: int, target_cs: int) -> dict:
    """Phase (d): a streamed discipline grid, reduced on the device."""
    from benchmarks import sweep

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        grid, t = timed(sweep.discipline_grid, n_scenarios=n_scenarios,
                        target_cs=target_cs, backend="pallas", stream=True,
                        verbose=False)
    meta = grid["meta"]
    halved = [w for w in caught if "allocation error" in str(w.message)]
    if meta["n_failures"] or halved:
        raise RuntimeError(f"stream: {meta['n_failures']} quarantined, "
                           f"{len(halved)} OOM halvings")
    if sum(c["n"] for c in grid["phase"]) != n_scenarios:
        raise RuntimeError("streamed win counts do not cover the grid")
    info(f"(d) streamed discipline_grid: {meta['n_configs']} configs in "
         f"{meta['n_chunks']} chunk(s) of {meta['chunk_size']}, budget "
         f"{meta['budget_mb']} MiB, {t:.1f} s incl. compile")
    return {"configs": meta["n_configs"], "chunks": meta["n_chunks"],
            "s": t}


def phase_des() -> dict:
    """Phase (e): Fig. 3 cells against the exact DES (host)."""
    from benchmarks import sweep
    from repro.configs.catalog import (LOCK_CORES, LOCK_REGIMES,
                                       LOCK_THREADS, LOCK_WAKE)
    from repro.core.des import simulate

    f3, t = timed(sweep.fig3_batched, target_cs=FIG3_TARGET_CS, seeds=(0,),
                  backend="pallas", verbose=False)

    def cell(regime, lock, threads):
        return f3[regime]["rows"][lock][LOCK_THREADS.index(threads)]

    lo, hi = DES_BAND
    for regime, lock, threads in DES_CELLS:
        cs, ncs = LOCK_REGIMES[regime]
        d = simulate(lock, threads=threads, cores=LOCK_CORES, cs=cs,
                     ncs=ncs, wake_latency=LOCK_WAKE,
                     target_cs=DES_TARGET_CS, seed=0)
        x = cell(regime, lock, threads)["throughput"]
        info(f"(e) {regime}/{lock}/{threads}t: xdes {x:.6g} cs/s, DES "
             f"{d.throughput:.6g} cs/s, ratio {x / d.throughput:.4f}")
        if not lo * d.throughput < x < hi * d.throughput:
            raise RuntimeError(f"{regime}/{lock}: xdes {x} outside "
                               f"{DES_BAND} x DES {d.throughput}")
    ss, ls = "cs_short_ncs_short", "cs_long_ncs_short"
    if not (cell(ss, "ttas", 20)["throughput"]
            > cell(ss, "sleep", 20)["throughput"]):
        raise RuntimeError("short CS: ttas does not beat sleep")
    if not (cell(ls, "sleep", 20)["sync_cpu_per_cs"]
            < cell(ls, "ttas", 20)["sync_cpu_per_cs"]):
        raise RuntimeError("long CS: sleep does not burn less than ttas")
    claims = f3["claims"]
    if not (claims["C2"] and claims["C3"] and claims["C4"]):
        raise RuntimeError(f"Fig. 3 claims fail: {claims}")
    info(f"(e) fig3_batched: {f3['meta']['n_configs']} configs, "
         f"{t:.1f} s incl. compile; claims {claims}")
    return {"configs": f3["meta"]["n_configs"], "s": t}


def phase_four_chips(n_scenarios: int, target_cs: int) -> dict:
    """Phase (b)'s configs sharded over four chips == unsharded."""
    from repro.configs.catalog import lock_discipline_sweep
    from repro.core import xdes

    configs = lock_discipline_sweep(n_scenarios=n_scenarios)
    sh, t_sh = timed(xdes.simulate_batch, configs, target_cs=target_cs,
                     backend="pallas", shard=True)
    info(f"sharded x4: {len(configs)} configs x {int(sh.steps_run.max())}"
         f" steps, {t_sh:.1f} s incl. compile")
    one, t_one = timed(xdes.simulate_batch, configs, target_cs=target_cs,
                       backend="pallas", shard=False)
    info(f"unsharded: {t_one:.1f} s incl. compile")
    diff = first_difference(sh, one)
    if diff:
        raise RuntimeError(f"sharded and unsharded differ: {diff}")
    info("sharded == unsharded bit-identical on every per-config field")
    return {"configs": len(configs), "sharded_s": t_sh,
            "unsharded_s": t_one}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path, on four chips")
    ap.add_argument("--report", default=None,
                    help="also write the phase record as JSON here")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    info(f"compile cache {enable_compile_cache()}")
    n_chips = 4 if args.four_chips else 1
    record: dict = {}
    try:
        device = check_device(n_chips)
        if args.four_chips:
            record["four_chips"] = phase_four_chips(GRID_SCENARIOS,
                                                    TARGET_CS)
        else:
            record["grid"] = phase_grid(GRID_SCENARIOS, TARGET_CS)
            record["arrival"] = phase_arrival(ARRIVAL_SCENARIOS, TARGET_CS)
            record["stream"] = phase_stream(STREAM_SCENARIOS, TARGET_CS)
            record["des"] = phase_des()
        # checked once every phase has imported what it needs
        if "repro.launch.dryrun" in sys.modules:
            raise RuntimeError("repro.launch.dryrun (512 fake host "
                               "devices) was imported on the chip path")
    except Exception as e:                      # noqa: BLE001 (reported)
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    finally:
        if args.report:
            os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
            with open(args.report, "w") as f:
                json.dump(record, f, indent=1)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
