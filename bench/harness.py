"""One run of one cell: set-up, the measured window, the traced window's
reduction, the correctness check, and the result line.

Everything that belongs to a cell is found by name from
``BENCHMARK.json``: the configuration file (``configs/<config>.json``,
which names its generator in ``generators/`` and, under ``reference``,
its plain reference in ``reference/``, ``lockdes`` where it names none),
the traffic mix (``traffic/<traffic>.json``), the check's limits
(``limits/<cell>.json``) and one reader per per-layer metric
(``metrics/<metric>.py``). Generators, references and readers are loaded
from their paths under the checkout's root. A generator's sweep may hand
the program static keyword arguments (``program``) and the reference
further columns (``reference_cols``). Adding a cell, a configuration with
its own generator and reference, or a metric adds files and entries; this
file does not change.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: The warm-up sweep's index: outside every window's sequence 0, 1, ...
WARMUP_K = 2**31
#: JAX monitoring events that mean an executable was compiled or loaded.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = ROOT):
    """``bench/<kind>/<name>.py`` under ``root``, loaded from its path as
    module ``bench.<kind>.<name>``, kept in ``sys.modules`` (dataclasses
    look their module up there); a module already loaded from that path is
    reused."""
    path = os.path.join(root, "bench", kind, name + ".py")
    modname = f"bench.{kind}.{name.replace('.', '_')}"
    mod = sys.modules.get(modname)
    if mod is not None and os.path.realpath(
            getattr(mod, "__file__", None) or "") == os.path.realpath(path):
        return mod
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """Resolve a cell of ``BENCHMARK.json`` to its files and metrics."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[cell["config"]]["file"]))

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": config,
        "root": root,
        "reference": config.get("reference", "lockdes"),
        "traffic": _json(os.path.join(root, "bench", "traffic",
                                      cell["traffic"] + ".json")),
        "limits": _json(os.path.join(root, "bench", "limits",
                                     name + ".json")),
        "generator": load_module("generators", config["generator"], root),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def metric_reader(name: str):
    """The ``read(record)`` function of ``metrics/<name>.py``."""
    return load_module("metrics", name).read


def check_devices(chips: int):
    """The cell's chips, or an error: a host without a TPU, or with
    another number of chips than the cell asks for, never runs it."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) != chips:
        raise SystemExit(f"the cell asks for {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs


def device_info(devs) -> dict:
    peak = [((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
            for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peak))}


class CompileCounter:
    """Counts executables compiled or loaded while ``active``."""

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        self._event(event)

    def _event(self, event, **kw):
        if self.active and event in COMPILE_EVENTS:
            self.count += 1


def one_sweep(gen, cfg: dict, traffic: dict, seed: int, k: int, run_sweep):
    """Spec to diagram for sweep ``k``, each stage under its host span.
    The program gets the sweep's ``program`` keywords, where it has any."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.spec"):
        sw = gen.sweep(cfg, traffic, seed, k)
    with TraceAnnotation("bench.sweep_stream"):
        res = run_sweep(sw["cols"], target_cs=int(cfg["target_cs"]),
                        max_threads=int(traffic["max_threads"]),
                        reduce=sw["reduce"], **sw.get("program", {}))
    with TraceAnnotation("bench.diagram"):
        diagram = gen.diagram(sw, res)
    return {"sw": sw, "res": res, "diagram": diagram}


class Tracer:
    """The profiler over the window's first sweep only, kept in memory.

    A sweep's trace holds every operation of every rollout block, about a
    million events per chip; exporting that to files takes minutes, so the
    session's serialized trace is read directly (``jax.profiler`` has no
    public call that stops without exporting)."""

    def __init__(self):
        import jax
        from jax._src.lib import _profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.session = _profiler.ProfilerSession(opts)
        self.xspace = None

    def stop(self):
        if self.session is not None:
            self.xspace = self.session.stop()
            self.session = None


def window(gen, cfg: dict, traffic: dict, seed: int, seconds: float,
           run_sweep, clock=time.monotonic, tracer=None) -> dict:
    """Sweeps back to back from one client until the first one that ends
    at or after ``seconds`` past the window's opening. A sweep that
    raises counts its configurations as failed. A ``tracer`` is stopped
    when the first sweep ends."""
    done, failed, attempted, k = [], 0, 0, 0
    t_open = clock()
    while True:
        try:
            out = one_sweep(gen, cfg, traffic, seed, k, run_sweep)
        except Exception as e:  # noqa: BLE001 (counted, reported, go on)
            n = len(gen.sweep(cfg, traffic, seed, k)["cols"]["lock"])
            attempted += n
            failed += n
            print(f"sweep {k} raised {type(e).__name__}: {e}",
                  file=sys.stderr)
        else:
            n = len(out["res"].completed)
            attempted += n
            failed += len(out["res"].failures)
            done.append(out)
        if tracer:
            tracer.stop()
        k += 1
        t_end = clock()
        if t_end - t_open >= seconds:
            break
    return {"sweeps": done, "attempted": attempted, "failed": failed,
            "window_s": t_end - t_open}


def configs_per_s(win: dict) -> float:
    """Configurations of every sweep completed in the window over the
    window's length."""
    return sum(len(s["res"].completed) for s in win["sweeps"]) \
        / win["window_s"]


def print_setup(t_devs: float, setup_s: float) -> None:
    """Where set-up went, on standard error: start to devices ready, the
    warm-up sweep, and of it the program's compile counters (tracing,
    lowering, backend compile or cache load; the persistent cache's hits
    and misses), where the program has them."""
    from bench import entry

    module = importlib.import_module(entry.enable_compile_cache.__module__)
    counter = getattr(module, "compile_seconds", None)
    c = counter() if counter else {}
    print(f"set-up {setup_s:.2f} s: devices ready at {t_devs:.2f} s, "
          f"warm-up sweep {setup_s - t_devs:.2f} s; compile "
          + ", ".join(f"{k} {v:.2f}" if isinstance(v, float) else f"{k} {v}"
                      for k, v in c.items()), file=sys.stderr)


def run(args, t0: float, *, require_chip: bool = True, run_sweep=None,
        cell: dict | None = None) -> dict:
    """One run of ``args.workload``; returns the result line's object.
    ``require_chip=False``, a ``run_sweep`` stand-in and a resized
    ``cell`` are for tests."""
    import jax

    from bench import check, entry, trace

    cell = cell or load_cell(args.workload)
    cfg, traffic, gen = cell["config"], cell["traffic"], cell["generator"]
    entry.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = (check_devices(int(cell["cell"]["chips"])) if require_chip
            else jax.devices())
    run_sweep = run_sweep or entry.run_sweep

    t_devs = time.monotonic() - t0
    one_sweep(gen, cfg, traffic, args.seed, WARMUP_K, run_sweep)
    setup_s = time.monotonic() - t0
    print_setup(t_devs, setup_s)

    counter = CompileCounter()
    tracer = Tracer() if args.trace else None
    counter.active = True
    try:
        win = window(gen, cfg, traffic, args.seed, args.seconds, run_sweep,
                     tracer=tracer)
    finally:
        counter.active = False
        if tracer:
            tracer.stop()
    device = device_info(devs)
    print(f"window: {len(win['sweeps'])} sweeps, {win['attempted']} "
          f"configs in {win['window_s']:.3f} s; executables compiled or "
          f"loaded inside it: {counter.count}", file=sys.stderr)

    metrics, breakdown = {}, None
    if tracer:
        t_read = time.monotonic()
        summary = trace.summarize(*trace.read(tracer.xspace))
        print(f"trace of the first sweep read in "
              f"{time.monotonic() - t_read:.1f} s", file=sys.stderr)
        device["busy_s"] = summary["busy_ns"] / 1e9
        device["window_s"] = summary["window_ns"] / 1e9
        breakdown = {
            "device_ops": [[n, d / 1e9] for n, d in summary["device_ops"]],
            "idle_gaps": [[n, d / 1e9] for n, d in summary["idle_gaps"]]}
        record = {"trace": summary, "sweeps": win["sweeps"][:1],
                  "target_cs": cfg["target_cs"]}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"configs_per_s": configs_per_s(win), "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    lim = cell["limits"]
    t_check = time.monotonic()
    numbers = check.compare(win["sweeps"], args.seed, lim,
                            reference=cell["reference"], root=cell["root"])
    print(f"reference check took {time.monotonic() - t_check:.1f} s",
          file=sys.stderr)
    ok, table = check.verdict(numbers, lim["limits"])
    for name in sorted(set(numbers) - set(table)):
        print(f"reading {name} {numbers[name]!r} (no limit)",
              file=sys.stderr)
    for name, row in table.items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    out = {"correct": bool(ok and win["sweeps"]),
           "attempted": win["attempted"], "failed": win["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = table
    return out


@contextlib.contextmanager
def stdout_to_stderr():
    """Keep the program's own prints off standard output, whose last line
    is the result."""
    saved = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield
    finally:
        sys.stdout = saved
