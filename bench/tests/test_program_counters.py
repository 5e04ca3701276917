"""The readers of the program's own counters (``padded_share``,
``setup_compile_s``), each with nothing to read where a program lacks the
counter, and the program's host spans kept out of the traced window."""

from types import SimpleNamespace

import numpy as np
import pytest

from bench import entry, trace
from bench.harness import metric_reader


def _res(steps_run, row_steps=None):
    res = SimpleNamespace(steps_run=np.asarray(steps_run, np.int32))
    if row_steps is not None:
        res.row_steps = row_steps
    return res


def test_padded_share_arithmetic():
    read = metric_reader("padded_share")
    # 7,680 real rows padded to 8,192, all 100 steps: 512 / 8,192
    rec = {"sweeps": [{"res": _res(np.full(7680, 100), 8192 * 100)}]}
    assert read(rec) == pytest.approx(6.25)
    # nothing padded
    rec = {"sweeps": [{"res": _res(np.full(15, 40), 15 * 40)}]}
    assert read(rec) == pytest.approx(0.0)
    # two sweeps pool their steps
    rec = {"sweeps": [{"res": _res([10, 10], 40)},
                      {"res": _res([30], 30)}]}
    assert read(rec) == pytest.approx(100.0 * (1 - 50 / 70))


def test_padded_share_reads_nothing_without_the_counter():
    read = metric_reader("padded_share")
    assert read({"sweeps": [{"res": _res([10, 10])}]}) is None
    assert read({"sweeps": [{"res": _res([0], 0)}]}) is None
    assert read({"sweeps": []}) is None


def test_padded_share_of_a_streamed_sweep():
    # 30 configurations with no reduction run as one call of 32 rows
    from repro.configs import catalog

    cols = catalog.lock_discipline_columns(2)
    res = entry.sweep_stream(cols, n_steps=4, block_steps=4, shard=False,
                             max_threads=32)
    assert res.padded_rows == 2
    read = metric_reader("padded_share")
    assert read({"sweeps": [{"res": res}]}) == pytest.approx(
        100.0 * 2 / 32)


def test_setup_compile_s_reads_the_program_counter(monkeypatch):
    import importlib

    read = metric_reader("setup_compile_s")
    module = importlib.import_module(entry.enable_compile_cache.__module__)
    monkeypatch.setattr(module, "compile_seconds",
                        lambda: {"total": 4.5, "trace": 1.0})
    assert read({}) == 4.5
    monkeypatch.delattr(module, "compile_seconds")
    assert read({}) is None


def test_program_spans_leave_the_window_as_it_was():
    # a recorded sweep: the program's stream.* spans nest inside the
    # benchmark's spans and are not read, so the window, busy time and
    # the metrics that read them are those of the benchmark spans alone
    import jax
    from jax.profiler import TraceAnnotation

    from bench.harness import Tracer
    from repro.configs import catalog

    cols = catalog.lock_discipline_columns(1)
    kw = dict(n_steps=4, block_steps=4, shard=False, max_threads=32)
    entry.sweep_stream(cols, **kw)
    tracer = Tracer()
    try:
        with TraceAnnotation("bench.spec"):
            jax.numpy.ones(4).block_until_ready()
        with TraceAnnotation("bench.sweep_stream"):
            entry.sweep_stream(cols, **kw)
        with TraceAnnotation("bench.diagram"):
            pass
    finally:
        tracer.stop()
    devices, spans = trace.read(tracer.xspace)
    assert sorted(n for _, _, n in spans) == sorted(trace.SPANS)
    # the same device operations under the same window read the same
    ops = {"/device:TPU:0": [(spans[0][0], spans[0][0] + 10, "fusion.1")]}
    s = trace.summarize(ops, spans)
    assert s["window_ns"] == max(e for _, e, _ in spans) \
        - min(b for b, _, _ in spans)
    rec = {"trace": s, "sweeps": [{"res": _res([5])}], "target_cs": 5}
    assert metric_reader("device_idle_share")(rec) == pytest.approx(
        100.0 * (1 - 10 / s["window_ns"]))
    assert metric_reader("device_ns_per_config_step")(rec) == 2.0
