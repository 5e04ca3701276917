"""The trace reduction on small traces: hand-made ones whose answers are
known, and one recorded by the profiler session a traced run uses."""

import pytest

from bench import trace


def test_union_merges_overlaps():
    assert trace.union_ns([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) \
        == [(0, 4), (5, 10)]
    assert trace.covered_ns([(0, 10), (2, 3), (8, 15)]) == 15


def test_summary_of_a_known_trace():
    # window 0..100 ns from the host spans; device 0 runs overlapping ops
    # (busy 0-30 and 50-80 = 60 ns); device 1 is busy 0-100 (ops outside
    # the window are clipped away).
    spans = [(0, 10, "bench.spec"), (10, 90, "bench.sweep_stream"),
             (90, 100, "bench.diagram")]
    devices = {
        "/device:TPU:0": [(0, 20, "fusion.1"), (10, 30, "fusion.2"),
                          (50, 70, "fusion.1"), (60, 80, "copy.3")],
        "/device:TPU:1": [(-50, 60, "fusion.1"), (60, 150, "fusion.2")],
    }
    s = trace.summarize(devices, spans)
    assert s["window_ns"] == 100
    assert s["n_devices"] == 2
    assert s["busy_ns_total"] == 60 + 100
    assert s["busy_ns"] == 80
    ops = dict(s["device_ops"])
    assert ops["fusion.1"] == 20 + 20 + 60
    # device 0 idles 30-50 (inside bench.sweep_stream) and 80-100 (10 ns
    # in sweep_stream, 10 in diagram; named by the gap's middle, 90 ->
    # bench.diagram): totals are halved over the two devices
    gaps = dict(s["idle_gaps"])
    assert gaps == {"bench.sweep_stream": 10.0, "bench.diagram": 10.0}


def test_enclosing_operations_do_not_count_as_busy():
    # a loop spans 0-100 and its body runs 0-20, 40-50 and 60-90: the
    # device waits 30 ns inside the loop, and 30 ns from 90 to the end
    # of the window (a gap named by its middle, 105, after the loop)
    ops = [(0, 100, "while.7"), (0, 20, "fusion.1"), (40, 50, "fusion.2"),
           (60, 90, "call.4"), (60, 90, "fusion.3"), (95, 100, "while.8")]
    leaf, outer = trace.leaves(ops)
    assert {n for _, _, n in leaf} == {"fusion.1", "fusion.2", "fusion.3"}
    assert {n for _, _, n in outer} == {"while.7", "call.4", "while.8"}
    s = trace.summarize({"/device:TPU:0": ops},
                        [(0, 120, "bench.sweep_stream")])
    assert s["busy_ns"] == 60
    assert s["any_op_ns"] == 100
    assert dict(s["idle_gaps"]) == {
        "bench.sweep_stream, inside a device loop": 30.0,
        "bench.sweep_stream": 30.0}
    assert "while.7" not in dict(s["device_ops"])


def test_idle_metric_reads_the_summary():
    from bench.harness import metric_reader

    s = trace.summarize({"/device:TPU:0": [(0, 25, "f"), (25, 50, "g")]},
                        [(0, 100, "bench.sweep_stream")])
    rec = {"trace": s, "sweeps": [], "target_cs": 50}
    assert metric_reader("device_idle_share")(rec) == pytest.approx(50.0)


def test_a_recorded_trace_yields_the_benchmark_spans():
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from bench.harness import Tracer

    f = jax.jit(lambda x: (x * 2.0).sum())
    f(jnp.ones(8)).block_until_ready()
    tracer = Tracer()
    try:
        for name in trace.SPANS:
            with TraceAnnotation(name):
                f(jnp.ones(8)).block_until_ready()
    finally:
        tracer.stop()
    devices, spans = trace.read(tracer.xspace)
    assert sorted({n for _, _, n in spans}) == sorted(trace.SPANS)
    assert all(e >= s for s, e, _ in spans)
    s = trace.summarize(devices, spans)
    assert s["window_ns"] > 0
    # on the CPU there is no device plane, so nothing is read as busy
    assert s["n_devices"] == len(devices)
