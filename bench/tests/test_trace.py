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


def test_op_head_keeps_name_shape_and_opcode():
    hlo = ("%psum.13 = s32[] all-reduce(%x.2), channel_id=1, "
           "replica_groups={{0,1,2,3}}, to_apply=%region_0.7")
    assert trace.op_head(hlo) == "%psum.13 = s32[] all-reduce"
    assert trace.op_code(trace.op_head(hlo)) == "all-reduce"
    assert trace.op_name(trace.op_head(hlo)) == "psum.13"
    tup = "%t.1 = (s32[], f32[8,128]{1,0:T(8,128)}) tuple(%a, %b)"
    assert trace.op_head(tup) == tup.split("(%")[0].rstrip()
    assert trace.op_code(trace.op_head(tup)) == "tuple"
    # a bare name says no opcode
    assert trace.op_head("all-reduce.7") == "all-reduce.7"
    assert trace.op_code("all-reduce.7") == ""


def test_collective_time_on_four_devices():
    # window 0..100 ns. Each device runs compute and the exit vote's
    # exchange inside a loop. Collectives are told by their opcode: the
    # loop itself, a fusion whose name starts like an exchange, and the
    # part of an exchange outside the window do not count.
    spans = [(0, 100, "bench.sweep_stream")]

    def op(name, shape, code):
        return trace.op_head(f"%{name} = {shape} {code}(%p.1), metadata={{}}")

    def dev(k):
        return [(0, 100, op("while.3", "(s32[], f32[8])", "while")),
                (0, 40, op("fusion.1", "f32[8]", "fusion")),
                (40, 40 + 5 * (k + 1), op("psum.13", "s32[]", "all-reduce")),
                (60, 70, op("all-reduce-like.2", "f32[8]", "fusion")),
                (70, 74, op("ag.2", "(f32[8], f32[32])", "all-gather-start")),
                (74, 76, op("ag.3", "f32[32]", "all-gather-done")),
                (80, 83, op("rs.1", "f32[2]", "reduce-scatter")),
                (83, 85, op("cp.1", "f32[8]", "collective-permute-start")),
                (85, 86, op("a2a.4", "f32[8]", "all-to-all")),
                (88, 90, "all-reduce.5"),
                (95, 120, op("ar.9", "f32[]", "all-reduce-start"))]

    devices = {f"/device:TPU:{k}": dev(k) for k in range(4)}
    s = trace.summarize(devices, spans)
    # per device: the psum's all-reduce 5(k+1), all-gather 6,
    # reduce-scatter 3, collective-permute 2, all-to-all 1, the clipped
    # all-reduce 5; the bare name all-reduce.5 says no opcode
    per = [5 * (k + 1) + 6 + 3 + 2 + 1 + 5 for k in range(4)]
    assert s["n_devices"] == 4
    assert s["collective_ns_total"] == sum(per)
    busy = [40 + 5 * (k + 1) + 10 + 6 + 3 + 2 + 1 + 2 + 5 for k in range(4)]
    assert s["busy_ns_total"] == sum(busy)
    ops = dict(s["device_ops"])
    assert ops["psum.13"] == sum(5 * (k + 1) for k in range(4))
    assert "while.3" not in ops

    from bench.harness import metric_reader

    read = metric_reader("collective_share")
    rec = {"trace": s, "sweeps": [], "target_cs": 50}
    assert read(rec) == pytest.approx(100.0 * sum(per) / sum(busy))
    # one chip exchanges nothing: nothing to read
    one = trace.summarize({"/device:TPU:0": dev(0)}, spans)
    assert one["collective_ns_total"] == per[0]
    assert read({"trace": one}) is None
    assert read({"trace": None}) is None


def test_summary_keys_stay():
    s = trace.summarize({"/device:TPU:0": [(0, 25, "f")]},
                        [(0, 100, "bench.sweep_stream")])
    assert set(s) == {"window_ns", "n_devices", "busy_ns", "busy_ns_total",
                      "any_op_ns", "collective_ns_total", "device_ops",
                      "idle_gaps"}
    assert s["collective_ns_total"] == 0
