"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A ``--trace 1`` run records the window's first sweep with the JAX
profiler and :func:`read` takes the serialized trace (an ``XSpace``,
what an ``.xplane.pb`` file holds) through ``jax.profiler.ProfileData``
(nothing but JAX). A chip's device plane (``/device:<KIND>:<n>``) has an
``XLA Ops`` line with one event per operation that ran, the control-flow
operations that enclose others (a ``while`` loop spans every operation
of its body) included; device planes without that line are not chips
and are left out. The host plane holds the benchmark's own
``TraceAnnotation`` spans (``bench.spec``, ``bench.sweep_stream``,
``bench.diagram``), on the same clock.

Busy time counts leaf operations only: an enclosing operation would
cover the gaps between the operations of its body, so the device would
read busy for the whole of a loop however long it waits.

:func:`summarize` takes plain lists of ``(start_ns, end_ns, name)``, so
the arithmetic is checked on small hand-made traces in ``bench/tests``.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

#: The line of a device plane that holds one event per operation.
OPS_LINE = "XLA Ops"
#: Operations that only run other operations.
CONTROL_OPS = ("while", "conditional", "call")
#: XLA's opcodes that exchange data between chips; an opcode that starts
#: with one of them (``all-reduce-start``, ``all-gather-done``) is one too.
#: An operation's name does not say it: a ``psum`` in ``shard_map``
#: compiles to ``%psum.13 = s32[] all-reduce(...)``.
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
#: The benchmark's host spans, in the order a sweep passes through them.
SPANS = ("bench.spec", "bench.sweep_stream", "bench.diagram")


def union_ns(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered_ns(intervals) -> int:
    return sum(e - s for s, e in union_ns(intervals))


def op_name(hlo: str) -> str:
    """An operation's HLO instruction name (``%fusion.12 = f32[...] ...``
    gives ``fusion.12``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_head(hlo: str) -> str:
    """An operation's HLO text up to its operands: ``%psum.13 = s32[]
    all-reduce(%x), channel_id=1, ...`` gives ``%psum.13 = s32[]
    all-reduce``. Text without `` = `` is a bare name and stays as it is."""
    name, eq, rhs = hlo.partition(" = ")
    if not eq:
        return hlo
    depth = 0
    for k, ch in enumerate(rhs):     # the shape, a tuple's in parentheses
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return f"{name} = {rhs[:k]} {rhs[k + 1:].split('(', 1)[0]}"
    return hlo


def op_code(head: str) -> str:
    """The opcode of an :func:`op_head` (its last word); ``""`` for a bare
    name, which says none."""
    _, eq, rhs = head.partition(" = ")
    return rhs.rsplit(" ", 1)[-1] if eq else ""


def leaves(ops) -> tuple[list, list]:
    """``(leaf, enclosing)`` operations of one line: an enclosing one is a
    control-flow operation by name, or any event that another event of
    the line lies inside. Operations of a line run one at a time, except
    where one encloses others."""
    kinds = {n: op_name(n).split(".")[0] in CONTROL_OPS
             for n in {op[2] for op in ops}}
    control = [kinds[op[2]] for op in ops]
    # of two events with one interval, a control-flow one is the outer
    order = sorted(range(len(ops)),
                   key=lambda k: (ops[k][0], -ops[k][1], not control[k]))
    inner = [False] * len(ops)
    stack: list[int] = []
    for k in order:
        s, e, _ = ops[k]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            inner[stack[-1]] = True
        stack.append(k)
    leaf = [not (has_inner or c) for has_inner, c in zip(inner, control)]
    return ([op for op, f in zip(ops, leaf) if f],
            [op for op, f in zip(ops, leaf) if not f])


def read(data) -> tuple[dict, list]:
    """``({device: [(start_ns, end_ns, op_head), ...]}, [(start_ns,
    end_ns, span_name), ...])``: device operations per chip, each by its
    :func:`op_head` (one string per distinct instruction), and the
    benchmark's host spans, from a ``jax.profiler.ProfileData`` or the
    serialized trace a profiler session returns."""
    if isinstance(data, bytes):
        from jax.profiler import ProfileData

        data = ProfileData.from_serialized_xspace(data)
    devices: dict = {}
    spans: list = []
    heads: dict = {}

    def head(hlo):
        h = heads.get(hlo)
        if h is None:
            h = heads[hlo] = op_head(hlo)
        return h

    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            if lines:        # a chip; planes without operations are not
                devices[plane.name] = [
                    (ev.start_ns, ev.end_ns, head(ev.name))
                    for ln in lines for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.end_ns, ev.name)
                             for ev in line.events if ev.name in SPANS)
    return devices, spans


def summarize(devices: dict, spans: list, top: int = 10) -> dict:
    """Busy and idle time of the traced window.

    The window runs from the first host span's start to the last one's
    end. A device is busy where any of its leaf operations runs (the
    union of their intervals); shares are means over the devices. Idle
    gaps are named by the host span in progress at the gap's middle and
    by whether an enclosing operation (a device loop) spans it, and
    ``any_op_ns`` is the time in which any operation, an enclosing one
    included, was in progress. ``collective_ns_total`` is the time of the
    leaf operations whose opcode exchanges data between chips
    (:data:`COLLECTIVE_OPS`), summed over the devices as
    ``busy_ns_total`` is. An operation is named by its :func:`op_head` or
    a bare name; ``device_ops`` names it by :func:`op_name`."""
    if not spans:
        raise ValueError("no benchmark spans in the trace")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    window = hi - lo
    busy, any_op = [], []
    op_time: dict = defaultdict(int)
    gaps: list = []
    for name, ops in sorted(devices.items()):
        ops = [(s, e, n) for s, e, n in ops if min(e, hi) > max(s, lo)]
        leaf, outer = leaves(ops)
        merged = union_ns(clip([(s, e) for s, e, _ in leaf], lo, hi))
        loops = union_ns(clip([(s, e) for s, e, _ in outer], lo, hi))
        starts = [s for s, _ in loops]
        busy.append(sum(e - s for s, e in merged))
        any_op.append(covered_ns(clip([(s, e) for s, e, _ in ops], lo, hi)))
        for s, e, n in leaf:
            op_time[n] += min(e, hi) - max(s, lo)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = (a + b) // 2
                host = [n for s, e, n in spans if s <= mid < e]
                where = host[-1] if host else "between spans"
                k = bisect.bisect_right(starts, mid) - 1
                if k >= 0 and mid < loops[k][1]:
                    where += ", inside a device loop"
                gaps.append((b - a, where))
    n_dev = max(len(busy), 1)
    by_name: dict = defaultdict(int)
    for n, t in op_time.items():
        by_name[op_name(n)] += t
    by_span: dict = defaultdict(int)
    for d, n in gaps:
        by_span[n] += d
    return {
        "window_ns": window,
        "n_devices": len(busy),
        "busy_ns": sum(busy) / n_dev,
        "busy_ns_total": sum(busy),
        "any_op_ns": sum(any_op) / n_dev,
        "collective_ns_total": sum(
            t for n, t in op_time.items()
            if op_code(n).startswith(COLLECTIVE_OPS)),
        "device_ops": sorted(by_name.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((n, d / n_dev) for n, d in by_span.items()),
                            key=lambda kv: -kv[1])[:top],
    }
