"""What the program tells a profiler and what it counts: host spans in
the sweep engine, stage scopes in the device program, the compile-time
counters of the compilation cache, and the padding rows of a sweep."""

import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import catalog
from repro.core import policy as P
from repro.core import stream as xstream
from repro.core import xdes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# reading an event's arguments builds a type JAX's profiler bindings name
# without a module; Python warns about that once
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")

ROLLOUT_SCOPES = ("rollout.init", "rollout.block", "rollout.exit_vote",
                  "rollout.out")
STAGE_SCOPES = ("gps_advance", "fault_rewind", "spin_exhaust", "wake",
                "release", "handoff", "wake_quota", "backoff_poll",
                "arrivals", "tickets", "oracle", "step_limit")
OPEN_SCOPES = ("open_admit", "open_depart", "open_bind")


def _host_spans(log_dir) -> list:
    """``(start_ns, end_ns, name, stats)`` of every host event whose name
    starts with ``stream.`` or is ``caller``."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        out.extend((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("stream.") or ev.name == "caller")
    return out


def _parent(spans, child):
    """The innermost other span that encloses ``child``."""
    s, e = child[0], child[1]
    outer = [p for p in spans if p is not child and p[0] <= s
             and e <= p[1]]
    return min(outer, key=lambda p: p[1] - p[0])[2] if outer else None


def test_sweep_records_nested_stream_spans(tmp_path):
    cols = catalog.lock_discipline_columns(2)            # 30 configs
    red = xstream.CellReduce(group=15, cell_ids=np.zeros(2, np.int32),
                             n_cells=1)
    kw = dict(n_steps=8, block_steps=4, chunk=15, shard=False,
              max_threads=32, reduce=red)
    xstream.sweep_stream(cols, **kw)                     # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("caller"):
            res = xstream.sweep_stream(cols, **kw)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = [n for _, _, n, _ in spans]
    assert names.count("stream.sweep") == 1
    for name in ("stream.encode", "stream.plan"):
        assert names.count(name) == 1
    chunks = [sp for sp in spans if sp[2] == "stream.chunk"]
    assert len(chunks) == res.n_chunks == 2
    assert [c[3]["index"] for c in chunks] == [0, 1]
    assert all(c[3]["rows"] == 15 and c[3]["padded_rows"] == 0
               and c[3]["horizon"] == 8 for c in chunks)
    for name in ("stream.dispatch", "stream.wait", "stream.quarantine",
                 "stream.reduce"):
        assert names.count(name) >= 2, name
    parents = {"stream.sweep": "caller", "stream.encode": "stream.sweep",
               "stream.plan": "stream.sweep", "stream.chunk": "stream.sweep",
               "stream.dispatch": "stream.chunk",
               "stream.quarantine": "stream.sweep",
               "stream.reduce": "stream.sweep"}
    for sp in spans:
        if sp[2] in parents:
            assert _parent(spans, sp) == parents[sp[2]], sp[2]
    # a chunk's readback waits inside it; the win table's, after the loop
    waits = [_parent(spans, sp) for sp in spans if sp[2] == "stream.wait"]
    assert waits == ["stream.chunk", "stream.chunk", "stream.sweep"]
    # no checkpoint manager, no checkpoint span
    assert "stream.checkpoint" not in names


def test_checkpoint_span_only_with_a_manager(tmp_path):
    cols = catalog.lock_discipline_columns(1)
    kw = dict(n_steps=8, block_steps=4, chunk=8, shard=False,
              max_threads=32)
    xstream.sweep_stream(cols, **kw)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        xstream.sweep_stream(cols, checkpoint_dir=str(tmp_path / "ck"),
                             **kw)
    finally:
        jax.profiler.stop_trace()
    names = [n for _, _, n, _ in _host_spans(tmp_path / "trace")]
    # one at set-up, one per committed chunk (15 configs in chunks of 8)
    assert names.count("stream.checkpoint") == 3


@pytest.mark.parametrize("columns", ["closed", "open"])
def test_lowered_rollout_names_every_stage(columns):
    cols = (catalog.lock_discipline_columns(1) if columns == "closed"
            else catalog.lock_arrival_columns(1))
    arrs = P.encode_columns(cols)
    arrs["dt"] = np.full(len(arrs["policy"]), 1e-7, np.float32)
    open_loop = columns == "open"
    text = xdes._simulate_dyn.lower(
        arrs, np.int32(8), T=32, backend="ref", rollout="blocked",
        block_steps=4, target_cs=np.int32(5), early_exit=True,
        keep_per_thread=False, open_loop=open_loop,
    ).as_text(debug_info=True)
    scopes = ROLLOUT_SCOPES + STAGE_SCOPES + (OPEN_SCOPES if open_loop
                                              else ())
    for scope in scopes:
        assert re.search(r'["/]' + re.escape(scope) + "/", text), scope
    if not open_loop:
        for scope in OPEN_SCOPES:
            assert scope not in text


def test_cell_reduce_is_scoped():
    import jax.numpy as jnp

    text = xstream._cell_update.lower(
        jnp.zeros((2, 3), jnp.int32), jnp.ones(6, jnp.int32),
        jnp.ones(6, jnp.float32), jnp.zeros(2, jnp.int32), group=3,
    ).as_text(debug_info=True)
    assert "cell_reduce/" in text


_COMPILE_SCRIPT = """
import json, sys
import jax, jax.numpy as jnp
from repro.compile_cache import compile_seconds, enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
scoped = sys.argv[1] == "scoped"

def f(x):
    with jax.named_scope("stage_a" if scoped else "stage_b"):
        return jnp.sin(x) * 3 + jnp.cos(x)

x, y = jnp.ones(64), jnp.zeros(64)
fn = jax.jit(f)
reads = [compile_seconds()]
fn(x).block_until_ready()
reads.append(compile_seconds())
fn(y).block_until_ready()                 # the same shapes: cached
reads.append(compile_seconds())
text = fn.lower(x).compile().as_text()
print(json.dumps({"reads": reads, "stage_a": "stage_a" in text}))
"""


def _compile_run(cache, mode):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", _COMPILE_SCRIPT, mode],
                         capture_output=True, text=True, timeout=300,
                         cwd=str(cache.parent), env=env)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_seconds_count_fresh_compiles_and_cache_keys(tmp_path):
    cache = tmp_path / "cache"
    first = _compile_run(cache, "plain")
    r0, r1, r2 = first["reads"]
    assert set(r0) == {"trace", "lower", "backend", "cache_load", "total",
                       "hits", "misses"}
    assert r1["total"] > r0["total"] and r1["backend"] > 0
    assert r1["trace"] > 0 and r1["lower"] > 0
    assert r1["total"] == pytest.approx(
        r1["trace"] + r1["lower"] + r1["backend"])
    assert r1["misses"] >= 1 and r1["hits"] == 0
    assert r2["total"] == r1["total"]           # a cached call: nothing
    # a second process loads every executable from the cache
    again = _compile_run(cache, "plain")["reads"][1]
    assert again["hits"] >= 1 and again["misses"] == 0
    assert again["cache_load"] > 0
    # code that differs only in its scope names is another key, so a
    # profile never reads the scopes of the code that wrote the cache
    scoped = _compile_run(cache, "scoped")
    assert scoped["reads"][1]["misses"] >= 1
    assert scoped["stage_a"]


@pytest.mark.parametrize("group,padded", [(1, 512), (15, 0)])
def test_padded_rows_count_the_chunk_ladder(group, padded):
    # 7,680 configurations: alone they pad to the ladder's 8,192; in
    # reduction groups of 15 the ladder is 15 x 512 = 7,680 exactly
    cols = catalog.lock_discipline_columns(512)
    C = len(cols["lock"])
    assert C == 7680
    red = (None if group == 1 else xstream.CellReduce(
        group=group, cell_ids=np.zeros(C // group, np.int32), n_cells=1))
    res = xstream.sweep_stream(cols, n_steps=2, block_steps=2, shard=False,
                               max_threads=32, reduce=red,
                               early_exit=False)
    assert res.n_chunks == 1
    assert res.padded_rows == padded
    assert res.row_steps == (C + padded) * 2
    assert int(res.steps_run.sum()) == C * 2
