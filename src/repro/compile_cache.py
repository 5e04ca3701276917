"""JAX's persistent compilation cache, placed before the first compile,
and the seconds compilation costs.

A compile of the full-size rollout takes tens of seconds per padded
shape, and a fresh process pays it again unless the executable is cached
on disk.  An entry is found again only in the directory it was written
to, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is
set (and no other), else
the fixed, git-ignored ``.jax_cache/`` at the repository root.  Entry
points (``chip_smoke.py`` and the ``benchmarks/`` CLIs) call
:func:`enable_compile_cache` before they compile anything; library code
never does.

The cache key includes the program's metadata (source locations and the
``jax.named_scope`` names that profiles read): by default JAX strips it,
so a cache written by other code with the same operations would serve
an executable whose profile names stale or missing scopes.

:func:`enable_compile_cache` also registers listeners on JAX's own
compile events, and :func:`compile_seconds` returns what they added up
since: seconds spent tracing to a jaxpr, lowering to MLIR, and in the
backend (a compile, or a load from the cache), and the cache's hits and
misses.
"""

from __future__ import annotations

import os
import time

import jax
import jax.monitoring

#: Environment variable that, when set, names the only cache directory.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: The fixed default: ``<repo>/.jax_cache`` (this file is
#: ``<repo>/src/repro/compile_cache.py``).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: The phase :func:`compile_seconds` names for each of JAX's duration
#: events.  ``backend`` holds a cache load too: JAX times the cache lookup
#: inside its backend compile event, and times the load alone as well.
PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_HITS = "/jax/compilation_cache/cache_hits"

# JAX's compile events are process-wide, and so are these counters.  Per
# phase, the disjoint intervals (on time.monotonic) its events covered,
# merged as they arrive: a jit traced inside another's trace reports its
# own event, and its seconds are already in the outer one.
_spans: dict = {p: [] for p in PHASES.values()}
_seconds: dict = {p: 0.0 for p in PHASES.values()}
_counts = {"requests": 0, "hits": 0}
_listening = False


def compile_cache_dir() -> str:
    """The directory the cache lives in (see the module docstring)."""
    return os.environ.get(ENV_CACHE_DIR) or REPO_CACHE_DIR


def _on_duration(event: str, duration: float, **kw) -> None:
    phase = PHASES.get(event)
    if phase is None:
        return
    end = time.monotonic()
    start = end - duration
    spans = _spans[phase]
    # every earlier span ended before now: pop those this one reaches back
    # over, and keep the union
    while spans and spans[-1][1] >= start:
        s, e = spans.pop()
        _seconds[phase] -= e - s
        start = min(start, s)
    spans.append((start, end))
    _seconds[phase] += end - start


def _on_event(event: str, **kw) -> None:
    if event == _REQUESTS:
        _counts["requests"] += 1
    elif event == _HITS:
        _counts["hits"] += 1


def compile_seconds() -> dict:
    """Totals since :func:`enable_compile_cache` first ran: seconds per
    phase (``trace``, ``lower``, ``backend``, of which ``cache_load``),
    ``total`` (trace + lower + backend), and the persistent cache's
    ``hits`` and ``misses`` (lookups that found no executable)."""
    out = dict(_seconds)
    out["total"] = out["trace"] + out["lower"] + out["backend"]
    out["hits"] = _counts["hits"]
    out["misses"] = _counts["requests"] - _counts["hits"]
    return out


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir`, key it on the program's metadata too, start
    counting :func:`compile_seconds`, and return the directory."""
    global _listening
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path
