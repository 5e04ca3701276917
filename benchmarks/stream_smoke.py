"""CI smoke for the streaming sweep engine: bounded-memory proof.

Runs a 20k-config discipline sweep through
:func:`repro.core.stream.sweep_stream` under a deliberately SMALL memory
budget (default 16 MiB, forcing many chunks) and asserts, in order:

* the chunk plan respects the budget — ``chunk_size x bytes_per_config``
  fits the resolved budget (or the plan bottomed out at one group);
* the run actually streamed (``n_chunks > 1`` at this scale);
* peak-RSS growth over the run (``resource.getrusage`` high-water mark,
  snapshotted after a small warmup that loads jax and compiles the
  kernels) stays under ``--rss-ceiling-mb`` — the observable guarantee
  that a 20k sweep never materializes its full ``(C, T)`` state on host.

Exit status is the contract: 0 = streamed within budget, 1 = any assert
failed.  CI runs this next to the tier-1 tests; scale or budget can be
overridden for local experiments:

    PYTHONPATH=src python -m benchmarks.stream_smoke \\
        [--configs 20000] [--mem-mb 16] [--rss-ceiling-mb 512]
"""

from __future__ import annotations

import argparse
import resource
import time


def _maxrss_mb() -> float:
    # ru_maxrss is KiB on Linux (bytes on macOS; this smoke runs on CI's
    # Linux runners where the tier-1 suite runs).
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", type=int, default=20_000)
    ap.add_argument("--target-cs", type=int, default=20)
    ap.add_argument("--mem-mb", type=float, default=16.0,
                    help="streaming budget — small on purpose, so the "
                         "20k sweep MUST chunk")
    ap.add_argument("--rss-ceiling-mb", type=float, default=512.0,
                    help="max allowed peak-RSS growth over the streamed "
                         "run (measured from the post-warmup high-water "
                         "mark)")
    args = ap.parse_args(argv)

    from repro.configs.catalog import (lock_discipline_columns,
                                       lock_discipline_variants)
    from repro.core import stream as xstream

    V = len(lock_discipline_variants())
    n_scenarios = max(1, args.configs // V)
    C = n_scenarios * V

    # Warmup: touch the whole path at toy scale so jax import, kernel
    # compiles, and allocator pools land in the RSS baseline, not the
    # measured growth.
    xstream.sweep_stream(lock_discipline_columns(n_scenarios=8),
                         target_cs=5, backend="ref", bucket_steps=True,
                         mem_mb=args.mem_mb)
    rss0 = _maxrss_mb()

    cols = lock_discipline_columns(n_scenarios=n_scenarios)
    t0 = time.perf_counter()
    res = xstream.sweep_stream(cols, target_cs=args.target_cs,
                               backend="ref", bucket_steps=True,
                               mem_mb=args.mem_mb)
    wall = time.perf_counter() - t0
    rss1 = _maxrss_mb()
    grown = rss1 - rss0

    budget_bytes = res.budget_mb * (1 << 20)
    chunk_bytes = res.chunk_size * res.bytes_per_config
    print(f"stream smoke: {C} configs in {res.n_chunks} chunk(s) of "
          f"<= {res.chunk_size} ({wall:.1f}s, {C / wall:.0f} cfg/s); "
          f"chunk footprint {chunk_bytes / 2**20:.1f} MB of "
          f"{res.budget_mb:.0f} MB budget; peak RSS {rss1:.0f} MB "
          f"(+{grown:.0f} MB over warmup baseline, ceiling "
          f"{args.rss_ceiling_mb:.0f} MB)")

    failures = []
    # a plan may exceed a too-small budget only when floored at one
    # group (chunk_size == V on a single device)
    if chunk_bytes > budget_bytes and res.chunk_size > V:
        failures.append(f"chunk plan over budget: {chunk_bytes} B > "
                        f"{budget_bytes:.0f} B")
    if res.n_chunks <= 1:
        failures.append(f"did not stream: {res.n_chunks} chunk at "
                        f"C={C}, budget {args.mem_mb} MB")
    if grown > args.rss_ceiling_mb:
        failures.append(f"peak RSS grew {grown:.0f} MB > ceiling "
                        f"{args.rss_ceiling_mb:.0f} MB")
    if failures:
        for line in failures:
            print(f"FAIL: {line}")
        raise SystemExit(1)
    print("stream smoke: OK")
    return {"n_configs": C, "n_chunks": res.n_chunks,
            "chunk_size": res.chunk_size, "wall_s": wall,
            "rss_grown_mb": grown}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
