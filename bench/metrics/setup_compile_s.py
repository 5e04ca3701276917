"""``setup_compile_s`` (s): the seconds the process spent compiling, as
the program counts them from JAX's own compile events
(``compile_seconds()["total"]`` beside ``enable_compile_cache`` in the
program's compile-cache module): tracing to jaxprs, lowering to MLIR,
and backend compiles or loads from the persistent cache. The counters
start when the harness enables the cache, just before the warm-up sweep,
and the window compiles nothing (the harness counts and prints that), so
this is the warm-up's compile time. A program without the counter reads
nothing. Moves ``setup_s``."""

import importlib

from bench import entry


def read(record: dict) -> float | None:
    # entry.py names enable_compile_cache alone; its module holds the
    # counters
    module = importlib.import_module(entry.enable_compile_cache.__module__)
    counter = getattr(module, "compile_seconds", None)
    if counter is None:
        return None
    return float(counter()["total"])
