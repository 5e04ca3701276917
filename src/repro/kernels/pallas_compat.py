"""The one import site of the Pallas TPU compiler parameters, and the
shared backend auto-detect for every ``pallas_call`` site.

:func:`default_interpret` picks interpret mode only when no accelerator is
attached (CPU hosts, CI), compiled lowering on real GPU/TPU devices.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental.pallas.tpu import CompilerParams


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """True iff Pallas should run in interpret mode on this host.

    Kernels take ``interpret: bool | None = None`` and resolve ``None``
    through this helper: interpret on CPU-only hosts (Pallas has no CPU
    lowering), compiled on any attached GPU/TPU.  Pass an explicit bool to
    override (tests pin ``interpret=True`` for determinism on CPU).
    """
    return jax.default_backend() not in ("gpu", "tpu", "cuda", "rocm")


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> :func:`default_interpret`; bools pass through."""
    return default_interpret() if interpret is None else bool(interpret)


__all__ = ["CompilerParams", "default_interpret", "resolve_interpret"]
