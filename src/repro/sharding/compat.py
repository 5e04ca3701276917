"""The one import site of ``shard_map`` (sibling of
``repro.kernels.pallas_compat``): model, train and engine code import
:func:`shard_map` from here, with the ``check_vma`` / ``axis_names``
keywords of ``jax.shard_map``."""

from __future__ import annotations

from jax import shard_map

__all__ = ["shard_map"]
