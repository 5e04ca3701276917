"""Compile the fused rollout kernel for a TPU v5e, ahead of time.

Nothing here runs on a chip: the TPU compiler is handed a described v5e
and the kernel's shapes, and refuses what the chip's compiler would
refuse (an unsupported primitive, more scoped VMEM than the limit).
Interpret mode cannot show either.  The topology is described inside a
fixture, never while the module is imported, and the tests skip where it
cannot be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import policy as P
from repro.kernels import lock_sim as LS

C, T, SUB_STEPS = 4096, 32, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001 (skip reason)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _block_args(sharding, open_loop):
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=sharding)
    thread = [S((C, T), d) for _, d, _ in LS._THREAD_STATE_SPEC]
    conf = [S((C,), jnp.int32)] * LS._N_CONF
    advance = [S((C,), jnp.float32), S((C,), jnp.float32),
               S((C,), jnp.bool_)]
    ctx = [S((C,), d) for d in LS._CONTEXT_DTYPES[2:]]
    args = (*thread, *conf, S((C,), jnp.float32), S((), jnp.int32),
            *advance, *ctx)
    open_state = None
    if open_loop:
        open_state = (S((C, T), jnp.float32),
                      S((C, P.QUEUE_MAX), jnp.float32),
                      S((C, P.LAT_NBINS), jnp.int32)) \
            + tuple(S((C,), d) for d in LS._OPEN_COL_DTYPES)
    return args, S((), jnp.int32), open_state


@pytest.mark.parametrize("open_loop", [False, True], ids=["closed", "open"])
def test_lock_sim_block_compiles_for_v5e(one_chip, open_loop):
    args, limit, open_state = _block_args(one_chip, open_loop)
    compiled = LS.lock_sim_block.lower(
        *args, n_sub_steps=SUB_STEPS, block_configs=LS.BLOCK_CONFIGS,
        interpret=False, limit=limit, open_state=open_state).compile()
    assert "tpu_custom_call" in compiled.as_text()
