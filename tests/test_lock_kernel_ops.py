"""The Mosaic-compilable spellings in :mod:`repro.kernels.ref` are exactly
the jnp primitives they replace (uint32 -> float32, lane cumsum, first
True, the ring read), so the fused kernel keeps every simulated bit."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import (count_scale, first_true, lane_cumsum,
                               ring_take, u32_to_f32)

EDGES = np.array([0, 1, 2**16 - 1, 2**16, 2**24 - 1, 2**24, 2**24 + 1,
                  2**24 + 3, 2**25 + 2, 2**31 - 1, 2**31, 0xFFFFFF7F,
                  0xFFFFFF80, 0xFFFFFF81, 0xFFFFFFFE, 0xFFFFFFFF],
                 np.uint32)


def _bits(x):
    return np.asarray(x).view(np.uint32)


def test_u32_to_f32_edges():
    x = jnp.asarray(EDGES)
    np.testing.assert_array_equal(_bits(u32_to_f32(x)),
                                  _bits(x.astype(jnp.float32)))


@pytest.mark.parametrize("seed", [0, 1])
def test_u32_to_f32_random(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 2**32, 1 << 20, dtype=np.uint64)
                    .astype(np.uint32))
    np.testing.assert_array_equal(_bits(u32_to_f32(x)),
                                  _bits(x.astype(jnp.float32)))


def test_u32_to_f32_rounding_ties():
    # values exactly halfway between two float32 neighbours, above 2**24:
    # round-to-nearest-even must pick the same neighbour as the direct cast
    base = np.arange(2**24, 2**24 + 4096, dtype=np.uint64)
    for shift in (1, 4, 7):
        x = jnp.asarray(((base << shift) + (1 << (shift - 1)))
                        .astype(np.uint32))
        np.testing.assert_array_equal(_bits(u32_to_f32(x)),
                                      _bits(x.astype(jnp.float32)))


@pytest.mark.parametrize("T", [1, 7, 32, 128])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_lane_cumsum_matches_cumsum(T, density):
    rng = np.random.default_rng(T)
    mask = jnp.asarray(rng.random((257, T)) < density)
    got = lane_cumsum(mask)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        got, jnp.cumsum(mask.astype(jnp.int32), axis=-1))


@pytest.mark.parametrize("T", [1, 7, 32, 128])
def test_first_true_matches_argmax(T):
    rng = np.random.default_rng(T)
    mask = rng.random((513, T)) < 0.1
    mask[:3] = False                           # rows with no True
    mask[3, -1] = True
    got = np.asarray(first_true(jnp.asarray(mask)))[:, 0]
    has = mask.any(axis=-1)
    np.testing.assert_array_equal(got[has], np.argmax(mask, axis=-1)[has])
    assert (got[~has] == T).all()


@pytest.mark.parametrize("T", [1, 7, 32, 128])
def test_ring_take_matches_take_along_axis(T):
    """Every ring head 0..127 (so every wrap-around), slots as the open
    bind forms them, rings holding -0.0, repeats and +-inf."""
    Q = 128
    rng = np.random.default_rng(T)
    qbuf = rng.standard_normal((Q, Q)).astype(np.float32)
    qbuf[:, ::5] = -0.0
    qbuf[:, 1::7] = 0.0
    qbuf[:, 2::11] = np.inf
    qbuf[:, 3::13] = -np.inf
    qbuf[:, 4::3] = qbuf[:, 4:5]               # repeated values
    qhead = np.arange(Q, dtype=np.int32)[:, None]
    rank = np.sort(rng.integers(-1, T, (Q, T)), axis=1).astype(np.int32)
    qpos = jnp.asarray((qhead + rank) % Q)
    got = ring_take(jnp.asarray(qbuf), qpos)
    want = jnp.take_along_axis(jnp.asarray(qbuf), qpos, axis=1)
    assert got.dtype == want.dtype and got.shape == (Q, T)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_count_scale_rounds_once_even_when_fused():
    """``acc + count_scale(x, n)`` inside one jitted expression (where
    XLA:CPU may form FMAs) equals numpy's separately rounded product and
    sum."""
    import jax

    rng = np.random.default_rng(7)
    x = rng.uniform(1e-9, 1e-4, 1 << 16).astype(np.float32)
    n = rng.integers(0, 4096, 1 << 16).astype(np.float32)
    n[:8] = [0, 1, 2, 3, 127, 128, 4094, 4095]
    acc = rng.uniform(0.0, 1e-2, 1 << 16).astype(np.float32)
    got = jax.jit(lambda a, x, n: a + count_scale(x, n))(acc, x, n)
    np.testing.assert_array_equal(_bits(got), _bits(acc + x * n))
    np.testing.assert_array_equal(_bits(count_scale(x, n)), _bits(x * n))


def test_spin_burn_ignores_lane_padding():
    """The per-step spin burn is one rounding of rate x spinner count, so
    padding the thread axis to a full lane tile (as the Pallas kernel
    does) cannot change a bit of it."""
    from repro.core.policy import DONE
    from repro.kernels.ref import lock_sim_step_ref

    rng = np.random.default_rng(5)
    C, T = 301, 29
    st = rng.integers(0, 6, (C, T)).astype(np.int32)
    rem = rng.uniform(0.0, 1e-4, (C, T)).astype(np.float32)
    alpha = rng.uniform(0.0, 0.1, C).astype(np.float32)
    cores = rng.integers(1, 33, C).astype(np.float32)
    dt = rng.uniform(1e-7, 2e-6, C).astype(np.float32)
    hb = rng.integers(0, 2, C).astype(bool)
    pad = ((0, 0), (0, 128 - T))
    _, burn = lock_sim_step_ref(st, rem, alpha, cores, dt, hb)
    _, burn_p = lock_sim_step_ref(np.pad(st, pad, constant_values=DONE),
                                  np.pad(rem, pad), alpha, cores, dt, hb)
    np.testing.assert_array_equal(_bits(burn), _bits(burn_p))
