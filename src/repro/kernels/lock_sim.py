"""Pallas kernels for the batched lock simulator.

The stages of one :mod:`repro.core.xdes` timestep live here as fused
kernels, bit-identical to their XLA references in :mod:`repro.kernels.ref`:

* :func:`lock_sim_block` — the time-blocked rollout kernel (the default
  engine path): GPS advance + oracle update + transitions iterated for
  ``n_sub_steps`` timesteps in ONE dispatch, the whole
  ``(block_configs, T)`` state block staying in VMEM/registers across the
  inner loop.  The body IS
  :func:`repro.kernels.ref.lock_sim_block_ref` applied per block.
* :func:`lock_sim_step` — the standalone GPS advance: runnable counts, the
  generalized-processor-sharing rate ``min(1, cores/n_runnable)``, the
  cache-contention slowdown of the CS holder (``1/(1 + alpha·n_spinners)``,
  paper §2), work advance and spin-CPU burn — one VMEM-resident pass over
  the ``(configs, threads)`` state block (the legacy per-step scan path).
* :func:`lock_transitions_step` — the transition stage (budget exhaustion,
  wake completions, release/handoff with discipline-row dispatch incl.
  FIFO ticket grants, arrivals) as a grid over config blocks.  The kernel
  body IS :func:`repro.kernels.ref.lock_transitions_ref` applied to each
  block, so ref and Pallas backends share one implementation and stay
  bit-identical by construction (and by test).
* :func:`oracle_step` — the standalone fused SWS-oracle observation.

Rows are configurations (grid-parallel); the thread axis stays whole in
VMEM (T ≤ 128 lanes after padding — a few KB per row).  ``interpret=None``
auto-detects: interpret mode on CPU-only hosts, compiled lowering when a
GPU/TPU is attached (:func:`repro.kernels.pallas_compat.default_interpret`).

The row-registry contract: all policy decisions inside these kernels —
oracle families, waiting disciplines, workload hold-time models — come
from the registries in :mod:`repro.core.policy` (``ORACLE_ROWS``,
``DISCIPLINE_ROWS``, ``WORKLOAD_ROWS``), dispatched per config by integer
columns with masked arithmetic selects.  Adding a row therefore never
touches this module: the Pallas kernels apply the *ref* bodies per block,
so a row lands in :mod:`repro.kernels.ref` once and both lowerings stay
bit-identical by construction.  When changing kernel signatures, update
the context tuples in lockstep: ``TRANSITION_CONTEXT``/``BLOCK_CONTEXT``
(ref), ``_CONTEXT_DTYPES``/``_BLOCK_CTX_DTYPES`` (here) and
``_PRM_FIELDS`` (:mod:`repro.core.xdes`).  Blocked-rollout invariants:
``now2 = (step0 + s + 1) * dt`` with the step index carried in int32, and
``spin_cpu`` accumulated inside the inner loop — both required for the
blocked path to stay bit-identical to the per-step scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.policy import CS, NCS, SPIN, oracle_update

from .pallas_compat import CompilerParams, resolve_interpret
from .ref import (NO_TICKET, count_scale, lock_sim_block_ref,
                  lock_transitions_ref)

LANE = 128          # TPU lane width: thread axis is padded to this


def _kernel(state_ref, rem_ref, alpha_ref, cores_ref, dt_ref, budget_ref,
            rem_out_ref, burn_out_ref):
    st = state_ref[...]                                       # (bc, T) int32
    rem = rem_ref[...]                                        # (bc, T) f32
    is_cs = st == CS
    is_ncs = st == NCS
    is_spin = st == SPIN
    n_run = jnp.sum((is_cs | is_ncs | is_spin).astype(jnp.float32),
                    axis=-1, keepdims=True)                   # (bc, 1)
    n_spin = jnp.sum(is_spin.astype(jnp.float32), axis=-1, keepdims=True)
    cores = cores_ref[...]                                    # (bc, 1)
    rate = jnp.minimum(1.0, cores / jnp.maximum(n_run, 1.0))
    holder_rate = rate / (1.0 + alpha_ref[...] * n_spin)
    dt = dt_ref[...]                                          # (bc, 1)
    d_rate = dt * rate
    burn = jnp.where(is_spin, d_rate, 0.0)
    dec = (jnp.where(is_cs, dt * holder_rate, 0.0)
           + jnp.where(is_ncs, d_rate, 0.0)
           + jnp.where(budget_ref[...] > 0, burn, 0.0))
    rem_out_ref[...] = rem - dec
    burn_out_ref[...] = count_scale(d_rate, n_spin)


@functools.partial(jax.jit, static_argnames=("block_configs", "interpret"))
def lock_sim_step(tstate, rem, alpha, cores, dt, has_budget, *,
                  block_configs: int = 256, interpret: bool | None = None):
    """Pallas-fused GPS advance; signature mirrors ``lock_sim_step_ref``.

    tstate: (C, T) int32; rem: (C, T) f32; alpha/cores/dt: (C,) f32;
    has_budget: (C,) bool.  Returns ``(rem', spin_burn)``.
    ``interpret=None`` auto-detects the backend (interpret iff no GPU/TPU).
    """
    interpret = resolve_interpret(interpret)
    C, T = tstate.shape
    bc = min(block_configs, C)
    pc = (-C) % bc
    pt = (-T) % LANE
    # Pad threads to the lane width with DONE-state slots (no rate effect)
    # and configs to the block size.
    st2 = jnp.pad(tstate, ((0, pc), (0, pt)), constant_values=5)  # DONE
    rem2 = jnp.pad(rem, ((0, pc), (0, pt)))
    col = lambda v, dt_: jnp.pad(v.astype(dt_), (0, pc))[:, None]
    nc = (C + pc) // bc

    rem_new, burn = pl.pallas_call(
        _kernel,
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((bc, T + pt), lambda i: (i, 0)),
            pl.BlockSpec((bc, T + pt), lambda i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bc, T + pt), lambda i: (i, 0)),
            pl.BlockSpec((bc, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((C + pc, T + pt), jnp.float32),
            jax.ShapeDtypeStruct((C + pc, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=CompilerParams(dimension_semantics=("parallel",)),
    )(st2, rem2, col(alpha, jnp.float32), col(cores, jnp.float32),
      col(dt, jnp.float32), col(has_budget, jnp.int32))
    return rem_new[:C, :T], burn[:C, 0]


# --------------------------------------------------------------------------
# Fused SWS-oracle observation: one elementwise pass over (C,) config
# vectors evaluating every oracle family row (paper EvalSWS / AIMD /
# fixed-budget / history, repro.core.policy.ORACLE_ROWS) and selecting by
# oracle id, A16-A17 clamp included.  This is the building block for
# moving the scan body's transition stage into the kernel (scalar-prefetch
# grid over configs); the batched simulator evaluates the same rows today
# via repro.core.policy inside its vmapped transition step, and tests pin
# kernel == ref == scalar rows bit-identically.
# --------------------------------------------------------------------------
def _oracle_kernel(oid_ref, spun_ref, slept_ref, sws_ref, cnt_ref,
                   ewma_ref, k_ref, smax_ref,
                   delta_out_ref, cnt_out_ref, ewma_out_ref):
    sws = sws_ref[...]
    delta, cnt1, ewma1 = oracle_update(
        oid_ref[...], spun_ref[...], slept_ref[...], sws,
        cnt_ref[...], ewma_ref[...], k_ref[...])
    delta_out_ref[...] = jnp.clip(delta, 1 - sws, smax_ref[...] - sws)
    cnt_out_ref[...] = cnt1
    ewma_out_ref[...] = ewma1


@functools.partial(jax.jit, static_argnames=("block_configs", "interpret"))
def oracle_step(oracle_id, spun, slept, sws, cnt, ewma, k, sws_max, *,
                block_configs: int = 1024, interpret: bool | None = None):
    """Pallas-fused oracle observation; signature mirrors
    :func:`repro.kernels.ref.oracle_update_ref`.

    All inputs ``(C,)``: ``oracle_id/sws/cnt/ewma/k/sws_max`` int32,
    ``spun``/``slept`` bool or 0/1 int32.  Returns ``(delta, cnt', ewma')``
    int32 with the A16-A17 clamp applied to ``delta``.
    ``interpret=None`` auto-detects the backend (interpret iff no GPU/TPU).
    """
    interpret = resolve_interpret(interpret)
    C = oracle_id.shape[0]
    bc = min(block_configs, C)
    pc = (-C) % bc
    nc = (C + pc) // bc
    col = lambda v: jnp.pad(v.astype(jnp.int32), (0, pc))[:, None]
    spec = pl.BlockSpec((bc, 1), lambda i: (i, 0))

    delta, cnt1, ewma1 = pl.pallas_call(
        _oracle_kernel,
        grid=(nc,),
        in_specs=[spec] * 8,
        out_specs=[spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((C + pc, 1), jnp.int32)] * 3,
        interpret=interpret,
        compiler_params=CompilerParams(dimension_semantics=("parallel",)),
    )(col(oracle_id), col(spun), col(slept), col(sws), col(cnt),
      col(ewma), col(k), col(sws_max))
    return delta[:C, 0], cnt1[:C, 0], ewma1[:C, 0]


# --------------------------------------------------------------------------
# Fused transition stage: the whole discipline-row state machine (budget
# exhaustion -> wakes -> release/handoff -> arrivals) as ONE kernel over
# (block_configs, T) state blocks.  The body is literally
# repro.kernels.ref.lock_transitions_ref applied per block, so the two
# backends cannot drift: same code, same dtypes, bit-identical results
# (padded thread lanes sit in DONE state and padded config rows have
# threads=0, so neither contributes to any mask or reduction).
# --------------------------------------------------------------------------

#: (name, dtype, thread-axis pad value) of the 8 (C, T) state arrays, in
#: the canonical TRANSITION_THREAD_STATE order.
_THREAD_STATE_SPEC = (
    ("st", jnp.int32, 5),               # DONE — inert in every mask
    ("rem", jnp.float32, 0),
    ("wake_at", jnp.float32, 0),
    ("slept", jnp.int32, 0),
    ("spun", jnp.int32, 0),
    ("ctr", jnp.uint32, 0),
    ("ticket", jnp.int32, NO_TICKET),
    ("completed_pt", jnp.int32, 0),
)

#: dtypes of the 29 per-config context columns (TRANSITION_CONTEXT order).
_CONTEXT_DTYPES = (
    jnp.float32,                        # now2
    jnp.int32,                          # stepi (per-step RNG counter)
    jnp.int32, jnp.int32,               # policy, threads
    jnp.float32, jnp.float32,           # dt, wake
    jnp.float32, jnp.float32, jnp.float32, jnp.float32,  # cs/ncs lo/hi
    jnp.int32, jnp.int32,               # k, sws_max
    jnp.float32,                        # spin_budget
    jnp.uint32, jnp.int32,              # seed, oracle
    jnp.int32,                          # workload
    jnp.float32, jnp.float32, jnp.float32, jnp.float32,  # wl_* knobs
    jnp.int32, jnp.float32,             # arrival, arr_rate
    jnp.int32, jnp.float32, jnp.int32,  # q_cap, slo, tb
    jnp.int32, jnp.float32, jnp.float32,  # fault, flt_rate, flt_scale
    jnp.float32,                        # park_cost
)

_N_THREAD, _N_CONF, _N_CTX = 8, 8, len(_CONTEXT_DTYPES)

#: dtypes of the 8 (C,) open-loop counter columns (OPEN_STATE[3:] order:
#: qhead, qlen, arrived, shed, departed, slo_viol int32; lat_sum, occ_int
#: float32).  The first three OPEN_STATE arrays are 2-d: ``req_t`` (C, T)
#: f32 (padded thread lanes hold the -1 free sentinel), ``qbuf``
#: (C, QUEUE_MAX) f32 and ``hist`` (C, LAT_NBINS) i32 (their second axes
#: are never thread-padded).
_OPEN_COL_DTYPES = (jnp.int32,) * 6 + (jnp.float32,) * 2
_N_OPEN = 3 + len(_OPEN_COL_DTYPES)


def _pad_open(open_state, pc, pt):
    """Pad the 11 OPEN_STATE arrays to the kernel's block grid: config
    rows with copies of zero / free sentinels, thread lanes of ``req_t``
    with -1 (free — inert in the busy count, which is also gated by
    ``threads``)."""
    req_t, qbuf, hist = open_state[:3]
    padded = [jnp.pad(req_t.astype(jnp.float32), ((0, pc), (0, pt)),
                      constant_values=-1.0),
              jnp.pad(qbuf.astype(jnp.float32), ((0, pc), (0, 0))),
              jnp.pad(hist.astype(jnp.int32), ((0, pc), (0, 0)))]
    padded += [jnp.pad(v.astype(d), (0, pc))[:, None]
               for v, d in zip(open_state[3:], _OPEN_COL_DTYPES)]
    return padded


def _open_specs_shapes(open_state, bc, C, pc, Tp, mat, colspec):
    """(in/out specs, out shapes) for the 11 OPEN_STATE kernel operands."""
    Qn = open_state[1].shape[1]
    NBn = open_state[2].shape[1]
    specs = [mat, pl.BlockSpec((bc, Qn), lambda i: (i, 0)),
             pl.BlockSpec((bc, NBn), lambda i: (i, 0))] + [colspec] * 8
    shapes = [jax.ShapeDtypeStruct((C + pc, Tp), jnp.float32),
              jax.ShapeDtypeStruct((C + pc, Qn), jnp.float32),
              jax.ShapeDtypeStruct((C + pc, NBn), jnp.int32)] \
        + [jax.ShapeDtypeStruct((C + pc, 1), d) for d in _OPEN_COL_DTYPES]
    return specs, shapes


def _read_open(orefs):
    """Materialize the open-state refs for the ref body: 2-d arrays whole,
    counter columns squeezed to (C,)."""
    return [orefs[0][...], orefs[1][...], orefs[2][...]] \
        + [r[...][:, 0] for r in orefs[3:]]


def _transitions_kernel(open_run, *refs):
    n_in = _N_THREAD + _N_CONF + _N_CTX + (_N_OPEN if open_run else 0)
    ins, outs = refs[:n_in], refs[n_in:]
    thread = [r[...] for r in ins[:_N_THREAD]]
    conf = [r[...][:, 0] for r in ins[_N_THREAD:_N_THREAD + _N_CONF]]
    base = _N_THREAD + _N_CONF
    ctx = [r[...][:, 0] for r in ins[base:base + _N_CTX]]
    ostate = _read_open(ins[base + _N_CTX:]) if open_run else None
    out = lock_transitions_ref(*thread, *conf, *ctx, open_state=ostate)
    for r, v in zip(outs, out):
        r[...] = v if v.ndim == 2 else v[:, None]


@functools.partial(jax.jit, static_argnames=("block_configs", "interpret"))
def lock_transitions_step(st, rem, wake_at, slept, spun, ctr, ticket,
                          completed_pt, sws, cnt, ewma, wuc, permits,
                          nticket, completed, wake_count,
                          now2, stepi, policy, threads, dt, wake, cs_lo,
                          cs_hi, ncs_lo, ncs_hi, k, sws_max, spin_budget,
                          seed, oracle, workload, wl_period, wl_duty,
                          wl_burst, wl_spread, arrival, arr_rate, q_cap,
                          slo, tb, fault, flt_rate, flt_scale, park_cost, *,
                          open_state=None,
                          block_configs: int = 256,
                          interpret: bool | None = None):
    """Pallas-fused transition stage; signature mirrors
    :func:`repro.kernels.ref.lock_transitions_ref` and returns the same
    16 updated state arrays (27 with ``open_state``, the 11 OPEN_STATE
    arrays appended).  ``interpret=None`` auto-detects the backend
    (interpret iff no GPU/TPU is attached)."""
    interpret = resolve_interpret(interpret)
    C, T = st.shape
    bc = min(block_configs, C)
    pc = (-C) % bc
    pt = (-T) % LANE
    Tp = T + pt
    nc = (C + pc) // bc

    thread_in = []
    for arr, (_, dtype, padval) in zip(
            (st, rem, wake_at, slept, spun, ctr, ticket, completed_pt),
            _THREAD_STATE_SPEC):
        thread_in.append(jnp.pad(arr.astype(dtype), ((0, pc), (0, pt)),
                                 constant_values=padval))
    conf_in = [jnp.pad(v.astype(jnp.int32), (0, pc))[:, None]
               for v in (sws, cnt, ewma, wuc, permits, nticket, completed,
                         wake_count)]
    ctx_in = [jnp.pad(jnp.broadcast_to(jnp.asarray(v, dtype), (C,)),
                      (0, pc))[:, None]
              for v, dtype in zip((now2, stepi, policy, threads, dt, wake,
                                   cs_lo, cs_hi, ncs_lo, ncs_hi, k, sws_max,
                                   spin_budget, seed, oracle, workload,
                                   wl_period, wl_duty, wl_burst, wl_spread,
                                   arrival, arr_rate, q_cap, slo, tb,
                                   fault, flt_rate, flt_scale, park_cost),
                                  _CONTEXT_DTYPES)]

    mat = pl.BlockSpec((bc, Tp), lambda i: (i, 0))
    colspec = pl.BlockSpec((bc, 1), lambda i: (i, 0))
    open_run = open_state is not None
    open_in, open_specs, open_shapes = [], [], []
    if open_run:
        open_in = _pad_open(open_state, pc, pt)
        open_specs, open_shapes = _open_specs_shapes(
            open_state, bc, C, pc, Tp, mat, colspec)
    out = pl.pallas_call(
        functools.partial(_transitions_kernel, open_run),
        grid=(nc,),
        in_specs=[mat] * _N_THREAD + [colspec] * (_N_CONF + _N_CTX)
        + open_specs,
        out_specs=[mat] * _N_THREAD + [colspec] * _N_CONF + open_specs,
        out_shape=[jax.ShapeDtypeStruct((C + pc, Tp), s[1])
                   for s in _THREAD_STATE_SPEC]
        + [jax.ShapeDtypeStruct((C + pc, 1), jnp.int32)] * _N_CONF
        + open_shapes,
        interpret=interpret,
        compiler_params=CompilerParams(dimension_semantics=("parallel",)),
    )(*thread_in, *conf_in, *ctx_in, *open_in)
    nclosed = _N_THREAD + _N_CONF
    res = tuple(v[:C, :T] for v in out[:_N_THREAD]) \
        + tuple(v[:C, 0] for v in out[_N_THREAD:nclosed])
    if open_run:
        o = out[nclosed:]
        res += (o[0][:C, :T], o[1][:C], o[2][:C]) \
            + tuple(v[:C, 0] for v in o[3:])
    return res


# --------------------------------------------------------------------------
# Time-blocked fused simulation kernel: GPS advance + transitions iterated
# for n_sub_steps timesteps in ONE dispatch, with the (block_configs, T)
# state block resident in VMEM/registers across the inner fori_loop.  The
# body is repro.kernels.ref.lock_sim_block_ref applied per block — the
# same single-implementation trick as lock_transitions_step — so ref and
# Pallas blocked rollouts are bit-identical by construction (and by test).
# One dispatch per step-block replaces the legacy two-dispatches-per-step
# scan: 2*B pad/slice round trips and kernel launches become 1 per block.
# --------------------------------------------------------------------------

#: dtypes of the 32 per-config context columns of the block kernel
#: (repro.kernels.ref.BLOCK_CONTEXT order): step0, the step limit, the GPS
#: advance inputs (alpha, cores, has_budget), then TRANSITION_CONTEXT
#: minus now2 and stepi (both recomputed in-block from step0 + s).
_BLOCK_CTX_DTYPES = (jnp.int32, jnp.int32, jnp.float32, jnp.float32,
                     jnp.int32) + _CONTEXT_DTYPES[2:]

_N_BLOCK_CTX = len(_BLOCK_CTX_DTYPES)

#: Config rows per grid step of :func:`lock_sim_block`, and the scoped
#: VMEM it may claim when compiled for a TPU.  Every ``(bc, 1)`` column
#: block takes a whole ``(8, 128)`` lane tile and is double-buffered, so
#: the 41 per-config input and 9 output columns, not the thread state,
#: dominate the footprint.  Least limit that compiles for TPU v5e (AOT,
#: T <= 128 lanes, 32 sub-steps): 10 MiB closed / 14 MiB open at 128 rows,
#: 20 / 30 MiB at 256 rows — over the compiler's 16 MiB default, so the
#: limit is set, with 2x headroom over the open-loop need.
BLOCK_CONFIGS = 128
BLOCK_VMEM_LIMIT = 32 * 2**20


def _block_kernel(n_sub_steps, open_run, *refs):
    n_in = _N_THREAD + 1 + _N_CONF + _N_BLOCK_CTX \
        + (_N_OPEN if open_run else 0)
    ins, outs = refs[:n_in], refs[n_in:]
    thread = [r[...] for r in ins[:_N_THREAD]]
    spin_cpu = ins[_N_THREAD][...][:, 0]
    conf = [r[...][:, 0] for r in ins[_N_THREAD + 1:_N_THREAD + 1 + _N_CONF]]
    base = _N_THREAD + 1 + _N_CONF
    ctx = [r[...][:, 0] for r in ins[base:base + _N_BLOCK_CTX]]
    step0, limit, alpha, cores, hb = ctx[:5]
    ostate = _read_open(ins[base + _N_BLOCK_CTX:]) if open_run else None
    out = lock_sim_block_ref(*thread, *conf, spin_cpu, step0, alpha, cores,
                             hb > 0, *ctx[5:], n_sub_steps=n_sub_steps,
                             limit=limit, open_state=ostate)
    for r, v in zip(outs, out):
        r[...] = v if v.ndim == 2 else v[:, None]


@functools.partial(jax.jit, static_argnames=("n_sub_steps", "block_configs",
                                             "interpret"))
def lock_sim_block(st, rem, wake_at, slept, spun, ctr, ticket,
                   completed_pt, sws, cnt, ewma, wuc, permits, nticket,
                   completed, wake_count, spin_cpu,
                   step0, alpha, cores, has_budget,
                   policy, threads, dt, wake, cs_lo, cs_hi, ncs_lo, ncs_hi,
                   k, sws_max, spin_budget, seed, oracle, workload,
                   wl_period, wl_duty, wl_burst, wl_spread, arrival,
                   arr_rate, q_cap, slo, tb, fault, flt_rate, flt_scale,
                   park_cost, *,
                   n_sub_steps: int, block_configs: int = BLOCK_CONFIGS,
                   interpret: bool | None = None, limit=None,
                   open_state=None):
    """Pallas time-blocked rollout kernel; signature mirrors
    :func:`repro.kernels.ref.lock_sim_block_ref` and returns the same 17
    updated state arrays after ``n_sub_steps`` fused timesteps (28 with
    ``open_state``, the 11 OPEN_STATE arrays appended).  ``step0``
    (int32 scalar or (C,) vector) is the global index of the block's first
    step; ``limit`` (same broadcast, optionally traced) masks sub-steps at
    global index >= limit into exact passthroughs (see the ref twin) and
    defaults to unlimited.  ``interpret=None`` auto-detects the backend
    (interpret iff no GPU/TPU is attached)."""
    interpret = resolve_interpret(interpret)
    if limit is None:
        limit = jnp.int32(2**31 - 1)      # no masked sub-steps
    C, T = st.shape
    bc = min(block_configs, C)
    pc = (-C) % bc
    pt = (-T) % LANE
    Tp = T + pt
    nc = (C + pc) // bc

    thread_in = []
    for arr, (_, dtype, padval) in zip(
            (st, rem, wake_at, slept, spun, ctr, ticket, completed_pt),
            _THREAD_STATE_SPEC):
        thread_in.append(jnp.pad(arr.astype(dtype), ((0, pc), (0, pt)),
                                 constant_values=padval))
    cpu_in = jnp.pad(spin_cpu.astype(jnp.float32), (0, pc))[:, None]
    conf_in = [jnp.pad(v.astype(jnp.int32), (0, pc))[:, None]
               for v in (sws, cnt, ewma, wuc, permits, nticket, completed,
                         wake_count)]
    ctx_in = [jnp.pad(jnp.broadcast_to(jnp.asarray(v, dtype), (C,)),
                      (0, pc))[:, None]
              for v, dtype in zip((step0, limit, alpha, cores, has_budget,
                                   policy, threads, dt, wake, cs_lo, cs_hi,
                                   ncs_lo, ncs_hi, k, sws_max, spin_budget,
                                   seed, oracle, workload, wl_period,
                                   wl_duty, wl_burst, wl_spread, arrival,
                                   arr_rate, q_cap, slo, tb,
                                   fault, flt_rate, flt_scale, park_cost),
                                  _BLOCK_CTX_DTYPES)]

    mat = pl.BlockSpec((bc, Tp), lambda i: (i, 0))
    colspec = pl.BlockSpec((bc, 1), lambda i: (i, 0))
    open_run = open_state is not None
    open_in, open_specs, open_shapes = [], [], []
    if open_run:
        open_in = _pad_open(open_state, pc, pt)
        open_specs, open_shapes = _open_specs_shapes(
            open_state, bc, C, pc, Tp, mat, colspec)
    out = pl.pallas_call(
        functools.partial(_block_kernel, n_sub_steps, open_run),
        grid=(nc,),
        in_specs=[mat] * _N_THREAD
        + [colspec] * (1 + _N_CONF + _N_BLOCK_CTX) + open_specs,
        out_specs=[mat] * _N_THREAD + [colspec] * (_N_CONF + 1)
        + open_specs,
        out_shape=[jax.ShapeDtypeStruct((C + pc, Tp), s[1])
                   for s in _THREAD_STATE_SPEC]
        + [jax.ShapeDtypeStruct((C + pc, 1), jnp.int32)] * _N_CONF
        + [jax.ShapeDtypeStruct((C + pc, 1), jnp.float32)]
        + open_shapes,
        interpret=interpret,
        compiler_params=CompilerParams(dimension_semantics=("parallel",),
                                       vmem_limit_bytes=BLOCK_VMEM_LIMIT),
    )(*thread_in, cpu_in, *conf_in, *ctx_in, *open_in)
    nclosed = _N_THREAD + _N_CONF + 1
    res = tuple(v[:C, :T] for v in out[:_N_THREAD]) \
        + tuple(v[:C, 0] for v in out[_N_THREAD:nclosed])
    if open_run:
        o = out[nclosed:]
        res += (o[0][:C, :T], o[1][:C], o[2][:C]) \
            + tuple(v[:C, 0] for v in o[3:])
    return res
