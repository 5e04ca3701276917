"""xdes — batched, fixed-timestep simulation of lock disciplines on JAX.

The event-driven DES (:mod:`repro.core.des`) is exact but interpreter-bound:
one Python loop per ``(lock, threads, cores, cs, ncs)`` cell, so the Fig. 3
grid (5 locks x 8 thread counts x 4 regimes x seeds) runs sequentially for
minutes.  This module simulates *thousands of configurations in one device
program*: a generalized-processor-sharing step on a fixed timestep.

The rollout is **time-blocked** (``rollout="blocked"``, the default): a
chunked ``lax.while_loop`` whose body is ONE fused kernel dispatch per
``block_steps`` timesteps — GPS advance + oracle update + transitions
iterated with the whole (C, T) state block resident in VMEM/registers
(:func:`repro.kernels.ref.lock_sim_block_ref` on the XLA backend, the
bit-identical Pallas twin :func:`repro.kernels.lock_sim.lock_sim_block` on
``backend="pallas"``), so the outer loop shrinks from ``n_steps``
dispatches to ``n_steps / block_steps``.  The loop carries a per-config
``done = completed >= target_cs`` mask and **exits early** as soon as
every config has converged (``early_exit``, on by default for
auto-planned horizons; the executed step count is reported as
``BatchResult.steps_run``).  ``rollout="scan"`` keeps the legacy
two-dispatches-per-step ``lax.scan`` — the parity reference the blocked
path is pinned bit-identical against.  Both per-step stages remain
swappable kernel backends in the scan path:

* GPS advance — :func:`repro.kernels.ref.lock_sim_step_ref` (XLA) or the
  fused Pallas kernel :func:`repro.kernels.lock_sim.lock_sim_step`;
* transitions — :func:`repro.kernels.ref.lock_transitions_ref` (XLA) or
  :func:`repro.kernels.lock_sim.lock_transitions_step` (Pallas grid over
  config blocks).

Model fidelity: same state machine, same policy decisions — every waiting
discipline is a row in :data:`repro.core.policy.DISCIPLINE_ROWS` (spin,
sleep, adaptive, mutable, FIFO/MCS ticket handoff), every SWS oracle a
row in ``ORACLE_ROWS``, and every hold-time model a row in
``WORKLOAD_ROWS`` (constant, bursty ON/OFF, heterogeneous per-thread
scales, Poisson-like jittered arrivals — docs/workloads.md), all
dispatched per config by integer columns, so one batch mixes disciplines,
oracle families and workloads freely.  The row-registry contract: a new
row is pure elementwise arithmetic in :mod:`repro.core.policy`, lands in
the kernels once via :mod:`repro.kernels.ref` (the Pallas twin applies
the same body per block — ref/Pallas bit-identity is by construction and
by test), gets an event-driven twin in :mod:`repro.core.des` pinned by
randomized parity tests, and must preserve the blocked-rollout
invariants (``now2 = (step0+s+1)*dt`` in int32 index arithmetic,
``spin_cpu`` accumulated in-loop) so blocked == per-step stays exact.
The differences from the DES are (a) time is quantized to ``dt`` instead
of exact event times, and (b) simultaneous events inside one step resolve
in thread-id order instead of RNG order — reducible via the seeded
per-thread arrival-phase randomization (``SimConfig.arrival_phase``).
The quantization-error band is measured by the dt-convergence study
(``benchmarks/fidelity_study.py``; docs/performance.md "Fidelity").
Equivalence tests pin xdes against the Python DES on the paper's four
regimes (qualitative claims C2-C4) and per-row.

Threads are array slots: state ``(configs, max_threads)`` int32 plus small
per-config integers (sws, cnt, wuc, permits, next-ticket) — exactly the
array-encodable policy state :mod:`repro.core.policy` defines.

Scale: :func:`simulate_batch` shards the batch over every visible device
with ``shard_map`` (config axis, manual mapping; the only collective is a
one-int ``psum`` per block agreeing on early exit) when more than one
device is attached — 10-100k-config sweeps split across a host's
accelerators with no change to the calling code.  ``bucket_steps=True``
additionally groups heterogeneous configs by planned step count
(power-of-two buckets of :func:`plan_schedule`'s per-config estimate), so
a 100µs-CS cell no longer pins a µs-spin cell to its scan length.  See
docs/performance.md for the block-size/early-exit/bucketing trade-offs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.ref import (NO_TICKET, REM_EPS,  # noqa: F401
                               counter_uniform, fault_rewind,
                               workload_init_rem)

from . import policy as P

#: Hard cap on scan length (compile + runtime guard).
MAX_STEPS = 200_000
#: Default timesteps fused into one kernel dispatch by the blocked rollout.
DEFAULT_BLOCK_STEPS = 32
_INF = np.float32(np.inf)

#: Context columns threaded to the transition kernels each step
#: (TRANSITION_CONTEXT minus the per-step ``now2``/``stepi``, same order).
_PRM_FIELDS = ("policy", "threads", "dt", "wake", "cs_lo", "cs_hi",
               "ncs_lo", "ncs_hi", "k", "sws_max", "spin_budget", "seed",
               "oracle", "workload", "wl_period", "wl_duty", "wl_burst",
               "wl_spread", "arrival", "arr_rate", "q_cap", "slo", "tb",
               "fault", "flt_rate", "flt_scale", "park_cost")


# --------------------------------------------------------------------------
# The rollout.  Default: a chunked lax.while_loop whose body is ONE fused
# kernel dispatch per block of timesteps (with target_cs early exit);
# legacy: lax.scan over steps, two kernel dispatches per step.  Both sit
# behind the swappable ref/pallas kernel boundary and are bit-identical.
# --------------------------------------------------------------------------
def _step_backends(backend: str):
    if backend == "ref":
        from repro.kernels.ref import lock_sim_step_ref, lock_transitions_ref
        return lock_sim_step_ref, lock_transitions_ref
    if backend == "pallas":
        from repro.kernels.lock_sim import lock_sim_step, lock_transitions_step
        return lock_sim_step, lock_transitions_step
    raise ValueError(f"unknown backend {backend!r} (ref|pallas)")


def _block_backend(backend: str):
    if backend == "ref":
        from repro.kernels.ref import lock_sim_block_ref
        return lock_sim_block_ref
    if backend == "pallas":
        from repro.kernels.lock_sim import lock_sim_block
        return lock_sim_block
    raise ValueError(f"unknown backend {backend!r} (ref|pallas)")


def _init_state(arrs, T: int, open_loop: bool = False):
    """The 17-array carry (16 transition-state arrays + spin_cpu): every
    thread starts in NCS with a fresh workload-row duration draw plus the
    seeded arrival-order phase offset (:func:`repro.kernels.ref.
    workload_init_rem`).

    With ``open_loop=True`` the 11 OPEN_STATE arrays are appended and
    threads of open-arrival configs (``arrival != closed``) start DONE
    with no request bound (``req_t = -1``) — the population is empty
    until requests arrive.  Closed configs in the same batch are
    untouched (their threads circulate from step 0 exactly as in the
    closed-loop engine)."""
    C = arrs["policy"].shape[0]
    tid = jnp.arange(T, dtype=jnp.int32)[None, :]
    active = tid < arrs["threads"][:, None]
    ctr0 = jnp.zeros((C, T), jnp.uint32)
    col = lambda k: arrs[k][:, None]
    rem0 = workload_init_rem(
        col("seed"), jnp.broadcast_to(tid, (C, T)), ctr0,
        col("ncs_lo"), col("ncs_hi"), col("workload"), col("wl_period"),
        col("wl_duty"), col("wl_burst"), col("wl_spread"),
        col("arrival_phase"))
    circulate = active
    if open_loop:
        circulate = active & (col("arrival") == P.AR_CLOSED)
    state = (
        jnp.where(circulate, P.NCS, P.DONE).astype(jnp.int32),  # st
        jnp.where(circulate, rem0, _INF),                     # rem
        jnp.full((C, T), _INF),                               # wake_at
        jnp.zeros((C, T), jnp.int32),                         # slept
        jnp.zeros((C, T), jnp.int32),                         # spun
        ctr0 + 1,                                             # ctr
        jnp.full((C, T), NO_TICKET, jnp.int32),               # ticket
        jnp.zeros((C, T), jnp.int32),                         # completed_pt
        arrs["sws_init"].astype(jnp.int32),                   # sws
        jnp.zeros((C,), jnp.int32),                           # cnt
        jnp.zeros((C,), jnp.int32),                           # ewma
        jnp.zeros((C,), jnp.int32),                           # wuc
        jnp.zeros((C,), jnp.int32),                           # permits
        jnp.zeros((C,), jnp.int32),                           # nticket
        jnp.zeros((C,), jnp.int32),                           # completed
        jnp.zeros((C,), jnp.int32),                           # wake_count
        jnp.zeros((C,), jnp.float32),                         # spin_cpu
    )
    if not open_loop:
        return state
    return state + (
        jnp.full((C, T), -1.0, jnp.float32),                  # req_t
        jnp.zeros((C, P.QUEUE_MAX), jnp.float32),             # qbuf
        jnp.zeros((C, P.LAT_NBINS), jnp.int32),               # hist
        jnp.zeros((C,), jnp.int32),                           # qhead
        jnp.zeros((C,), jnp.int32),                           # qlen
        jnp.zeros((C,), jnp.int32),                           # arrived
        jnp.zeros((C,), jnp.int32),                           # shed
        jnp.zeros((C,), jnp.int32),                           # departed
        jnp.zeros((C,), jnp.int32),                           # slo_viol
        jnp.zeros((C,), jnp.float32),                         # lat_sum
        jnp.zeros((C,), jnp.float32),                         # occ_int
    )


def _out_dict(state, executed, arrs, keep_per_thread: bool = True):
    (st, rem, wake_at, slept, spun, ctr, ticket, completed_pt,
     sws, cnt, ewma, wuc, permits, nticket, completed, wake_count,
     spin_cpu) = state[:17]
    executed = jnp.asarray(executed, jnp.int32)
    out = {
        "completed": completed,
        "spin_cpu": spin_cpu,
        "wake_count": wake_count,
        "final_sws": sws,
        "t_end": executed.astype(jnp.float32) * arrs["dt"],
        "steps_run": jnp.broadcast_to(executed, completed.shape),
    }
    if len(state) > 17:          # open-loop run: the 11 OPEN_STATE arrays
        (req_t, qbuf, hist, qhead, qlen, arrived, shed, departed,
         slo_viol, lat_sum, occ_int) = state[17:]
        T = req_t.shape[1]
        tid = jnp.arange(T, dtype=jnp.int32)[None, :]
        act = tid < arrs["threads"][:, None]
        busy = jnp.sum((act & (req_t >= 0.0)).astype(jnp.int32), axis=-1)
        out.update(lat_hist=hist, arrived=arrived, shed=shed,
                   departed=departed, slo_viol=slo_viol, lat_sum=lat_sum,
                   occ_int=occ_int, in_flight=qlen + busy)
    if keep_per_thread:
        out["completed_per_thread"] = completed_pt
    else:
        # fairness on device: max-min completed-CS spread over the active
        # thread slots — the (C, T) array never reaches the host.
        T = completed_pt.shape[1]
        tid = jnp.arange(T, dtype=jnp.int32)[None, :]
        act = tid < arrs["threads"][:, None]
        big = jnp.int32(2**31 - 1)
        mx = jnp.max(jnp.where(act, completed_pt, -big), axis=-1)
        mn = jnp.min(jnp.where(act, completed_pt, big), axis=-1)
        out["fairness"] = mx - mn
    return out


def _simulate_core(arrs, n_steps, T: int, backend: str = "ref",
                   rollout: str = "blocked",
                   block_steps: int = DEFAULT_BLOCK_STEPS,
                   target_cs=0, shard_axis: str | None = None,
                   early_exit: bool | None = None,
                   keep_per_thread: bool = True,
                   open_loop: bool = False):
    """One device program simulating ``n_steps`` timesteps of every config.

    ``rollout="blocked"``: chunked ``lax.while_loop``, one fused kernel
    dispatch (:func:`_block_backend`) per ``block_steps`` timesteps.  Both
    ``n_steps`` and ``target_cs`` may be traced int32 scalars here: the
    loop runs ``ceil(n_steps / block_steps)`` blocks with the kernels'
    step-``limit`` mask turning the tail block's overshoot sub-steps into
    exact passthroughs, so one compiled executable serves every horizon
    at a given padded shape.  When early exit is on the loop stops at the
    first block boundary where every config has completed ``target_cs``
    critical sections (under ``shard_axis`` the decision is agreed across
    devices with a one-int ``psum``, keeping sharded results
    bit-identical).  ``early_exit=None`` infers the flag from a static
    ``target_cs`` (on iff > 0); pass it explicitly when ``target_cs`` is
    traced.  ``rollout="scan"``: the legacy per-step ``lax.scan`` (two
    kernel dispatches per step, static ``n_steps``, no early exit) — the
    parity reference.
    """
    C = arrs["policy"].shape[0]
    budget_f = P.discipline_flags(arrs["policy"])[2]
    has_budget = budget_f > 0
    state0 = _init_state(arrs, T, open_loop)
    prm = tuple(arrs[f] for f in _PRM_FIELDS)
    if early_exit is None:
        early_exit = isinstance(target_cs, int) and target_cs > 0

    if rollout == "scan":
        advance, transitions = _step_backends(backend)

        def body(carry, i):
            state, spin_cpu = carry[:16], carry[16]
            ostate = carry[17:] if open_loop else None
            st, rem = state[0], state[1]
            now2 = (i.astype(jnp.float32) + 1.0) * arrs["dt"]
            rem, burn = advance(st, rem, arrs["alpha"], arrs["cores"],
                                arrs["dt"], has_budget)
            rem = fault_rewind(st, rem, arrs["alpha"], arrs["cores"],
                               arrs["dt"], i.astype(jnp.float32) * arrs["dt"],
                               arrs["seed"], arrs["fault"],
                               arrs["flt_rate"], arrs["flt_scale"])
            out = transitions(st, rem, *state[2:], now2, i, *prm,
                              open_state=ostate)
            new, onew = out[:16], out[16:]
            return (*new, spin_cpu + burn, *onew), None

        final, _ = jax.lax.scan(body, state0, jnp.arange(int(n_steps)))
        return _out_dict(final, int(n_steps), arrs, keep_per_thread)

    if rollout != "blocked":
        raise ValueError(f"unknown rollout {rollout!r} (blocked|scan)")

    block = _block_backend(backend)
    B = max(1, int(block_steps))
    limit = jnp.asarray(n_steps, jnp.int32)
    n_blocks = (limit + (B - 1)) // B
    tc = jnp.asarray(target_cs, jnp.int32)

    def run_block(state, step0):
        ostate = tuple(state[17:]) if open_loop else None
        return block(*state[:17], jnp.asarray(step0, jnp.int32),
                     arrs["alpha"], arrs["cores"], has_budget, *prm,
                     n_sub_steps=B, limit=limit, open_state=ostate)

    def all_done(completed):
        if not early_exit:
            return jnp.bool_(False)
        done = jnp.all(completed >= tc)
        if shard_axis is not None:    # agree across shards: exit globally
            done = (jax.lax.psum(done.astype(jnp.int32), shard_axis)
                    == jax.lax.psum(1, shard_axis))
        return done

    def cond(c):
        return (c[-2] < n_blocks) & jnp.logical_not(c[-1])

    def body(c):
        s = run_block(c[:-2], c[-2] * B)
        return (*s, c[-2] + 1, all_done(s[14]))

    *state, nblk, done = jax.lax.while_loop(
        cond, body, (*state0, jnp.int32(0), jnp.bool_(False)))
    executed = jnp.minimum(nblk * B, limit)
    return _out_dict(tuple(state), executed, arrs, keep_per_thread)


#: Fully-static jit entry (legacy + scan path): one executable per
#: (n_steps, target_cs, shapes) combination.
_simulate = functools.partial(jax.jit, static_argnames=(
    "n_steps", "T", "backend", "rollout", "block_steps", "target_cs",
    "shard_axis", "early_exit", "keep_per_thread",
    "open_loop"))(_simulate_core)

#: Dynamic-horizon jit entry for the blocked rollout: ``n_steps`` and
#: ``target_cs`` are traced int32 scalars, so ONE executable per padded
#: (C, T) shape serves every step-count bucket and stream chunk.
_simulate_dyn = functools.partial(jax.jit, static_argnames=(
    "T", "backend", "rollout", "block_steps", "shard_axis", "early_exit",
    "keep_per_thread", "open_loop"))(_simulate_core)


@functools.lru_cache(maxsize=None)
def _sharded_fn(n_steps: int | None, T: int, backend: str, n_dev: int,
                rollout: str, block_steps: int, target_cs: int | None,
                early_exit: bool = False, keep_per_thread: bool = True,
                open_loop: bool = False):
    """jit(shard_map(core)) over a 1-d ``configs`` device mesh — every
    config is independent, so the mapping is manual (the single collective
    is the one-int early-exit psum per block, which agrees on the exit
    step) and results are bit-identical to the unsharded call.

    With ``n_steps=None`` (blocked rollout only) the returned callable
    takes ``(arrs, n_steps, target_cs)`` with the two scalars traced and
    replicated across the mesh — the sharded twin of :data:`_simulate_dyn`.
    """
    from jax.sharding import Mesh, PartitionSpec

    from repro.sharding.compat import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("configs",))
    spec = PartitionSpec("configs")
    rep = PartitionSpec()

    # check_vma=False: with the check on, JAX 0.9 types every loop carry
    # and every pallas_call output by the mesh axes it varies over.  The
    # XLA rollout passes once its constant initial carry is pcast to
    # varying, but interpret-mode Pallas drops the axis inside its grid
    # loop ("varying manual axes do not match").  The check adds no
    # safety here: every output is config-partitioned, never replicated.
    if n_steps is None:
        def run_dyn(arrs, ns, tc):
            return _simulate_core(arrs, ns, T=T, backend=backend,
                                  rollout=rollout, block_steps=block_steps,
                                  target_cs=tc, shard_axis="configs",
                                  early_exit=early_exit,
                                  keep_per_thread=keep_per_thread,
                                  open_loop=open_loop)

        return jax.jit(shard_map(run_dyn, mesh=mesh,
                                 in_specs=(spec, rep, rep),
                                 out_specs=spec, check_vma=False))

    def run(arrs):
        return _simulate_core(arrs, n_steps=n_steps, T=T, backend=backend,
                              rollout=rollout, block_steps=block_steps,
                              target_cs=target_cs, shard_axis="configs",
                              keep_per_thread=keep_per_thread,
                              open_loop=open_loop)

    return jax.jit(shard_map(run, mesh=mesh, in_specs=(spec,),
                             out_specs=spec, check_vma=False))


def _simulate_sharded(arrs, n_steps: int, T: int, backend: str,
                      rollout: str = "blocked",
                      block_steps: int = DEFAULT_BLOCK_STEPS,
                      target_cs: int = 0, keep_per_thread: bool = True,
                      open_loop: bool = False):
    n_dev = len(jax.devices())
    C = arrs["policy"].shape[0]
    pad = (-C) % n_dev
    if pad:            # pad with copies of the last row, sliced off below
        arrs = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in arrs.items()}
    if rollout == "blocked":
        fn = _sharded_fn(None, T, backend, n_dev, rollout, block_steps,
                         None, target_cs > 0, keep_per_thread, open_loop)
        out = fn(arrs, np.int32(n_steps), np.int32(target_cs))
    else:
        out = _sharded_fn(n_steps, T, backend, n_dev, rollout, block_steps,
                          target_cs, False, keep_per_thread,
                          open_loop)(arrs)
    return {k: v[:C] for k, v in out.items()}


# --------------------------------------------------------------------------
# Scheduling heuristics + public API
# --------------------------------------------------------------------------
def plan_schedule(configs, target_cs: int = 300):
    """Pick per-config ``dt`` and per-config planned step counts.

    ``dt`` resolves the fastest load-bearing timescale (the *base* CS
    length and wake latency — NCS shorter than the CS only shifts
    arrivals within a step); each config's step count covers
    ~``target_cs`` critical sections for that cell, with the mean CS/NCS
    durations corrected for the config's workload row
    (:func:`repro.core.policy.workload_mean_scale` — a bursty row's
    effective arrival gap is ``duty + (1-duty)·burst`` times the base, so
    an uncorrected horizon would under-sample it severalfold).  Returns
    ``(dt, steps)``: (C,) float32 timesteps and (C,) int64 planned
    counts.  Counts are unclamped — :func:`simulate_batch` runs
    ``steps.max()`` for the whole batch (or per bucket with
    ``bucket_steps=True``), capped at :data:`MAX_STEPS` with a diagnostic
    naming the cells the cap under-samples.
    """
    return plan_schedule_columns(P.config_columns(configs), target_cs)


def plan_schedule_columns(cols, target_cs: int = 300):
    """:func:`plan_schedule` over RAW struct-of-arrays columns
    (:data:`repro.core.policy.RAW_CONFIG_FIELDS`) — the array-native
    planner the streaming sweep uses.  All arithmetic is float64 and
    elementwise-identical to the per-object path (``plan_schedule`` is
    now this function applied to :func:`repro.core.policy.
    config_columns`), so plans never depend on which form fed them."""
    cs_lo = np.asarray(cols["cs_lo"], np.float64)
    cs_hi = np.asarray(cols["cs_hi"], np.float64)
    ncs_lo = np.asarray(cols["ncs_lo"], np.float64)
    ncs_hi = np.asarray(cols["ncs_hi"], np.float64)
    wake = (np.asarray(cols["wake_latency"], np.float64)
            * np.asarray(cols.get("park_cost", 1.0), np.float64))
    threads = np.asarray(cols["threads"], np.int64)
    cores = np.asarray(cols["cores"], np.int64)
    cs_scale, ncs_scale = P.workload_mean_scale_columns(
        cols["workload"], cols["wl_duty"], cols["wl_burst"],
        cols["wl_spread"])
    cs_b = (cs_lo + cs_hi) / 2.0
    cs_m = cs_b * cs_scale
    ncs_m = (ncs_lo + ncs_hi) / 2.0 * ncs_scale
    dt = np.minimum(np.maximum(cs_b, 1e-8), np.maximum(wake, 1e-8)) / 6.0
    per_cs = (np.maximum(cs_m, (cs_m + ncs_m) / np.minimum(threads, cores))
              * 1.35 + 0.25 * wake + 2.0 * dt)
    steps = np.ceil(target_cs * per_cs / dt).astype(np.int64)
    return dt.astype(np.float32), steps


def plan_buckets(steps) -> list[np.ndarray]:
    """Group config indices into power-of-two buckets of planned step
    count (``ceil(log2(steps))``), ascending.

    Within a bucket the shared scan length (the bucket max) is at most 2x
    any member's own plan, so a 100µs-CS cell no longer pins a µs-spin
    cell to its horizon — versus the single global ``steps.max()``, which
    can overshoot fast cells by orders of magnitude on log-uniform
    workload sweeps.
    """
    ids = np.ceil(np.log2(np.maximum(np.asarray(steps), 1))).astype(int)
    return [np.nonzero(ids == b)[0] for b in np.unique(ids)]


def _warn_undersampled(configs, steps, cap: int, target_cs: int,
                       bucketed: bool = False) -> None:
    """Step-cap diagnostic: name which cells under-sample ``target_cs``
    (count + worst offender) instead of one generic warning."""
    import warnings

    steps = np.asarray(steps)
    over = np.nonzero(steps > cap)[0]
    worst = int(steps.argmax())
    c = configs[worst]
    expect = int(target_cs * cap / steps[worst])
    advice = ("the truncated cells need a shorter horizon (smaller "
              "target_cs) or a split sweep"
              if bucketed else
              "bucket_steps=True keeps fast cells fully sampled; the "
              "truncated cells need a shorter horizon (smaller "
              "target_cs) or a split sweep")
    warnings.warn(
        f"step cap {cap} truncates {len(over)}/{len(configs)} configs "
        f"below target_cs={target_cs}; worst offender is config {worst} "
        f"({c.lock}, threads={c.threads}, cores={c.cores}, "
        f"cs<={c.cs[1]:.3g}s, ncs<={c.ncs[1]:.3g}s, "
        f"wake={c.wake_latency:.3g}s): planned {int(steps[worst])} steps, "
        f"expect ~{expect} completed CS.  {advice}.", stacklevel=3)


@dataclass
class BatchResult:
    """Struct-of-arrays results for one batched run (numpy, length C)."""

    configs: list
    n_steps: int
    backend: str
    dt: np.ndarray
    t_end: np.ndarray
    completed: np.ndarray
    spin_cpu: np.ndarray
    wake_count: np.ndarray
    final_sws: np.ndarray
    #: (C, T) per-slot CS counts; ``None`` when the run was made with
    #: ``keep_per_thread=False`` (the (C, T) array then never reaches the
    #: host and ``fairness`` carries the on-device spread instead).
    completed_per_thread: np.ndarray | None = None
    #: (C,) timesteps actually executed per config — less than ``n_steps``
    #: when early exit fired, and per-bucket under ``bucket_steps=True``.
    steps_run: np.ndarray | None = None
    #: (C,) max-min completed-CS spread over active threads, computed on
    #: device when ``keep_per_thread=False`` (else derivable from
    #: ``completed_per_thread``).
    fairness: np.ndarray | None = None
    #: Open-loop outputs, ``None`` on closed-loop runs: (C, LAT_NBINS)
    #: per-request latency histogram (log-spaced bins,
    #: :func:`repro.core.policy.latency_bin_edges`) plus (C,) request
    #: counters — arrivals offered, shed at the full queue, departed,
    #: SLO violations among departures — and the exact latency /
    #: occupancy-integral accumulators behind Little's law
    #: (``occ_int = ∫L dt``, ``lat_sum = Σ latency``; see
    #: docs/open_loop.md).  ``in_flight`` is the end-of-run system
    #: occupancy (queued + bound to a thread).
    lat_hist: np.ndarray | None = None
    arrived: np.ndarray | None = None
    shed: np.ndarray | None = None
    departed: np.ndarray | None = None
    slo_viol: np.ndarray | None = None
    lat_sum: np.ndarray | None = None
    occ_int: np.ndarray | None = None
    in_flight: np.ndarray | None = None

    @property
    def throughput(self) -> np.ndarray:
        return self.completed / np.maximum(self.t_end, 1e-30)

    @property
    def sync_cpu_per_cs(self) -> np.ndarray:
        return self.spin_cpu / np.maximum(self.completed, 1)

    def latency_quantiles(self, qs=(0.50, 0.95, 0.99)) -> np.ndarray:
        """(len(qs), C) per-request latency percentiles from the on-device
        histogram (geometric bin midpoints; NaN where nothing departed)."""
        if self.lat_hist is None:
            raise ValueError("closed-loop run: no latency histogram")
        return P.latency_percentiles(self.lat_hist, qs)

    @property
    def p50(self) -> np.ndarray:
        return self.latency_quantiles((0.50,))[0]

    @property
    def p95(self) -> np.ndarray:
        return self.latency_quantiles((0.95,))[0]

    @property
    def p99(self) -> np.ndarray:
        return self.latency_quantiles((0.99,))[0]

    @property
    def slo_frac(self) -> np.ndarray:
        """Fraction of departed requests whose latency exceeded the
        config's SLO (NaN where nothing departed)."""
        if self.slo_viol is None:
            raise ValueError("closed-loop run: no SLO accounting")
        dep = np.asarray(self.departed, np.float64)
        return np.where(dep > 0, self.slo_viol / np.maximum(dep, 1.0),
                        np.nan)

    @property
    def mean_latency(self) -> np.ndarray:
        """Exact mean departed-request latency (NaN where none departed)."""
        if self.lat_sum is None:
            raise ValueError("closed-loop run: no latency accounting")
        dep = np.asarray(self.departed, np.float64)
        return np.where(dep > 0, self.lat_sum / np.maximum(dep, 1.0),
                        np.nan)

    def validate(self, where: str = "batch") -> "BatchResult":
        """Fail loudly on engine non-finites, naming the offending config.

        Distinguishes *intentional* NaN from poison: latency quantiles,
        ``mean_latency`` and ``slo_frac`` are NaN by design for configs
        where no request departed (the empty-histogram readout), so those
        are only flagged when ``departed > 0``.  Everything else —
        throughput, spin CPU, wake counts, the open-loop accumulators —
        must be finite for every config; a violation raises
        :class:`ValueError` with the config index and its parameters, so
        a poisoned sweep cell surfaces at the diagram CLI instead of
        silently propagating NaN into the phase-diagram reduction.
        Returns ``self`` so call sites can chain it.
        """
        checks = [("t_end", self.t_end), ("completed", self.completed),
                  ("spin_cpu", self.spin_cpu),
                  ("wake_count", self.wake_count),
                  ("final_sws", self.final_sws),
                  ("throughput", self.throughput),
                  ("sync_cpu_per_cs", self.sync_cpu_per_cs)]
        if self.lat_hist is not None:
            dep = np.asarray(self.departed, np.int64)
            checks += [("lat_sum", self.lat_sum),
                       ("occ_int", self.occ_int),
                       ("mean_latency",
                        np.where(dep > 0, self.mean_latency, 0.0)),
                       ("slo_frac",
                        np.where(dep > 0, self.slo_frac, 0.0)),
                       ("p50", np.where(dep > 0, self.p50, 0.0))]
        for name, arr in checks:
            a = np.asarray(arr, np.float64)
            badm = ~np.isfinite(a)
            if badm.any():
                i = int(np.nonzero(badm)[0][0])
                cfg = (self.configs[i] if i < len(self.configs)
                       else "<padded row>")
                raise ValueError(
                    f"non-finite {name}={a[i]!r} at config {i} in "
                    f"{where}: {cfg!r}")
        return self

    def fairness_spread(self, i: int) -> int:
        """Max-min completed-CS spread across config ``i``'s threads —
        ~0/1 under FIFO ticket grants, unbounded under barging locks."""
        if self.completed_per_thread is None:
            return int(self.fairness[i])
        per = self.completed_per_thread[i, :self.configs[i].threads]
        return int(per.max() - per.min())

    def row(self, i: int) -> dict:
        return {
            "config": self.configs[i],
            "completed_cs": int(self.completed[i]),
            "throughput": float(self.throughput[i]),
            "sync_cpu_per_cs": float(self.sync_cpu_per_cs[i]),
            "wake_count": int(self.wake_count[i]),
            "final_sws": int(self.final_sws[i]),
            "t_end": float(self.t_end[i]),
        }


def _pad_quantum(n: int) -> int:
    """Next power of two — the shared config-axis padding quantum of the
    bucketed path, so buckets of nearby sizes land on the SAME padded
    (C, T) shape and (with the traced-horizon blocked rollout) reuse one
    compiled executable instead of compiling per bucket."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _simulate_bucketed(configs, buckets, steps, *, target_cs, dt, backend,
                       max_threads, shard, rollout, block_steps,
                       early_exit, keep_per_thread=True,
                       open_loop=False) -> BatchResult:
    """Run each step-count bucket as its own batched call and stitch the
    per-config results back into the caller's row order.  ``dt`` and
    ``steps`` are the (C,) planned arrays — passed down sliced, so the
    per-bucket calls skip re-planning.  Each bucket's config axis is
    padded to the next power of two (copies of its last row, sliced off
    again), so buckets share padded shapes and — the horizon being traced
    in the blocked rollout — compiled executables.  ``open_loop`` is
    resolved once here and forced on every bucket, so a mixed batch
    whose open configs all land in one bucket still returns open-loop
    outputs for every row."""
    C = len(configs)
    T = max_threads or max(c.threads for c in configs)
    parts = []
    for idx in buckets:
        parts.append(simulate_batch(
            [configs[i] for i in idx], target_cs=target_cs,
            dt=np.asarray(dt)[idx],
            n_steps=min(int(steps[idx].max()), MAX_STEPS),
            backend=backend, max_threads=T, shard=shard, rollout=rollout,
            block_steps=block_steps, early_exit=early_exit,
            bucket_steps=False, keep_per_thread=keep_per_thread,
            open_loop=open_loop,
            pad_configs=_pad_quantum(len(idx)) if rollout == "blocked"
            else None))
    res = BatchResult(
        configs=configs, n_steps=max(p.n_steps for p in parts),
        backend=backend,
        dt=np.empty(C, np.float32), t_end=np.empty(C, np.float32),
        completed=np.empty(C, np.int32), spin_cpu=np.empty(C, np.float32),
        wake_count=np.empty(C, np.int32), final_sws=np.empty(C, np.int32),
        completed_per_thread=(np.empty((C, T), np.int32)
                              if keep_per_thread else None),
        steps_run=np.empty(C, np.int32),
        fairness=None if keep_per_thread else np.empty(C, np.int32),
        lat_hist=(np.empty((C, P.LAT_NBINS), np.int32)
                  if open_loop else None),
        arrived=np.empty(C, np.int32) if open_loop else None,
        shed=np.empty(C, np.int32) if open_loop else None,
        departed=np.empty(C, np.int32) if open_loop else None,
        slo_viol=np.empty(C, np.int32) if open_loop else None,
        lat_sum=np.empty(C, np.float32) if open_loop else None,
        occ_int=np.empty(C, np.float32) if open_loop else None,
        in_flight=np.empty(C, np.int32) if open_loop else None)
    fields = ["dt", "t_end", "completed", "spin_cpu", "wake_count",
              "final_sws", "steps_run"]
    fields.append("completed_per_thread" if keep_per_thread else "fairness")
    if open_loop:
        fields += ["lat_hist", "arrived", "shed", "departed", "slo_viol",
                   "lat_sum", "occ_int", "in_flight"]
    for idx, p in zip(buckets, parts):
        for f in fields:
            getattr(res, f)[idx] = getattr(p, f)
    return res


def simulate_batch(configs, *, target_cs: int = 300, n_steps: int | None = None,
                   dt=None, backend: str = "ref",
                   max_threads: int | None = None,
                   shard: bool | None = None, rollout: str = "blocked",
                   block_steps: int | None = None,
                   early_exit: bool | None = None,
                   bucket_steps: bool = False,
                   keep_per_thread: bool = True,
                   pad_configs: int | None = None,
                   open_loop: bool | None = None) -> BatchResult:
    """Simulate every :class:`repro.core.policy.SimConfig` in ``configs``
    in ONE jit-compiled device call (or one per step-count bucket).

    All configurations in a call share the scan length; each carries its
    own ``dt``, so heterogeneous regimes (µs spin cells next to 100µs-CS
    cells) batch together without resolution loss.  ``backend="pallas"``
    routes the rollout through :mod:`repro.kernels.lock_sim`.

    Rollout and horizon controls (see docs/performance.md):

    * ``rollout="blocked"`` (default) fuses ``block_steps`` timesteps
      (default :data:`DEFAULT_BLOCK_STEPS`) into one kernel dispatch per
      loop iteration — bit-identical to ``rollout="scan"``, the legacy
      two-dispatches-per-step path kept as the parity reference.
    * ``early_exit`` (default: on iff ``n_steps`` is auto-planned) stops
      the blocked rollout at the first block boundary where every config
      has completed ``target_cs`` critical sections;
      ``BatchResult.steps_run`` records the executed count.  Ignored
      under ``rollout="scan"``.
    * ``bucket_steps=True`` groups configs into power-of-two buckets of
      planned step count (:func:`plan_buckets`) and runs one call per
      bucket, so slow cells no longer pin fast cells to their horizon.
      Results per config are identical to a direct call on its bucket.

    ``shard=None`` (auto) splits the config axis across all visible
    devices via ``shard_map`` whenever more than one is attached;
    ``shard=True`` forces the sharded path (a 1-device mesh on
    single-device hosts), ``shard=False`` disables it.  Sharded and
    unsharded results are bit-identical (configs are independent; the
    early-exit decision is agreed across devices).

    ``keep_per_thread=False`` drops the (C, T) ``completed_per_thread``
    output (the fairness spread is reduced on device into
    ``BatchResult.fairness`` instead) — the memory-lean mode the
    streaming sweep (:mod:`repro.core.stream`) runs in.  ``pad_configs``
    pads the batch with copies of the last config up to the given count
    (results sliced back), stabilizing compiled shapes across calls;
    results are bit-identical because configs are independent and the
    padded copies converge exactly when their source row does.

    ``open_loop=None`` (auto) switches on the open-loop arrival engine iff
    any config has a non-closed arrival row; closed batches compile the
    exact legacy graph (the flag is static, so the 11 OPEN_STATE carry
    arrays simply don't exist).  Forcing ``open_loop=True`` on an
    all-closed batch is valid — the open machinery runs but stays inert
    (rate 0 admits nothing), which the bit-identity tests exploit.
    """
    configs = list(configs)
    if open_loop is None:
        open_loop = any(c.open_loop for c in configs)
    if dt is None or n_steps is None:
        auto_dt, steps_arr = plan_schedule(configs, target_cs)
    if bucket_steps and n_steps is None and len(configs) > 1:
        buckets = plan_buckets(steps_arr)
        if len(buckets) > 1:
            if int(steps_arr.max()) > MAX_STEPS:
                _warn_undersampled(configs, steps_arr, MAX_STEPS,
                                   target_cs, bucketed=True)
            if dt is None:
                dt = auto_dt
            else:
                dt = np.broadcast_to(np.asarray(dt, np.float32),
                                     (len(configs),)).copy()
            return _simulate_bucketed(
                configs, buckets, steps_arr, target_cs=target_cs, dt=dt,
                backend=backend, max_threads=max_threads, shard=shard,
                rollout=rollout, block_steps=block_steps,
                # a bucketed horizon is auto-planned: exit by default
                early_exit=True if early_exit is None else early_exit,
                keep_per_thread=keep_per_thread, open_loop=open_loop)
    arrs = P.encode_configs(configs)
    if dt is None:
        dt = auto_dt
    else:
        dt = np.broadcast_to(np.asarray(dt, np.float32),
                             arrs["policy"].shape).copy()
    if n_steps is None:
        auto_steps = int(steps_arr.max())
        if auto_steps > MAX_STEPS:
            _warn_undersampled(configs, steps_arr, MAX_STEPS, target_cs,
                               bucketed=bucket_steps)
        n_steps = min(auto_steps, MAX_STEPS)
        if early_exit is None:
            early_exit = True
    elif early_exit is None:
        early_exit = False       # a pinned horizon means: run exactly it
    if n_steps > MAX_STEPS:
        raise ValueError(f"n_steps={n_steps} exceeds MAX_STEPS={MAX_STEPS}")
    arrs["dt"] = np.asarray(dt, np.float32)
    C = len(configs)
    if pad_configs is not None and pad_configs > C:
        pad = pad_configs - C
        arrs = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in arrs.items()}
    T = max_threads or int(arrs["threads"].max())
    if T < int(arrs["threads"].max()):
        raise ValueError("max_threads smaller than widest config")
    if shard is None:
        shard = len(jax.devices()) > 1
    if block_steps is None:
        block_steps = DEFAULT_BLOCK_STEPS
    tc = int(target_cs) if (early_exit and rollout == "blocked") else 0
    if shard:
        out = _simulate_sharded(arrs, n_steps=int(n_steps), T=int(T),
                                backend=backend, rollout=rollout,
                                block_steps=int(block_steps), target_cs=tc,
                                keep_per_thread=keep_per_thread,
                                open_loop=open_loop)
    elif rollout == "blocked":
        # traced horizon/target: one executable per padded (C, T) shape
        out = _simulate_dyn(arrs, np.int32(n_steps), T=int(T),
                            backend=backend, rollout=rollout,
                            block_steps=int(block_steps),
                            target_cs=np.int32(tc), early_exit=tc > 0,
                            keep_per_thread=keep_per_thread,
                            open_loop=open_loop)
    else:
        out = _simulate(arrs, n_steps=int(n_steps), T=int(T),
                        backend=backend, rollout=rollout,
                        block_steps=int(block_steps), target_cs=tc,
                        keep_per_thread=keep_per_thread,
                        open_loop=open_loop)
    out = {k: np.asarray(v)[:C] for k, v in out.items()}
    return BatchResult(configs=configs, n_steps=int(n_steps), backend=backend,
                       dt=np.asarray(dt, np.float32)[:C],
                       t_end=out["t_end"], completed=out["completed"],
                       spin_cpu=out["spin_cpu"],
                       wake_count=out["wake_count"],
                       final_sws=out["final_sws"],
                       completed_per_thread=out.get("completed_per_thread"),
                       steps_run=out["steps_run"],
                       fairness=out.get("fairness"),
                       lat_hist=out.get("lat_hist"),
                       arrived=out.get("arrived"), shed=out.get("shed"),
                       departed=out.get("departed"),
                       slo_viol=out.get("slo_viol"),
                       lat_sum=out.get("lat_sum"),
                       occ_int=out.get("occ_int"),
                       in_flight=out.get("in_flight"))
