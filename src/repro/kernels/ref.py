"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are *definitions*, not fast paths: direct dense math, f32 accumulate.
The model code has its own (chunked/blockwise) implementations; tests check
kernel == ref and model-path == ref independently.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    """q: (BH, Sq, hd); k, v: (BKV, Sk, hd)."""
    BH, Sq, hd = q.shape
    BKV, Sk, _ = k.shape
    g = BH // BKV
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= qp >= kp
    if window:
        m &= (qp - kp) < window
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def rwkv6_scan_ref(r, k, v, w, u, s0=None):
    """Sequential definition.  r,k,v,w: (BH, T, n); u: (BH, n)."""
    BH, T, n = r.shape
    S = (jnp.zeros((BH, n, n), jnp.float32) if s0 is None
         else s0.astype(jnp.float32))

    def step(S, t):
        r_t, k_t, v_t, w_t = t                               # (BH, n)
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bk,bkv->bv", r_t, S + u[..., None] * kv)
        S = w_t[..., None] * S + kv
        return S, y

    ts = tuple(a.swapaxes(0, 1).astype(jnp.float32) for a in (r, k, v, w))
    S, ys = jax.lax.scan(step, S, ts)
    return ys.swapaxes(0, 1), S


def mamba_scan_ref(dt, x, Bm, Cm, a):
    """Sequential definition.  dt,x: (B,T,d); Bm,Cm: (B,T,N); a: (d,N)."""
    B, T, d = x.shape
    N = a.shape[-1]
    s0 = jnp.zeros((B, d, N), jnp.float32)

    def step(s, t):
        dt_t, x_t, B_t, C_t = t
        da = jnp.exp(dt_t[..., None] * a)
        s = s * da + (dt_t * x_t)[..., None] * B_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", s, C_t)
        return s, y

    ts = tuple(v.swapaxes(0, 1).astype(jnp.float32)
               for v in (dt, x, Bm, Cm))
    _, ys = jax.lax.scan(step, s0, ts)
    return ys.swapaxes(0, 1)


def rmsnorm_ref(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


@jax.named_scope("gps_advance")
def lock_sim_step_ref(tstate, rem, alpha, cores, dt, has_budget):
    """One generalized-processor-sharing advance of the batched lock sim.

    The hot inner update of :mod:`repro.core.xdes` (paper §2 model): every
    runnable thread advances at rate ``min(1, cores / n_runnable)``; the CS
    holder is additionally slowed by cache-coherency pressure
    ``1 / (1 + alpha * n_spinners)``; spinners burn CPU, and the adaptive
    discipline's spinners consume their spin budget.

    tstate: (C, T) int32 thread states (repro.core.policy encoding);
    rem:    (C, T) f32 remaining work (CS/NCS) or spin budget (adaptive);
    alpha, cores, dt: (C,) f32; has_budget: (C,) bool.
    Returns ``(rem', spin_burn)`` with spin_burn (C,) f32 — the CPU-seconds
    burnt spinning this step (the paper's sync-waste metric).  Every
    spinner burns the same ``dt * rate``, so spin_burn is that times the
    exact spinner count: one rounding, whatever order or lane padding a
    compiler reduces in (an f32 lane sum differs between XLA and Mosaic).
    :func:`count_scale` keeps that one rounding when a compiler fuses the
    product into the caller's ``spin_cpu + burn`` as an FMA (XLA:CPU does,
    and not in the same places in both twins).
    """
    from repro.core.policy import CS, NCS, SPIN

    is_cs = tstate == CS
    is_ncs = tstate == NCS
    is_spin = tstate == SPIN
    n_run = jnp.sum(is_cs | is_ncs | is_spin, axis=-1).astype(jnp.float32)
    n_spin = jnp.sum(is_spin, axis=-1).astype(jnp.float32)
    rate = jnp.minimum(1.0, cores / jnp.maximum(n_run, 1.0))
    holder_rate = rate / (1.0 + alpha * n_spin)
    d_rate = dt * rate
    burn = jnp.where(is_spin, d_rate[:, None], 0.0)
    dec = (jnp.where(is_cs, (dt * holder_rate)[:, None], 0.0)
           + jnp.where(is_ncs, d_rate[:, None], 0.0)
           + jnp.where(has_budget[:, None], burn, 0.0))
    return rem - dec, count_scale(d_rate, n_spin)


# --------------------------------------------------------------------------
# The batched lock simulator's transition stage — the (C, T)-block reference
# behind the swappable kernel boundary.  repro.core.xdes calls either this
# function or its Pallas twin (repro.kernels.lock_sim.lock_transitions_step,
# which wraps the SAME body in a grid over config blocks); tests pin the two
# bit-identical.  All discipline decisions dispatch through
# repro.core.policy.DISCIPLINE_ROWS, all oracle decisions through
# ORACLE_ROWS — the engine itself is discipline-agnostic.
# --------------------------------------------------------------------------

#: Residual work (CPU-seconds) under which a CS/NCS counts as finished.
REM_EPS = 1e-9
#: Retired-ticket sentinel (no thread ever draws this many tickets).
NO_TICKET = 2**31 - 1

#: Canonical argument order of the transition boundary: per-thread (C, T)
#: state, per-config (C,) state, then the per-config context columns.
TRANSITION_THREAD_STATE = ("st", "rem", "wake_at", "slept", "spun", "ctr",
                           "ticket", "completed_pt")
TRANSITION_CONFIG_STATE = ("sws", "cnt", "ewma", "wuc", "permits", "nticket",
                           "completed", "wake_count")
TRANSITION_CONTEXT = ("now2", "stepi", "policy", "threads", "dt", "wake",
                      "cs_lo", "cs_hi", "ncs_lo", "ncs_hi", "k", "sws_max",
                      "spin_budget", "seed", "oracle", "workload",
                      "wl_period", "wl_duty", "wl_burst", "wl_spread",
                      "arrival", "arr_rate", "q_cap", "slo", "tb",
                      "fault", "flt_rate", "flt_scale", "park_cost")

#: Open-loop state appended after the closed carry (spin_cpu) — only
#: materialized when a batch contains an open-arrival config
#: (``SimConfig.arrival != "closed"``; see docs/open_loop.md).  Shapes:
#: ``req_t`` (C, T) f32 bound-request arrival times (-1 when the slot is
#: free), ``qbuf`` (C, QUEUE_MAX) f32 queued arrival times (a ring
#: buffer), ``hist`` (C, LAT_NBINS) i32 latency histogram, then (C,)
#: counters: queue head/length, arrived/shed/departed/SLO-violation
#: counts (i32), latency sum and queue+service occupancy time-integral
#: (f32) — the exact Little's-law pair (``occ_int - lat_sum`` equals the
#: summed ages of still-in-system requests at the horizon).
OPEN_STATE = ("req_t", "qbuf", "hist", "qhead", "qlen", "arrived", "shed",
              "departed", "slo_viol", "lat_sum", "occ_int")


# --------------------------------------------------------------------------
# Mosaic-compilable spellings of three primitives the TPU kernel lowering
# refuses (uint32 -> float32 casts, lane-axis cumsum, argmax of a bool
# mask), each exactly equal to the primitive it replaces, and a product
# that rounds the same under every compiler's fusion.  So the XLA
# reference and the compiled kernel keep every simulated bit (pinned by
# tests/test_lock_kernel_ops.py).
# --------------------------------------------------------------------------
def u32_to_f32(x):
    """``x.astype(jnp.float32)`` for a uint32 ``x``, bit for bit.

    The high and low 16-bit halves each convert exactly (< 2**16), the
    scaled high half ``hi * 2**16`` is exact too, so the one f32 add rounds
    the exact value once, to nearest even — the direct conversion."""
    hi = (x >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(2.0 ** 16) + lo


def lane_cumsum(mask):
    """``jnp.cumsum(mask.astype(jnp.int32), axis=-1)`` of a bool mask.

    A 0/1 upper-triangular matmul: every product is 0 or 1 and every
    partial sum an integer <= the lane count, so the f32 accumulation is
    exact at any matmul precision."""
    T = mask.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    tri = (row <= col).astype(jnp.float32)
    return jnp.dot(mask.astype(jnp.float32), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def first_true(mask):
    """Index of the first True along the last axis, keepdims; the axis
    length where a row has none.  Equals ``jnp.argmax(mask, -1)`` on every
    row with a True."""
    T = mask.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)
    return jnp.min(jnp.where(mask, idx, T), axis=-1, keepdims=True)


def count_scale(x, n):
    """``x * n`` for f32 ``x`` and an integer-valued f32 count ``0 <= n <
    2**12``, rounded once, and never fused with a following add.

    ``x`` splits into a head with 12 significant bits and the exact rest,
    so both partial products are exact and only their sum rounds.  The
    result is an add, not a multiply, so no compiler can contract it with
    what the caller adds to it; and contracting the sum itself into an FMA
    changes nothing, its products being exact."""
    head = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-4096),
        jnp.float32)
    return head * n + (x - head) * n


def counter_uniform(seed, tid, ctr):
    """Counter-based RNG: uniform [0,1) per (config, thread, event) from a
    splitmix-style avalanche — deterministic, stateless, replayable per
    cell independently of batch composition."""
    x = seed ^ (tid.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)) \
        ^ ((ctr + jnp.uint32(1)) * jnp.uint32(0x85EBCA6B))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return u32_to_f32(x) * jnp.float32(2.0 ** -32)


# --------------------------------------------------------------------------
# Workload rows (repro.core.policy.WORKLOAD_ROWS) — the hold-time stage of
# the kernel boundary.  The helpers below precompute the per-thread
# workload state (duty-cycle phase, OFF gate, heterogeneity scale) from
# dedicated counter-RNG streams and feed policy.workload_hold, the masked
# row dispatch.  The Pallas kernels inherit them by applying the same
# transition/init bodies per block, so ref and Pallas lowerings of every
# workload row are bit-identical by construction.
# --------------------------------------------------------------------------
def workload_state(seed, tid, now, wl_period, wl_duty, wl_spread):
    """Per-(config, thread) workload state at time ``now``.

    Returns ``(phase_u, gate_off, tscale)``: the thread's persistent
    duty-cycle phase uniform, its 0/1 OFF-phase gate at ``now``, and its
    persistent heterogeneity scale.  ``seed``/``now``/parameter columns
    broadcast against ``tid``; the two uniforms come from salted counter
    streams (policy.WL_PHASE_SALT / WL_SPREAD_SALT), so they never collide
    with the event-draw stream and replay identically per cell."""
    from repro.core import policy as P

    zero = jnp.uint32(0)
    phase_u = counter_uniform(seed ^ jnp.uint32(P.WL_PHASE_SALT), tid, zero)
    spread_u = counter_uniform(seed ^ jnp.uint32(P.WL_SPREAD_SALT), tid,
                               zero)
    gate_off = P.workload_off_gate(now, phase_u, wl_period, wl_duty)
    tscale = P.workload_thread_scale(spread_u, wl_spread)
    return phase_u, gate_off, tscale


def workload_draw(u, lo, hi, is_ncs, workload, gate_off, tscale, wl_burst):
    """One workload-row hold-time draw from the uniform ``u``.

    ``is_ncs`` is a static 0/1 flag (CS vs NCS/arrival-gap draw); the
    exponential deviate for the jitter row is only materialized on the NCS
    path.  The constant row's output is bit-identical to the plain uniform
    draw ``lo + u * (hi - lo)``.

    The deviate clamps ``u`` below 1: ``counter_uniform`` casts a uint32
    to float32, which rounds the top ~2**8 values to exactly 1.0
    (probability ~6e-8 per draw), and ``-log1p(-1.0)`` is +inf — which
    the masked row dispatch would turn into NaN (``0.0 * inf``) for every
    non-jitter config.  Clamping caps the deviate at ~16.6 means instead
    and leaves ``base`` (hence the constant row) untouched."""
    from repro.core import policy as P

    base = lo + u * (hi - lo)
    expd = ((0.5 * (lo + hi))
            * (-jnp.log1p(-jnp.minimum(u, jnp.float32(1.0 - 2.0 ** -24))))
            if is_ncs else base)
    return P.workload_hold(workload, is_ncs, base, expd, gate_off, tscale,
                           wl_burst)


def workload_init_rem(seed, tid, ctr0, ncs_lo, ncs_hi, workload, wl_period,
                      wl_duty, wl_burst, wl_spread, arrival_phase):
    """The initial per-thread NCS draw (every thread starts in NCS),
    workload-modulated at ``now = 0``, plus the seeded per-thread
    arrival-order randomization: first arrivals are staggered by up to
    ``arrival_phase`` mean-NCS lengths drawn from the phase stream, so
    simultaneous arrivals no longer resolve in thread-id order.  With the
    constant row and ``arrival_phase = 0`` this is bit-identical to the
    plain uniform init draw."""
    u0 = counter_uniform(seed, tid, ctr0)
    phase_u, gate_off, tscale = workload_state(seed, tid, 0.0, wl_period,
                                               wl_duty, wl_spread)
    rem0 = workload_draw(u0, ncs_lo, ncs_hi, 1, workload, gate_off, tscale,
                         wl_burst)
    return rem0 + phase_u * arrival_phase * (0.5 * (ncs_lo + ncs_hi))


@jax.named_scope("fault_rewind")
def fault_rewind(st, rem, alpha, cores, dt, now_start, seed, fault,
                 flt_rate, flt_scale):
    """Fault-row progress theft for one timestep (FAULT_ROWS dispatch).

    Recomputes the GPS progress each CS/NCS thread made during the step
    that :func:`lock_sim_step_ref` just applied (from the SAME pre-step
    ``st``, so the rates match bit-for-bit) and gives the stolen fraction
    back to ``rem``: a thread whose fault window is off-CPU makes no (or
    partial) progress while spinners keep burning CPU — the asymmetry
    that lets sleep-leaning disciplines overtake pure spin under heavy
    preemption.  Windows are ``flt_scale`` seconds; the per-(thread,
    window) gate uniform comes from the FLT_GATE_SALT counter stream, so
    an off-CPU stretch persists across every sub-step of its window.

    Applied through ``where(giveback > 0)``, so a fault-free config's
    ``rem`` is a structural passthrough — bit-identical to the pre-fault
    engine.  ``now_start`` is the step's START time ``i * dt`` (scalar or
    (C,)); spin burn and the adaptive budget are deliberately not
    modulated (see the FAULT_ROWS registry comment).
    """
    from repro.core import policy as P

    C, T = st.shape
    col = lambda v: v[:, None]
    is_cs = st == P.CS
    is_ncs = st == P.NCS
    is_spin = st == P.SPIN
    n_run = jnp.sum(is_cs | is_ncs | is_spin, axis=-1).astype(jnp.float32)
    n_spin = jnp.sum(is_spin, axis=-1).astype(jnp.float32)
    rate = jnp.minimum(1.0, cores / jnp.maximum(n_run, 1.0))
    holder_rate = rate / (1.0 + alpha * n_spin)
    prog = (jnp.where(is_cs, (dt * holder_rate)[:, None], 0.0)
            + jnp.where(is_ncs, (dt * rate)[:, None], 0.0))
    tid = jnp.arange(T, dtype=jnp.int32)[None, :]
    tidb = jnp.broadcast_to(tid, (C, T))
    win = jnp.floor(now_start / flt_scale).astype(jnp.int32) \
        .astype(jnp.uint32)
    winT = win[:, None] if jnp.ndim(win) else win
    gate_u = counter_uniform(col(seed) ^ jnp.uint32(P.FLT_GATE_SALT),
                             tidb, winT)
    scale = P.fault_progress_scale(col(fault), is_cs * 1.0, gate_u,
                                   col(flt_rate))
    giveback = prog * (1.0 - scale)
    return jnp.where(giveback > 0.0, rem + giveback, rem)


def lock_transitions_ref(st, rem, wake_at, slept, spun, ctr, ticket,
                         completed_pt, sws, cnt, ewma, wuc, permits,
                         nticket, completed, wake_count,
                         now2, stepi, policy, threads, dt, wake, cs_lo,
                         cs_hi, ncs_lo, ncs_hi, k, sws_max, spin_budget,
                         seed, oracle, workload, wl_period, wl_duty,
                         wl_burst, wl_spread, arrival, arr_rate, q_cap,
                         slo, tb, fault, flt_rate, flt_scale, park_cost, *,
                         open_state=None):
    """One transition step for a (C, T) block of configurations.

    Stages (same order as the event-driven DES resolves a timestep):
    [open-loop admission] -> budget exhaustion -> wake completions ->
    CS release/handoff [+ open-loop departure] -> arrivals ->
    [open-loop request binding + occupancy].  Per-thread state is
    int32/f32/uint32 arrays of shape (C, T) (``slept``/``spun`` as 0/1
    int32, ``ticket`` int32 with :data:`NO_TICKET` when not queued);
    per-config state and context are (C,) vectors; ``stepi`` is the
    global step index (int32 scalar or (C,), the counter of the per-step
    RNG streams).  Every CS/NCS duration draw dispatches through the
    workload rows (:func:`workload_draw`; constant rows reproduce the
    plain uniform draw bit-identically).  Returns the 16 updated state
    arrays in the canonical order (:data:`TRANSITION_THREAD_STATE` +
    :data:`TRANSITION_CONFIG_STATE`), plus the 11 :data:`OPEN_STATE`
    arrays when ``open_state`` is given.  A closed config
    (``arrival == AR_CLOSED``) inside an open batch takes every open
    stage as an exact masked no-op, and ``tb == 0`` reproduces the
    historical thread-id tie-break bit-identically.
    """
    from repro.core import policy as P

    C, T = st.shape
    inf = jnp.float32(jnp.inf)
    tid = jnp.arange(T, dtype=jnp.int32)[None, :]              # (1, T)
    tidb = jnp.broadcast_to(tid, (C, T))
    col = lambda v: v[:, None]                                 # (C,) -> (C,1)
    active = tid < col(threads)
    (hand_f, fifo_f, budget_f, w2s_f, repark_f,
     win_f, bscale_f, backoff_f) = P.discipline_flags(policy)
    teps = dt * jnp.float32(1e-3)
    stepu = jnp.asarray(stepi).astype(jnp.uint32)  # scalar or (C,)
    stepuT = stepu if stepu.ndim == 0 else stepu[:, None]

    # Effective per-(thread, step) wake latency under the config's fault
    # row (lost wake-ups recover at the `flt_scale` timeout; jitter rows
    # stretch the delay).  The FLT_WAKE/FLT_MAG streams are salted apart
    # from every other draw; for no-fault rows the masked dispatch returns
    # `wake` bit-identically, so `col(now2) + wake_eff` reproduces the
    # historical `col(now2 + wake)` exactly.
    flt_w1 = counter_uniform(col(seed) ^ jnp.uint32(P.FLT_WAKE_SALT), tidb,
                             stepuT)
    flt_w2 = counter_uniform(col(seed) ^ jnp.uint32(P.FLT_MAG_SALT), tidb,
                             stepuT)
    # M:N environment axis: park_cost re-prices the sleep/wake round trip
    # (green threads << 1, kernel threads 1, oversubscribed VMs >> 1).
    # The default 1.0 multiplies exactly, so pre-park_cost configs are
    # bit-identical.
    wake_base = col(wake) * col(park_cost)
    wake_eff = P.fault_wake_delay(col(fault), wake_base, flt_w1, flt_w2,
                                  col(flt_rate), col(flt_scale))
    # Fissile competitive pricing: a budget_scaled row spins for about the
    # park round trip before parking — spin_budget * sws * park_cost, with
    # the oracle window sws as the adaptive multiplier.  Exact *1.0 for
    # every other row (adaptive keeps its flat glibc budget).
    budget_eff = lambda sws_now: col(spin_budget) * jnp.where(
        col(bscale_f) > 0,
        col(sws_now).astype(jnp.float32) * col(park_cost),
        jnp.float32(1.0))

    # -- open-loop admission (arrival rows; see docs/open_loop.md) --------
    # Runs FIRST so a request admitted at step i is in the system for
    # steps i..j-1 when it departs at step j — the occupancy integral
    # accumulated at the END of the step then equals the recorded latency
    # (j - i)·dt exactly (the Little's-law invariant the property tests
    # pin).  Requests carry their admission timestamp ``now2`` through
    # the ring buffer into the bound thread's ``req_t`` slot.
    open_run = open_state is not None
    if open_run:
        with jax.named_scope("open_admit"):
            (req_t, qbuf, hist, qhead, qlen, arrived, shed, departed,
             slo_viol, lat_sum, occ_int) = open_state
            Q = qbuf.shape[1]
            NB = hist.shape[1]
            openc = col(arrival != P.AR_CLOSED)
            zero_u = jnp.zeros_like(seed)
            ar_phase = counter_uniform(seed ^ jnp.uint32(P.AR_PHASE_SALT),
                                       zero_u, jnp.uint32(0))
            gate_on = 1.0 - P.workload_off_gate(now2, ar_phase, wl_period,
                                                wl_duty)
            rate = P.arrival_rate_at(arrival, arr_rate, gate_on, wl_burst)
            # Bernoulli-rounded count: floor(rate·dt) plus a trial on the
            # fractional part — the expected count is exactly rate·dt, so
            # the admitted load is dt-independent (closed rows: rate 0,
            # count 0).
            m = rate * dt
            mf = jnp.floor(m)
            u_arr = counter_uniform(seed ^ jnp.uint32(P.AR_SALT), zero_u,
                                    stepu)
            n_arr = (mf + (u_arr < (m - mf))).astype(jnp.int32)
            n_adm = jnp.minimum(n_arr, q_cap - qlen)   # bounded queue: shed
            qi = jnp.arange(Q, dtype=jnp.int32)[None, :]
            wr = ((qi - col(qhead + qlen)) % Q) < col(n_adm)
            qbuf = jnp.where(wr, col(now2), qbuf)
            qlen = qlen + n_adm
            arrived = arrived + n_arr
            shed = shed + (n_arr - n_adm)

    def first_oh(mask):
        """One-hot of the lowest-tid True per row (all-False rows stay
        all-False: their first_true is T, which no tid equals)."""
        return tid == first_true(mask)

    def pick(c, a, b):
        """``where(c, a, b)`` for bool ``a``/``b`` (Mosaic cannot select
        between two bool vectors)."""
        return (c & a) | (~c & b)

    def thc_of(s):
        """Algorithm 1's thc: holder + every waiter (CS/SPIN/SLEEP/WAKING),
        per config."""
        return jnp.sum((active & (s >= P.CS) & (s <= P.WAKING))
                       .astype(jnp.int32), axis=-1)

    wl_phase_u, wl_gate_off, wl_tscale = workload_state(
        col(seed), tidb, col(now2), col(wl_period), col(wl_duty),
        col(wl_spread))

    def draw_into(mask, lo, hi, c, is_ncs=0):
        u = counter_uniform(col(seed), tidb, c)
        val = workload_draw(u, col(lo), col(hi), is_ncs, col(workload),
                            wl_gate_off, wl_tscale, col(wl_burst))
        return val, jnp.where(mask, c + jnp.uint32(1), c)

    def park(mask, st, wake_at, permits, wake_count, slept, rem):
        """DES ``_sleep``: park, absorbing banked permits (semaphore law —
        an absorbed permit still pays the park/unpark round trip)."""
        rank = lane_cumsum(mask) - 1
        grant = mask & (rank < col(permits))
        n_grant = jnp.sum(grant.astype(jnp.int32), axis=-1)
        st = jnp.where(grant, P.WAKING,
                       jnp.where(mask, P.SLEEP_ST, st))
        wake_at = jnp.where(grant, col(now2) + wake_eff, wake_at)
        return (st, wake_at, permits - n_grant, wake_count + n_grant,
                jnp.where(mask, 1, slept), jnp.where(mask, inf, rem))

    @jax.named_scope("oracle")
    def oracle_acquire(happened, winner_oh, thc, sws, cnt, ewma, wuc):
        """A12-A33 at an acquisition: oracle family dispatch, A16-A17
        clamp, C1/C2 correction — windowed disciplines only."""
        do = happened & (win_f > 0)
        spun_w = jnp.sum(jnp.where(winner_oh, spun, 0), axis=-1)
        # budget_scaled rows feed the oracle "did this acquisition park?"
        # alone: every fissile arrival spins first, so the raw spun flag
        # would mask the late signal and freeze the window at 1.
        spun_w = spun_w * (1 - bscale_f)
        slept_w = jnp.sum(jnp.where(winner_oh, slept, 0), axis=-1)
        delta, cnt2, ewma2 = P.oracle_update(                  # E2-E11
            oracle, spun_w, slept_w, sws, cnt, ewma, k)
        delta = jnp.clip(delta, 1 - sws, sws_max - sws)        # A16-A17
        sws2 = sws + delta                                     # A20
        tmp = jnp.where((delta < 0) & (thc > sws2), thc - sws2,       # C2
                        jnp.where((delta > 0) & (thc > sws), thc - sws,
                                  0))                                 # C1
        corr = jnp.sign(delta) * jnp.minimum(jnp.abs(delta), tmp)  # A32
        return (jnp.where(do, sws2, sws), jnp.where(do, cnt2, cnt),
                jnp.where(do, ewma2, ewma), jnp.where(do, wuc + corr, wuc))

    # -- spin-budget exhaustion -> sleep (DES stage order) -----------------
    with jax.named_scope("spin_exhaust"):
        exhausted = (st == P.SPIN) & (col(budget_f) > 0) & (rem <= REM_EPS)
        st, wake_at, permits, wake_count, slept, rem = park(
            exhausted, st, wake_at, permits, wake_count, slept, rem)

    # -- wake completions --------------------------------------------------
    with jax.named_scope("wake"):
        due = (st == P.WAKING) & (wake_at <= col(now2 + teps))
        holder_free = ~jnp.any(st == P.CS, axis=-1, keepdims=True)
        # FIFO rows that park (hapax) keep tickets through SLEEP/WAKING, and a
        # wake completion grants the oldest ticket, not the lowest tid.  For
        # every other row (and the never-parking fifo row) due threads carry
        # no ordering constraint and the historical id pick is unchanged.
        wkey = jnp.where(due, ticket, NO_TICKET)
        winA_f = first_oh(due & (wkey == jnp.min(wkey, axis=-1,
                                                 keepdims=True)))
        winA = pick(col(fifo_f) > 0, winA_f, first_oh(due)) & holder_free
        cs_val, ctr = draw_into(winA, cs_lo, cs_hi, ctr)
        rem = jnp.where(winA, cs_val, rem)
        st = jnp.where(winA, P.CS, st)
        # the sleep->spin transition's payoff: a woken thread that finds the
        # lock free acquired "slept and not spun" -> EvalSWS doubles the window
        sws, cnt, ewma, wuc = oracle_acquire(jnp.any(winA, axis=-1), winA,
                                             thc_of(st), sws, cnt, ewma, wuc)
        losers = due & ~winA
        to_spin = losers & (col(w2s_f) > 0)    # woken into the spinning window
        st = jnp.where(to_spin, P.SPIN, st)
        spun = jnp.where(to_spin, 1, spun)
        # fissile (budget_spin + wake_to_spin) re-arms a fresh bounded budget;
        # the mutable row's window spinners keep the unbounded inf sentinel
        rem = jnp.where(to_spin,
                        jnp.where(col(budget_f) > 0, budget_eff(sws), inf),
                        rem)
        to_park = losers & (col(repark_f) > 0)     # barged: park again
        st, wake_at, permits, wake_count, slept, rem = park(
            to_park, st, wake_at, permits, wake_count, slept, rem)

    # -- CS completion / release ------------------------------------------
    with jax.named_scope("release"):
        holder_done = (st == P.CS) & (rem <= REM_EPS)
        rel = jnp.any(holder_done, axis=-1)
        completed = completed + rel.astype(jnp.int32)
        completed_pt = completed_pt + holder_done.astype(jnp.int32)
        thc_pre = thc_of(st)                                   # R14 (pre-FAD)
        do_latch = rel & (win_f > 0)
        r_wuc = jnp.where(do_latch & (wuc >= 0), wuc, -1)      # R2-R6
        wuc = jnp.where(do_latch, jnp.where(wuc >= 0, 0, wuc + 1),
                        wuc)                                   # R4/R7
        ncs_val, ctr = draw_into(holder_done, ncs_lo, ncs_hi, ctr, is_ncs=1)
        rem = jnp.where(holder_done, ncs_val, rem)
        st = jnp.where(holder_done, P.NCS, st)                 # R9-R10
    # -- open-loop departure: an open config's completed request leaves
    # the system instead of drawing a fresh NCS — latency = now2 - req_t
    # lands in the log-spaced histogram and the SLO/latency counters; the
    # thread slot frees (DONE) for the end-of-step binding stage.
    if open_run:
        with jax.named_scope("open_depart"):
            depart = holder_done & openc
            latv = col(now2) - req_t
            binv = jnp.clip(
                jnp.floor(jnp.log2(jnp.maximum(latv, jnp.float32(1e-30))
                                   / jnp.float32(P.LAT_BIN0))
                          * jnp.float32(P.LAT_BINS_PER_OCTAVE)),
                0, NB - 1).astype(jnp.int32)
            has_dep = jnp.any(depart, axis=-1)
            dep_bin = jnp.sum(jnp.where(depart, binv, 0), axis=-1)
            nbi = jnp.arange(NB, dtype=jnp.int32)[None, :]
            hist = hist + ((nbi == dep_bin[:, None]) & has_dep[:, None]
                           ).astype(jnp.int32)
            lat_sum = lat_sum + jnp.sum(jnp.where(depart, latv, 0.0), axis=-1)
            departed = departed + has_dep.astype(jnp.int32)
            slo_viol = slo_viol + jnp.sum(
                (depart & (latv > col(slo))).astype(jnp.int32), axis=-1)
            st = jnp.where(depart, P.DONE, st)
            rem = jnp.where(depart, inf, rem)
            req_t = jnp.where(depart, jnp.float32(-1.0), req_t)
    # handoff: grant priority is the arrival ticket for FIFO rows, the
    # thread id otherwise — or, with tie_break="random", a fresh seeded
    # per-(thread, step) key (the DES picks a spinner at random; tb == 0
    # keeps the historical id order bit-identically, equal random keys
    # fall back to it)
    with jax.named_scope("handoff"):
        spinners = st == P.SPIN
        can_handoff = rel & (hand_f > 0) & jnp.any(spinners, axis=-1)
        tb_u = counter_uniform(col(seed) ^ jnp.uint32(P.TB_SALT), tidb, stepuT)
        rkey = (tb_u * jnp.float32(2 ** 23)).astype(jnp.int32)
        key = jnp.where(spinners,
                        jnp.where(col(fifo_f) > 0, ticket,
                                  jnp.where(col(tb) > 0, rkey, tidb)),
                        NO_TICKET)
        cand = spinners & (key == jnp.min(key, axis=-1, keepdims=True))
        winB = first_oh(cand) & col(can_handoff)
        cs_valB, ctr = draw_into(winB, cs_lo, cs_hi, ctr)
        rem = jnp.where(winB, cs_valB, rem)
        st = jnp.where(winB, P.CS, st)
        sws, cnt, ewma, wuc = oracle_acquire(can_handoff, winB, thc_pre - 1,
                                             sws, cnt, ewma, wuc)
    # wake quota: per-discipline rule (R11-R21 for the mutable row,
    # wake-one for sleep/adaptive, none for pure spin/FIFO)
    with jax.named_scope("wake_quota"):
        n_parked = jnp.sum(((st == P.SLEEP_ST) | (st == P.WAKING))
                           .astype(jnp.int32), axis=-1)
        quota = P.discipline_release_quota(policy, r_wuc, thc_pre, sws,
                                           n_parked,
                                           can_handoff.astype(jnp.int32))
        quota = jnp.where(rel, quota, 0)
        sleepers = st == P.SLEEP_ST
        rank_s = lane_cumsum(sleepers) - 1
        sel_id = sleepers & (rank_s < col(quota))
        # FIFO rows wake the oldest ticket first (hapax head-of-queue unlock;
        # their quota is 0/1, so the single min-ticket pick covers it) — the
        # never-parking fifo row has no sleepers, leaving sel_id untouched.
        skey = jnp.where(sleepers, ticket, NO_TICKET)
        sel_f = first_oh(sleepers
                         & (skey == jnp.min(skey, axis=-1, keepdims=True))) \
            & (col(quota) > 0)
        sel = pick(col(fifo_f) > 0, sel_f, sel_id)
        n_sel = jnp.sum(sel.astype(jnp.int32), axis=-1)
        st = jnp.where(sel, P.WAKING, st)
        wake_at = jnp.where(sel, col(now2) + wake_eff, wake_at)
        wake_count = wake_count + n_sel
        permits = permits + (quota - n_sel)    # park-free permits are banked

    # -- ttas_backoff polls (backoff rows only; exact no-op otherwise) ----
    # A handoff=0 release just frees the lock, so the poll IS the acquire
    # path: an eligible spinner (next-poll time reached, lock free) picks
    # the lock up here; every other eligible poller re-arms with a
    # truncated-binary-exponential delay ``spin_budget * 2^min(attempt,
    # BO_CAP) * u`` from the dedicated BO_SALT stream.  Backoff rows never
    # park, so ``wake_at`` doubles as the next-poll time and ``ticket`` as
    # the failed-attempt counter (both unread by the generic stages for
    # spinning threads).
    with jax.named_scope("backoff_poll"):
        bo_u = counter_uniform(col(seed) ^ jnp.uint32(P.BO_SALT), tidb, stepuT)
        poll = (st == P.SPIN) & (col(backoff_f) > 0) \
            & (wake_at <= col(now2 + teps))
        holder_freeP = ~jnp.any(st == P.CS, axis=-1, keepdims=True)
        winP = first_oh(poll) & holder_freeP
        cs_valP, ctr = draw_into(winP, cs_lo, cs_hi, ctr)
        rem = jnp.where(winP, cs_valP, rem)
        st = jnp.where(winP, P.CS, st)
        poll_fail = poll & ~winP
        ticket = jnp.where(poll_fail, ticket + 1, ticket)
        bo_exp = jnp.exp2(jnp.minimum(ticket, P.BO_CAP).astype(jnp.float32))
        wake_at = jnp.where(poll_fail,
                            col(now2) + col(spin_budget) * bo_exp * bo_u,
                            wake_at)

    # -- arrivals (NCS finished) ------------------------------------------
    with jax.named_scope("arrivals"):
        arr = (st == P.NCS) & (rem <= REM_EPS) & active
        thc_base = thc_of(st)
        rank_a = lane_cumsum(arr) - 1
        thc_pre_i = col(thc_base) + rank_a                     # A4 per arrival
        slept = jnp.where(arr, 0, slept)                       # A3
        spun = jnp.where(arr, 0, spun)
        holder_free2 = ~jnp.any(st == P.CS, axis=-1, keepdims=True)
        sleeps = arr & (P.discipline_arrival_sleeps(
            col(policy), rank_a, thc_pre_i, col(sws),
            holder_free2.astype(jnp.int32)) > 0)               # A7 per row
        nonsleep = arr & ~sleeps
        winC = first_oh(nonsleep) & holder_free2
        cs_valC, ctr = draw_into(winC, cs_lo, cs_hi, ctr)
        rem = jnp.where(winC, cs_valC, rem)
        st = jnp.where(winC, P.CS, st)
        sws, cnt, ewma, wuc = oracle_acquire(jnp.any(winC, axis=-1), winC,
                                             thc_base + 1, sws, cnt, ewma, wuc)
        to_spinC = nonsleep & ~winC
        st = jnp.where(to_spinC, P.SPIN, st)
        spun = jnp.where(to_spinC, 1, spun)
        rem = jnp.where(to_spinC,
                        jnp.where(col(budget_f) > 0, budget_eff(sws), inf),
                        rem)
    # ticket-order bookkeeping: every new waiter takes the next ticket
    # (rank order within the step); only FIFO rows read them for grants.
    # FIFO rows that park (hapax) ticket their parking arrivals too — for
    # every other row the joiner set is exactly the new spinners.
    with jax.named_scope("tickets"):
        joiners = to_spinC | (sleeps & (col(fifo_f) > 0))
        rank_t = lane_cumsum(joiners) - 1
        ticket = jnp.where(joiners, col(nticket) + rank_t, ticket)
        nticket = nticket + jnp.sum(joiners.astype(jnp.int32), axis=-1)
        # backoff rows: a new spinner starts its attempt counter at 0 and
        # schedules its first re-poll within one base delay
        bo_new = to_spinC & (col(backoff_f) > 0)
        ticket = jnp.where(bo_new, 0, ticket)
        wake_at = jnp.where(bo_new, col(now2) + col(spin_budget) * bo_u,
                            wake_at)
    with jax.named_scope("arrivals"):
        st, wake_at, permits, wake_count, slept, rem = park(
            sleeps, st, wake_at, permits, wake_count, slept, rem)
    # retire tickets: spinners keep theirs; FIFO rows that park keep them
    # through SLEEP/WAKING so grants stay in arrival order
    with jax.named_scope("tickets"):
        queued = (st == P.SPIN) | ((col(fifo_f) > 0)
                                   & ((st == P.SLEEP_ST) | (st == P.WAKING)))
        ticket = jnp.where(queued, ticket, NO_TICKET)

    if not open_run:
        return (st, rem, wake_at, slept, spun, ctr, ticket, completed_pt,
                sws, cnt, ewma, wuc, permits, nticket, completed,
                wake_count)

    # -- open-loop binding: queued requests claim free thread slots (DONE
    # under an open config) in queue order, entering NCS with a workload
    # draw and carrying their admission timestamp; then the occupancy
    # integral accumulates LAST, so every in-system request (queued or
    # bound) is counted for exactly the steps between its admission and
    # its departure.
    with jax.named_scope("open_bind"):
        freem = active & (st == P.DONE) & openc
        rank_f = lane_cumsum(freem) - 1
        n_free = jnp.sum(freem.astype(jnp.int32), axis=-1)
        n_bind = jnp.minimum(qlen, n_free)
        bindm = freem & (rank_f < col(n_bind))
        qpos = (col(qhead) + rank_f) % Q
        rt = jnp.take_along_axis(qbuf, qpos, axis=1)
        ncs_b, ctr = draw_into(bindm, ncs_lo, ncs_hi, ctr, is_ncs=1)
        st = jnp.where(bindm, P.NCS, st)
        rem = jnp.where(bindm, ncs_b, rem)
        req_t = jnp.where(bindm, rt, req_t)
        slept = jnp.where(bindm, 0, slept)
        spun = jnp.where(bindm, 0, spun)
        qhead = (qhead + n_bind) % Q
        qlen = qlen - n_bind
        busy = jnp.sum((active & (req_t >= 0.0)).astype(jnp.int32), axis=-1)
        occ_int = occ_int + (qlen + busy).astype(jnp.float32) * dt

    return (st, rem, wake_at, slept, spun, ctr, ticket, completed_pt,
            sws, cnt, ewma, wuc, permits, nticket, completed, wake_count,
            req_t, qbuf, hist, qhead, qlen, arrived, shed, departed,
            slo_viol, lat_sum, occ_int)


# --------------------------------------------------------------------------
# Time-blocked fused rollout body: GPS advance + transitions iterated for
# ``n_sub_steps`` timesteps in ONE call, with the whole (C, T) state block
# staying in registers/VMEM across the inner loop.  This is the reference
# twin of the Pallas kernel repro.kernels.lock_sim.lock_sim_block (which
# applies THIS function per config block), and the body repro.core.xdes
# drives from its chunked while_loop: the outer rollout shrinks from
# ``n_steps`` dispatches to ``n_steps / n_sub_steps``.
# --------------------------------------------------------------------------

#: Context columns of the block boundary, after the per-step state: the GPS
#: advance inputs, then the transition context minus ``now2`` (recomputed
#: inside the loop as ``(step0 + s + 1) * dt`` — the exact expression of
#: the per-step path, so blocked and per-step rollouts are bit-identical).
BLOCK_CONTEXT = ("step0", "limit", "alpha", "cores", "has_budget",
                 "policy", "threads", "dt", "wake", "cs_lo", "cs_hi",
                 "ncs_lo", "ncs_hi", "k", "sws_max", "spin_budget", "seed",
                 "oracle", "workload", "wl_period", "wl_duty", "wl_burst",
                 "wl_spread", "arrival", "arr_rate", "q_cap", "slo", "tb",
                 "fault", "flt_rate", "flt_scale", "park_cost")


def lock_sim_block_ref(st, rem, wake_at, slept, spun, ctr, ticket,
                       completed_pt, sws, cnt, ewma, wuc, permits, nticket,
                       completed, wake_count, spin_cpu,
                       step0, alpha, cores, has_budget,
                       policy, threads, dt, wake, cs_lo, cs_hi,
                       ncs_lo, ncs_hi, k, sws_max, spin_budget, seed,
                       oracle, workload, wl_period, wl_duty, wl_burst,
                       wl_spread, arrival, arr_rate, q_cap, slo, tb,
                       fault, flt_rate, flt_scale, park_cost,
                       *, n_sub_steps: int, limit=None, open_state=None):
    """``n_sub_steps`` fused timesteps for a (C, T) block of configurations.

    Each sub-step is exactly one per-step iteration of the legacy rollout
    — :func:`lock_sim_step_ref` (GPS advance) followed by
    :func:`lock_transitions_ref` — with ``now2 = (step0 + s + 1) * dt``
    computed from the global step index ``step0 + s`` in int32 before the
    float multiply, and ``spin_cpu`` accumulated inside the loop in the
    same order as the per-step carry.  Both choices make the blocked
    rollout bit-identical to the per-step path (pinned by tests).

    State is the 16 transition arrays plus ``spin_cpu`` (C,) f32;
    ``step0`` is the global index of the first sub-step (int32 scalar or
    (C,) vector); the remaining context matches
    :data:`TRANSITION_CONTEXT`/``has_budget`` of the advance.  Returns the
    17 updated state arrays — plus the 11 :data:`OPEN_STATE` arrays,
    carried through the loop and masked by ``limit`` exactly like the
    closed state, when ``open_state`` is given (open-loop batches).

    ``limit`` (int32 scalar or (C,) vector, optionally traced) caps the
    global step index: sub-steps with ``step0 + s >= limit`` select the
    pre-step state unchanged (a ``where`` passthrough), so a partial tail
    block of ``limit - step0`` live sub-steps is bit-identical to running
    exactly that many steps.  This is what lets the blocked rollout treat
    the total step count as a traced value (one compiled executable per
    padded shape instead of one per horizon).  ``limit=None`` keeps the
    legacy unmasked graph.
    """

    n_open = 0 if open_state is None else len(open_state)

    def body(s, carry):
        state, cpu = carry[:16], carry[16]
        ostate = carry[17:]
        st_s, rem_s = state[0], state[1]
        i = step0 + s
        now2 = (i.astype(jnp.float32) + 1.0) * dt
        rem_s, burn = lock_sim_step_ref(st_s, rem_s, alpha, cores, dt,
                                        has_budget)
        rem_s = fault_rewind(st_s, rem_s, alpha, cores, dt,
                             i.astype(jnp.float32) * dt, seed, fault,
                             flt_rate, flt_scale)
        out = lock_transitions_ref(st_s, rem_s, *state[2:], now2, i,
                                   policy, threads, dt, wake, cs_lo,
                                   cs_hi, ncs_lo, ncs_hi, k, sws_max,
                                   spin_budget, seed, oracle, workload,
                                   wl_period, wl_duty, wl_burst,
                                   wl_spread, arrival, arr_rate, q_cap,
                                   slo, tb, fault, flt_rate, flt_scale,
                                   park_cost,
                                   open_state=ostate if n_open else None)
        new, onew = out[:16], out[16:]
        if limit is None:
            return (*new, cpu + burn, *onew)
        with jax.named_scope("step_limit"):
            act = i < limit                   # bool scalar or (C,)
            actT = act[..., None] if jnp.ndim(act) else act  # (C, 1)
            state = tuple(jnp.where(actT if n.ndim == 2 else act, n, o)
                          for n, o in zip(new, state))
            ostate = tuple(jnp.where(actT if n.ndim == 2 else act, n, o)
                           for n, o in zip(onew, ostate))
            return (*state, cpu + jnp.where(act, burn, 0.0), *ostate)

    carry = (st, rem, wake_at, slept, spun, ctr, ticket, completed_pt,
             sws, cnt, ewma, wuc, permits, nticket, completed, wake_count,
             spin_cpu, *(open_state or ()))
    return jax.lax.fori_loop(0, n_sub_steps, body, carry)


def oracle_update_ref(oracle_id, spun, slept, sws, cnt, ewma, k, sws_max):
    """Batched SWS-oracle observation over ``(C,)`` config vectors.

    Pure-jnp reference for the fused Pallas kernel
    :func:`repro.kernels.lock_sim.oracle_step`: one observation of every
    oracle family row (:data:`repro.core.policy.ORACLE_ROWS` — paper
    EvalSWS, AIMD, fixed-budget retrial, history EWMA) dispatched by
    ``oracle_id``, with the A16-A17 clamp applied.  All inputs int32
    except ``spun``/``slept`` (bool or 0/1 int32).  Returns
    ``(delta, cnt', ewma')`` with ``1 <= sws + delta <= sws_max``.
    """
    from repro.core.policy import oracle_update

    delta, cnt1, ewma1 = oracle_update(oracle_id, spun, slept, sws, cnt,
                                       ewma, k)
    delta = jnp.clip(delta, 1 - sws, sws_max - sws)
    return delta, cnt1, ewma1
