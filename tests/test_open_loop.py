"""Open-loop arrival engine: registry/encoding semantics, the exact
Little's-law + conservation invariants behind the on-device accounting,
queue-bound/shedding behaviour, zero-arrival bit-identity to the closed
engine, ref-vs-Pallas and blocked-vs-scan bit-identity (histograms
included), same-seed latency-percentile determinism (the CI check),
streamed-vs-one-shot identity through the open summary columns, the
seeded randomized tie-break, refine_grid boundary-cell coverage, and the
arrival sweep / serve plumbing.  Randomized-input variants of the
invariants live in tests/test_open_loop_props.py (hypothesis)."""

from dataclasses import replace

import numpy as np
import pytest

from repro.core import policy as P
from repro.core import stream as xstream
from repro.core import xdes
from repro.core.des import LockSim
from repro.core.policy import SimConfig

SHORT = (0.0, 3.7e-6)
WAKE = 8e-6
OPEN_ROWS = ["poisson", "bursty"]


def open_cfg(lock="mutable", arrival="poisson", rate=2e5, seed=0,
             threads=4, cores=4, **kw) -> SimConfig:
    kw.setdefault("wl_period", 8e-5)
    return SimConfig(lock, threads=threads, cores=cores, cs=SHORT,
                     ncs=SHORT, wake_latency=WAKE, seed=seed,
                     arrival=arrival, arrival_rate=rate, **kw)


def check_open_invariants(res, i, cfg, rtol=1e-3, atol=1e-6):
    """The exact per-config open-loop accounting contract:

    * conservation: arrived == shed + departed + in_flight (integers),
    * occupancy bound: in_flight <= queue_cap + threads (the ring buffer
      plus one bound request per simulated thread),
    * Little's law, sharp form: requests are counted in the occupancy
      integral for exactly their sojourn-so-far, so
      ``0 <= occ_int - lat_sum <= in_flight * t_end`` up to float32
      accumulation error,
    * histogram totals: the latency histogram holds every departure.
    """
    arrived = int(res.arrived[i])
    shed = int(res.shed[i])
    departed = int(res.departed[i])
    fly = int(res.in_flight[i])
    assert arrived - shed - departed - fly == 0, (i, cfg.lock)
    assert 0 <= fly <= cfg.queue_cap + cfg.threads, (i, cfg.lock)
    assert 0 <= shed <= arrived
    assert int(res.slo_viol[i]) <= departed
    assert int(res.lat_hist[i].sum()) == departed, (i, cfg.lock)
    occ = float(res.occ_int[i])
    lat = float(res.lat_sum[i])
    slack = rtol * max(occ, lat) + atol
    assert occ - lat >= -slack, (i, cfg.lock, occ, lat)
    assert occ - lat <= fly * float(res.t_end[i]) + slack, (i, cfg.lock)


# --------------------------------------------------------------------------
# Registry + scalar semantics + validation
# --------------------------------------------------------------------------
def test_arrival_registry():
    assert sorted(P.ARRIVAL_IDS) == ["bursty", "closed", "poisson"]
    assert P.ARRIVAL_IDS["closed"] == P.AR_CLOSED == 0
    assert all(P.ARRIVAL_ROWS[n].aid == i
               for n, i in P.ARRIVAL_IDS.items())
    assert P.ARRIVAL_ROWS["bursty"].time_varying == 1
    assert P.ARRIVAL_ROWS["poisson"].time_varying == 0


def test_arrival_rate_scalar_semantics():
    # closed: rate 0 regardless of base; poisson: the base, untouched
    assert P.arrival_rate_at(P.AR_CLOSED, 5e4, 1.0, 8.0) == 0.0
    assert P.arrival_rate_at(P.AR_POISSON, 5e4, 1.0, 8.0) == 5e4
    # bursty: `burst` x base inside the ON window, base outside
    assert P.arrival_rate_at(P.AR_BURSTY, 5e4, 1.0, 8.0) == 4e5
    assert P.arrival_rate_at(P.AR_BURSTY, 5e4, 0.0, 8.0) == 5e4
    # time-averaged multipliers (saturation math + DES twin share these)
    assert P.arrival_mean_scale(P.AR_CLOSED, 0.25, 8.0) == 0.0
    assert P.arrival_mean_scale(P.AR_POISSON, 0.25, 8.0) == 1.0
    assert P.arrival_mean_scale(P.AR_BURSTY, 0.25, 8.0) == \
        pytest.approx(1.0 + 0.25 * 7.0)


def test_latency_histogram_readout():
    edges = P.latency_bin_edges()
    assert len(edges) == P.LAT_NBINS + 1
    assert edges[0] == P.LAT_BIN0
    np.testing.assert_allclose(edges[1:] / edges[:-1], np.sqrt(2.0))
    # nearest-rank readout at geometric bin midpoints
    hist = np.zeros((1, P.LAT_NBINS), np.int32)
    hist[0, 10] = 50
    hist[0, 20] = 50
    p50, p95, p99 = P.latency_percentiles(hist)
    assert p50[0] == pytest.approx(np.sqrt(edges[10] * edges[11]))
    assert p95[0] == p99[0] == pytest.approx(np.sqrt(edges[20] * edges[21]))
    # empty histogram reads NaN
    assert np.isnan(P.latency_percentiles(np.zeros((1, P.LAT_NBINS)))[0][0])


def test_sim_config_validates_open_fields():
    c = open_cfg(rate=1e5, queue_cap=17, slo=5e-4)
    assert c.open_loop and not open_cfg(arrival="closed", rate=0.0).open_loop
    assert c.arrival_kwargs() == dict(arrival="poisson", arrival_rate=1e5,
                                      queue_cap=17)
    with pytest.raises(ValueError):
        open_cfg(arrival="nope")
    with pytest.raises(ValueError):
        open_cfg(rate=-1.0)
    with pytest.raises(ValueError):
        open_cfg(queue_cap=0)
    with pytest.raises(ValueError):
        open_cfg(queue_cap=P.QUEUE_MAX + 1)
    with pytest.raises(ValueError):
        open_cfg(slo=0.0)
    with pytest.raises(ValueError):
        open_cfg(tie_break="coin")
    arrs = P.encode_configs([c])
    assert arrs["arrival"][0] == P.AR_POISSON
    assert arrs["q_cap"][0] == 17
    assert arrs["tb"][0] == P.TIE_BREAK_IDS["id"]


# --------------------------------------------------------------------------
# Exact invariants: Little's law, conservation, queue bound
# --------------------------------------------------------------------------
def _invariant_batch(seed=0):
    rng = np.random.default_rng(seed)
    cfgs = []
    for arrival in OPEN_ROWS:
        for lock in ("ttas", "mutable", "sleep", "fifo"):
            cfgs.append(open_cfg(
                lock, arrival=arrival,
                rate=float(rng.uniform(5e4, 8e5)),
                seed=int(rng.integers(0, 1000)),
                threads=int(rng.integers(2, 8)),
                cores=int(rng.integers(2, 8)),
                queue_cap=int(rng.integers(4, 64)),
                slo=float(rng.uniform(1e-5, 1e-3))))
    return cfgs


def test_littles_law_exact_invariant():
    """One batched call over both arrival rows x several locks at random
    rates spanning under- to over-saturation; every config must satisfy
    the sharp Little's-law inequality and exact request conservation."""
    cfgs = _invariant_batch(seed=1)
    res = xdes.simulate_batch(cfgs, n_steps=4000, dt=5e-8)
    assert int(np.asarray(res.arrived).sum()) > 0
    assert int(np.asarray(res.departed).sum()) > 0
    for i, c in enumerate(cfgs):
        check_open_invariants(res, i, c)


def test_littles_law_band():
    """L = lambda * W as a band on a long stable run: the occupancy
    integral over the horizon must agree with the departure rate times
    the mean sojourn within the dt-fidelity band (the boundary term —
    still-in-flight requests — is small when the system is stable)."""
    cfgs = [open_cfg(lock, rate=2e5, seed=3)
            for lock in ("ttas", "mutable", "sleep")]
    res = xdes.simulate_batch(cfgs, n_steps=40000, dt=5e-8)
    for i in range(len(cfgs)):
        assert res.departed[i] > 100
        L = float(res.occ_int[i]) / float(res.t_end[i])
        lam_w = float(res.lat_sum[i]) / float(res.t_end[i])
        assert lam_w <= L * (1 + 1e-3) + 1e-9
        assert L < 1.6 * lam_w + 0.1, (i, L, lam_w)


def test_queue_bound_and_shedding():
    """Offered load far past saturation with a tiny queue: the bound is
    never exceeded (in_flight <= cap + threads) and the overflow is shed,
    not lost — conservation still holds exactly."""
    cfgs = [open_cfg("ttas", rate=5e6, queue_cap=8, seed=s)
            for s in range(3)]
    res = xdes.simulate_batch(cfgs, n_steps=3000, dt=5e-8)
    for i, c in enumerate(cfgs):
        check_open_invariants(res, i, c)
        assert int(res.shed[i]) > 0, "saturated tiny queue must shed"


# --------------------------------------------------------------------------
# Zero-arrival row == closed-loop engine, bit for bit
# --------------------------------------------------------------------------
def test_zero_arrival_bit_identical_to_closed():
    """Forcing the open-loop machinery onto an all-closed batch must not
    move a single bit of the closed outputs — the closed row admits
    nothing, so the OPEN_STATE arrays stay inert."""
    cfgs = [SimConfig(lock, threads=5, cores=4, cs=SHORT, ncs=SHORT,
                      wake_latency=WAKE, seed=s)
            for s, lock in enumerate(("ttas", "mutable", "sleep", "fifo"))]
    closed = xdes.simulate_batch(cfgs, n_steps=300)
    forced = xdes.simulate_batch(cfgs, n_steps=300, open_loop=True)
    assert closed.lat_hist is None and forced.lat_hist is not None
    for f in ("completed", "completed_per_thread", "wake_count",
              "final_sws", "spin_cpu"):
        np.testing.assert_array_equal(getattr(closed, f),
                                      getattr(forced, f), err_msg=f)
    assert int(np.asarray(forced.arrived).sum()) == 0
    assert int(np.asarray(forced.lat_hist).sum()) == 0
    assert int(np.asarray(forced.in_flight).sum()) == 0


# --------------------------------------------------------------------------
# ref vs Pallas / blocked vs scan bit-identity, histograms included
# --------------------------------------------------------------------------
def _parity_batch(seed=17):
    rng = np.random.default_rng(seed)
    cfgs = []
    for arrival in OPEN_ROWS:
        for lock, tb in (("mutable", "id"), ("mutable", "random"),
                         ("ttas", "id"), ("ttas", "random"),
                         ("sleep", "id"), ("fifo", "random"),
                         ("adaptive", "id")):
            cfgs.append(open_cfg(
                lock, arrival=arrival,
                rate=float(rng.uniform(5e4, 6e5)),
                seed=int(rng.integers(0, 1000)),
                threads=int(rng.integers(2, 9)),
                cores=int(rng.integers(2, 9)),
                queue_cap=int(rng.integers(4, 32)),
                wl_duty=float(rng.uniform(0.1, 0.9)),
                wl_burst=float(rng.uniform(1, 10)),
                tie_break=tb))
    return cfgs


def _assert_open_equal(a, b, msg=""):
    for f in ("completed", "completed_per_thread", "wake_count",
              "final_sws", "spin_cpu", "lat_hist", "arrived", "shed",
              "departed", "slo_viol", "lat_sum", "occ_int", "in_flight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{msg}: {f}")


@pytest.mark.parametrize("block_steps", [1, 32])
def test_open_ref_vs_pallas_blocked(block_steps):
    cfgs = _parity_batch()
    ref = xdes.simulate_batch(cfgs, n_steps=260, rollout="blocked",
                              block_steps=block_steps, backend="ref")
    pal = xdes.simulate_batch(cfgs, n_steps=260, rollout="blocked",
                              block_steps=block_steps, backend="pallas")
    _assert_open_equal(ref, pal, f"ref==pallas B={block_steps}")
    scan = xdes.simulate_batch(cfgs, n_steps=260, rollout="scan",
                               backend="ref")
    _assert_open_equal(ref, scan, f"blocked==scan B={block_steps}")


@pytest.mark.parametrize("block_steps", [1, 32])
def test_open_bind_ring_take_matches_gather(block_steps, monkeypatch):
    """The whole open-loop stage with the compare-and-select ring read
    equals the stage with the ``take_along_axis`` gather it replaced."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref as R

    cfgs = _parity_batch()
    run = lambda: xdes.simulate_batch(cfgs, n_steps=260, rollout="blocked",
                                      block_steps=block_steps, backend="ref")
    new = run()
    try:
        with monkeypatch.context() as m:
            m.setattr(R, "ring_take",
                      lambda x, i: jnp.take_along_axis(x, i, axis=1))
            jax.clear_caches()              # retrace with the gather
            old = run()
    finally:
        jax.clear_caches()
    _assert_open_equal(new, old, f"ring_take==gather B={block_steps}")


def test_latency_percentile_determinism():
    """Same seed => identical on-device histograms and identical
    p50/p95/p99, across separate calls (the CI determinism check)."""
    cfgs = _parity_batch(seed=23)
    a = xdes.simulate_batch(cfgs, n_steps=300)
    b = xdes.simulate_batch(cfgs, n_steps=300)
    np.testing.assert_array_equal(a.lat_hist, b.lat_hist)
    np.testing.assert_array_equal(a.latency_quantiles(),
                                  b.latency_quantiles())
    np.testing.assert_array_equal(np.asarray(a.slo_frac),
                                  np.asarray(b.slo_frac), err_msg="slo")
    # a different seed realizes a different arrival stream
    c = xdes.simulate_batch([replace(cfgs[0], seed=cfgs[0].seed + 1)],
                            n_steps=300)
    assert not np.array_equal(c.lat_hist[0], a.lat_hist[0])


# --------------------------------------------------------------------------
# Streamed == one-shot through the open summary columns
# --------------------------------------------------------------------------
def test_streamed_open_loop_matches_one_shot():
    cfgs = _parity_batch(seed=5)
    one = xdes.simulate_batch(cfgs, n_steps=250, keep_per_thread=False)
    s = xstream.sweep_stream(cfgs, n_steps=250, chunk=4)
    assert s.n_chunks > 1
    for f in ("completed", "lat_hist", "arrived", "shed", "departed",
              "slo_viol", "lat_sum", "occ_int", "in_flight"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s, f)), np.asarray(getattr(one, f)),
            err_msg=f"stream: {f}")
    np.testing.assert_array_equal(s.latency_quantiles(),
                                  one.latency_quantiles())


# --------------------------------------------------------------------------
# DES parity per arrival row (the event-driven twin)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("arrival", OPEN_ROWS)
def test_xdes_vs_des_open_loop_parity(arrival):
    """Seed-averaged departure throughput AND mean sojourn per arrival
    row: the fixed-increment engine vs the event-driven DES twin over the
    same horizon.  Single realizations see different arrival streams
    (counter RNG vs thinning), so the pin is the 3-seed mean in a
    [0.7, 1.4] band — the same fidelity contract as the workload rows."""
    seeds = (0, 1, 2)
    cfgs = [open_cfg("ttas", arrival=arrival, rate=2e5, seed=s,
                     wl_period=4e-4, wl_burst=4.0)
            for s in seeds]
    res = xdes.simulate_batch(cfgs, n_steps=40000, dt=5e-8)
    t_end = float(res.t_end[0])
    x_thr = float(np.asarray(res.departed).mean()) / t_end
    x_lat = float(np.nanmean(np.asarray(res.mean_latency)))

    d_thr, d_lat = [], []
    for s in seeds:
        sim = LockSim("ttas", 4, 4, SHORT, SHORT, WAKE, seed=s,
                      wl_period=4e-4, wl_burst=4.0,
                      **cfgs[0].arrival_kwargs())
        r = sim.run(target_cs=10**9, horizon=t_end)
        assert len(r.latencies) > 50
        d_thr.append(len(r.latencies) / t_end)
        d_lat.append(r.mean_latency)
    d_thr, d_lat = np.mean(d_thr), np.mean(d_lat)
    assert 0.7 * d_thr < x_thr < 1.4 * d_thr, (arrival, x_thr, d_thr)
    assert 0.7 * d_lat < x_lat < 1.4 * d_lat, (arrival, x_lat, d_lat)


# --------------------------------------------------------------------------
# Randomized same-step tie-break (satellite: DES-fidelity fix)
# --------------------------------------------------------------------------
def test_tie_break_registry_and_default():
    assert P.TIE_BREAK_IDS == {"id": 0, "random": 1}
    assert SimConfig("ttas", threads=2, cores=2, cs=SHORT,
                     ncs=SHORT).tie_break == "id"


def test_tie_break_id_is_the_default_bit_for_bit():
    """tie_break="id" must be byte-identical to a config that never
    mentions the field — the pre-tie-break engine behaviour is the
    default, so every committed artifact stays reproducible."""
    base = [SimConfig(lock, threads=6, cores=6, cs=SHORT, ncs=SHORT,
                      wake_latency=WAKE, seed=s)
            for s, lock in enumerate(("tas", "ttas", "mutable", "fifo"))]
    a = xdes.simulate_batch(base, n_steps=400)
    b = xdes.simulate_batch([replace(c, tie_break="id") for c in base],
                            n_steps=400)
    for f in ("completed", "completed_per_thread", "wake_count",
              "final_sws", "spin_cpu"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)
    # ... while "random" actually moves the handoff order
    c = xdes.simulate_batch([replace(x, tie_break="random")
                             for x in base], n_steps=400)
    assert not np.array_equal(a.completed_per_thread,
                              c.completed_per_thread)


def test_tie_break_random_ref_vs_pallas():
    cfgs = [SimConfig(lock, threads=7, cores=7, cs=SHORT, ncs=SHORT,
                      wake_latency=WAKE, seed=s, tie_break="random")
            for s, lock in enumerate(("tas", "ttas", "mutable",
                                      "adaptive", "sleep"))]
    ref = xdes.simulate_batch(cfgs, n_steps=300, backend="ref")
    pal = xdes.simulate_batch(cfgs, n_steps=300, backend="pallas")
    for f in ("completed", "completed_per_thread", "wake_count",
              "final_sws", "spin_cpu"):
        np.testing.assert_array_equal(getattr(ref, f), getattr(pal, f),
                                      err_msg=f)
    # seeded: repeat runs identical
    again = xdes.simulate_batch(cfgs, n_steps=300, backend="ref")
    np.testing.assert_array_equal(ref.completed_per_thread,
                                  again.completed_per_thread)


def test_tie_break_random_fixes_tas_starvation():
    """The deterministic lowest-tid tie-break systematically starves high
    tids under barging locks (the DES resolves such ties by RNG).  The
    randomized tie-break must collapse that artificial spread."""
    mk = lambda tb: SimConfig("tas", threads=8, cores=8, cs=SHORT,
                              ncs=SHORT, wake_latency=WAKE, seed=4,
                              tie_break=tb)
    rid = xdes.simulate_batch([mk("id")], target_cs=300)
    rnd = xdes.simulate_batch([mk("random")], target_cs=300)
    assert rnd.fairness_spread(0) < 0.5 * rid.fairness_spread(0), (
        rid.fairness_spread(0), rnd.fairness_spread(0))


def test_discipline_diagram_byte_identical_at_id_tie_break(tmp_path):
    """The discipline phase diagram predates the tie-break; "id" is the
    default and executes the exact pre-tie-break code path (pinned
    bit-for-bit above), so regenerating ``discipline_phase_diagram.csv``
    must be byte-for-byte reproducible — the artifact cannot drift just
    because the tie-break machinery landed.  (reports/ itself is
    gitignored, so the check regenerates at smoke scale rather than
    hashing a checked-in file.)"""
    from benchmarks.discipline_diagram import write_phase_diagram
    from benchmarks.sweep import discipline_grid

    blobs = []
    for sub in ("a", "b"):
        res = discipline_grid(n_scenarios=2, target_cs=25, verbose=False)
        csv_path, _ = write_phase_diagram(res, str(tmp_path / sub))
        blobs.append(open(csv_path, "rb").read())
    assert blobs[0] == blobs[1]
    assert blobs[0].startswith(b"cs,subscription")


# --------------------------------------------------------------------------
# refine_grid boundary-cell coverage (satellite)
# --------------------------------------------------------------------------
def _refine_case(**kw):
    from benchmarks.sweep import refine_grid

    kw.setdefault("nx", 5)
    kw.setdefault("ny", 3)
    kw.setdefault("factor", 2)
    kw.setdefault("target_cs", 25)
    kw.setdefault("verbose", False)
    return refine_grid(**kw)


def test_refine_grid_matches_dense_on_boundary_points():
    """Every dense point refine_grid reports must carry the same winner a
    brute-force dense run reports at that exact lattice point, and the
    reported point set must be EXACTLY the dense points whose enclosing
    coarse cell touches a phase boundary — interior cells never re-run."""
    from benchmarks.sweep import (LOCK_CORES, LOCK_SHORT, LOCK_WAKE,
                                  _product_columns,
                                  lock_discipline_variants)

    out = _refine_case(disciplines=("ttas", "sleep", "mutable"),
                       oracles=("paper",))
    nx, ny, factor = (out["meta"][k] for k in ("nx", "ny", "factor"))
    names = out["meta"]["variant_names"]
    grid = np.array([[names.index(w) for w in row]
                     for row in out["coarse"]])

    boundary = np.zeros((ny, nx), bool)
    boundary[:, 1:] |= grid[:, 1:] != grid[:, :-1]
    boundary[:, :-1] |= grid[:, 1:] != grid[:, :-1]
    boundary[1:, :] |= grid[1:, :] != grid[:-1, :]
    boundary[:-1, :] |= grid[1:, :] != grid[:-1, :]

    cs_coarse = np.array(out["axes"]["cs_us"]) * 1e-6
    th_coarse = np.array(out["axes"]["threads"])
    cs_dense = np.geomspace(1e-6, 4e-4, factor * nx)
    th_dense = np.unique(np.rint(np.linspace(2, 32, factor * ny))
                         .astype(np.int64))
    ix = np.clip(np.searchsorted(np.sqrt(cs_coarse[1:] * cs_coarse[:-1]),
                                 cs_dense), 0, nx - 1)
    iy = np.clip(np.searchsorted((th_coarse[1:] + th_coarse[:-1]) / 2.0,
                                 th_dense), 0, ny - 1)

    expected = {(round(float(c) * 1e6, 4), int(t))
                for j, t in enumerate(th_dense)
                for i, c in enumerate(cs_dense)
                if boundary[iy[j], ix[i]]}
    reported = {(d["cs_us"], d["threads"]) for d in out["dense"]}
    assert reported == expected      # no interior point, no missed point
    assert out["meta"]["n_dense"] == len(expected)

    # brute-force the FULL dense lattice and compare winners pointwise
    variants = lock_discipline_variants(("ttas", "sleep", "mutable"),
                                        ("paper",))
    V = len(variants)
    cs, th = np.meshgrid(cs_dense, th_dense)
    cs, th = cs.ravel(), th.ravel()
    Pn = cs.size
    cols = _product_columns(
        {"threads": th.astype(np.int64),
         "cores": np.full(Pn, LOCK_CORES, np.int64),
         "cs_hi": cs.astype(np.float64),
         "ncs_hi": np.full(Pn, LOCK_SHORT[1], np.float64),
         "wake": np.full(Pn, LOCK_WAKE, np.float64),
         "contention": np.ones(Pn, np.float64),
         "seed": np.zeros(Pn, np.int64)}, variants)
    red = xstream.CellReduce(V, np.arange(Pn, dtype=np.int32), Pn)
    res = xstream.sweep_stream(cols, target_cs=25, reduce=red)
    dense_win = {(round(float(c) * 1e6, 4), int(t)): names[w]
                 for c, t, w in zip(cs, th,
                                    np.asarray(res.wins).argmax(axis=1))}
    for d in out["dense"]:
        assert d["winner"] == dense_win[(d["cs_us"], d["threads"])], d


def test_refine_grid_uniform_winner_runs_no_dense_points():
    out = _refine_case(disciplines=("ttas",), oracles=("paper",))
    assert out["meta"]["n_dense"] == 0
    assert out["dense"] == []
    assert out["meta"]["n_configs"] == out["meta"]["n_coarse"]


# --------------------------------------------------------------------------
# Sweep + serve plumbing
# --------------------------------------------------------------------------
def test_arrival_sweep_catalog_shape():
    from repro.configs.catalog import (LOCK_ARRIVAL_RHOS, LOCK_ARRIVALS,
                                       lock_arrival_sweep,
                                       lock_arrival_variants,
                                       lock_discipline_variants)

    disc = lock_discipline_variants()
    variants = lock_arrival_variants()
    assert len(variants) == (len(LOCK_ARRIVALS) * len(LOCK_ARRIVAL_RHOS)
                             * len(disc))
    cfgs = lock_arrival_sweep(n_scenarios=2)
    assert len(cfgs) == 2 * len(variants)
    B = len(variants)
    for s in range(2):
        block = cfgs[s * B:(s + 1) * B]
        assert len({(c.threads, c.cores, c.cs, c.wake_latency)
                    for c in block}) == 1
        assert all(c.open_loop for c in block)
        # arrival-major, rho next, disciplines minor
        assert [c.arrival for c in block] == [
            a for a in LOCK_ARRIVALS
            for _ in LOCK_ARRIVAL_RHOS for _ in disc]
        # capacity: lock-serialization vs thread-turnover bound
        c0 = block[0]
        cs_hi, ncs_hi = c0.cs[1], c0.ncs[1]
        cap = min(1.0 / (0.5 * cs_hi),
                  min(c0.threads, c0.cores) / (0.5 * (cs_hi + ncs_hi)))
        assert c0.arrival_rate == pytest.approx(
            LOCK_ARRIVAL_RHOS[0] * cap)
        assert block[0].slo == pytest.approx(
            4.0 * (block[0].cs[1] + block[0].ncs[1]))


def test_arrival_grid_smoke_and_stream_identity():
    from benchmarks.sweep import arrival_grid

    one = arrival_grid(n_scenarios=2, target_cs=25, verbose=False,
                       stream=False)
    A, R = len(one["meta"]["arrivals"]), len(one["meta"]["rhos"])
    V = one["meta"]["n_variants"]
    assert one["meta"]["n_configs"] == 2 * A * R * V
    assert len(one["phase"]) == A * R
    for cell in one["phase"]:
        assert 0 < cell["win_share"] <= 1
        assert 0 < cell["lat_win_share"] <= 1
        assert 0.0 <= cell["mean_shed_frac"] <= 1.0
    st = arrival_grid(n_scenarios=2, target_cs=25, verbose=False,
                      stream=True, mem_mb=64)
    assert st["meta"]["n_chunks"] >= 1
    assert st["phase"] == one["phase"]
    assert st["variants"] == one["variants"]


def test_sched_scenario_open_loop_rows():
    from repro.serve import SchedScenario, sample_sched_scenarios

    sc = SchedScenario(slots=8, requests=20, decode_s=0.05, think_s=0.1,
                       prefill_s=0.01, seed=3, arrival="poisson",
                       arrival_rate_rps=12.0, slo_s=0.6)
    c = sc.to_sim_config("mutable")
    assert c.open_loop and c.arrival == "poisson"
    assert c.arrival_rate == pytest.approx(12.0)
    assert c.slo == pytest.approx(0.6)
    assert sc.capacity_rps > 0
    # open sampling sees the same machines as the closed sweep, with the
    # offered load tied to each scenario's own capacity
    base = sample_sched_scenarios(6)
    opened = sample_sched_scenarios(6, arrival="poisson")
    for a, b in zip(base, opened):
        assert (a.slots, a.requests, a.decode_s, a.think_s) == \
            (b.slots, b.requests, b.decode_s, b.think_s)
        assert b.arrival == "poisson"
        assert 0.3 * b.capacity_rps <= b.arrival_rate_rps \
            <= 1.2 * b.capacity_rps
        assert b.slo_s == pytest.approx(4.0 * (b.decode_s + b.think_s))


def test_continuous_batcher_sheds_at_queue_cap():
    from repro.serve import ContinuousBatcher, Request, SimulatedEngine

    b = ContinuousBatcher(SimulatedEngine(max_slots=2), queue_cap=2)
    reqs = [Request(rid=i, prompt=[2] * 4, max_new_tokens=2)
            for i in range(4)]
    admitted = [b.submit(r) for r in reqs]
    assert admitted == [True, True, False, False]
    assert b.stats.shed == 2 and b.stats.submitted == 4
    assert b.stats.summary()["shed_rate"] == pytest.approx(0.5)
    # the admitted half still drains to completion
    stats = b.run_until_drained()
    assert stats.completed == 2
