"""The generator copies give exactly the columns of the program's own
generators today, values and dtypes."""

import json
import os

import numpy as np
import pytest

from bench.generators import design_space as G
from bench.generators import fig3_grid as F
from repro.configs import catalog
from repro.core import policy

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _same(a: dict, b: dict):
    assert sorted(a) == sorted(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype, k
        np.testing.assert_array_equal(x, y, err_msg=k)


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 7])
def test_scenario_draw_is_catalogs(seed):
    assert G.sample_scenarios(40, seed) == catalog.sample_scenarios(40, seed)
    _same(G.sample_scenario_columns(40, seed),
          catalog.sample_scenario_columns(40, seed))


@pytest.mark.parametrize("seed", [0, 11])
def test_discipline_columns_are_catalogs(seed):
    ours = G._product_columns(
        G.sample_scenario_columns(12, seed),
        G.lock_discipline_variants(catalog.LOCK_DISCIPLINE_SET,
                                   catalog.LOCK_ORACLES))
    _same(ours, catalog.lock_discipline_columns(12, seed))


@pytest.mark.parametrize("seed", [0, 11])
def test_arrival_columns_are_catalogs(seed):
    variants = G.lock_arrival_variants(
        catalog.LOCK_ARRIVALS, catalog.LOCK_ARRIVAL_RHOS,
        catalog.LOCK_DISCIPLINE_SET, catalog.LOCK_ORACLES)
    ours = G.arrival_columns(G.sample_scenario_columns(5, seed), variants)
    _same(ours, catalog.lock_arrival_columns(5, seed))


def test_phase_cells_are_sweeps():
    from benchmarks import sweep

    sc = G.sample_scenario_columns(60, 4)
    assert G._scenario_feats(sc) == sweep._scenario_feats(sc)
    keys = [(f["cs"], f["sub"], f["wake"]) for f in G._scenario_feats(sc)]
    u1, ids1 = G._phase_cells(keys)
    u2, ids2 = sweep._phase_cells(keys)
    assert u1 == u2
    np.testing.assert_array_equal(ids1, ids2)


@pytest.mark.parametrize("seeds", [(0, 1), (5, 2**32 - 1, 77)])
def test_fig3_columns_are_config_columns_of_the_grid(seeds):
    ours = F.fig3_columns(seeds)
    theirs = policy.config_columns(catalog.lock_fig3_grid(seeds=seeds))
    np.testing.assert_array_equal(np.isnan(ours["alpha"]),
                                  np.isnan(theirs["alpha"]))
    ours["alpha"] = theirs["alpha"] = np.zeros(1)
    _same(ours, theirs)


def test_fig3_constants_are_catalogs():
    assert F.LOCK_REGIMES == catalog.LOCK_REGIMES
    assert F.LOCK_THREADS == catalog.LOCK_THREADS
    assert F.LOCK_DISCIPLINES == catalog.LOCK_DISCIPLINES
    assert (F.LOCK_WAKE, F.LOCK_CORES) == (catalog.LOCK_WAKE,
                                           catalog.LOCK_CORES)


@pytest.mark.parametrize("traffic", ["closed_512", "open_16"])
def test_every_seed_offers_the_same_work(traffic):
    """A run's seed reorders the pool and reseeds the simulations; the
    set of scenarios, so the planned horizon, is the same for all."""
    from repro.core import xdes

    cfg = json.load(open(os.path.join(ROOT, "bench/configs/"
                                      "design_space.json")))
    tr = json.load(open(os.path.join(ROOT, f"bench/traffic/{traffic}.json")))
    tr["scenarios"] = 24
    plans = []
    for seed, k in ((1, 0), (1, 1), (2**31 + 5, 3)):
        sw = G.sweep(cfg, tr, seed, k)
        plans.append(np.sort(xdes.plan_schedule_columns(sw["cols"], 50)[1]))
    for p in plans[1:]:
        np.testing.assert_array_equal(p, plans[0])
    a, b = G.sweep(cfg, tr, 1, 0), G.sweep(cfg, tr, 1, 1)
    assert not np.array_equal(a["cols"]["seed"], b["cols"]["seed"])
    _same(G.sweep(cfg, tr, 9, 2)["cols"], G.sweep(cfg, tr, 9, 2)["cols"])


def test_fig3_seeds_reorder_the_same_replicas():
    tr = {"replicas": 6, "max_threads": 32}
    a, b = F.sweep({}, tr, 1, 0), F.sweep({}, tr, 2**31 + 3, 0)
    assert sorted(a["cols"]["seed"]) == sorted(b["cols"]["seed"])
    assert not np.array_equal(a["cols"]["seed"], b["cols"]["seed"])
    c = F.sweep({}, tr, 1, 1)
    assert set(a["cols"]["seed"]).isdisjoint(c["cols"]["seed"])
