"""The traffic generators repeat from a seed and differ across seeds."""
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402

SEED = 2 ** 31 + 977


def _driver(cell, seed, root=run.ROOT, **config):
    found = run.resolve_cell(cell, root)
    mod = run.load_module(found["driver"], "gen_" + cell)
    return mod, mod.Driver(cell=found["cell"],
                           config={**found["config"], **config},
                           traffic=found["traffic"], seed=seed,
                           spans=run.Spans(), log=print)


def test_fleet_layout_is_frontier_in_thirds():
    mod, drv = _driver("plane_frontier_fleet", SEED)
    assert len(drv.names) == 9408
    for name in ("gros", "dahu", "yeti"):
        mine = drv.names == name
        assert mine.sum() == 3136
        counts = np.bincount(drv.group[mine], minlength=4)
        assert list(counts) == [1726, 470, 470, 470]
    assert drv.detect.sum() == 3 * 470


def test_heartbeats_repeat_from_a_seed():
    _, a = _driver("plane_frontier_fleet", SEED)
    _, b = _driver("plane_frontier_fleet", SEED)
    _, c = _driver("plane_frontier_fleet", SEED + 1)
    for k in (0, 5):
        ta, xa = a.beats(k)
        tb, xb = b.beats(k)
        tc, xc = c.beats(k)
        assert np.array_equal(ta, tb) and np.array_equal(xa, xb)
        assert len(xa) != len(xc) or not np.array_equal(xa, xc)
        assert np.all((xa >= k) & (xa < k + 1))
        same = ta[1:] == ta[:-1]
        assert np.all(np.diff(xa)[same] >= 0)  # ordered within a tenant


def test_heartbeat_count_follows_the_static_map():
    mod, drv = _driver("plane_frontier_fleet", SEED)
    lam = mod.static_progress(drv.params, drv.applied) * drv.dt
    tenant, _ = drv.beats(0)
    assert abs(len(tenant) - lam.sum()) < 5 * np.sqrt(lam.sum())


def test_campaign_seed_blocks_repeat_and_never_overlap():
    _, a = _driver("campaign_fixed_pi", SEED)
    _, b = _driver("campaign_fixed_pi", SEED)
    _, c = _driver("campaign_fixed_pi", SEED + 1)
    assert np.array_equal(a._seeds(3), b._seeds(3))
    assert not np.array_equal(a._seeds(3), c._seeds(3))
    blocks = np.concatenate([a._seeds(i) for i in range(-1, 50)])
    assert len(np.unique(blocks)) == len(blocks)
    assert blocks.min() >= 0 and blocks.max() < 2 ** 32


def test_phased_scenario_is_built_from_the_traffic(checkout):
    _, drv = _driver("campaign_phased_faulted", SEED, checkout)
    kw = drv._scenario()
    assert drv.grid == (3, 2, 3, 256) and drv.runs_per_call == 4608
    assert [p.branch for p in kw["policies"]] == ["pi", "pi_rls",
                                                  "dutycycle"]
    sched = kw["workloads"]
    assert sched.cyclic and sched.duration == 200.0
    assert [w.kind for w in kw["faults"].windows] == ["hb_dropout",
                                                      "meter_freeze"]
    assert kw["guard"].failsafe_k == 12 and kw["detector"].min_gap == 10


def test_scan_draws_repeat_from_a_seed():
    from reference import noise

    assert noise.scan_horizon(2000.0, 1.0) == 2048
    assert noise.scan_horizon(100.0, 1.0) == 256
    seeds = [2 ** 31 + 5, 7]
    a = noise.ScanDraws(seeds, 2000.0, 1.0)
    b = noise.ScanDraws(seeds, 2000.0, 1.0)
    for step in (0, 70):
        assert all(np.array_equal(x, y)
                   for x, y in zip(a.plant(step), b.plant(step)))
        lam = np.asarray([20.0, 3.5])
        assert np.array_equal(a.counts(step, lam), b.counts(step, lam))
    z = a.plant(3)
    assert not np.array_equal(z[0][:1], z[0][1:])
    assert np.all((z[2] >= 0) & (z[2] < 1))
