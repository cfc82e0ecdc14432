"""Phased-workload subsystem (repro.core.workloads): schedule packing /
resolution semantics, the engine's static-path bit-for-bit guarantee,
the vmapped workload sweep axis, the change-point detector's recovery
guarantees, the RLS-reset reaction, and per-node fleet schedules."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis import given, settings, st

from repro.core import policies as pol
from repro.core import sim
from repro.core.adaptive import RLSConfig
from repro.core.controller import PIGains
from repro.core.plant import PROFILE_FIELDS, PROFILES
from repro.core.sim import simulate_closed_loop, sweep
from repro.core.workloads import (MAX_PHASES, DetectorConfig, Phase,
                                  PhaseSchedule, active_profile,
                                  detect_init, detect_step,
                                  detector_values, markov_schedule,
                                  stream_dgemm_schedule)

STREAM = {"alpha": 3.0, "beta": 0.6}
DGEMM = {"alpha": 0.3, "beta": 1.14, "K_L": 2.0}


# ---- schedule packing / resolution ----------------------------------------

def test_phase_resolution_order_and_packing():
    base = PROFILES["gros"]
    ph = Phase(10.0, profile=PROFILES["dahu"], delta={"K_L": 50.0},
               scale={"K_L": 2.0, "alpha": 0.5})
    p = ph.resolve(base)
    assert p.K_L == pytest.approx(100.0)          # delta then scale
    assert p.alpha == pytest.approx(PROFILES["dahu"].alpha * 0.5)
    assert p.beta == PROFILES["dahu"].beta        # absolute profile wins
    sv = PhaseSchedule((ph, Phase(5.0))).resolve(base)
    assert sv.ends.shape == (MAX_PHASES,)
    assert sv.profiles.shape == (MAX_PHASES, len(PROFILE_FIELDS))
    np.testing.assert_allclose(np.asarray(sv.ends[:1]), [10.0])
    assert np.isinf(np.asarray(sv.ends[1:]).astype(float)).all()
    # second phase holds the BASE profile forever (padding repeats it)
    kl_col = PROFILE_FIELDS.index("K_L")
    assert float(sv.profiles[1, kl_col]) == pytest.approx(base.K_L)
    assert float(sv.profiles[-1, kl_col]) == pytest.approx(base.K_L)


def test_active_profile_half_open_and_cyclic():
    base = PROFILES["gros"]
    sched = PhaseSchedule((Phase(10.0, scale={"K_L": 2.0}), Phase(10.0)),
                          cyclic=True)
    sv = sched.resolve(base)
    kl_col = PROFILE_FIELDS.index("K_L")
    for t, want_phase, want_kl in ((0.0, 0, 2 * base.K_L),
                                   (9.99, 0, 2 * base.K_L),
                                   (10.0, 1, base.K_L),   # boundary -> next
                                   (19.99, 1, base.K_L),
                                   (20.0, 0, 2 * base.K_L),  # cycle wrap
                                   (35.0, 1, base.K_L)):
        row, idx = active_profile(sv, jnp.float32(t))
        assert int(idx) == want_phase, t
        assert float(row[kl_col]) == pytest.approx(want_kl)
    # non-cyclic: the last phase holds forever
    sv2 = PhaseSchedule((Phase(10.0, scale={"K_L": 2.0}),
                         Phase(10.0))).resolve(base)
    row, idx = active_profile(sv2, jnp.float32(1e6))
    assert int(idx) == 1 and float(row[kl_col]) == pytest.approx(base.K_L)


def test_schedule_validation():
    with pytest.raises(ValueError, match="at least one phase"):
        PhaseSchedule(())
    # > MAX_PHASES no longer raises: the script packs by piecewise
    # chaining into whole 16-row pieces
    long = PhaseSchedule(tuple(Phase(1.0) for _ in range(MAX_PHASES + 1)))
    sv = long.resolve(PROFILES["gros"])
    assert sv.ends.shape == (2 * MAX_PHASES,)
    assert sv.profiles.shape == (2 * MAX_PHASES, len(PROFILE_FIELDS))
    # ... but a rows= override that cannot hold the script still does
    with pytest.raises(ValueError, match="pieces"):
        long.resolve(PROFILES["gros"], rows=MAX_PHASES)
    with pytest.raises(ValueError, match="positive"):
        Phase(0.0)
    with pytest.raises(ValueError, match="unknown plant field"):
        Phase(1.0, delta={"nope": 1.0})


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 999), n_phases=st.integers(17, 26))
def test_long_cyclic_schedule_matches_unrolled_reference(seed, n_phases):
    """Piecewise-chained cyclic schedules (> MAX_PHASES phases) must run
    exactly like the same script unrolled flat across the horizon:
    same plant trajectory, phase index wrapping modulo the cycle."""
    base = PROFILES["gros"]
    chain = markov_schedule(seed, base, n_phases=n_phases,
                            mean_dwell=12.0)
    assert len(chain.phases) > MAX_PHASES
    cyc = PhaseSchedule(chain.phases, cyclic=True)
    horizon = float(min(1.6 * cyc.duration, 900.0))
    # unrolled reference: repeat the cycle flat until it covers horizon
    flat, t = [], 0.0
    while t < horizon:
        ph = chain.phases[len(flat) % n_phases]
        flat.append(ph)
        t += ph.duration
    unrolled = PhaseSchedule(tuple(flat))
    a = simulate_closed_loop(base, 0.1, total_work=1e9,
                             max_time=horizon, seed=seed, workload=cyc)
    b = simulate_closed_loop(base, 0.1, total_work=1e9,
                             max_time=horizon, seed=seed,
                             workload=unrolled)
    assert a.n_steps == b.n_steps
    for k in ("progress", "pcap", "energy", "work"):
        np.testing.assert_array_equal(a.traces[k], b.traces[k],
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(a.traces["phase"]),
                                  np.asarray(b.traces["phase"])
                                  % n_phases)


def test_generators():
    sd = stream_dgemm_schedule("gros", dwell=50.0, n_cycles=2)
    assert len(sd.phases) == 4 and sd.duration == pytest.approx(200.0)
    a0 = sd.phases[0].resolve(PROFILES["gros"])
    a1 = sd.phases[1].resolve(PROFILES["gros"])
    assert a0.alpha > a1.alpha  # STREAM knee sharper than DGEMM
    cyc = stream_dgemm_schedule("gros", dwell=50.0, cyclic=True)
    assert len(cyc.phases) == 2 and cyc.cyclic
    mk = markov_schedule(0, "gros", mean_dwell=30.0, n_phases=5)
    assert len(mk.phases) == 5
    # consecutive phases always differ (uniform jump to ANOTHER state)
    rows = [p.resolve(PROFILES["gros"]) for p in mk.phases]
    for a, b in zip(rows, rows[1:]):
        assert (a.alpha, a.beta) != (b.alpha, b.beta)
    assert markov_schedule(3, "gros").phases != \
        markov_schedule(4, "gros").phases


# ---- engine: static path unchanged, scheduled path correct ----------------

def _oracle_step(profile, gains, c, total_work, max_time, dt, key):
    """The PRE-PHASES engine_step, transcribed verbatim (PI branch, no
    cap limit / summary warmup): the static path's bit-for-bit oracle."""
    policy_vals = jnp.zeros((pol.POLICY_PARAM_DIM,), jnp.float32)
    kplant, khb = jax.random.split(key)
    from repro.core.plant import plant_step
    plant_s, meas = plant_step(profile, c.plant, c.pcap, dt, kplant)
    t = c.t + dt
    n = jax.random.poisson(khb, jnp.maximum(meas["progress"], 0.0) * dt)
    progress = sim._window_median(n, c.anchor_gap, c.has_anchor, dt)
    anchor_gap = jnp.where(n > 0,
                           0.5 * dt / jnp.maximum(
                               n.astype(jnp.float32), 1.0),
                           c.anchor_gap + dt)
    has_anchor = c.has_anchor | (n > 0)
    obs = pol.PolicyObs(progress=progress, power=meas["power"], dt=dt,
                        gains=gains)
    pol_s, pcap = pol.branch_step(("pi",))(policy_vals, c.pol, obs)
    frz = lambda new, old: jax.tree_util.tree_map(
        lambda a, b: jnp.where(c.done, b, a), new, old)
    plant_s = frz(plant_s, c.plant)
    pol_s = frz(pol_s, c.pol)
    pcap = jnp.where(c.done, c.pcap, pcap)
    anchor_gap = jnp.where(c.done, c.anchor_gap, anchor_gap)
    has_anchor = jnp.where(c.done, c.has_anchor, has_anchor)
    t = jnp.where(c.done, c.t, t)
    progress = jnp.where(c.done, 0.0, progress)
    power = jnp.where(c.done, 0.0, meas["power"])
    done = (c.done | (plant_s.work >= total_work)
            | (t >= max_time - 1e-6))
    out = {"t": t, "progress": progress, "pcap": pcap, "power": power,
           "energy": plant_s.energy, "work": plant_s.work}
    return c._replace(plant=plant_s, pol=pol_s, pcap=pcap,
                      anchor_gap=anchor_gap, has_anchor=has_anchor,
                      t=t, done=done,
                      steps=c.steps + (~c.done).astype(jnp.int32)), out


# The oracle's scan and the engine are different XLA executables, and
# XLA does not promise bit equality between two executables: fusion may
# reorder a float32 sum (ROADMAP D0). Counts stay exact; float fields
# agree to this many units in the last place.
MAX_ULP = 4


def _assert_same_trace(ref, ref_steps, res):
    assert int(ref_steps) == res.n_steps
    n = res.n_steps
    for k in ("progress", "pcap", "power", "energy", "work", "t"):
        np.testing.assert_array_max_ulp(np.asarray(ref[k][:n]),
                                        np.asarray(res.traces[k]),
                                        maxulp=MAX_ULP)


def test_static_path_bit_for_bit_vs_prephases_engine():
    """With no schedule/detector the refactored engine reproduces the
    pre-phases step — same RNG stream, same arithmetic: the same step
    count, and every traced float within `MAX_ULP` ulp. One period's
    key changed in the oracle fails the comparison."""
    p32 = sim._unpack_profile(sim.profile_values(PROFILES["gros"]))
    g32 = sim._unpack_gains(sim.gains_values(
        PIGains.from_model(PROFILES["gros"], 0.1)))
    total_work, max_time, dt = jnp.float32(600.0), jnp.float32(512.0), \
        jnp.float32(1.0)
    carry0 = sim._default_init(p32, g32)

    def body(c, k):
        return _oracle_step(p32, g32, c, total_work, max_time, dt, k)

    keys = jax.random.split(jax.random.PRNGKey(11), 512)
    final, ref = jax.lax.scan(body, carry0, keys)

    res = simulate_closed_loop(PROFILES["gros"], 0.1, total_work=600.0,
                               max_time=512.0, seed=11)
    _assert_same_trace(ref, final.steps, res)

    other = keys.at[5].set(jax.random.split(keys[5])[0])
    final, ref = jax.lax.scan(body, carry0, other)
    with pytest.raises(AssertionError):
        _assert_same_trace(ref, final.steps, res)


def test_one_phase_base_schedule_equals_static_run():
    """A schedule that scripts 'the base profile forever' must be
    bit-for-bit the static run: the gather changes the graph, not the
    numbers."""
    hold = PhaseSchedule((Phase(50.0),))
    a = simulate_closed_loop("gros", 0.1, total_work=500.0, seed=7,
                             workload=hold)
    b = simulate_closed_loop("gros", 0.1, total_work=500.0, seed=7)
    assert a.n_steps == b.n_steps
    for k in ("progress", "pcap", "energy", "work"):
        np.testing.assert_array_equal(a.traces[k], b.traces[k])
    assert (np.asarray(a.traces["phase"]) == 0).all()


def test_phased_run_switches_dynamics_mid_run():
    """The scripted K_L doubling changes the closed loop mid-run: the
    controller keeps progress at the setpoint, so the faster plant lets
    it shed power — the cap drops when the fast phase starts."""
    sched = PhaseSchedule((Phase(100.0), Phase(100.0,
                                               scale={"K_L": 2.0})))
    res = simulate_closed_loop("gros", 0.1, total_work=1e9,
                               max_time=200.0, seed=0, workload=sched)
    phase = np.asarray(res.traces["phase"])
    assert set(np.unique(phase)) == {0, 1}
    pcap = res.traces["pcap"]
    cap0 = pcap[(phase == 0)][30:].mean()   # past the descent transient
    cap1 = pcap[(phase == 1)][30:].mean()
    assert cap1 < cap0 - 5.0, (cap0, cap1)
    # work accrues faster in the fast phase
    prog = res.traces["progress"]
    assert prog[(phase == 1)].mean() > 0.8 * prog[(phase == 0)].mean()


def test_sweep_workload_axis_shapes_summary_and_one_compile():
    """A 3-phase STREAM<->DGEMM sweep runs vmapped in summary mode; a
    second sweep with different schedules/profiles in the same
    scan-length bucket reuses the SAME compiled engine."""
    s3 = PhaseSchedule((Phase(80.0, scale=STREAM),
                        Phase(80.0, scale=DGEMM),
                        Phase(80.0, scale=STREAM)))
    kw = dict(total_work=1e9, max_time=240.0, collect_traces=False)
    res = sweep(("gros", "dahu"), [0.1, 0.2], range(2),
                workloads=[s3, markov_schedule(1, "gros")], **kw)
    assert res.traces is None
    assert res.exec_time.shape == (2, 2, 2, 2)  # (P, E, W, S)
    assert np.isfinite(np.asarray(res.summary["progress_mean"])).all()
    info0 = sim._jit_sweep.cache_info()
    jitted = sim._jit_sweep(sim._bucket_steps(240), ("pi",), False,
                            True, False)
    size0 = jitted._cache_size()
    assert size0 >= 1
    # different schedule values + different profile count, same bucket:
    # same lru entry, no new XLA compile for the same grid SHAPES
    sweep(("gros", "dahu"),  [0.1, 0.2], range(2),
          workloads=[markov_schedule(2, "dahu"),
                     stream_dgemm_schedule("dahu", dwell=40.0,
                                           cyclic=True)], **kw)
    assert sim._jit_sweep.cache_info().misses == info0.misses
    assert jitted._cache_size() == size0
    # single-schedule call squeezes the W axis
    res1 = sweep("gros", [0.1], range(2), workloads=s3, **kw)
    assert res1.exec_time.shape == (1, 2)


def test_sweep_matches_single_run_with_workload():
    s = stream_dgemm_schedule("gros", dwell=60.0, n_cycles=1)
    res = sweep("gros", [0.1], [5], total_work=1e9, max_time=120.0,
                workloads=s)
    one = simulate_closed_loop("gros", 0.1, total_work=1e9,
                               max_time=120.0, seed=5, workload=s)
    assert float(res.exec_time[0, 0]) == pytest.approx(one.exec_time)
    assert float(res.energy[0, 0]) == pytest.approx(one.energy,
                                                    rel=1e-5)


# ---- change-point detector -------------------------------------------------

def test_detector_recovers_injected_boundary_within_5_periods():
    """Acceptance: an injected phase boundary at paper-scale noise is
    recovered within 5 control periods, across seeds; a static plant
    never alarms."""
    sched = PhaseSchedule((Phase(200.0), Phase(400.0,
                                               scale={"K_L": 2.0})))
    for seed in range(4):
        res = simulate_closed_loop("gros", 0.1, total_work=1e9,
                                   max_time=400.0, seed=seed,
                                   workload=sched,
                                   detector=DetectorConfig())
        alarms = np.nonzero(res.traces["phase_change"])[0]
        assert len(alarms) >= 1
        # phase 1 starts at the step whose window begins at t=200
        assert 200 <= alarms[0] <= 205, alarms
        static = simulate_closed_loop("gros", 0.1, total_work=1e9,
                                      max_time=400.0, seed=seed,
                                      detector=DetectorConfig())
        assert static.n_phase_changes == 0


def _settle_periods(res, a: int) -> int:
    """Periods after alarm `a` until kl_hat stays inside 20% of its own
    jump toward the run's final estimate."""
    kl = np.asarray(res.traces["kl_hat"])
    final = kl[-20:].mean()
    band = 0.2 * abs(kl[a - 2] - final)
    for t in range(a, len(kl)):
        if (abs(kl[t] - final) <= band
                and abs(kl[min(t + 5, len(kl) - 1)] - final)
                <= 2 * band):
            return t - a
    return len(kl) - a


def test_detection_resets_rls_and_reconverges_gains_vs_baseline():
    """Acceptance: the alarm resets the RLS covariance and forces an
    immediate gain re-placement, so the detector arm's K_L estimate
    settles at its new-phase value several times faster than the
    slow-forgetting no-detector baseline (same seeds, same plant).
    The shift (K_L*1.5) keeps the loop inside the actuator's
    controllable region, where gain adaptation actually matters."""
    p = PROFILES["gros"]
    sched = PhaseSchedule((Phase(150.0), Phase(250.0,
                                               scale={"K_L": 1.5})))
    faster = 0
    for seed in range(3):
        kw = dict(gains=PIGains.from_model(p, 0.1), total_work=1e9,
                  max_time=400.0, seed=seed, workload=sched,
                  adaptive=RLSConfig())
        base = simulate_closed_loop(p, **kw)
        det = simulate_closed_loop(p, detector=DetectorConfig(), **kw)
        alarms = np.nonzero(det.traces["phase_change"])[0]
        assert len(alarms) >= 1
        a = int(alarms[0])
        assert 150 <= a <= 162, alarms  # boundary recovered promptly
        # the reset re-derives the gains: the estimator moves much
        # further in the first 5 post-alarm periods than the baseline
        jump_det = abs(float(det.traces["kl_hat"][a + 5])
                       - float(det.traces["kl_hat"][a - 2]))
        jump_base = abs(float(base.traces["kl_hat"][a + 5])
                        - float(base.traces["kl_hat"][a - 2]))
        assert jump_det > jump_base, (jump_det, jump_base)
        if _settle_periods(det, a) < _settle_periods(base, a):
            faster += 1
    assert faster >= 2  # re-converges faster on (at least) 2/3 seeds


def test_pi_rls_on_change_hook_resets_covariance():
    """Unit: the pi_rls branch's on_change blows P back to fresh-init
    and forces the next step's gain re-placement."""
    from repro.core.adaptive import rls_unpack, rls_values
    from repro.core.policies.pi import PI_RLS_HI, PI_RLS_LO
    p = PROFILES["gros"]
    g = PIGains.from_model(p, 0.1)
    policy = pol.PIPolicy(adaptive=RLSConfig(dwell=7))
    vals = pol.policy_values(policy, p, g)
    state = pol.policy_init(policy, vals, g)
    # converge the estimator a little so P shrinks
    obs = pol.PolicyObs(progress=jnp.float32(20.0),
                        power=jnp.float32(80.0), dt=jnp.float32(1.0),
                        gains=g)
    for _ in range(20):
        state, _ = pol.policy_step(policy, vals, state, obs)
    before = rls_unpack(state[PI_RLS_LO:PI_RLS_HI])
    assert not np.allclose(np.asarray(before.P), np.eye(2) * 1e2)
    after = rls_unpack(pol.branch_on_change(policy)(vals, state)
                       [PI_RLS_LO:PI_RLS_HI])
    np.testing.assert_allclose(np.asarray(after.P), np.eye(2) * 1e2)
    assert float(after.since_update) == pytest.approx(7.0)  # >= dwell
    assert not bool(after.has_prev)
    np.testing.assert_allclose(np.asarray(after.theta),
                               np.asarray(before.theta))  # prior kept


def test_resume_t0_continues_the_schedule_clock():
    """resume_init(t0=...) carries the sim-time the schedule gathers
    by, so a split scheduled run continues mid-script instead of
    snapping back to phase 0."""
    from repro.core.sim import resume_init
    p = PROFILES["gros"]
    g = PIGains.from_model(p, 0.1)
    sched = PhaseSchedule((Phase(100.0), Phase(100.0,
                                               scale={"K_L": 2.0})))
    r1 = simulate_closed_loop(p, gains=g, total_work=1e9,
                              max_time=150.0, seed=3, workload=sched)
    assert int(np.asarray(r1.traces["phase"])[-1]) == 1
    init = resume_init(r1.plant_state,
                       type(r1.pi_state)(*map(np.float32, r1.pi_state)),
                       r1.pcap, t0=r1.exec_time)
    r2 = simulate_closed_loop(p, gains=g, total_work=1e9,
                              max_time=200.0, seed=4, workload=sched,
                              init=init)
    phase2 = np.asarray(r2.traces["phase"])
    assert int(phase2[0]) == 1          # continued, not restarted
    assert float(r2.traces["t"][0]) == pytest.approx(151.0)
    # default t0=0 restarts the script (the per-segment NRM semantics)
    init0 = resume_init(r1.plant_state,
                        type(r1.pi_state)(*map(np.float32, r1.pi_state)),
                        r1.pcap)
    r3 = simulate_closed_loop(p, gains=g, total_work=1e9,
                              max_time=50.0, seed=4, workload=sched,
                              init=init0)
    assert int(np.asarray(r3.traces["phase"])[0]) == 0


def test_detector_state_resumes_and_counts():
    """SimResult.detector_state resumes via resume_init(det_state=...)
    and carries the cumulative alarm count."""
    from repro.core.sim import resume_init
    p = PROFILES["gros"]
    g = PIGains.from_model(p, 0.1)
    r1 = simulate_closed_loop(p, gains=g, total_work=300.0, seed=1,
                              detector=DetectorConfig())
    assert r1.detector_state is not None
    init = resume_init(r1.plant_state,
                       type(r1.pi_state)(*map(np.float32, r1.pi_state)),
                       r1.pcap, det_state=r1.detector_state)
    r2 = simulate_closed_loop(p, gains=g, total_work=600.0, seed=2,
                              init=init, detector=DetectorConfig())
    assert r2.detector_state is not None
    assert r2.n_phase_changes >= r1.n_phase_changes
    # resuming WITH detector state but WITHOUT detector= is an error
    with pytest.raises(ValueError, match="detector"):
        simulate_closed_loop(p, gains=g, total_work=100.0, init=init)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_markov_phased_runs_stay_finite(seed):
    """Property: random Markov phase chains never break the engine —
    traces stay finite, caps stay inside the actuator range."""
    mk = markov_schedule(seed, "dahu", mean_dwell=40.0, n_phases=4)
    res = simulate_closed_loop("dahu", 0.15, total_work=1e9,
                               max_time=160.0, seed=seed % 7,
                               workload=mk, detector=DetectorConfig())
    prog = res.traces["progress"]
    pcap = res.traces["pcap"]
    assert np.isfinite(prog).all() and np.isfinite(pcap).all()
    p = PROFILES["dahu"]
    assert (pcap >= p.pcap_min - 1e-3).all()
    assert (pcap <= p.pcap_max + 1e-3).all()


# ---- fleet ----------------------------------------------------------------

def test_fleet_per_node_schedules_shift_budget():
    """Phase-staggered fleet: when class 0 flips memory->compute-bound
    (watts buy progress again) while class 1 stays at its knee, the
    water-filling moves budget toward class 0's new demand."""
    from repro.core.hierarchy import FleetConfig, simulate_fleet
    profs = [PROFILES["gros"], PROFILES["dahu"]]
    peak = sum(float(p.power_of_pcap(p.pcap_max)) for p in profs) * 6
    fc = FleetConfig(n_nodes=12, epsilon=0.05, power_budget=0.55 * peak,
                     reallocate_every=5)
    flip = PhaseSchedule((Phase(60.0, scale=STREAM),
                          Phase(200.0, scale=DGEMM)))
    hold = PhaseSchedule((Phase(60.0, scale=STREAM),))
    tr = simulate_fleet(profs, fc, steps=160, node_class=[0, 1] * 6,
                        schedules=[flip, hold])
    assert tr["phase_class"].shape == (160, 2)
    assert tr["phase_class"][30].tolist() == [0.0, 0.0]
    assert tr["phase_class"][100].tolist() == [1.0, 0.0]
    # class-0 allocation share grows after its compute-bound flip
    alloc = np.asarray(tr["alloc_class"])
    share0_before = alloc[30, 0] / alloc[30].sum()
    share0_after = alloc[140:, 0].mean() / alloc[140:].mean(0).sum()
    assert share0_after > share0_before + 0.02, (share0_before,
                                                 share0_after)
    # static fleets (schedules=None) keep the pre-phases trace contract
    tr2 = simulate_fleet(profs, fc, steps=40, node_class=[0, 1] * 6)
    assert "phase_class" not in tr2


def test_fleet_schedule_normalization_errors():
    from repro.core.hierarchy import FleetConfig, simulate_fleet
    fc = FleetConfig(n_nodes=4, epsilon=0.1)
    with pytest.raises(ValueError, match="schedules"):
        simulate_fleet(PROFILES["gros"], fc, steps=8,
                       schedules=[PhaseSchedule((Phase(1.0),))] * 3)
