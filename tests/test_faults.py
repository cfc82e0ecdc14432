"""Fault injection + guarded degradation (repro.core.faults).

The load-bearing contracts, pinned from both ends:

* fault-free invariance — `faults=None` and a NO-OP `FaultSchedule`
  produce bit-for-bit identical runs (trace AND summary mode), and an
  armed-but-untriggered guard computes exactly the unguarded graph.
* degradation is bounded — under heartbeat blackouts the guarded
  adaptive controller stays within a small factor of its clean tracking
  error while the unguarded one blows up (the fig9 acceptance bound).
* the watchdog ladder — stale signal -> HOLD (cap frozen, policy and
  detector state frozen) -> FAILSAFE (pcap_max) -> recovery through the
  policy's on_change reset.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults as flt
from repro.core import policies as pol
from repro.core.adaptive import (RLSAdapter, RLSConfig, rls_init,
                                 rls_step, rls_values)
from repro.core.controller import PIGains
from repro.core.plane import plane_step
from repro.core.plant import PROFILES
from repro.core.policies import DutyCyclePolicy, PIPolicy
from repro.core.sim import simulate_closed_loop, sweep
from repro.core.workloads.detect import DetectorConfig
from repro.core.workloads.schedule import Phase, PhaseSchedule

KW = dict(total_work=400.0, max_time=300.0)


def _noop_schedule():
    return flt.FaultSchedule(name="noop")


# ---------------------------------------------------------------------------
# fault channels: packed/traced view vs the host-side schedule
# ---------------------------------------------------------------------------

def test_fault_channels_matches_host_schedule():
    sched = flt.FaultSchedule((
        flt.FaultWindow("hb_dropout", 10.0, 5.0, p1=0.5),
        flt.FaultWindow("meter_bias", 12.0, 8.0, p1=3.0),
        flt.FaultWindow("meter_bias", 14.0, 2.0, p1=4.0),  # overlapping
        flt.FaultWindow("act_quant", 30.0, 10.0, p1=2.0),
        flt.FaultWindow("crash", 45.0, 5.0),
    ), period=60.0)
    fv = sched.resolve()
    chan = jax.jit(flt.fault_channels)
    for t in (0.0, 10.0, 13.0, 14.5, 20.5, 31.0, 47.0, 61.0, 73.0,
              105.0):
        af = chan(fv, jnp.float32(t))
        host = sched.active(t)
        kinds = [w.kind for w in host]
        assert float(af.hb_drop) == (0.5 if "hb_dropout" in kinds
                                     else 0.0), t
        # overlapping bias windows sum
        bias = sum(w.p1 for w in host if w.kind == "meter_bias")
        assert float(af.meter_bias) == pytest.approx(bias), t
        assert float(af.act_quant) == (2.0 if "act_quant" in kinds
                                       else 0.0), t
        assert float(af.crash) == (1.0 if "crash" in kinds else 0.0), t


def test_fault_schedule_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        flt.FaultWindow("gremlins", 0.0, 1.0)
    with pytest.raises(ValueError, match="duration"):
        flt.FaultWindow("crash", 0.0, 0.0)
    with pytest.raises(ValueError, match="overruns the period"):
        flt.FaultSchedule((flt.FaultWindow("crash", 50.0, 20.0),),
                          period=60.0)
    with pytest.raises(ValueError, match="MAX_FAULT_ROWS"):
        flt.FaultSchedule(tuple(flt.FaultWindow("crash", i * 10.0, 1.0)
                                for i in range(flt.MAX_FAULT_ROWS + 1)))


# ---------------------------------------------------------------------------
# fault-free invariance: the tentpole's first acceptance criterion
# ---------------------------------------------------------------------------

def test_noop_schedule_bit_identical_trace_mode():
    clean = simulate_closed_loop("gros", 0.1, **KW)
    noop = simulate_closed_loop("gros", 0.1, faults=_noop_schedule(),
                                **KW)
    for k in clean.traces:
        np.testing.assert_array_equal(np.asarray(clean.traces[k]),
                                      np.asarray(noop.traces[k]),
                                      err_msg=k)
    assert clean.exec_time == noop.exec_time
    assert clean.energy == noop.energy and clean.work == noop.work
    # the faulted run additionally reports the injection trace — all
    # zero on a no-op script
    assert float(np.abs(noop.traces["fault_active"]).max()) == 0.0


def test_noop_schedule_bit_identical_summary_mode():
    clean = simulate_closed_loop("gros", 0.1, collect_traces=False,
                                 **KW)
    noop = simulate_closed_loop("gros", 0.1, collect_traces=False,
                                faults=_noop_schedule(), **KW)
    assert not clean.traces and not noop.traces
    for k in clean.summary:
        np.testing.assert_array_equal(np.asarray(clean.summary[k]),
                                      np.asarray(noop.summary[k]),
                                      err_msg=k)
    assert clean.energy == noop.energy and clean.work == noop.work


def test_untriggered_guard_bit_identical_full_run():
    clean = simulate_closed_loop("gros", 0.1, **KW)
    guarded = simulate_closed_loop("gros", 0.1, guard=True, **KW)
    for k in clean.traces:
        np.testing.assert_array_equal(np.asarray(clean.traces[k]),
                                      np.asarray(guarded.traces[k]),
                                      err_msg=k)
    # the guard observed the whole run without engaging
    assert guarded.guard_state is not None
    assert float(np.abs(guarded.traces["guard_mode"]).max()) == 0.0
    assert float(guarded.guard_state[flt.G_MODE]) == flt.GUARD_NORMAL
    assert clean.guard_state is None


def test_sweep_noop_fault_axis_bit_identical_to_clean():
    clean = sweep("gros", [0.1, 0.2], range(2), collect_traces=False,
                  **KW)
    scheds = [_noop_schedule(),
              flt.FaultSchedule((flt.FaultWindow("crash", 5.0, 10.0),))]
    faulted = sweep("gros", [0.1, 0.2], range(2), faults=scheds,
                    collect_traces=False, **KW)
    # faults= adds one grid axis before seeds: (E, F, S)
    assert faulted.energy.shape == (2, 2, 2)
    np.testing.assert_array_equal(np.asarray(clean.energy),
                                  np.asarray(faulted.energy[:, 0]))
    np.testing.assert_array_equal(
        np.asarray(clean.summary["progress_hist"]),
        np.asarray(faulted.summary["progress_hist"][:, 0]))
    # the crash freezes work for 10 s, so its slice completes later
    assert (np.asarray(faulted.exec_time[:, 1])
            > np.asarray(faulted.exec_time[:, 0])).all()
    # a single schedule rides the carry without a grid axis
    single = sweep("gros", [0.1, 0.2], range(2), faults=scheds[1],
                   collect_traces=False, **KW)
    assert single.energy.shape == (2, 2)
    np.testing.assert_array_equal(np.asarray(single.energy),
                                  np.asarray(faulted.energy[:, 1]))


# An unchunked grid and its chunks run the same flat-row engine
# (`sim._flat_core`) at different chunk shapes, which are different XLA
# executables, and XLA does not promise bit equality between two
# executables: fusion may reorder a float32 sum. Counts stay exact;
# float fields agree to this many units in the last place.
MAX_ULP = 4
GUARD_FLOATS = [flt.G_LAST_PROGRESS, flt.G_LAST_POWER]
GUARD_COUNTS = [i for i in range(flt.GUARD_STATE_DIM)
                if i not in GUARD_FLOATS]


def _assert_same_runs(a, b):
    np.testing.assert_array_max_ulp(np.asarray(a.energy),
                                    np.asarray(b.energy), maxulp=MAX_ULP)
    np.testing.assert_array_equal(np.asarray(a.summary["pcap_hist"]),
                                  np.asarray(b.summary["pcap_hist"]))
    ga, gb = np.asarray(a.guard_state), np.asarray(b.guard_state)
    np.testing.assert_array_equal(ga[..., GUARD_COUNTS],
                                  gb[..., GUARD_COUNTS])
    np.testing.assert_array_max_ulp(ga[..., GUARD_FLOATS],
                                    gb[..., GUARD_FLOATS], maxulp=MAX_ULP)


def _period_key_changed(seed: int, period: int, n_steps: int):
    """`jax.random.split` as the scan engine sees it, except that the
    runs keyed by ``seed`` draw period ``period`` from another key."""
    from repro.core import sim
    real = jax.random.split
    target = jnp.asarray(sim.seed_keys([seed])[0])

    def split(key, num=2):
        keys = real(key, num)
        if num != n_steps:
            return keys
        mine = jnp.all(key == target)
        return keys.at[period].set(
            jnp.where(mine, real(keys[period])[0], keys[period]))

    return split


def test_chunked_faulted_guarded_sweep_equals_one_shot(monkeypatch):
    from repro.core import sim
    scheds = [_noop_schedule(),
              flt.FaultSchedule((flt.FaultWindow("hb_dropout", 20.0,
                                                 15.0, p1=1.0),))]
    kw = dict(faults=scheds, guard=flt.GuardConfig(),
              collect_traces=False, **KW)
    one = sweep("gros", [0.1, 0.2], range(2), **kw)
    ch = sweep("gros", [0.1, 0.2], range(2), chunk_size=3, **kw)
    assert one.guard_state.shape == (2, 2, 2, flt.GUARD_STATE_DIM)
    _assert_same_runs(one, ch)

    # period 5's key of seed 1's runs changed in the chunked path: the
    # comparison fails, and only those runs differ
    sim._flat_core.cache_clear()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax.random, "split", _period_key_changed(
                1, 5, sim._bucket_steps(int(KW["max_time"]))))
            moved = sweep("gros", [0.1, 0.2], range(2), chunk_size=3, **kw)
    finally:
        sim._flat_core.cache_clear()  # no later sweep reuses the patch
    with pytest.raises(AssertionError):
        _assert_same_runs(one, moved)
    np.testing.assert_array_max_ulp(np.asarray(one.energy)[..., 0],
                                    np.asarray(moved.energy)[..., 0],
                                    maxulp=MAX_ULP)
    assert (np.asarray(one.energy)[..., 1]
            != np.asarray(moved.energy)[..., 1]).all()


# ---------------------------------------------------------------------------
# summary mode's early stop: the while loop ends where the scan's steps
# stop changing anything
# ---------------------------------------------------------------------------

_BLACKOUT = flt.FaultSchedule((
    flt.FaultWindow("hb_dropout", 30.0, 20.0, p1=1.0),
    flt.FaultWindow("meter_freeze", 30.0, 20.0)), period=150.0)
# a tenant down for the whole horizon never reaches total_work
_DOWN = flt.FaultSchedule((flt.FaultWindow("crash", 0.0, 1000.0),))
_RUN_FIELDS = ("exec_time", "energy", "work", "n_steps", "detections",
               "guard_state")


def _phased_grid(faults, chunk_size=None, collect_traces=False):
    """A phased, faulted, guarded, detector, mixed-policy grid over two
    plants whose runs finish ~100 steps apart."""
    phases = PhaseSchedule((
        Phase(50.0, scale=(("alpha", 3.0), ("beta", 0.6))),
        Phase(50.0, scale=(("alpha", 0.3), ("beta", 1.14)))), cyclic=True)
    return sweep(["gros", "yeti"], [0.1, 0.3], range(3),
                 total_work=3000.0, max_time=400.0,
                 policies=[PIPolicy(), PIPolicy(adaptive=RLSConfig()),
                           DutyCyclePolicy()],
                 workloads=phases, detector=DetectorConfig(),
                 faults=faults, guard=flt.GuardConfig(),
                 chunk_size=chunk_size, collect_traces=collect_traces)


@pytest.mark.parametrize("faults,chunk_size", [
    (_BLACKOUT, None),          # one batch
    (_BLACKOUT, 5),             # runs finish unevenly across chunks
    ([_BLACKOUT, _DOWN], None),  # half the runs last to max_time
], ids=["one_batch", "chunks", "to_max_time"])
def test_summary_loop_stops_with_the_scans_final_carry(faults, chunk_size):
    """Summary mode's while loop stops when every run of its batch is
    done; trace mode scans every period. Their final carries agree bit
    for bit: a done run's step leaves its carry unchanged."""
    lean = _phased_grid(faults, chunk_size)
    full = _phased_grid(faults, collect_traces=True)
    assert lean.traces is None and full.traces is not None
    for f in _RUN_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(lean, f)),
                                      np.asarray(getattr(full, f)),
                                      err_msg=f)
    for k in full.summary:
        np.testing.assert_array_equal(np.asarray(lean.summary[k]),
                                      np.asarray(full.summary[k]),
                                      err_msg=k)
    steps = np.asarray(lean.n_steps)
    assert steps.max() < 400 if faults is _BLACKOUT else steps.max() == 400


def test_scan_steps_counter_counts_each_batch():
    """``sweep_scan_steps_total{kind="run"}`` rises by each chunk's
    largest ``n_steps`` (the loop's steps), ``{kind="horizon"}`` by
    chunks x ``max_steps``; trace mode runs the whole horizon. Chunked
    and unchunked summary sweeps agree."""
    from repro.core import sim
    from repro.obs import metrics
    c = metrics.get_registry().counter("sweep_scan_steps_total",
                                       labelnames=("kind",))

    def counted(**kw):
        before = c.value(kind="run"), c.value(kind="horizon")
        res = _phased_grid(_BLACKOUT, **kw)
        return res, (c.value(kind="run") - before[0],
                     c.value(kind="horizon") - before[1])

    max_steps = sim._bucket_steps(400)
    one, (run1, hor1) = counted()
    steps = np.asarray(one.n_steps).reshape(-1)
    assert (run1, hor1) == (steps.max(), max_steps)
    ch, (run5, hor5) = counted(chunk_size=5)
    n_chunks = -(-steps.size // 5)
    assert run5 == sum(steps[i:i + 5].max()
                       for i in range(0, steps.size, 5))
    assert hor5 == n_chunks * max_steps and run5 < hor5
    _assert_same_runs(one, ch)
    np.testing.assert_array_equal(np.asarray(one.n_steps),
                                  np.asarray(ch.n_steps))
    _, (run_t, hor_t) = counted(collect_traces=True)
    assert run_t == hor_t == max_steps


# ---------------------------------------------------------------------------
# plane_step guard: untriggered identity + the watchdog ladder
# ---------------------------------------------------------------------------

def _pi_args(prof, gains, progress, pcap_applied):
    vals = pol.policy_values(PIPolicy(), prof, gains)
    st = pol.policy_init(PIPolicy(), vals, gains)
    return (gains, "pi", vals, st, pcap_applied,
            jnp.float32(progress), jnp.float32(80.0), jnp.float32(1.0))


def test_guarded_plane_step_untriggered_is_unguarded_bitwise():
    prof = PROFILES["gros"]
    gains = PIGains.from_model(prof, 0.1)
    args = _pi_args(prof, gains, 0.8 * prof.progress_max,
                    float(prof.pcap_max))
    plain = plane_step(*args)
    out = plane_step(*args, guard_vals=flt.guard_values(),
                     guard_state=flt.guard_init())
    assert float(out[5]) == flt.GUARD_NORMAL
    for a, b in zip(plain, out[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_guard_watchdog_hold_then_failsafe_then_recovery():
    prof = PROFILES["gros"]
    gains = PIGains.from_model(prof, 0.1)
    cfg = flt.GuardConfig(hold_k=2, failsafe_k=4)
    gv = flt.guard_values(cfg)
    vals = pol.policy_values(PIPolicy(), prof, gains)
    state = pol.policy_init(PIPolicy(), vals, gains)
    gs = flt.guard_init()
    applied = float(prof.pcap_max) - 10.0
    good = jnp.float32(0.8 * prof.progress_max)

    def step(progress, state, gs):
        return plane_step(gains, "pi", vals, state, applied, progress,
                          jnp.float32(80.0), jnp.float32(1.0),
                          guard_vals=gv, guard_state=gs)

    # one healthy period seeds G_LAST_PROGRESS
    state, _, _, _, gs, mode = step(good, state, gs)
    assert float(mode) == flt.GUARD_NORMAL
    modes, caps, states = [], [], []
    for _ in range(6):  # signal goes dark
        state, _, cap, _, gs, mode = step(jnp.float32(0.0), state, gs)
        modes.append(float(mode))
        caps.append(float(cap))
        states.append(np.asarray(state))
    # ladder: stale=1,2 normal (substituted last-good progress), 3,4
    # hold, 5,6 fail safe
    assert modes == [flt.GUARD_NORMAL] * 2 + [flt.GUARD_HOLD] * 2 \
        + [flt.GUARD_FAILSAFE] * 2
    assert caps[2] == applied and caps[3] == applied  # HOLD holds
    assert caps[4] == float(prof.pcap_max)            # FAILSAFE
    # an engaged watchdog freezes the policy state
    np.testing.assert_array_equal(states[3], states[2])
    assert float(gs[flt.G_STALE]) == 6.0
    assert float(gs[flt.G_N_FAILSAFE]) == 2.0
    assert float(gs[flt.G_N_INVALID]) == 6.0
    # recovery: the first fresh signal drops back to NORMAL and routes
    # through on_change (counted as a forced reset)
    state, _, cap, _, gs, mode = step(good, state, gs)
    assert float(mode) == flt.GUARD_NORMAL
    assert float(gs[flt.G_STALE]) == 0.0
    assert float(gs[flt.G_N_RESETS]) == 1.0


def test_guard_rejects_nonfinite_and_outlier_signals():
    prof = PROFILES["gros"]
    gains = PIGains.from_model(prof, 0.1)
    gv = flt.guard_values(flt.GuardConfig(outlier_mult=4.0))
    vals = pol.policy_values(PIPolicy(), prof, gains)
    state = pol.policy_init(PIPolicy(), vals, gains)
    gs = flt.guard_init()
    for bad in (jnp.float32(jnp.nan), jnp.float32(jnp.inf),
                jnp.float32(100.0 * prof.progress_max)):
        _, _, _, _, gs2, _ = plane_step(
            gains, "pi", vals, state, float(prof.pcap_max), bad,
            jnp.float32(80.0), jnp.float32(1.0), guard_vals=gv,
            guard_state=gs)
        assert float(gs2[flt.G_N_INVALID]) == 1.0
        assert float(gs2[flt.G_STALE]) == 1.0


# ---------------------------------------------------------------------------
# the fig9 acceptance bound, at test scale
# ---------------------------------------------------------------------------

def test_guard_contains_adaptive_degradation_under_blackouts():
    """10% duty heartbeat blackout + frozen meter: the unguarded RLS
    identifies the zero-progress garbage and its tracking error blows
    up; the guard's HOLD plateau keeps the estimator clean. Loose
    margins of the fig9 headline (quick grids are noisy)."""
    period, start = 400.0, 80.0
    blackout = flt.FaultSchedule((
        flt.FaultWindow("hb_dropout", start, 40.0, p1=1.0),
        flt.FaultWindow("meter_freeze", start, 40.0),
    ), period=period)
    scheds = [_noop_schedule(), blackout]
    prof = PROFILES["gros"]
    setpoint = 0.9 * prof.progress_max
    kw = dict(total_work=1e12, max_time=2000.0,
              policies=[PIPolicy(adaptive=RLSConfig())], faults=scheds,
              collect_traces=False, summary_warmup=60)
    errs = {}
    for arm, g in (("unguarded", None),
                   ("guarded", flt.GuardConfig(hold_k=3,
                                               failsafe_k=60))):
        res = sweep("gros", [0.1], range(3), guard=g, **kw)
        w = np.asarray(res.work).reshape(2, 3)        # (F, S)
        t = np.asarray(res.exec_time).reshape(2, 3)
        err = np.abs(w / np.maximum(t, 1e-9) - setpoint) / setpoint
        errs[arm] = err.mean(-1)             # (F,)
        if arm == "guarded":
            # the blackout windows are bridged in HOLD, never FAILSAFE
            gs = np.asarray(res.guard_state).reshape(
                2, 3, flt.GUARD_STATE_DIM)
            assert float(gs[..., flt.G_N_FAILSAFE].max()) == 0.0
            assert float(gs[1, :, flt.G_N_INVALID].min()) > 0.0
    clean_u, fault_u = errs["unguarded"]
    clean_g, fault_g = errs["guarded"]
    assert fault_u > 5.0 * clean_u, (clean_u, fault_u)
    assert fault_g < 2.5 * max(clean_g, 1e-4), (clean_g, fault_g)
    assert fault_u > 3.0 * fault_g


# ---------------------------------------------------------------------------
# RLS covariance clamp (divergence guard) regression
# ---------------------------------------------------------------------------

def test_rls_trace_clamp_bounds_unexcited_covariance_growth():
    """lam < 1 with a silent regressor inflates P geometrically (1/lam
    per period); the trace clamp must bound it while the numpy oracle
    (same clamp) stays in lockstep."""
    prof = PROFILES["gros"]
    gains = PIGains.from_model(prof, 0.1)
    cfg = RLSConfig(lam=0.9, p_trace_max=5e3)
    rv = rls_values(cfg, prof, gains)
    s = rls_init(rv, gains.k_p, gains.k_i)
    adapter = RLSAdapter(gains, prof, lam=cfg.lam, dwell=cfg.dwell,
                         kl_clamp=cfg.kl_clamp,
                         p_trace_max=cfg.p_trace_max)
    g = gains
    # zero-information stream: progress pinned at the design K_L and a
    # zero linearized command -> phi == 0, P /= lam every step
    for _ in range(200):
        s = rls_step(rv, s, jnp.float32(prof.K_L), jnp.float32(0.0),
                     jnp.float32(1.0))
        g = adapter.update(g, float(prof.K_L), 0.0, 1.0)
    tr = float(s.P[0, 0] + s.P[1, 1])
    assert np.isfinite(np.asarray(s.P)).all()
    assert tr <= cfg.p_trace_max * 1.001
    np.testing.assert_allclose(np.asarray(s.P, np.float64), adapter.P,
                               rtol=1e-4)
    # without the clamp this stream reaches ~200 / 0.9^200 ≈ 3e11 —
    # six orders of magnitude past the bound — so the clamp is what is
    # holding the trace here, not the dynamics
    assert (200.0 / cfg.lam ** 200) > 1e6 * cfg.p_trace_max


def test_rls_spike_corrupted_stream_keeps_gains_bounded():
    prof = PROFILES["gros"]
    gains = PIGains.from_model(prof, 0.1)
    cfg = RLSConfig(lam=0.97, p_trace_max=1e5)
    rv = rls_values(cfg, prof, gains)
    s = rls_init(rv, gains.k_p, gains.k_i)
    rng = np.random.default_rng(0)
    for i in range(300):
        progress = 0.8 * prof.progress_max + rng.normal(0.0, 0.5)
        if i % 17 == 5:
            progress = 1e6  # telemetry spike
        s = rls_step(rv, s, jnp.float32(progress),
                     jnp.float32(rng.uniform(-5.0, 5.0)),
                     jnp.float32(1.0))
        assert np.isfinite(np.asarray(s.P)).all(), i
        assert float(s.P[0, 0] + s.P[1, 1]) <= cfg.p_trace_max * 1.001
    # the scheduled gains never leave the clamp-implied envelope
    assert np.isfinite(float(s.k_p)) and np.isfinite(float(s.k_i))
    tau_obj = 1.0 / (prof.K_L * gains.k_i)
    k_i_min = 1.0 / (prof.K_L * cfg.kl_clamp * tau_obj)
    k_i_max = cfg.kl_clamp / (prof.K_L * tau_obj)
    assert k_i_min * 0.99 <= float(s.k_i) <= k_i_max * 1.01
