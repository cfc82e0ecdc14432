"""Power-policy subsystem (repro.core.policies): bit-for-bit equivalence
of PI-via-policy with the pre-refactor engine, heterogeneous policy-axis
sweeps through the lax.switch engine (shapes, squeeze, compile sharing),
the offline-RL dataset/trainer, duty-cycle behaviour, custom-policy
registration, and the NRM resume round-trip for non-PI policies."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PowerControlConfig
from repro.core import policies as pol
from repro.core import sim
from repro.core.adaptive import RLSConfig, rls_init, rls_step, rls_values
from repro.core.controller import PIGains, pi_init, pi_step
from repro.core.nrm import NRM
from repro.core.plant import PROFILES, plant_init, plant_step
from repro.core.policies import (DutyCyclePolicy, OfflineRLPolicy, PIPolicy,
                                 build_dataset, fit_offline_rl)
from repro.core.sim import simulate_closed_loop, sweep
from test_sim import _engine_cache_sizes


# ---------------------------------------------------------------------------
# The PRE-REFACTOR engine step, transcribed verbatim (PIState/RLSState as
# NamedTuple carry, PI/RLS called inline): the oracle proving the policy
# dispatch did not change the paper's closed loop.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _prerefactor_jit(max_steps, adaptive):
    def run(profile_vals, gains_vals, rls_vals, total_work, max_time, dt,
            key):
        profile = sim._unpack_profile(profile_vals)
        gains = sim._unpack_gains(gains_vals)

        def body(c, k):
            plant, pi, pcap0, anchor_gap0, has_anchor0, t0, done0, rls0 \
                = c
            kplant, khb = jax.random.split(k)
            plant_s, meas = plant_step(profile, plant, pcap0, dt, kplant)
            t = t0 + dt
            n = jax.random.poisson(
                khb, jnp.maximum(meas["progress"], 0.0) * dt)
            progress = sim._window_median(n, anchor_gap0, has_anchor0, dt)
            anchor_gap = jnp.where(
                n > 0, 0.5 * dt / jnp.maximum(n.astype(jnp.float32), 1.0),
                anchor_gap0 + dt)
            has_anchor = has_anchor0 | (n > 0)

            g, rls = gains, rls0
            if adaptive:
                rls = rls_step(rls_vals, rls, progress, pi.prev_pcap_l,
                               dt)
                g = gains.with_gains(rls.k_p, rls.k_i)
            pi_s, pcap = pi_step(g, pi, progress, dt)

            frz = lambda new, old: jax.tree_util.tree_map(
                lambda a, b: jnp.where(done0, b, a), new, old)
            plant_s = frz(plant_s, plant)
            pi_s = frz(pi_s, pi)
            if adaptive:
                rls = frz(rls, rls0)
            pcap = jnp.where(done0, pcap0, pcap)
            anchor_gap = jnp.where(done0, anchor_gap0, anchor_gap)
            has_anchor = jnp.where(done0, has_anchor0, has_anchor)
            t = jnp.where(done0, t0, t)
            progress = jnp.where(done0, 0.0, progress)
            power = jnp.where(done0, 0.0, meas["power"])
            done = (done0 | (plant_s.work >= total_work)
                    | (t >= max_time - 1e-6))
            out = {"t": t, "progress": progress, "pcap": pcap,
                   "power": power, "energy": plant_s.energy,
                   "work": plant_s.work, "valid": ~done0}
            if adaptive:
                out.update({"k_p": rls.k_p, "k_i": rls.k_i,
                            "tau_hat": rls.tau_hat, "kl_hat": rls.kl_hat,
                            "theta1": rls.theta[0],
                            "theta2": rls.theta[1]})
            return (plant_s, pi_s, pcap, anchor_gap, has_anchor, t, done,
                    rls), out

        rls = (rls_init(rls_vals, gains.k_p, gains.k_i) if adaptive
               else jnp.float32(0.0))
        c0 = (plant_init(profile), pi_init(gains),
              jnp.float32(profile.pcap_max), jnp.float32(0.0),
              jnp.array(False), jnp.float32(0.0), jnp.array(False), rls)
        keys = jax.random.split(key, max_steps)
        final, traces = jax.lax.scan(body, c0, keys)
        return traces, final

    return jax.jit(run)


def _prerefactor_run(profile, epsilon, total_work, max_time, seed,
                     adaptive=None):
    gains = PIGains.from_model(profile, epsilon)
    rv = (rls_values(adaptive, profile, gains) if adaptive
          else jnp.zeros((5,), jnp.float32))
    max_steps = sim._bucket_steps(int(max_time))
    traces, _ = _prerefactor_jit(max_steps, adaptive is not None)(
        sim.profile_values(profile), sim.gains_values(gains), rv,
        jnp.float32(total_work), jnp.float32(max_time), jnp.float32(1.0),
        jax.random.PRNGKey(seed))
    return traces


@pytest.mark.parametrize("adaptive", [None, RLSConfig()],
                         ids=["fixed", "adaptive"])
def test_pi_via_policy_bit_for_bit_vs_prerefactor_engine(adaptive):
    """The policy-dispatched engine must reproduce the pre-refactor
    hard-wired PI(/RLS) engine EXACTLY — same RNG stream, same op order,
    bitwise-identical trajectories."""
    prof, eps, work, mt, seed = PROFILES["gros"], 0.1, 800.0, 600.0, 3
    ref = _prerefactor_run(prof, eps, work, mt, seed, adaptive)
    res = simulate_closed_loop(prof, eps, total_work=work, max_time=mt,
                               seed=seed, adaptive=adaptive)
    n = res.n_steps
    assert n > 0 and res.completed
    keys = ["t", "progress", "pcap", "power", "energy", "work"]
    if adaptive is not None:
        keys += ["k_p", "k_i", "tau_hat", "kl_hat", "theta1", "theta2"]
    for k in keys:
        if adaptive is None:
            # the paper's PI: EXACT equality, no tolerance
            np.testing.assert_array_equal(
                np.asarray(ref[k][:n]), res.traces[k], err_msg=k)
        else:
            # pi_rls carries the estimator packed in a vector instead of
            # a NamedTuple; XLA fuses the two graphs differently (FMA
            # contraction), so allow float32-ulp-level differences only
            np.testing.assert_allclose(
                np.asarray(ref[k][:n]), res.traces[k], rtol=1e-6,
                atol=1e-5 * max(1.0, float(np.abs(ref[k][:n]).max())),
                err_msg=k)


def test_sweep_policies_pi_equals_legacy_sweep():
    """sweep(policies=[PIPolicy()]) and the default sweep are the same
    computation; the explicit PI policy must be bit-for-bit identical."""
    kw = dict(total_work=500.0, max_time=600.0)
    a = sweep("gros", [0.1, 0.2], range(2), **kw)
    b = sweep("gros", [0.1, 0.2], range(2), policies=[PIPolicy()], **kw)
    # single-policy list keeps the A axis; index it away for comparison
    np.testing.assert_array_equal(np.asarray(a.exec_time),
                                  np.asarray(b.exec_time[:, 0]))
    np.testing.assert_array_equal(np.asarray(a.traces["pcap"]),
                                  np.asarray(b.traces["pcap"][:, 0]))
    # a single adaptive PIPolicy is the squeezed A axis of its list
    cfgs = [RLSConfig(lam=0.99), RLSConfig(lam=0.999)]
    c = sweep("gros", [0.1], range(2), policies=PIPolicy(adaptive=cfgs[0]),
              collect_traces=False, **kw)
    d = sweep("gros", [0.1], range(2),
              policies=[PIPolicy(adaptive=cf) for cf in cfgs],
              collect_traces=False, **kw)
    np.testing.assert_array_equal(np.asarray(c.exec_time),
                                  np.asarray(d.exec_time[:, 0]))
    np.testing.assert_array_equal(np.asarray(c.summary["power_mean"]),
                                  np.asarray(d.summary["power_mean"][:, 0]))


def test_policy_axis_shapes_squeeze_and_errors():
    pls = [PIPolicy(), OfflineRLPolicy(weights=(0, 0, 0, 1.4, -1.0, 0)),
           DutyCyclePolicy()]
    kw = dict(total_work=400.0, max_time=600.0)
    res = sweep(["gros", "dahu"], [0.1, 0.2], range(2), policies=pls,
                **kw)
    assert res.exec_time.shape == (2, 2, 3, 2)  # (P, E, A, S)
    assert res.traces["progress"].shape[:4] == (2, 2, 3, 2)
    assert bool(np.asarray(res.completed).all())
    # single Policy instance squeezes the axis (like a single RLSConfig)
    res1 = sweep("gros", [0.1], range(2), policies=DutyCyclePolicy(),
                 **kw)
    assert res1.exec_time.shape == (1, 2)
    # summary mode carries the policy axis too
    res2 = sweep("gros", [0.1], range(2), policies=pls,
                 collect_traces=False, **kw)
    assert res2.traces is None
    assert res2.summary["power_mean"].shape == (1, 3, 2)
    with pytest.raises(TypeError):  # the policy axis has one name
        sweep("gros", [0.1], range(2), policies=pls,
              adaptive=RLSConfig(), **kw)
    with pytest.raises(ValueError):
        sweep("gros", [0.1], range(2), policies=[], **kw)


def test_mixed_policy_sweep_pi_lane_matches_pure_pi():
    """The lax.switch dispatch must not disturb a lane's computation:
    the PI lane of a heterogeneous sweep equals a pure-PI sweep
    bit-for-bit (same seeds -> same RNG streams)."""
    kw = dict(total_work=400.0, max_time=600.0)
    mixed = sweep("gros", [0.1], range(3),
                  policies=[PIPolicy(), DutyCyclePolicy()], **kw)
    pure = sweep("gros", [0.1], range(3), **kw)
    for k in ("progress", "pcap", "energy"):
        np.testing.assert_array_equal(
            np.asarray(mixed.traces[k][:, 0]),
            np.asarray(pure.traces[k]), err_msg=k)


def test_policy_grids_share_one_compile_per_bucket():
    """Policy hyperparameters are traced: same grid shapes + same branch
    set reuse the jitted executable; only a scan-length bucket change
    makes a new one."""
    pls_a = [OfflineRLPolicy(weights=(0, 0, 0, 1.4, -1.0, 0)),
             DutyCyclePolicy(deadband=0.02)]
    pls_b = [OfflineRLPolicy(weights=(0.2, 0.1, 0, 0.9, -0.8, 0)),
             DutyCyclePolicy(deadband=0.05)]
    kw = dict(total_work=300.0, collect_traces=False)
    sweep("gros", [0.1], range(2), policies=pls_a, max_time=600.0, **kw)
    info0 = sim._flat_core.cache_info()
    sizes0 = _engine_cache_sizes()
    assert sum(sizes0.values()) >= 1
    # different hyperparameters, same shapes: no new trace, no new jit
    sweep("gros", [0.1], range(2), policies=pls_b, max_time=600.0, **kw)
    assert sim._flat_core.cache_info().misses == info0.misses
    assert _engine_cache_sizes() == sizes0
    # crossing a bucket boundary compiles a fresh engine (and logs)
    sweep("gros", [0.1], range(2), policies=pls_b, max_time=1500.0, **kw)
    assert sim._flat_core.cache_info().misses == info0.misses + 1


def test_bucket_crossing_logged_once(caplog):
    import logging
    kw = dict(total_work=200.0, collect_traces=False)
    with caplog.at_level(logging.WARNING, logger="repro.core.sim"):
        sim._BUCKETS_SEEN.discard(8192)
        sweep("gros", [0.1], [0], max_time=5000.0, **kw)   # new bucket
        n_logs = sum("length bucket" in r.message for r in caplog.records)
        assert n_logs == 1
        sweep("gros", [0.1], [0], max_time=5000.0, **kw)   # same bucket
        assert sum("length bucket" in r.message
                   for r in caplog.records) == n_logs


# ---------------------------------------------------------------------------
# offline-RL: dataset harvesting + fitted-Q trainer
# ---------------------------------------------------------------------------

def test_build_dataset_masks_and_normalization():
    res = sweep("gros", [0.1], range(2), total_work=400.0, max_time=600.0)
    tr = {k: np.asarray(v) for k, v in res.traces.items()}
    ds = build_dataset(tr, PROFILES["gros"], 0.1)
    n_live = int(np.asarray(res.n_steps).sum())
    # one transition per consecutive live pair, per run
    assert len(ds["s"]) == n_live - len(np.asarray(res.n_steps).ravel())
    assert set(ds) == {"s", "a", "r", "s2"}
    assert (ds["a"] >= 0).all() and (ds["a"] <= 1).all()
    assert (ds["r"] <= 0).all()  # cost-shaped reward
    assert np.isfinite(ds["s"]).all() and np.isfinite(ds["r"]).all()


def test_fitted_q_recovers_known_optimal_action():
    """gamma=0 on a synthetic dataset with reward -(a - 0.7)^2 reduces
    fitted-Q to regression; the greedy policy must pick the candidate
    cap nearest u=0.7 everywhere."""
    rng = np.random.default_rng(0)
    n = 4000
    s = rng.uniform(0.4, 1.4, n).astype(np.float32)
    a = rng.uniform(0.0, 1.0, n).astype(np.float32)
    r = -((a - 0.7) ** 2).astype(np.float32)
    ds = {"s": s, "a": a, "r": r, "s2": s}
    policy = fit_offline_rl(ds, gamma=0.0, n_iters=3)
    gains = PIGains.from_model(PROFILES["gros"], 0.1)
    us = np.linspace(0.0, 1.0, pol.N_ACTIONS)
    state = pol.policy_init(policy, policy.values(PROFILES["gros"],
                                                  gains), gains)
    for prog in (0.5 * gains.setpoint, gains.setpoint,
                 1.3 * gains.setpoint):
        obs = pol.PolicyObs(progress=jnp.float32(prog),
                            power=jnp.float32(0.0), dt=jnp.float32(1.0),
                            gains=gains)
        _, pcap = pol.policy_step(policy, policy.values(
            PROFILES["gros"], gains), state, obs)
        u = (float(pcap) - gains.pcap_min) / (gains.pcap_max
                                              - gains.pcap_min)
        assert abs(u - 0.7) <= (us[1] - us[0])  # nearest grid level


def test_offline_rl_end_to_end_closes_the_loop():
    """Harvest -> train -> deploy: the trained policy must run inside the
    jitted engine and finish the workload."""
    har = sweep("gros", [0.1], range(2), total_work=600.0, max_time=600.0)
    ds = build_dataset({k: np.asarray(v) for k, v in har.traces.items()},
                       PROFILES["gros"], 0.1)
    policy = fit_offline_rl(ds, n_iters=20)
    res = simulate_closed_loop("gros", 0.1, total_work=600.0,
                               max_time=3600.0, seed=5, policy=policy)
    assert res.completed
    assert "action" in res.traces


# ---------------------------------------------------------------------------
# duty-cycle policy behaviour
# ---------------------------------------------------------------------------

def test_dutycycle_modulates_below_full_power():
    """With slack (large epsilon) the DDCM ladder must settle below the
    top level — saving energy — while keeping progress near the
    setpoint."""
    prof = PROFILES["gros"]
    res = simulate_closed_loop(prof, 0.3, total_work=2000.0, seed=1,
                               policy=DutyCyclePolicy())
    assert res.completed
    gains = PIGains.from_model(prof, 0.3)
    tail = res.traces["progress"][res.n_steps // 2:]
    assert tail.mean() == pytest.approx(float(gains.setpoint), rel=0.25)
    caps = res.traces["pcap"][res.n_steps // 2:]
    assert caps.mean() < 0.9 * prof.pcap_max   # shed levels
    assert caps.min() >= prof.pcap_min - 1e-6
    assert "dc_level" in res.traces
    # levels quantized onto the ladder
    lv = res.traces["dc_level"]
    np.testing.assert_allclose(lv, np.round(lv), atol=1e-5)


# ---------------------------------------------------------------------------
# extension point: a custom policy is one branch + one config
# ---------------------------------------------------------------------------

def test_register_custom_policy_runs_in_sweep():
    name = "bangbang_test"
    if name not in pol.BRANCHES:
        def step(vals, state, obs):
            g = obs.gains
            pcap = jnp.where(obs.progress < g.setpoint, g.pcap_max,
                             g.pcap_min)
            return state, pcap

        pol.register_branch(
            name, step,
            lambda vals, gains: jnp.zeros((pol.POLICY_STATE_DIM,),
                                          jnp.float32))

    @dataclasses.dataclass(frozen=True)
    class BangBang(pol.Policy):
        @property
        def branch(self):
            return name

    res = sweep("gros", [0.1], range(2), total_work=300.0,
                max_time=600.0, policies=[BangBang(), PIPolicy()])
    assert res.exec_time.shape == (1, 2, 2)
    assert bool(np.asarray(res.completed).all())
    caps = np.asarray(res.traces["pcap"][0, 0])
    valid = np.asarray(res.traces["valid"][0, 0])
    prof = PROFILES["gros"]
    assert set(np.round(caps[valid]).tolist()) <= {prof.pcap_min,
                                                   prof.pcap_max}


# ---------------------------------------------------------------------------
# NRM resume round-trip for non-PI policies (regression)
# ---------------------------------------------------------------------------

def test_nrm_resume_round_trips_non_pi_policy_state():
    nrm = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"),
              policy=DutyCyclePolicy())
    tr = nrm.run_simulated(total_work=300.0, seed=2)
    assert "dc_level" in tr and float(tr["work"][-1]) >= 300.0
    assert nrm._policy_state is not None
    level1 = float(nrm._policy_state[0])
    assert level1 == float(tr["dc_level"][-1])
    # second call resumes the SAME ladder position (a fresh policy would
    # restart from the top level), and the plant keeps its work
    tr2 = nrm.run_simulated(total_work=600.0, seed=3)
    assert float(tr2["work"][0]) > 300.0
    dc = DutyCyclePolicy()
    assert abs(float(tr2["dc_level"][0]) - level1) <= max(dc.up_step,
                                                          dc.down_step)
    fresh = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"),
                policy=DutyCyclePolicy())
    trf = fresh.run_simulated(total_work=300.0, seed=3)
    assert float(trf["dc_level"][0]) >= dc.n_levels - dc.down_step
    # checkpoint round-trip carries the policy state
    d = nrm.state_dict()
    assert "policy_state" in d
    nrm2 = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"),
               policy=DutyCyclePolicy())
    nrm2.load_state_dict(d)
    np.testing.assert_allclose(np.asarray(nrm2._policy_state),
                               np.asarray(nrm._policy_state))
    # loading a checkpoint saved BEFORE any run resets stale policy
    # state instead of silently mixing two runs
    pre_run = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"),
                  policy=DutyCyclePolicy()).state_dict()
    assert "policy_state" not in pre_run
    nrm2.load_state_dict(pre_run)
    assert nrm2._policy_state is None
    # a policy-less NRM rejects a checkpoint carrying policy state
    with pytest.raises(ValueError, match="policy"):
        NRM(PowerControlConfig(epsilon=0.1,
                               plant_profile="gros")).load_state_dict(d)
    # and a wrong-length weight tuple fails loudly, not under -O only
    from repro.core.policies import OfflineRLPolicy
    with pytest.raises(ValueError, match="weights"):
        simulate_closed_loop("gros", 0.1, total_work=100.0,
                             policy=OfflineRLPolicy(weights=(1.0, 2.0)))
    # the runtime path dispatches through the policy contract too (PR 4):
    # a control period continues the SAME resumed ladder state and the
    # actuator receives the command
    level_before = float(nrm._policy_state[0])
    rec = nrm.control_step()
    assert abs(float(nrm._policy_state[0]) - level_before) <= max(
        dc.up_step, dc.down_step)
    assert nrm.actuator._pcap == pytest.approx(
        np.clip(rec.pcap, nrm.profile.pcap_min, nrm.profile.pcap_max))


def test_nrm_adaptive_checkpoint_round_trips_estimator_state():
    """Regression: state_dict/load_state_dict must carry (or reset) the
    RLS estimator state like the policy state, not mix a rolled-back
    controller with a stale estimator."""
    nrm = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros",
                                 adaptive=True))
    nrm.run_simulated(total_work=400.0, seed=2)
    assert nrm._rls_state is not None
    d = nrm.state_dict()
    assert "rls_state" in d
    other = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros",
                                   adaptive=True))
    other.load_state_dict(d)
    np.testing.assert_allclose(np.asarray(other._rls_state.theta),
                               np.asarray(nrm._rls_state.theta))
    assert float(other._rls_state.kl_hat) == pytest.approx(
        float(nrm._rls_state.kl_hat))
    assert other.controller.gains.k_p == pytest.approx(
        float(nrm._rls_state.k_p))
    # loading a pre-estimator checkpoint resets instead of keeping stale
    fresh_ckpt = NRM(PowerControlConfig(
        epsilon=0.1, plant_profile="gros", adaptive=True)).state_dict()
    assert "rls_state" not in fresh_ckpt
    other.load_state_dict(fresh_ckpt)
    assert other._rls_state is None
    assert other.controller.gains.k_p == pytest.approx(other.gains.k_p)
    # a non-adaptive NRM rejects a checkpoint carrying estimator state
    with pytest.raises(ValueError, match="adaptive"):
        NRM(PowerControlConfig(epsilon=0.1,
                               plant_profile="gros")).load_state_dict(d)


def test_nrm_explicit_pi_policy_matches_default_path():
    """Regression: NRM(policy=PIPolicy()) must be the SAME computation
    as the default NRM — in particular the first run_simulated resumes
    from controller.state instead of discarding it for a fresh pack."""
    a = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"))
    b = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"),
            policy=PIPolicy())
    d = {"prev_error": -2.0, "prev_pcap_l": -0.2, "t": 0.0}
    a.load_state_dict(d)
    b.load_state_dict(d)  # pre-policy checkpoint: no policy_state key
    ta = a.run_simulated(total_work=300.0, seed=4)
    tb = b.run_simulated(total_work=300.0, seed=4)
    for k in ("progress", "pcap", "energy"):
        np.testing.assert_array_equal(ta[k], tb[k], err_msg=k)


def test_design_with_policy_raises():
    """design= only modifies the adaptive= sugar; silently ignoring it
    next to policy= would change the estimator's linearization model."""
    with pytest.raises(ValueError):
        simulate_closed_loop("gros", 0.1, total_work=100.0,
                             policy=PIPolicy(adaptive=RLSConfig()),
                             design=PROFILES["dahu"])


def test_nrm_accepts_adaptive_pi_policy():
    """Regression: NRM(policy=PIPolicy(adaptive=...)) must thread the
    estimator inside the packed policy state (no numpy-adapter sync, no
    crash) and keep adapting across resumed calls."""
    nrm = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"),
              policy=PIPolicy(adaptive=RLSConfig()))
    tr = nrm.run_simulated(total_work=300.0, seed=2)
    assert {"kl_hat", "tau_hat"} <= set(tr)
    assert nrm._policy_state is not None and nrm._rls_cfg is None
    tr2 = nrm.run_simulated(total_work=600.0, seed=3)
    assert float(tr2["work"][0]) > 300.0          # resumed, not restarted
    # estimator continued from the packed state, not re-initialized: a
    # FRESH estimator has no regressor history, so its first step leaves
    # theta at the init value kl_ref/2; a continued one updates at once
    theta1_init = 0.5 * PROFILES["gros"].K_L
    assert float(tr["theta1"][0]) == pytest.approx(theta1_init)
    assert float(tr2["theta1"][0]) != pytest.approx(theta1_init)
