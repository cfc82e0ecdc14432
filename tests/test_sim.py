"""Scan engine (repro.core.sim): equivalence with the stateful NRM loop,
the in-scan RLS estimator vs its numpy oracle, trace-free summary mode
vs full-trace reductions, vmapped sweep shapes/correctness, the
host-built seed keys vs PRNGKey, the Eq. 3 replay helper, and the
grid tables packed on the host (bit-equal to device packing, no device
call while the grid is built, no new engine on a second sweep)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PowerControlConfig
from repro.core import executor, plane, sim
from repro.core import faults as flt
from repro.core import policies as pol
from repro.core.adaptive import RLSAdapter, RLSConfig
from repro.core.controller import PIGains
from repro.core.nrm import NRM
from repro.core.plant import (PROFILE_FIELDS, PROFILES, PlantProfile,
                              pcap_linearize, simulate)
from repro.core.policies import DutyCyclePolicy, OfflineRLPolicy, PIPolicy
from repro.core.sim import (hist_quantile, open_loop_runs, replay_model,
                            seed_keys, simulate_closed_loop, sweep)
from repro.core.workloads.detect import DetectorConfig, detector_values
from repro.core.workloads.schedule import MAX_PHASES, Phase, PhaseSchedule


@pytest.mark.parametrize("name", ["gros", "dahu"])
def test_engine_matches_stateful_nrm_loop(name):
    """The jitted scan and the per-step Python loop are the same model up
    to RNG stream; at fixed seed their run-level statistics must agree
    within the plant's noise envelope."""
    eps, work = 0.15, 2000.0
    nrm = NRM(PowerControlConfig(epsilon=eps, plant_profile=name))
    ref = nrm._run_simulated_python(total_work=work, seed=3)
    res = simulate_closed_loop(name, eps, total_work=work, seed=3)
    assert res.completed
    assert res.exec_time == pytest.approx(float(ref["t"][-1]), rel=0.12)
    assert res.energy == pytest.approx(float(ref["energy"][-1]), rel=0.12)
    sp = float(nrm.gains.setpoint)
    for tr in (ref, res.traces):
        tail = tr["progress"][len(tr["progress"]) // 2:]
        assert abs(tail.mean() - sp) < 0.12 * sp
    # identical keys/contract as the old return value
    assert set(res.traces) == set(ref)


def test_nrm_delegation_threads_state():
    """run_simulated (non-adaptive) runs on the engine and must leave the
    controller/actuator state advanced, like the loop did."""
    nrm = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros"))
    tr = nrm.run_simulated(total_work=300.0, seed=2)
    assert float(tr["work"][-1]) >= 300.0
    assert nrm._t == pytest.approx(float(tr["t"][-1]))
    assert float(nrm.actuator.state.work) == pytest.approx(
        float(tr["work"][-1]))
    assert float(nrm.controller.state.prev_pcap_l) == pytest.approx(
        float(pcap_linearize(PROFILES["gros"], tr["pcap"][-1])), rel=1e-4)
    # a second call continues from the accumulated plant state
    tr2 = nrm.run_simulated(total_work=600.0, seed=5)
    assert float(tr2["work"][0]) > 300.0


def test_engine_run_on_shifted_plant_with_foreign_gains():
    """Gains designed on gros, plant with 2x gain (the adaptive
    benchmark's fixed-gains arm) must still complete."""
    shifted = dataclasses.replace(PROFILES["gros"],
                                  K_L=PROFILES["gros"].K_L * 2)
    res = simulate_closed_loop(
        shifted, gains=PIGains.from_model(PROFILES["gros"], 0.1),
        total_work=1500.0, seed=6)
    assert res.completed
    assert res.exec_time < 3600.0


def test_sweep_shapes_and_tradeoff_direction():
    eps = [0.0, 0.1, 0.3]
    res = sweep(["gros", "dahu"], eps, range(2), total_work=800.0,
                max_time=1200.0)
    assert res.exec_time.shape == (2, 3, 2)
    # scan length is bucketed to a power of two >= the requested horizon
    assert res.traces["progress"].shape[:3] == (2, 3, 2)
    assert res.traces["progress"].shape[-1] >= 1200
    assert bool(np.asarray(res.completed).all())
    t = np.asarray(res.exec_time).mean(-1)   # (P, E)
    e = np.asarray(res.energy).mean(-1)
    for p in range(2):
        assert e[p, 2] < e[p, 0]     # more degradation -> less energy
        assert t[p, 2] > t[p, 0]     # ... and more time
    # single-profile call squeezes the profile axis
    res1 = sweep("gros", eps, range(2), total_work=800.0, max_time=1200.0)
    assert res1.exec_time.shape == (3, 2)


@pytest.mark.parametrize("seed", [7, 2**31 + 4242, 2**32 + 5])
def test_sweep_matches_single_runs(seed):
    """A sweep cell equals simulate_closed_loop at the same (eps, seed),
    also for seeds past 2**31 and 2**32 (the sweep builds its keys on the
    host; the single run keys with PRNGKey(seed))."""
    res = sweep("gros", [0.1], [seed], total_work=1000.0)
    one = simulate_closed_loop("gros", 0.1, total_work=1000.0, seed=seed)
    assert float(res.exec_time[0, 0]) == pytest.approx(one.exec_time)
    assert float(res.energy[0, 0]) == pytest.approx(one.energy, rel=1e-5)
    assert int(res.n_steps[0, 0]) == one.n_steps


KEY_SEEDS = [0, 1, 7, 2**31 - 1, 2**31, 3_100_000_001, 2**32 - 1, 2**32,
             2**32 + 5, -1, -5, 2**40 + 3, 2**63 - 1, -2**63]


def _loop_keys(seeds):
    return np.stack([np.asarray(jax.random.PRNGKey(int(s))) for s in seeds])


@pytest.mark.parametrize("seed", KEY_SEEDS)
def test_seed_keys_equal_prngkey_bit_for_bit(seed):
    got, want = seed_keys([seed]), _loop_keys([seed])
    assert got.dtype == want.dtype == np.uint32
    assert got.shape == want.shape == (1, 2)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", [2**63, 2**64])
def test_seed_keys_overflow_like_prngkey(seed):
    with pytest.raises(OverflowError):
        jax.random.PRNGKey(seed)
    with pytest.raises(OverflowError):
        seed_keys([0, seed])


def test_seed_keys_fallback_is_the_prngkey_loop(monkeypatch):
    """Under any other JAX setting the keys come from PRNGKey itself."""
    monkeypatch.setattr(sim, "_keys_are_low_words", lambda: False)
    got = seed_keys(KEY_SEEDS)
    assert got.dtype == np.uint32 and got.shape == (len(KEY_SEEDS), 2)
    np.testing.assert_array_equal(got, _loop_keys(KEY_SEEDS))


def test_open_loop_runs_key_each_seed_with_prngkey():
    prof = PROFILES["gros"]
    seeds = [3, 2**32 + 3]
    out = open_loop_runs(prof, 16, seeds)
    for i, s in enumerate(seeds):
        one = simulate(prof, jnp.full((16,), prof.pcap_max), 1.0,
                       jax.random.PRNGKey(s))
        for k in one:
            np.testing.assert_array_equal(np.asarray(out[k])[i],
                                          np.asarray(one[k]), err_msg=k)


def test_early_exit_mask_freezes_state():
    res = sweep("gros", [0.1], [0], total_work=200.0, max_time=600.0)
    valid = np.asarray(res.traces["valid"])[0, 0]
    n = int(res.n_steps[0, 0])
    assert valid[:n].all() and not valid[n:].any()
    energy = np.asarray(res.traces["energy"])[0, 0]
    assert (energy[n:] == energy[n - 1]).all()  # frozen after completion
    assert float(res.exec_time[0, 0]) == pytest.approx(float(n))


def test_scan_rls_matches_numpy_adapter():
    """The in-scan RLS estimator and the numpy RLSAdapter are the same
    algorithm: driven with identical (progress, prev pcap_L) sequences —
    taken from an adaptive gain-shift run — their theta / tau_hat /
    K_L_hat trajectories must agree (f32 vs f64 accumulation only)."""
    design = PROFILES["gros"]
    shifted = dataclasses.replace(design, K_L=design.K_L * 2)
    gains = PIGains.from_model(design, 0.1)
    res = simulate_closed_loop(shifted, gains=gains, total_work=3000.0,
                               seed=6, adaptive=RLSConfig(),
                               design=design)
    assert res.completed and res.rls_state is not None
    tr, n = res.traces, res.n_steps
    # the estimator's pcap_L input at step i is the linearized command
    # applied that period, i.e. the previous step's traced command
    prev_pl = np.concatenate(
        [[float(pcap_linearize(design, design.pcap_max))],
         np.asarray(pcap_linearize(design, tr["pcap"][:-1]))])
    oracle = RLSAdapter(gains, design)
    g = gains
    th = np.zeros((n, 2))
    tau = np.zeros(n)
    kl = np.zeros(n)
    for i in range(n):
        g = oracle.update(g, float(tr["progress"][i]),
                          float(prev_pl[i]), 1.0)
        th[i] = oracle.theta
        tau[i], kl[i] = oracle.tau_hat, oracle.kl_hat
    np.testing.assert_allclose(tr["theta1"], th[:, 0], rtol=0.02,
                               atol=1e-3)
    np.testing.assert_allclose(tr["theta2"], th[:, 1], atol=5e-3)
    np.testing.assert_allclose(tr["tau_hat"], tau, rtol=0.05, atol=0.02)
    np.testing.assert_allclose(tr["kl_hat"], kl, rtol=0.01)
    # the final carried state mirrors the last traced estimates
    assert float(res.rls_state.kl_hat) == pytest.approx(
        float(tr["kl_hat"][-1]))


def test_nrm_adaptive_runs_on_engine_and_threads_rls_state():
    """run_simulated with adaptive=True must ride the scan engine (RLS
    trace keys present) and carry the estimator across calls."""
    nrm = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros",
                                 adaptive=True))
    tr = nrm.run_simulated(total_work=400.0, seed=2)
    assert {"kl_hat", "tau_hat", "k_p", "k_i"} <= set(tr)
    assert nrm._rls_state is not None
    kl1 = float(nrm._rls_state.kl_hat)
    # the scheduled gains reach the stateful controller (runtime
    # control_step continuity)
    assert nrm.controller.gains.k_p == pytest.approx(
        float(nrm._rls_state.k_p))
    tr2 = nrm.run_simulated(total_work=800.0, seed=3)
    assert float(tr2["work"][0]) > 400.0  # resumed, not restarted
    # estimator continued (history survives across the call boundary)
    assert bool(nrm._rls_state.has_prev)


def test_adaptive_resume_without_rls_state_starts_estimator():
    """A resume carry that predates the estimator must still honour
    adaptive= (fresh RLS state), not silently run fixed-gain."""
    from repro.core.controller import pi_init
    from repro.core.plant import plant_init
    from repro.core.sim import resume_init
    p = PROFILES["gros"]
    g = PIGains.from_model(p, 0.1)
    init = resume_init(plant_init(p), pi_init(g), p.pcap_max)
    res = simulate_closed_loop(p, gains=g, total_work=300.0, seed=1,
                               init=init, adaptive=RLSConfig())
    assert res.rls_state is not None
    assert "kl_hat" in res.traces


def test_adaptive_sweep_grid_axis_and_squeeze():
    cfgs = [RLSConfig(lam=0.99), RLSConfig(lam=0.995),
            RLSConfig(lam=0.999)]
    res = sweep("gros", [0.1, 0.2], range(2), total_work=500.0,
                max_time=600.0,
                policies=[PIPolicy(adaptive=c) for c in cfgs],
                collect_traces=False)
    assert res.exec_time.shape == (2, 3, 2)  # (E, A, S), profile squeezed
    assert bool(np.asarray(res.completed).all())
    assert res.traces is None
    # a single adaptive PIPolicy squeezes the A axis like a single
    # profile does
    res1 = sweep("gros", [0.1, 0.2], range(2), total_work=500.0,
                 max_time=600.0, policies=PIPolicy(adaptive=RLSConfig()),
                 collect_traces=False)
    assert res1.exec_time.shape == (2, 2)


def test_detector_sweep_grid_axis():
    """A SEQUENCE of DetectorConfigs sweeps the detector
    hyperparameters as their own vmapped axis (between [workloads] and
    seeds), exactly equal per-slice to single-config sweeps."""
    from repro.core.workloads.detect import DetectorConfig
    cfgs = [DetectorConfig(threshold=0.5, min_gap=5),
            DetectorConfig(threshold=1e6)]
    kw = dict(total_work=400.0, max_time=600.0, collect_traces=False)
    res = sweep("gros", [0.1, 0.2], range(2), detector=cfgs, **kw)
    assert res.exec_time.shape == (2, 2, 2)       # (E, D, S)
    det = np.asarray(res.detections)
    assert det.shape == (2, 2, 2)
    assert det[:, 0].sum() > 0      # hair-trigger threshold fires
    assert (det[:, 1] == 0).all()   # unreachable threshold never does
    for d, cfg in enumerate(cfgs):  # D slice == that config alone
        one = sweep("gros", [0.1, 0.2], range(2), detector=cfg, **kw)
        np.testing.assert_array_equal(np.asarray(one.exec_time),
                                      np.asarray(res.exec_time)[:, d])
        np.testing.assert_array_equal(np.asarray(one.detections),
                                      det[:, d])
    # the chunked executor path flattens/reassembles the D axis exactly
    ch = sweep("gros", [0.1, 0.2], range(2), detector=cfgs,
               chunk_size=3, **kw)
    np.testing.assert_array_equal(np.asarray(ch.exec_time),
                                  np.asarray(res.exec_time))
    np.testing.assert_array_equal(np.asarray(ch.detections), det)


# Trace and summary mode are two XLA executables, and XLA does not
# promise bit equality between two executables: fusion may reorder a
# float32 sum. Counts stay exact; float fields agree to this many units
# in the last place.
MAX_ULP = 4


def test_summary_mode_matches_trace_reductions():
    """The online (in-carry) reductions must agree with the same
    statistics computed from full traces, and the summary-mode executable
    must produce the full-trace one's results: counts exactly, floats
    within `MAX_ULP`."""
    full = sweep("gros", [0.1, 0.3], range(3), total_work=900.0,
                 max_time=1200.0)
    lean = sweep("gros", [0.1, 0.3], range(3), total_work=900.0,
                 max_time=1200.0, collect_traces=False)
    assert lean.traces is None and full.traces is not None
    np.testing.assert_array_equal(np.asarray(full.n_steps),
                                  np.asarray(lean.n_steps))
    for k in ("progress_hist", "pcap_hist"):
        np.testing.assert_array_equal(np.asarray(full.summary[k]),
                                      np.asarray(lean.summary[k]))
    for a, b in [(full.exec_time, lean.exec_time),
                 (full.energy, lean.energy)] + [
            (full.summary[k], lean.summary[k])
            for k in ("progress_mean", "power_mean")]:
        np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b),
                                        maxulp=MAX_ULP)
    # online moments == trace reductions
    np.testing.assert_allclose(np.asarray(full.summary["progress_mean"]),
                               full.masked_mean("progress"), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(full.summary["power_mean"]),
                               full.masked_mean("power"), rtol=1e-4)
    # histogram median-sketch == the trace's median sample, to half a
    # bin width. The sketch answers the bin of the ceil(n/2)-th smallest
    # sample (the inverted CDF); np.median would average the two middle
    # samples of an even count, which may lie bins apart
    med = hist_quantile(full.summary["progress_hist"],
                        full.summary["progress_edges"], 0.5)
    prog = np.asarray(full.traces["progress"])
    valid = np.asarray(full.traces["valid"])
    edges = np.asarray(full.summary["progress_edges"])
    half_bin = 0.5 * (edges[1] - edges[0])
    for e in range(2):
        for s in range(3):
            exact = np.quantile(prog[e, s][valid[e, s]], 0.5,
                                method="inverted_cdf")
            assert abs(med[e, s] - exact) <= half_bin + 1e-6
    # per-run histogram mass equals the live-step count
    np.testing.assert_allclose(
        np.asarray(full.summary["progress_hist"]).sum(-1),
        np.asarray(full.n_steps), rtol=1e-6)


def test_hist_quantile_edge_cases():
    edges = np.linspace(0.0, 10.0, 11, dtype=np.float32)
    centers = 0.5 * (edges[:-1] + edges[1:])
    # empty histogram -> NaN (not a silent first-bin answer)
    assert np.isnan(hist_quantile(np.zeros(10), edges, 0.5))
    # q=0 / q=1 land on the lowest / highest OCCUPIED bins
    h = np.zeros(10)
    h[3], h[7] = 2.0, 1.0
    assert hist_quantile(h, edges, 0.0) == pytest.approx(centers[3])
    assert hist_quantile(h, edges, 1.0) == pytest.approx(centers[7])
    assert hist_quantile(h, edges, 0.5) == pytest.approx(centers[3])
    # a single count answers its own bin for every q
    h1 = np.zeros(10)
    h1[5] = 1.0
    for q in (0.0, 0.25, 0.5, 1.0):
        assert hist_quantile(h1, edges, q) == pytest.approx(centers[5])
    # batched: empty and occupied rows coexist
    hb = np.stack([np.zeros(10), h1])
    out = hist_quantile(hb, edges, 0.5)
    assert np.isnan(out[0]) and out[1] == pytest.approx(centers[5])


def test_single_live_step_summary_and_quantile():
    """A run that completes in its first period: count==1, the histogram
    holds exactly one sample and every quantile answers it."""
    res = simulate_closed_loop("gros", 0.1, total_work=1e-6, seed=0)
    assert res.n_steps == 1 and res.completed
    assert res.summary["progress_hist"].sum() == pytest.approx(1.0)
    med = hist_quantile(res.summary["progress_hist"],
                        res.summary["progress_edges"], 0.5)
    lo = hist_quantile(res.summary["progress_hist"],
                       res.summary["progress_edges"], 0.0)
    assert med == pytest.approx(lo)
    assert res.summary["power_mean"] == pytest.approx(
        float(res.traces["power"][0]), rel=1e-5)


def test_resume_init_fresh_state_equals_default_run():
    """Resuming from freshly-initialized plant/controller state must be
    bit-for-bit the same run as starting from scratch."""
    from repro.core import sim
    from repro.core.controller import pi_init
    from repro.core.plant import plant_init
    from repro.core.sim import resume_init
    p = PROFILES["gros"]
    g = PIGains.from_model(p, 0.1)
    # build the fresh states from the f32-packed values, exactly like
    # the engine's internal default init does
    p32 = sim._unpack_profile(sim.profile_values(p))
    g32 = sim._unpack_gains(sim.gains_values(g))
    init = resume_init(plant_init(p32), pi_init(g32), p.pcap_max)
    a = simulate_closed_loop(p, gains=g, total_work=400.0, seed=4,
                             init=init)
    b = simulate_closed_loop(p, gains=g, total_work=400.0, seed=4)
    assert a.n_steps == b.n_steps
    for k in ("progress", "pcap", "energy"):
        np.testing.assert_array_equal(a.traces[k], b.traces[k])


def test_resume_init_policy_state_continues_non_pi_policy():
    """resume_init(policy_state=...) continues a non-PI policy exactly
    where SimResult.policy_state left it."""
    from repro.core.policies import DutyCyclePolicy
    from repro.core.sim import resume_init
    p = PROFILES["gros"]
    g = PIGains.from_model(p, 0.1)
    dc = DutyCyclePolicy()
    r1 = simulate_closed_loop(p, gains=g, total_work=300.0, seed=1,
                              policy=dc)
    init = resume_init(r1.plant_state, None, r1.pcap,
                       policy_state=r1.policy_state)
    r2 = simulate_closed_loop(p, gains=g, total_work=600.0, seed=2,
                              policy=dc, init=init)
    assert float(r2.traces["work"][0]) > 300.0
    assert abs(float(r2.traces["dc_level"][0])
               - float(r1.policy_state[0])) <= dc.up_step
    # a PI resume carry with leftover RLS state still demands adaptive=
    rls = simulate_closed_loop(p, gains=g, total_work=300.0, seed=1,
                               adaptive=RLSConfig())
    bad = resume_init(rls.plant_state,
                      type(rls.pi_state)(*map(np.float32, rls.pi_state)),
                      rls.pcap, rls=rls.rls_state)
    with pytest.raises(ValueError):
        simulate_closed_loop(p, gains=g, total_work=100.0, init=bad)
    # cross-branch resume is rejected: a duty-cycle state vector must
    # not be silently misread as PI slots (branch tag check)
    with pytest.raises(ValueError, match="branch"):
        simulate_closed_loop(p, gains=g, total_work=100.0, init=init)
    # ... while the pi -> adaptive-pi upgrade stays allowed
    from repro.core.controller import pi_init
    from repro.core.plant import plant_init
    up = resume_init(plant_init(p), pi_init(g), p.pcap_max)
    ok = simulate_closed_loop(p, gains=g, total_work=100.0, init=up,
                              adaptive=RLSConfig())
    assert ok.rls_state is not None


def test_replay_model_matches_reference_loop():
    p = PROFILES["dahu"]
    sched = np.concatenate([np.full(20, 60.0), np.full(20, 110.0)])
    pred = np.asarray(replay_model(p, sched, 1.0))
    pl = np.asarray(pcap_linearize(p, sched))
    w = 1.0 / (1.0 + p.tau)
    y = float(pl[0]) * p.K_L
    ref = np.zeros(len(sched))
    for i in range(len(sched)):
        y = p.K_L * w * pl[i] + (1 - w) * y
        ref[i] = y + p.K_L
    np.testing.assert_allclose(pred, ref, rtol=1e-5)


def test_compilation_cache_location(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR owns the cache location when set;
    otherwise it is the checkout's fixed experiments/xla_cache."""
    from pathlib import Path

    import jax

    from repro.core.sim import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", "set-by-jax")
        enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == "set-by-jax"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compilation_cache()
        root = Path(__file__).resolve().parents[1]
        assert jax.config.jax_compilation_cache_dir == str(
            root / "experiments" / "xla_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# The sweep's grid tables are packed on the host. Oracles: the device
# expressions the packers used before (jnp.asarray / .at[].set), rebuilt
# here; every table must come out bit-identical.
# ---------------------------------------------------------------------------

FIG7_EPS = (0.0, 0.01, 0.02, 0.05, 0.08, 0.1, 0.15, 0.2, 0.3, 0.4, 0.5)
GRID_POLICIES = {
    "pi": PIPolicy(),
    "pi_rls": PIPolicy(adaptive=RLSConfig()),
    "dutycycle": DutyCyclePolicy(),
    "offline_rl": OfflineRLPolicy(weights=(0.1, -1.3, 2.7, 1e-3, -0.45,
                                           3.3)),
}


def _phased_scenario():
    """The phased cell's scenario: cyclic STREAM/DGEMM knees, the
    detector, a 10% heartbeat blackout with a frozen meter, the guard."""
    return dict(
        workloads=PhaseSchedule((
            Phase(100.0, scale=(("alpha", 3.0), ("beta", 0.6))),
            Phase(100.0, scale=(("alpha", 0.3), ("beta", 1.14)))),
            cyclic=True),
        detector=DetectorConfig(),
        faults=flt.FaultSchedule((
            flt.FaultWindow("hb_dropout", 80.0, 40.0, p1=1.0),
            flt.FaultWindow("meter_freeze", 80.0, 40.0)), period=400.0),
        guard=flt.GuardConfig())


def _jnp_pack(kind, params=()):
    v = jnp.zeros((pol.POLICY_PARAM_DIM,), jnp.float32)
    if params:
        v = v.at[1:1 + len(params)].set(jnp.asarray(params, jnp.float32))
    return v.at[0].set(float(kind))


def _jnp_policy_values(policy, prof, gains, kind):
    if isinstance(policy, DutyCyclePolicy):
        return _jnp_pack(kind, (float(policy.n_levels),
                                float(policy.min_level), policy.deadband,
                                policy.down_step, policy.up_step))
    if isinstance(policy, OfflineRLPolicy):
        return _jnp_pack(kind, policy.weights)
    if policy.adaptive is None:
        return _jnp_pack(kind)
    cfg = policy.adaptive
    rv = jnp.asarray([cfg.lam, float(cfg.dwell), cfg.kl_clamp, prof.K_L,
                      1.0 / (prof.K_L * gains.k_i), cfg.p_trace_max],
                     jnp.float32)
    return _jnp_pack(kind, [rv[i] for i in range(6)])


def _assert_host_equal(got, want):
    assert isinstance(got, np.ndarray) and got.dtype == np.float32
    assert got.shape == np.shape(want)
    assert np.array_equal(got, np.asarray(want))


@pytest.mark.parametrize("policy", list(GRID_POLICIES))
@pytest.mark.parametrize("name", list(PROFILES))
def test_host_tables_bit_equal_to_device_packing(name, policy):
    prof = PROFILES[name]
    p = GRID_POLICIES[policy]
    kind = list(GRID_POLICIES).index(policy)
    assert prof.progress_max == float(prof.static_progress(prof.pcap_max))
    _assert_host_equal(sim.profile_values(prof), jnp.asarray(
        [getattr(prof, f) for f in PROFILE_FIELDS], jnp.float32))
    for eps in FIG7_EPS:
        g = PIGains.from_model(prof, eps, 10.0)
        _assert_host_equal(sim.gains_values(g), jnp.asarray(
            [getattr(g, f) for f in plane.GAIN_FIELDS], jnp.float32))
        _assert_host_equal(pol.policy_values(p, prof, g, kind=kind),
                           _jnp_policy_values(p, prof, g, kind))
    det = DetectorConfig()
    _assert_host_equal(detector_values(det, prof), jnp.asarray(
        [prof.K_L, prof.tau, prof.noise_scale * float(np.sqrt(
            prof.n_sockets)), det.drift, det.threshold,
         float(det.min_gap), det.level_eta, det.level_slack],
        jnp.float32))
    gcfg = flt.GuardConfig(hold_k=4, recover_reset=False)
    _assert_host_equal(flt.guard_values(gcfg), jnp.array(
        [4, gcfg.failsafe_k, gcfg.outlier_mult, 0.0, 0.0, 0.0],
        jnp.float32))
    scen = _phased_scenario()
    sched = scen["workloads"]
    sv = sched.resolve(prof)
    _assert_host_equal(sv.ends, jnp.asarray(np.concatenate([
        np.cumsum([ph.duration for ph in sched.phases]),
        np.full(MAX_PHASES - len(sched.phases), np.inf)]), jnp.float32))
    rows = [jnp.asarray([getattr(ph.resolve(prof), f)
                         for f in PROFILE_FIELDS], jnp.float32)
            for ph in sched.phases]
    _assert_host_equal(sv.profiles, jnp.stack(
        rows + [rows[-1]] * (MAX_PHASES - len(rows))))
    _assert_host_equal(sv.period, jnp.float32(200.0))
    fs = scen["faults"]
    fv = fs.resolve()
    pad = [np.inf] * (flt.MAX_FAULT_ROWS - len(fs.windows))
    zeros = [0.0] * len(pad)
    want = flt.FaultValues(
        jnp.asarray([w.start for w in fs.windows] + pad, jnp.float32),
        jnp.asarray([w.start + w.duration for w in fs.windows] + pad,
                    jnp.float32),
        jnp.asarray([flt.FAULT_KINDS.index(w.kind) for w in fs.windows]
                    + zeros, jnp.float32),
        jnp.asarray([1.0, 0.0] + zeros, jnp.float32),
        jnp.asarray([w.p2 for w in fs.windows] + zeros, jnp.float32),
        jnp.float32(fs.period))
    for got, w in zip(fv, want):
        _assert_host_equal(got, w)


GRIDS = {  # epsilons, policies, scenario of the two campaign cells
    "fixed_pi": (FIG7_EPS, ("pi",), False),
    "phased_faulted": ((0.1, 0.3), ("pi", "pi_rls", "dutycycle"), True),
}


@pytest.mark.parametrize("grid", list(GRIDS))
def test_grid_values_make_no_device_call(grid):
    """The grid tables of both campaign cells build with every host <->
    device transfer forbidden: nothing in them touches the device."""
    eps, names, phased = GRIDS[grid]
    scen = _phased_scenario() if phased else {}
    profs = [PROFILES[n] for n in ("gros", "dahu", "yeti")]
    pls = [GRID_POLICIES[n] for n in names]
    _, kinds = pol.resolve_kinds(pls)
    for p in profs:
        p.progress_max  # its one device evaluation, kept on the profile
    with jax.transfer_guard("disallow"):
        g = sim._grid_values(profs, eps, pls, kinds, 10.0, **scen)
    assert g.pv.shape == (3, len(PROFILE_FIELDS))
    assert g.gv.shape == (3, len(eps), plane.GAIN_DIM)
    assert g.av.shape == (3, len(pls), pol.POLICY_PARAM_DIM)
    for leaf in jax.tree_util.tree_leaves(g[:7]):
        assert isinstance(leaf, np.ndarray) and leaf.dtype == np.float32
    assert (g.sv is None) == (not phased)


def test_progress_max_evaluated_once_per_profile(monkeypatch):
    calls = []
    static = PlantProfile.static_progress

    def counted(self, pcap):
        calls.append(self.name)
        return static(self, pcap)

    monkeypatch.setattr(PlantProfile, "static_progress", counted)
    profs = [dataclasses.replace(PROFILES[n]) for n in ("gros", "dahu",
                                                         "yeti")]
    for _ in range(2):
        for p in profs:
            for eps in FIG7_EPS:
                PIGains.from_model(p, eps, 10.0)
        sim._grid_values(profs, FIG7_EPS, [PIPolicy()], (0,), 10.0)
    assert sorted(calls) == ["dahu", "gros", "yeti"]
    for p, n in zip(profs, ("gros", "dahu", "yeti")):
        assert p.progress_max == PROFILES[n].progress_max


def _phased_sweep(seeds):
    return sweep([PROFILES[n] for n in ("gros", "dahu", "yeti")],
                 [0.1, 0.3], seeds, total_work=400.0, max_time=40.0,
                 policies=[GRID_POLICIES[n]
                           for n in ("pi", "pi_rls", "dutycycle")],
                 collect_traces=False, backend="scan", chunk_size=12,
                 **_phased_scenario())


def _engine_cache_sizes():
    return {k: v._cache_size() for k, v in executor._COMPILED.items()
            if hasattr(v, "_cache_size")}


def test_second_scan_sweep_adds_no_compiled_engine():
    first = _phased_sweep([1, 2])
    sizes = _engine_cache_sizes()
    second = _phased_sweep([3, 4])
    assert _engine_cache_sizes() == sizes
    assert first.exec_time.shape == second.exec_time.shape == (3, 2, 3, 2)


def _fixed_pi_sweep(seeds):
    return sweep("gros", [0.1, 0.2], seeds, total_work=300.0,
                 max_time=64.0)


@pytest.mark.parametrize("grid, n_runs, guarded", [
    (_phased_sweep, 3 * 2 * 3 * 2, True),
    (_fixed_pi_sweep, 2 * 2, False)],
    ids=["phased_faulted_chunked", "fixed_pi_default"])
def test_sweep_grid_span_makes_no_device_call(monkeypatch, grid, n_runs,
                                              guarded):
    """From the sweep's entry to the executor's hand-off nothing moves
    between host and device; the engine's inputs, the shared scalars
    included, reach the executor as host float32. A sweep with every
    keyword at its default takes the same road as a chunked one."""
    grid([5, 6])  # warm: engine built, progress_max kept

    class Handoff(Exception):
        pass

    seen = {}

    def run_grid(fn, batched, shared, n_runs, **kw):
        seen.update(batched=batched, shared=shared, n_runs=n_runs)
        raise Handoff

    monkeypatch.setattr(executor, "run_grid", run_grid)
    with jax.transfer_guard("disallow"), pytest.raises(Handoff):
        grid([7, 8])
    assert seen["n_runs"] == n_runs
    for leaf in jax.tree_util.tree_leaves((seen["batched"],
                                           seen["shared"])):
        assert not isinstance(leaf, jax.Array)
    assert [type(x) for x in seen["shared"][:4]] == [np.float32] * 4
    if guarded:
        assert isinstance(seen["shared"][4], np.ndarray)  # guard vector
    else:
        assert len(seen["shared"]) == 4
