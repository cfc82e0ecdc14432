"""Fused closed-loop simulation engine (paper Figs. 5-7 at fleet scale).

The paper's evaluation is thousands of closed-loop runs sweeping the
degradation grid eps across clusters and seeds. `NRM.run_simulated` used
to drive ONE run as a Python while-loop with per-step jit dispatch; this
module fuses the whole loop — plant dynamics (Eq. 3 + noise), heartbeat
aggregation over the control window (Eq. 1 median), and the power-policy
command (`repro.core.policies`: Eq. 4 PI / RLS-adaptive PI by default,
offline-RL and duty-cycle policies as drop-in scan citizens) — into a
single `lax.scan` step. Plant, gain and policy parameters enter the
compiled function as traced arrays, so ONE compilation (keyed only by
the scan length, the trace/summary mode and the policy branch set)
serves every profile, epsilon, seed and policy hyperparameter; a
heterogeneous policy list dispatches through one `lax.switch` engine.

Entry points:

* `simulate_closed_loop(profile, ...)` — one run; trimmed numpy traces
  compatible with the old `NRM.run_simulated` return value. Pass
  `adaptive=RLSConfig(...)` to run RLS gain scheduling inside the scan.
* `sweep(profiles, epsilons, seeds, ...)` — profiles x epsilons
  [x policies] x seeds grid, flattened to per-run rows and run through
  `repro.core.executor` (one chunk unless ``chunk_size=`` cuts it); the
  substrate for Fig. 6/7, paper-scale (30-rep, full eps-grid) sweeps and
  adaptive hyperparameter grids in CI-feasible time.
* `engine_step(...)` — the fused single-period step, reused by
  `repro.core.hierarchy` (vmapped over fleet nodes) so fleet runs share
  this engine's compiled dynamics instead of duplicating them.
* `replay_model(profile, pcaps, dt)` — deterministic Eq. 3 replay (the
  Fig. 5 model-accuracy baseline).

Runs finish by early-exit-by-mask: once accumulated work reaches
`total_work` the carried state freezes and the remaining scan steps are
no-ops; the `valid` trace marks live steps.

Trace-free summary mode: with `collect_traces=False` the scan emits no
per-step outputs; instead the carry reduces them online (live-step
count, progress/power first and second moments, progress and cap
histograms). Memory drops from O(P*E*S*T) to O(P*E*S), which is what
makes 100k-run sweeps feasible; `hist_quantile` turns the carried
histograms into median/p95-style statistics. Every run also carries
these summaries in full-trace mode, so the two modes are directly
comparable (tests assert consistency).

Heartbeats: the sim path synthesizes n ~ Poisson(rate * dt) evenly
spaced beats per control period (exactly what `NRM.run_simulated` fed
the `HeartbeatAggregator`), so Eq. 1's median over the half-open window
has a closed form: n - 1 equal in-window rates of n/dt plus one anchor
rate spanning the window edge — see `_window_median`.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import os
from pathlib import Path
from typing import Dict, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import executor
from repro.core import faults as flt
from repro.core import plane
from repro.obs import events as evt
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.core import policies as pol
from repro.core.adaptive import (RLSConfig, RLSState, rls_init, rls_pack,
                                 rls_unpack, rls_values)
from repro.core.controller import PIGains, PIState
from repro.core.plant import (PROFILE_FIELDS, PROFILES, PlantProfile,
                              PlantState, pcap_linearize, plant_init,
                              plant_step, simulate)
from repro.core.policies.pi import (PI_RLS_HI, PI_RLS_LO, PIPolicy,
                                    pi_pack)
from repro.core.workloads.detect import (DET_N_DETECT, DET_STATE_DIM,
                                         DetectorConfig, detect_init,
                                         detector_values)
from repro.core.workloads.schedule import (PhaseSchedule, ScheduleValues,
                                           active_profile, chain_rows)

logger = logging.getLogger("repro.core.sim")


def enable_compilation_cache() -> None:
    """Turn on XLA's persistent compilation cache so the scan engine
    compiles once per machine, not once per process. Where
    $JAX_COMPILATION_CACHE_DIR is set, JAX keeps the cache there and no
    other location is set here; otherwise it is the checkout's fixed
    ``experiments/xla_cache`` (the path is part of the cache key, so it
    never moves). Called by tests/conftest.py, benchmarks/run.py and
    chip_smoke.py. Safe to call repeatedly."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = Path(__file__).resolve().parents[3] / "experiments" \
            / "xla_cache"
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


_BUCKETS_SEEN: set = set()


def _bucket_steps(n: int) -> int:
    """Round the scan length up to a power of two (min 256). Frozen steps
    after completion are no-ops, and `max_time` is enforced by a traced
    mask, so the only effect is that compiled engines are shared across
    nearby horizons (and across processes via the persistent cache).

    Crossing into a bucket this process has not used yet triggers a
    fresh trace/compile; that is logged ONCE per new bucket so silent
    recompiles show up in benchmark output instead of masquerading as a
    slow sweep."""
    b = 256
    while b < n:
        b *= 2
    if b not in _BUCKETS_SEEN:
        if _BUCKETS_SEEN:
            logger.warning(
                "scan horizon %d steps crosses into new length bucket %d "
                "(buckets used so far: %s): the first call in this bucket "
                "traces/compiles a fresh engine", n, b,
                sorted(_BUCKETS_SEEN))
        _BUCKETS_SEEN.add(b)
    return b

# Canonical packing order for traced plant / gain parameters. The plant
# order is owned by repro.core.plant (PROFILE_FIELDS); the gain order by
# repro.core.plane (GAIN_FIELDS, shared with the control-plane service
# tick) — re-exported here under the historical names.
_PROFILE_FIELDS = PROFILE_FIELDS
_GAIN_FIELDS = plane.GAIN_FIELDS
gains_values = plane.gains_values
_unpack_gains = plane.unpack_gains

# Online-summary histogram resolution. Progress bins span
# [0, PROG_HIST_SPAN * K_L] (noise can push progress above K_L); cap bins
# span the actuator range [pcap_min, pcap_max].
PROG_BINS = 64
CAP_BINS = 32
PROG_HIST_SPAN = 1.5


def profile_values(profile: PlantProfile) -> np.ndarray:
    """Pack a profile into the canonical (len(PROFILE_FIELDS),) f32 host
    vector, which the engines trace."""
    return np.asarray([getattr(profile, f) for f in _PROFILE_FIELDS],
                      np.float32)


def _unpack_profile(vals) -> PlantProfile:
    kw = {f: vals[i] for i, f in enumerate(_PROFILE_FIELDS)}
    return PlantProfile(name="_traced", **kw)


def _resolve(profile: Union[str, PlantProfile]) -> PlantProfile:
    return PROFILES[profile] if isinstance(profile, str) else profile


def _window_median(n, anchor_gap, has_anchor, dt):
    """Closed-form Eq. 1 median for n evenly spaced beats in one period.

    The window holds n beats at spacing dt/n; the first interval reaches
    back to the previous window's last beat (`anchor_gap` before the
    window start), so the rate multiset is {rate_first} + (n-1) x {n/dt}.
    With no anchor (no beat has ever fired) the first interval is
    undefined and the multiset is just (n-1) x {n/dt}.
    """
    nf = jnp.maximum(n.astype(jnp.float32), 1.0)
    r = n.astype(jnp.float32) / dt
    first_int = anchor_gap + 0.5 * dt / nf
    r_first = 1.0 / jnp.maximum(first_int, 1e-9)
    with_anchor = jnp.where(n >= 3, r,
                            jnp.where(n == 2, 0.5 * (r + r_first),
                                      jnp.where(n == 1, r_first, 0.0)))
    no_anchor = jnp.where(n >= 2, r, 0.0)
    return jnp.where(has_anchor, with_anchor, no_anchor)


class _Summary(NamedTuple):
    """Online per-run reductions carried through the scan (the trace-free
    summary mode's entire output; also carried in full-trace mode so the
    two modes stay comparable). `count` is the number of accumulated
    steps — live steps past the summary warmup — and the normalizer for
    the moments."""
    count: jnp.ndarray
    progress_sum: jnp.ndarray
    progress_sq_sum: jnp.ndarray
    power_sum: jnp.ndarray
    progress_hist: jnp.ndarray  # (PROG_BINS,)
    pcap_hist: jnp.ndarray      # (CAP_BINS,)


def _summary_init() -> _Summary:
    return _Summary(count=jnp.float32(0.0),
                    progress_sum=jnp.float32(0.0),
                    progress_sq_sum=jnp.float32(0.0),
                    power_sum=jnp.float32(0.0),
                    progress_hist=jnp.zeros((PROG_BINS,), jnp.float32),
                    pcap_hist=jnp.zeros((CAP_BINS,), jnp.float32))


def _hist_add(hist, x, lo, hi, nbins, live):
    idx = jnp.clip(((x - lo) / (hi - lo) * nbins).astype(jnp.int32),
                   0, nbins - 1)
    return hist.at[idx].add(live)


class _Carry(NamedTuple):
    plant: PlantState
    pol: jnp.ndarray         # packed policy state (POLICY_STATE_DIM,)
    pcap: jnp.ndarray        # command applied next period [W]
    anchor_gap: jnp.ndarray  # time from last beat to window start [s]
    has_anchor: jnp.ndarray  # bool: any beat ever fired
    t: jnp.ndarray           # simulated time [s]
    steps: jnp.ndarray       # live (pre-completion) step count
    done: jnp.ndarray        # bool: total_work reached
    summ: _Summary
    # packed change-point detector state (DET_STATE_DIM,), or None when
    # no detector runs — None has no pytree leaves, so detector-free
    # carries keep the exact pre-detector structure (and compiled graph)
    det: Optional[jnp.ndarray] = None
    # packed fault-injection state (faults.FAULT_STATE_DIM,) when a
    # FaultSchedule runs, else None; same None-has-no-leaves contract,
    # so fault-free carries keep the exact pre-faults structure
    fstate: Optional[jnp.ndarray] = None
    # packed guard state (faults.GUARD_STATE_DIM,) when the guarded
    # degradation layer runs, else None
    guard: Optional[jnp.ndarray] = None
    # packed flight-recorder ring (repro.obs.events layout) when event
    # recording is on, else None — same None-has-no-leaves contract, so
    # recorder-off carries keep the exact pre-recorder structure (and
    # compiled graph / bitstream)
    events: Optional[jnp.ndarray] = None


# state-vector slots of the PI branches; repro.core.policies.pi owns the
# layout ([0]=prev_error [1]=prev_pcap_l [RLS_LO:RLS_HI]=packed RLSState)
_PI_RLS_LO, _PI_RLS_HI = PI_RLS_LO, PI_RLS_HI


def _default_init(profile: PlantProfile, gains: PIGains,
                  policy=("pi",), policy_vals=None, schedule=None,
                  det_vals=None, faults=None, guard=None,
                  n_events: int = 0) -> _Carry:
    if policy_vals is None:
        policy_vals = jnp.zeros((pol.POLICY_PARAM_DIM,), jnp.float32)
    # a scheduled run starts in its phase-0 plant (the base profile only
    # provides the actuator/design context)
    plant_prof = (profile if schedule is None
                  else _unpack_profile(active_profile(schedule,
                                                      jnp.float32(0.0))[0]))
    return _Carry(plant=plant_init(plant_prof),
                  pol=pol.branch_init(policy)(policy_vals, gains),
                  pcap=jnp.float32(profile.pcap_max),
                  anchor_gap=jnp.float32(0.0),
                  has_anchor=jnp.array(False),
                  t=jnp.float32(0.0),
                  steps=jnp.int32(0),
                  done=jnp.array(False),
                  summ=_summary_init(),
                  det=(None if det_vals is None
                       else detect_init(det_vals, gains)),
                  fstate=(None if faults is None
                          else flt.fault_state_init(profile)),
                  guard=(None if guard is None else flt.guard_init()),
                  events=(evt.ring_init(n_events) if n_events else None))


def resume_init(plant: PlantState, pi: PIState, pcap,
                rls: Optional[RLSState] = None,
                policy_state=None, det_state=None, t0=0.0,
                fault_state=None, guard_state=None,
                event_state=None) -> _Carry:
    """Carry that resumes a run from existing plant/controller (and
    optionally RLS estimator) state — the NRM delegation path; the
    heartbeat window and the per-run summaries start fresh. Pass
    ``policy_state`` (a packed (POLICY_STATE_DIM,) vector from
    `SimResult.policy_state`) to resume a non-PI policy; otherwise the
    PI/RLS states are packed into the PI branch's layout. ``det_state``
    (a packed (DET_STATE_DIM,) vector from `SimResult.detector_state`)
    resumes the change-point detector. ``event_state`` (the packed ring
    from `SimResult.event_state`) resumes the flight recorder: the next
    segment keeps appending where the previous one stopped, so the
    monotonic event total and the surviving incident history span the
    whole resumed run.

    ``t0`` sets the carried sim-time the segment starts at. It defaults
    to 0 (each segment gets its own `max_time` budget — the NRM path),
    but a WORKLOAD-scripted run gathers its active phase by this clock:
    pass the previous segment's `exec_time` so the schedule continues
    instead of restarting at phase 0 (note `max_time` is then measured
    on the same absolute clock)."""
    if policy_state is None:
        vec = pi_pack(pi, None if rls is None else rls_pack(rls))
        vec = vec.at[pol.BRANCH_TAG_SLOT].set(float(pol.branch_tag(
            "pi_rls" if rls is not None else "pi")))
    else:
        vec = jnp.asarray(policy_state, jnp.float32)
    return _Carry(plant=plant, pol=vec, pcap=jnp.float32(pcap),
                  anchor_gap=jnp.float32(0.0),
                  has_anchor=jnp.array(False),
                  t=jnp.float32(t0),
                  steps=jnp.int32(0),
                  done=jnp.array(False),
                  summ=_summary_init(),
                  det=(None if det_state is None
                       else jnp.asarray(det_state, jnp.float32)),
                  fstate=(None if fault_state is None
                          else jnp.asarray(fault_state, jnp.float32)),
                  guard=(None if guard_state is None
                         else jnp.asarray(guard_state, jnp.float32)),
                  events=(None if event_state is None
                          else jnp.asarray(event_state, jnp.float32)))


def engine_step(profile: PlantProfile, gains: PIGains, c: _Carry,
                total_work, max_time, dt, key, *, policy=("pi",),
                policy_vals=None, cap_limit=None, summary_from=0.0,
                schedule=None, detector=None, faults=None, guard=None):
    """One fused control period: plant (Eq. 3) -> heartbeat median
    (Eq. 1) -> power-policy command (Eq. 4 PI by default), with
    early-exit-by-mask freezing and online summary reduction.

    The controller is dispatched through the `repro.core.policies`
    contract: ``policy`` is a branch-name tuple (static; more than one
    name switches on the traced kind in ``policy_vals[0]``) or a Policy
    instance, and ``policy_vals`` the packed traced hyperparameters.

    Pure and vmap/scan-safe; `repro.core.hierarchy` vmaps it over fleet
    nodes with `cap_limit` carrying the cluster-level budget allocation
    (the applied command is min(policy command, allocation)).
    `summary_from` (traced) excludes the first steps — the descent
    transient — from the online summary reductions (never from
    time/energy/work).

    ``schedule`` (a traced `ScheduleValues`, or None) makes the PLANT
    time-varying: the active segment's parameters are gathered by the
    carried sim-time each period, while gains/actuator context stay the
    base design's — the phased-workload scenario. ``detector`` (traced
    `detector_values`, or None) runs the Page-Hinkley change-point
    detector on progress-model residuals; an alarm applies the policy's
    `on_change` hook (e.g. RLS covariance reset) and is exposed via
    `PolicyObs.phase_change` and the `phase_change` trace. Both default
    to None, which leaves the static-profile graph byte-identical to the
    pre-phases engine.

    ``faults`` (traced `repro.core.faults.FaultValues`, or None) scripts
    telemetry/actuator failures: heartbeat dropout/staleness, meter
    freeze/bias/spike, stuck/quantized/delayed caps and tenant crashes.
    Sensor channels corrupt only what the controller OBSERVES (the
    plant's work/energy integrals stay truthful; the summary accumulates
    true power, the trace records the observed reading); the fault RNG
    folds off the period key, so a ``faults=None`` run keeps the exact
    pre-faults graph and bitstream. ``guard`` (traced
    `faults.guard_values`, or None) arms the guarded-degradation layer
    inside `plane_step` — stale-signal watchdog, sentinels, divergence
    rollback; every trigger is `where(trigger, ..., clean)`, so an
    untriggered guarded step matches the unguarded one bit-for-bit.

    Returns (new_carry, out) where out holds this period's trace row.
    """
    if policy_vals is None:
        policy_vals = jnp.zeros((pol.POLICY_PARAM_DIM,), jnp.float32)
    if schedule is None:
        plant_prof, phase_idx = profile, None
    else:
        vals, phase_idx = active_profile(schedule, c.t)
        plant_prof = _unpack_profile(vals)
    kplant, khb = jax.random.split(key)
    if faults is not None:
        # the fault stream folds off the PERIOD key, so kplant/khb — and
        # with them every clean trajectory — stay untouched
        kfault = jax.random.fold_in(key, 7)
        af = flt.fault_channels(faults, c.t)
        applied = flt.apply_actuator(af, c.fstate, c.pcap,
                                     plant_prof.pcap_min)
    else:
        applied = c.pcap
    plant_s, meas = plant_step(plant_prof, c.plant, applied, dt, kplant)
    t = c.t + dt
    if faults is not None:
        crash = af.crash > 0
        idle = plant_prof.power_of_pcap(plant_prof.pcap_min)
        # a crashed tenant does no work and burns idle power; progress_l
        # pins to -K_L (true progress 0) so the restart comes up cold
        plant_s = PlantState(
            progress_l=jnp.where(crash, -plant_prof.K_L,
                                 plant_s.progress_l),
            dropped=plant_s.dropped,
            energy=jnp.where(crash, c.plant.energy + idle * dt,
                             plant_s.energy),
            work=jnp.where(crash, c.plant.work, plant_s.work))
        true_power = jnp.where(crash, idle, meas["power"])
    # synthesize heartbeats at the measured rate (Eq. 1 input)
    n = jax.random.poisson(khb, jnp.maximum(meas["progress"], 0.0) * dt)
    if faults is not None:
        # dropout thins the window deterministically (floor of the kept
        # fraction); a crashed tenant emits no beats at all
        nf = jnp.floor(n.astype(jnp.float32)
                       * (1.0 - jnp.clip(af.hb_drop, 0.0, 1.0)))
        n = jnp.where(af.hb_drop > 0, nf.astype(n.dtype), n)
        n = jnp.where(crash, jnp.zeros_like(n), n)
    progress = _window_median(n, c.anchor_gap, c.has_anchor, dt)
    anchor_gap = jnp.where(n > 0,
                           0.5 * dt / jnp.maximum(
                               n.astype(jnp.float32), 1.0),
                           c.anchor_gap + dt)
    has_anchor = c.has_anchor | (n > 0)
    if faults is not None:
        # sensor-side corruption: what the CONTROLLER observes (the
        # plant integrals above stay truthful)
        prog_obs = jnp.where(af.hb_stale > 0,
                             c.fstate[flt.F_LAST_PROGRESS], progress)
        pw = jnp.where(af.meter_freeze > 0,
                       c.fstate[flt.F_LAST_POWER], true_power)
        pw = pw + af.meter_bias
        spike = jax.random.uniform(kfault) < af.meter_spike_p
        spike_v = jnp.where(af.meter_spike_v != 0.0, af.meter_spike_v,
                            jnp.float32(jnp.nan))
        power_obs = jnp.where(spike, spike_v, pw)
        fstate_n = jnp.stack([
            prog_obs,
            jnp.where(af.meter_freeze > 0,
                      c.fstate[flt.F_LAST_POWER], true_power),
            jnp.asarray(c.pcap, jnp.float32),
            jnp.asarray(applied, jnp.float32),
            af.crash, jnp.float32(0.0)])
        f_any = ((af.hb_drop > 0) | (af.hb_stale > 0)
                 | (af.meter_freeze > 0) | (af.meter_bias != 0)
                 | (af.meter_spike_p > 0) | (af.act_stuck_on > 0)
                 | (af.act_quant > 0) | (af.act_delay > 0)
                 | crash).astype(jnp.float32)
    else:
        prog_obs, power_obs = progress, meas["power"]
        fstate_n = c.fstate

    # the control plane's single control-law code path: detector
    # residual against the design model's replay of the APPLIED cap,
    # alarm -> the policy's on_change reaction, then the policy step
    # (repro.core.plane owns this section; the NRM runtime and the
    # multi-tenant service tick call the same function). The controller
    # sees the OBSERVED telemetry — identical to the measured values
    # when faults is None.
    if guard is None:
        pol_s, det_s, pcap, change = plane.plane_step(
            gains, policy, policy_vals, c.pol, c.pcap, prog_obs,
            power_obs, dt, det_vals=detector, det_state=c.det)
        guard_s, gmode = c.guard, None
    else:
        (pol_s, det_s, pcap, change, guard_s,
         gmode) = plane.plane_step(
            gains, policy, policy_vals, c.pol, c.pcap, prog_obs,
            power_obs, dt, det_vals=detector, det_state=c.det,
            guard_vals=guard, guard_state=c.guard)
    if cap_limit is not None:
        pcap = jnp.minimum(pcap, cap_limit)

    # early-exit-by-mask: freeze everything once done
    frz = lambda new, old: jax.tree_util.tree_map(
        lambda a, b: jnp.where(c.done, b, a), new, old)
    plant_s = frz(plant_s, c.plant)
    pol_s = frz(pol_s, c.pol)
    det_s = frz(det_s, c.det)
    guard_s = frz(guard_s, c.guard)
    fstate_n = frz(fstate_n, c.fstate)
    pcap = jnp.where(c.done, c.pcap, pcap)
    anchor_gap = jnp.where(c.done, c.anchor_gap, anchor_gap)
    has_anchor = jnp.where(c.done, c.has_anchor, has_anchor)
    t = jnp.where(c.done, c.t, t)
    progress = jnp.where(c.done, 0.0, prog_obs)
    power = jnp.where(c.done, 0.0,
                      meas["power"] if faults is None else true_power)
    change = jnp.where(c.done, 0.0, change) if detector is not None \
        else change

    acc = ((~c.done) & (c.steps.astype(jnp.float32) >= summary_from)
           ).astype(jnp.float32)
    summ = _Summary(
        count=c.summ.count + acc,
        progress_sum=c.summ.progress_sum + acc * progress,
        progress_sq_sum=c.summ.progress_sq_sum
        + acc * progress * progress,
        power_sum=c.summ.power_sum + acc * power,
        progress_hist=_hist_add(c.summ.progress_hist, progress,
                                0.0, PROG_HIST_SPAN * profile.K_L,
                                PROG_BINS, acc),
        pcap_hist=_hist_add(c.summ.pcap_hist, pcap, profile.pcap_min,
                            profile.pcap_max, CAP_BINS, acc))

    done = (c.done | (plant_s.work >= total_work)
            | (t >= max_time - 1e-6))
    out = {"t": t, "progress": progress, "pcap": pcap,
           "power": power, "energy": plant_s.energy,
           "work": plant_s.work, "valid": ~c.done}
    if faults is not None:
        # the trace keeps the OBSERVED reading (what the controller was
        # fed); the summary above accumulated the true one
        out["power"] = jnp.where(c.done, 0.0, power_obs)
        out["fault_active"] = jnp.where(c.done, 0.0, f_any)
    if guard is not None:
        out["guard_mode"] = jnp.where(c.done, 0.0, gmode)
    if schedule is not None:
        out["phase"] = jnp.where(c.done, -1, phase_idx)
    if detector is not None:
        out["phase_change"] = change
    out.update(pol.branch_extras(policy)(pol_s))

    # flight recorder: edge-triggered appends into the carried ring.
    # Every append is gated on the live mask (and the whole block on the
    # ring being carried at all), so recorder-off runs keep the exact
    # pre-recorder graph and a frozen run's ring stays untouched.
    ev = c.events
    if ev is not None:
        live = ~c.done
        if schedule is not None:
            prev_phase = ev[evt.H_PREV_PHASE]
            phase_f = phase_idx.astype(jnp.float32)
            ev = evt.ring_append(
                ev, live & (prev_phase >= 0) & (phase_f != prev_phase),
                c.t, evt.EV_PHASE_FLIP, evt.SRC_SCHEDULE,
                prev_phase, phase_f)
            ev = ev.at[evt.H_PREV_PHASE].set(
                jnp.where(live, phase_f, prev_phase))
        if faults is not None:
            prev_f = ev[evt.H_PREV_FAULT]
            ev = evt.ring_append(ev, live & (f_any > 0) & (prev_f <= 0),
                                 t, evt.EV_FAULT_ENTER, evt.SRC_FAULTS,
                                 af.crash, af.hb_drop, af.meter_freeze)
            ev = evt.ring_append(ev, live & (f_any <= 0) & (prev_f > 0),
                                 t, evt.EV_FAULT_EXIT, evt.SRC_FAULTS)
            ev = ev.at[evt.H_PREV_FAULT].set(
                jnp.where(live, f_any, prev_f))
        if detector is not None:
            ev = evt.ring_append(ev, live & (change > 0), t,
                                 evt.EV_DETECTOR_ALARM, evt.SRC_DETECTOR,
                                 progress, pcap)
        if guard is not None:
            prev_mode = c.guard[flt.G_MODE]
            stale = guard_s[flt.G_STALE]
            ev = evt.ring_append(
                ev, live & (gmode >= flt.GUARD_HOLD)
                & (prev_mode < flt.GUARD_HOLD),
                t, evt.EV_GUARD_HOLD, evt.SRC_GUARD, stale, pcap)
            ev = evt.ring_append(
                ev, live & (gmode >= flt.GUARD_FAILSAFE)
                & (prev_mode < flt.GUARD_FAILSAFE),
                t, evt.EV_GUARD_FAILSAFE, evt.SRC_GUARD, stale, pcap,
                guard_s[flt.G_N_INVALID])
            ev = evt.ring_append(
                ev, live & (gmode < flt.GUARD_HOLD)
                & (prev_mode >= flt.GUARD_HOLD),
                t, evt.EV_GUARD_RECOVER, evt.SRC_GUARD, prev_mode, pcap)
            ev = evt.ring_append(
                ev, live & (guard_s[flt.G_N_RESETS]
                            > c.guard[flt.G_N_RESETS]),
                t, evt.EV_RECOVERY_RESET, evt.SRC_GUARD,
                guard_s[flt.G_N_RESETS], pcap)
    return _Carry(plant_s, pol_s, pcap, anchor_gap, has_anchor, t,
                  c.steps + (~c.done).astype(jnp.int32), done, summ,
                  det_s, fstate_n, guard_s, ev), out


def _scan_core(max_steps: int, collect: bool = True,
               branches=("pi",), n_events: int = 0):
    """Pure closed-loop run: (profile_vals, gains_vals, policy_vals,
    sched, det_vals, fvals, gvals, init|None, total_work, max_time, dt,
    summary_from, key) -> (traces|None, final_carry). The policy branch
    set is static (part of the jit key); its hyperparameters ride in the
    traced policy_vals. ``sched``/``det_vals``/``fvals``/``gvals`` are
    None (static plant, no detector, no faults, no guard — the
    pre-existing graph, byte-identical) or traced `ScheduleValues` /
    detector / `FaultValues` / guard parameter vectors; jit separates
    the variants by pytree structure. ``n_events`` > 0 arms the flight
    recorder with that many ring slots (static: the ring shape keys the
    jit cache; 0 keeps the recorder-free carry).

    Trace mode (``collect``) scans all ``max_steps`` periods, one trace
    row each. Summary mode runs a `lax.while_loop` that stops once the
    run is done: step i still takes period key i of the same split, and
    a done run's step leaves its carry as it is, so the final carry is
    the scan's. Under `jax.vmap` the loop runs while any run of the
    batch is live."""

    def run(profile_vals, gains_vals, policy_vals, sched, det_vals,
            fvals, gvals, init: Optional[_Carry], total_work, max_time,
            dt, summary_from, key):
        profile = _unpack_profile(profile_vals)
        gains = _unpack_gains(gains_vals)
        carry0 = (_default_init(profile, gains, branches, policy_vals,
                                sched, det_vals, fvals, gvals, n_events)
                  if init is None else init)

        def body(c: _Carry, k):
            c2, out = engine_step(profile, gains, c, total_work,
                                  max_time, dt, k, policy=branches,
                                  policy_vals=policy_vals,
                                  summary_from=summary_from,
                                  schedule=sched, detector=det_vals,
                                  faults=fvals, guard=gvals)
            return c2, (out if collect else None)

        keys = jax.random.split(key, max_steps)
        if collect:
            final, traces = jax.lax.scan(body, carry0, keys)
            return traces, final

        # keys[i] read word by word: vmapped, step i is then two gathers
        # from dense (runs, max_steps) arrays, where the TPU would pad a
        # (runs, max_steps, 2) array's 2 words to a 128-word tile
        hi, lo = keys[:, 0], keys[:, 1]

        def live(s):
            i, c = s
            return (i < max_steps) & ~c.done

        def step(s):
            i, c = s
            return i + 1, body(c, jnp.stack([hi[i], lo[i]]))[0]

        _, final = jax.lax.while_loop(live, step, (jnp.int32(0), carry0))
        return None, final

    return run


# `init` is a pytree (or None); jit caches on its structure, so fresh and
# resumed variants trace separately (likewise schedule/detector None vs
# traced arrays). The branch tuple keys the policy's static compute
# graph; all its hyperparameters are traced.
@functools.lru_cache(maxsize=None)
def _jit_run(max_steps: int, collect: bool = True, branches=("pi",),
             n_events: int = 0):
    return jax.jit(_scan_core(max_steps, collect, branches,
                              n_events=n_events))


# ---- grid engines, run by the executor (chunked / sharded / donated) ------

@functools.lru_cache(maxsize=None)
def _flat_core(max_steps: int, branches, collect: bool, scheduled: bool,
               detected: bool, guarded: bool, n_events: int):
    """The scan engine of every `sweep` grid: ONE vmap over per-run rows
    (a dict of (N, ...) leaves: plant, gains, policy values, key, and
    the schedule / detector / fault rows when present). Every run's
    parameters and key ride in its own row, so any slice of the
    flattened grid computes the same per-run model. The guard parameter
    vector is grid-wide, so it rides the shared argument tail
    (``guarded`` selects the variant). ``scheduled`` and ``detected``
    only key the cache: the rows' pytree structure already separates
    those traces. In summary mode the batch's loop stops when every run
    of it is done (`_scan_core`); `sweep` counts the steps it ran in
    ``sweep_scan_steps_total``."""
    run = _scan_core(max_steps, collect, branches, n_events)

    def sweep_scan(batched, total_work, max_time, dt, summary_from, *rest):
        gvl = rest[0] if guarded else None

        def one(b):
            return run(b["prof"], b["gains"], b["pvals"],
                       b.get("sched"), b.get("det"), b.get("faults"),
                       gvl, None, total_work, max_time, dt,
                       summary_from, b["key"])

        return jax.vmap(one)(batched)

    return sweep_scan  # the module reads jit_sweep_scan in a profile


@functools.lru_cache(maxsize=None)
def _flat_core_pallas(collect: bool, block_b: int = 128,
                      chunk_t: int = 64, use_ref: bool = False):
    """The Pallas closed-loop mega-kernel (`repro.kernels.closed_loop`)
    as a flat-grid engine — fixed-gain PI, static plant, no detector;
    `sweep` dispatches here only when the grid fits those capabilities.
    The op jits internally around static shapes, so the executor runs
    it with wrap='none'. ``use_ref=True`` swaps in the kernel package's
    jnp oracle (same contract, no Pallas) for A/B tests."""
    from repro.kernels.closed_loop.ops import closed_loop_sim

    def flat(batched, total_work, max_time, dt, summary_from):
        traces, fin = closed_loop_sim(
            batched["prof"], batched["gains"], batched["key"],
            total_work=float(total_work), max_time=float(max_time),
            dt=float(dt), summary_from=float(summary_from),
            collect=collect, block_b=block_b, chunk_t=chunk_t,
            use_ref=use_ref)
        if traces is not None:
            traces = {k: v.T for k, v in traces.items()}
            traces["valid"] = traces["valid"] > 0.5
        return traces, fin

    return flat


def _carry_from_kernel_final(f: Dict[str, np.ndarray]) -> _Carry:
    """Kernel-final dict (`closed_loop.ref` layout, any leading shape)
    -> the engine's `_Carry`, so both backends share one summary /
    SweepResult assembly (the packed PI slots and branch tag are
    restored, like a scan run's final carry)."""
    vec = np.zeros(f["t"].shape + (pol.POLICY_STATE_DIM,), np.float32)
    vec[..., 0] = f["prev_error"]
    vec[..., 1] = f["prev_pcap_l"]
    vec[..., pol.BRANCH_TAG_SLOT] = float(pol.branch_tag("pi"))
    return _Carry(
        plant=PlantState(progress_l=f["progress_l"],
                         dropped=f["dropped"] > 0,
                         energy=f["energy"], work=f["work"]),
        pol=vec, pcap=f["pcap"], anchor_gap=f["anchor_gap"],
        has_anchor=f["has_anchor"] > 0, t=f["t"],
        steps=f["steps"].astype(np.int32), done=f["done"] > 0,
        summ=_Summary(count=f["count"], progress_sum=f["progress_sum"],
                      progress_sq_sum=f["progress_sq_sum"],
                      power_sum=f["power_sum"],
                      progress_hist=f["progress_hist"],
                      pcap_hist=f["pcap_hist"]),
        det=None)


@functools.lru_cache(maxsize=None)
def _jit_open_loop(steps: int):
    def run(profile_vals, pcap, dt, key):
        profile = _unpack_profile(profile_vals)
        return simulate(profile, jnp.full((steps,), pcap), dt, key)

    return jax.jit(jax.vmap(run, in_axes=(None, None, None, 0)))


def _keys_are_low_words() -> bool:
    """True where ``jax.random.PRNGKey(s)`` is ``[0, s mod 2**32]``: the
    default ``threefry2x32`` implementation with 64-bit types off."""
    return (jax.config.jax_default_prng_impl == "threefry2x32"
            and not jax.config.jax_enable_x64)


def seed_keys(seeds: Sequence[int]) -> np.ndarray:
    """The ``(S, 2)`` uint32 raw keys ``jax.random.PRNGKey(s)`` gives for
    each seed, built on the host in one step where the JAX setting allows
    (one eager device dispatch per seed otherwise). Ints outside int64
    raise ``OverflowError`` either way."""
    seeds = [int(s) for s in seeds]
    if not _keys_are_low_words():
        return np.stack([np.asarray(jax.random.PRNGKey(s)) for s in seeds])
    low = np.asarray(seeds, np.int64).astype(np.uint32)
    return np.stack([np.zeros_like(low), low], axis=1)


def open_loop_runs(profile: Union[str, PlantProfile], steps: int,
                   seeds: Sequence[int], pcap: Optional[float] = None,
                   dt: float = 1.0) -> dict:
    """Constant-cap open-loop runs vmapped over seeds (the uncontrolled
    full-power baseline of Fig. 7). One compile per trace length, shared
    across profiles."""
    profile = _resolve(profile)
    pcap = profile.pcap_max if pcap is None else pcap
    keys = seed_keys(seeds)
    return _jit_open_loop(int(steps))(profile_values(profile),
                                      jnp.float32(pcap), jnp.float32(dt),
                                      keys)


def _hist_edges(profile: PlantProfile) -> Dict[str, np.ndarray]:
    return {
        "progress_edges": np.linspace(0.0, PROG_HIST_SPAN * profile.K_L,
                                      PROG_BINS + 1, dtype=np.float32),
        "pcap_edges": np.linspace(profile.pcap_min, profile.pcap_max,
                                  CAP_BINS + 1, dtype=np.float32),
    }


def hist_quantile(hist, edges, q: float = 0.5) -> np.ndarray:
    """Quantile estimate from an online histogram (bin-center rule).

    `hist` has shape (..., N); `edges` is (N+1,) or (P, N+1) with P
    matching hist's leading axis (the sweep's profile axis). Accurate to
    half a bin width — PROG_HIST_SPAN*K_L/PROG_BINS for progress.

    Edge cases: an all-empty histogram yields NaN; q=0 / q=1 return the
    centers of the lowest / highest occupied bins (a single-count
    histogram therefore answers that bin for every q)."""
    hist = np.asarray(hist, np.float64)
    edges = np.asarray(edges, np.float64)
    centers = 0.5 * (edges[..., :-1] + edges[..., 1:])
    if centers.ndim == 2:  # per-profile edges -> broadcast over inner axes
        centers = centers.reshape(
            (centers.shape[0],) + (1,) * (hist.ndim - 2)
            + (centers.shape[-1],))
    c = hist.cumsum(-1)
    total = c[..., -1:]
    # strictly positive threshold so q=0 lands on the first OCCUPIED bin
    # (empty leading bins satisfy c >= 0 but not c >= tiny)
    thresh = np.maximum(q * total, np.finfo(np.float64).tiny)
    idx = (c >= thresh).argmax(-1)
    out = np.take_along_axis(np.broadcast_to(centers, hist.shape),
                             idx[..., None], -1)[..., 0]
    return np.where(total[..., 0] > 0, out, np.nan)


def _summary_dict(final: _Carry, edges: Dict[str, np.ndarray]) -> Dict:
    n = jnp.maximum(final.summ.count, 1.0)
    mean = final.summ.progress_sum / n
    var = jnp.maximum(final.summ.progress_sq_sum / n - mean * mean, 0.0)
    return {"progress_mean": mean,
            "progress_std": jnp.sqrt(var),
            "power_mean": final.summ.power_sum / n,
            "progress_hist": final.summ.progress_hist,
            "pcap_hist": final.summ.pcap_hist,
            **edges}


@dataclasses.dataclass(frozen=True)
class SimResult:
    """One closed-loop run, trimmed to the completed steps."""
    traces: Dict[str, np.ndarray]  # t, progress, pcap, power, energy, work
    exec_time: float
    energy: float
    work: float
    completed: bool
    n_steps: int
    pi_state: Optional[PIState]  # None for non-PI policies
    plant_state: PlantState
    pcap: float
    summary: Dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)
    rls_state: Optional[RLSState] = None  # final estimator (adaptive runs)
    # final packed policy state (resume via resume_init(policy_state=...))
    policy_state: Optional[np.ndarray] = None
    # final packed change-point detector state (detector= runs); resume
    # via resume_init(det_state=...). n_phase_changes is its alarm count.
    detector_state: Optional[np.ndarray] = None
    # final packed fault-injection state (faults= runs); resume via
    # resume_init(fault_state=...)
    fault_state: Optional[np.ndarray] = None
    # final packed guard state (guard= runs; faults.G_* slots carry the
    # watchdog counters); resume via resume_init(guard_state=...)
    guard_state: Optional[np.ndarray] = None
    # flight-recorder timeline (record_events= runs): decoded typed
    # records, oldest surviving first (see repro.obs.events)
    events: Optional[list] = None
    # the packed ring itself; resume via resume_init(event_state=...)
    event_state: Optional[np.ndarray] = None

    @property
    def n_events_total(self) -> int:
        """Monotonic count of every event appended (incl. evicted)."""
        return (0 if self.event_state is None
                else evt.ring_total(self.event_state))

    @property
    def n_phase_changes(self) -> int:
        return (0 if self.detector_state is None
                else int(self.detector_state[DET_N_DETECT]))


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Batched runs over profiles x epsilons [x policies] [x workloads]
    x seeds.

    Trace arrays have shape (..., T) where ... is (P, E, S) — or
    (P, E, A, S) for policy grids, (P, E, A, W, S) with a workload
    axis — with the P (and A, W) axes squeezed away when a single
    profile (single Policy, single PhaseSchedule) was passed. Frozen
    (post-completion) steps carry `valid == False`. In summary mode
    (`collect_traces=False`) `traces` is None and only `summary` (plus
    the scalar reductions) is materialized: O(grid) memory, not
    O(grid * T)."""
    traces: Optional[Dict[str, jnp.ndarray]]
    exec_time: jnp.ndarray
    energy: jnp.ndarray
    work: jnp.ndarray
    completed: jnp.ndarray
    n_steps: jnp.ndarray
    summary: Dict[str, jnp.ndarray] = dataclasses.field(
        default_factory=dict)
    # per-run change-point alarm counts (detector= sweeps), else None
    detections: Optional[jnp.ndarray] = None
    # per-run final guard state (..., GUARD_STATE_DIM) for guard= sweeps
    # (faults.G_N_FAILSAFE / G_N_INVALID etc. are the fig9 metrics),
    # else None
    guard_state: Optional[jnp.ndarray] = None
    # per-run packed flight-recorder rings (..., ring_dim) for
    # record_events= sweeps, else None; decode one run with
    # repro.obs.events.decode_ring or the whole grid with decode_grid
    events: Optional[jnp.ndarray] = None

    def masked_mean(self, key: str) -> np.ndarray:
        """Per-run mean of a trace over its live steps. For 'progress'
        and 'power' in summary mode use summary['progress_mean'] /
        summary['power_mean'] instead."""
        if self.traces is None:
            raise ValueError(
                "no traces collected (summary mode); use "
                "summary['progress_mean'] / summary['power_mean']")
        x = np.asarray(self.traces[key])
        m = np.asarray(self.traces["valid"])
        return (x * m).sum(-1) / np.maximum(m.sum(-1), 1)


def _resolve_n_events(record_events: Union[None, bool, int]) -> int:
    """record_events= sugar -> static ring slot count (0 = recorder
    off). True picks the default ring; an int sizes it explicitly."""
    if record_events is None or record_events is False:
        return 0
    if record_events is True:
        return evt.DEFAULT_MAX_EVENTS
    n = int(record_events)
    if n < 1:
        raise ValueError(f"record_events= wants True or a positive ring "
                         f"size, got {record_events!r}")
    return n


def simulate_closed_loop(profile: Union[str, PlantProfile],
                         epsilon: Optional[float] = None, *,
                         gains: Optional[PIGains] = None,
                         total_work: float,
                         max_time: float = 3600.0,
                         dt: float = 1.0,
                         seed: int = 0,
                         key: Optional[jax.Array] = None,
                         tau_obj: float = 10.0,
                         init: Optional[_Carry] = None,
                         adaptive: Optional[RLSConfig] = None,
                         design: Optional[PlantProfile] = None,
                         policy: Optional[pol.Policy] = None,
                         collect_traces: bool = True,
                         summary_warmup: int = 0,
                         workload: Optional[PhaseSchedule] = None,
                         detector: Optional[DetectorConfig] = None,
                         faults: Optional[flt.FaultSchedule] = None,
                         guard: Union[None, bool,
                                      flt.GuardConfig] = None,
                         record_events: Union[None, bool, int] = None
                         ) -> SimResult:
    """One fully-jitted closed-loop run (drop-in for NRM.run_simulated).

    Pass either `epsilon` (gains placed from the profile's identified
    model) or explicit `gains` (e.g. designed on a different profile, as
    in the gain-shift experiments). The controller is a
    `repro.core.policies` policy — `policy=` any Policy instance
    (default: the paper's PI). `adaptive=RLSConfig(...)` is sugar for
    ``policy=PIPolicy(adaptive=...)``: the RLS estimator runs inside the
    scan, re-placing the PI gains online; `design` names the model the
    initial gains were placed on (defaults to the plant profile) — the
    estimator linearizes against it. An `init` carry built by
    `resume_init` continues a previous run (including its estimator /
    policy / detector state when `rls=` / `policy_state=` /
    `det_state=` was passed).

    ``workload=PhaseSchedule(...)`` scripts a TIME-VARYING plant: each
    phase's (duration, plant-delta) resolves against `profile` and the
    engine gathers the active segment by carried sim-time; traces gain a
    `phase` index key. ``detector=DetectorConfig(...)`` runs the online
    change-point detector on progress-model residuals (traces gain
    `phase_change`; alarms trigger the policy's `on_change` hook — the
    RLS covariance reset for adaptive PI).

    ``faults=FaultSchedule(...)`` scripts telemetry/actuator failures
    inside the scan (see `repro.core.faults`; traces gain
    `fault_active`, and `power` records the controller's corrupted
    observation while energy/work stay truthful).
    ``guard=GuardConfig(...)`` (or ``guard=True`` for the defaults)
    arms the guarded-degradation layer in `plane_step`; traces gain
    `guard_mode` and the final watchdog counters come back in
    `SimResult.guard_state`.

    ``record_events=True`` (or an int ring size) arms the in-scan flight
    recorder (`repro.obs.events`): guard transitions, detector alarms,
    recovery resets, fault windows and phase flips append timestamped
    records into a fixed ring riding the carry; `SimResult.events` is
    the decoded timeline and `SimResult.event_state` the packed ring
    for resume. Recorder-off runs are bit-for-bit the recorder-free
    engine (the ring is a None carry field with no pytree leaves)."""
    profile = _resolve(profile)
    if gains is None:
        if epsilon is None:
            raise ValueError("pass epsilon or gains")
        gains = PIGains.from_model(profile, epsilon, tau_obj)
    if policy is not None and adaptive is not None:
        raise ValueError("pass policy= or adaptive=, not both "
                         "(adaptive= is sugar for PIPolicy(adaptive=...))")
    if policy is not None and design is not None:
        raise ValueError("design= only applies to the adaptive= sugar; "
                         "give the policy its design model directly "
                         "(PIPolicy(adaptive=..., design=...))")
    if policy is None:
        policy = PIPolicy(adaptive=adaptive,
                          design=None if design is None
                          else _resolve(design))
    branch = policy.branch
    pvals = pol.policy_values(policy, profile, gains)
    if init is not None:
        # host-side resume validation/fix-ups (init is concrete here)
        src = pol.tag_branch(int(np.asarray(init.pol)[
            pol.BRANCH_TAG_SLOT]))
        if src is not None and src != branch and not (
                src == "pi" and branch == "pi_rls"):
            # the one allowed upgrade is pi -> pi_rls (fresh estimator
            # below); anything else would silently misread the slots
            raise ValueError(
                f"init policy state was produced by branch '{src}' but "
                f"this run dispatches '{branch}'; resume with the same "
                f"policy (pi state does upgrade to adaptive pi)")
        rls_block = np.asarray(init.pol[_PI_RLS_LO:_PI_RLS_HI])
        if branch == "pi_rls" and not rls_block.any():
            # resume carry predates the estimator: start a fresh one so
            # adaptive= is honoured rather than silently dropped
            fresh = rls_init(pvals[1:7], gains.k_p, gains.k_i)
            init = init._replace(pol=jnp.asarray(init.pol)
                                 .at[_PI_RLS_LO:_PI_RLS_HI]
                                 .set(rls_pack(fresh))
                                 .at[pol.BRANCH_TAG_SLOT]
                                 .set(float(pol.branch_tag("pi_rls"))))
        elif branch == "pi" and rls_block.any():
            raise ValueError("init carries RLS state but adaptive=None; "
                             "pass the RLSConfig so estimator params are "
                             "traced")
    sched = None if workload is None else workload.resolve(profile)
    det_design = _resolve(design) if design is not None else profile
    dv = (None if detector is None
          else detector_values(detector, det_design))
    if init is not None and dv is not None and init.det is None:
        # resume carry predates the detector: start a fresh one so
        # detector= is honoured rather than silently dropped
        init = init._replace(det=detect_init(dv, gains))
    elif init is not None and dv is None and init.det is not None:
        raise ValueError("init carries detector state but detector=None; "
                         "pass the DetectorConfig so its params are "
                         "traced")
    fv = None if faults is None else faults.resolve()
    gvl = (None if not guard
           else flt.guard_values(None if guard is True else guard))
    if init is not None and fv is not None and init.fstate is None:
        # resume carry predates the fault script: fresh fault state
        init = init._replace(fstate=flt.fault_state_init(profile))
    elif init is not None and fv is None and init.fstate is not None:
        raise ValueError("init carries fault state but faults=None; "
                         "pass the FaultSchedule so its rows are traced")
    if init is not None and gvl is not None and init.guard is None:
        init = init._replace(guard=flt.guard_init())
    elif init is not None and gvl is None and init.guard is not None:
        raise ValueError("init carries guard state but guard=None; "
                         "pass the GuardConfig so its params are traced")
    n_events = _resolve_n_events(record_events)
    if init is not None and n_events and init.events is None:
        # resume carry predates the recorder: start an empty ring
        init = init._replace(events=evt.ring_init(n_events))
    elif init is not None and not n_events and init.events is not None:
        raise ValueError("init carries a flight-recorder ring but "
                         "record_events=None; pass record_events so the "
                         "ring stays a carry citizen")
    elif (init is not None and init.events is not None
          and evt.ring_capacity(init.events) != n_events):
        raise ValueError(
            f"init ring has {evt.ring_capacity(init.events)} slots but "
            f"record_events={n_events}; resume with the same ring size "
            "(the ring shape keys the compiled engine)")
    max_steps = _bucket_steps(int(np.ceil(max_time / dt)))
    if key is None:
        key = jax.random.PRNGKey(seed)
    traces, final = _jit_run(max_steps, collect_traces, (branch,),
                             n_events)(
        profile_values(profile), gains_values(gains), pvals, sched, dv,
        fv, gvl, init, jnp.float32(total_work), jnp.float32(max_time),
        jnp.float32(dt), jnp.float32(summary_warmup), key)
    # device-side trim: ONE scalar (the live-step counter) decides the
    # slice, so only n real steps cross to host — not the padded buffers
    n = int(final.steps)
    trimmed = {} if traces is None else {
        k: np.asarray(v[:n]) for k, v in traces.items() if k != "valid"}
    vec = np.asarray(final.pol)
    pi_state = (PIState(prev_error=vec[0], prev_pcap_l=vec[1])
                if branch in ("pi", "pi_rls") else None)
    rls_state = (jax.tree_util.tree_map(
        np.asarray, rls_unpack(final.pol[_PI_RLS_LO:_PI_RLS_HI]))
        if branch == "pi_rls" else None)
    return SimResult(traces=trimmed,
                     exec_time=float(final.t),
                     energy=float(final.plant.energy),
                     work=float(final.plant.work),
                     completed=bool(final.plant.work >= total_work),
                     n_steps=n,
                     pi_state=pi_state,
                     plant_state=jax.tree_util.tree_map(np.asarray,
                                                        final.plant),
                     pcap=float(final.pcap),
                     summary=jax.tree_util.tree_map(
                         np.asarray, _summary_dict(final,
                                                   _hist_edges(profile))),
                     rls_state=rls_state,
                     policy_state=vec,
                     detector_state=(None if final.det is None
                                     else np.asarray(final.det)),
                     fault_state=(None if final.fstate is None
                                  else np.asarray(final.fstate)),
                     guard_state=(None if final.guard is None
                                  else np.asarray(final.guard)),
                     events=(None if final.events is None
                             else evt.decode_ring(final.events)),
                     event_state=(None if final.events is None
                                  else np.asarray(final.events)))


class _GridValues(NamedTuple):
    """A sweep's parameter tables, host float32 throughout: the engines
    trace them, and they cross to the device with the engine's inputs
    (the executor's per-chunk transfer)."""
    pv: np.ndarray                  # (P, len(PROFILE_FIELDS))
    gv: np.ndarray                  # (P, E, GAIN_DIM)
    av: np.ndarray                  # (P, A, POLICY_PARAM_DIM)
    sv: Optional[ScheduleValues]    # leaves (P, W, ...)
    dv: Optional[np.ndarray]        # (P, [D,] DET_PARAM_DIM)
    fv: Optional[flt.FaultValues]   # leaves ([F,] ...)
    gvl: Optional[np.ndarray]       # packed GuardConfig
    squeeze_w: Optional[bool]       # one PhaseSchedule: no W axis kept
    det_grid: bool                  # a D axis of DetectorConfigs
    fault_grid: bool                # an F axis of FaultSchedules


def _grid_values(profs: Sequence[PlantProfile], eps: Sequence[float],
                 pls: Sequence[pol.Policy], kinds: Sequence[int],
                 tau_obj: float, workloads=None, detector=None,
                 faults=None, guard=None) -> _GridValues:
    """Pack a sweep grid's tables on the host (numpy, no device call):
    gains per (profile, epsilon), policy values per (profile, policy) at
    the eps[0] design point, and the scenario (``sweep/scenario``):
    phase schedules resolved per profile, detector values, fault rows,
    the guard vector. Each value is a Python float rounded to float32
    once."""
    pv = np.stack([profile_values(p) for p in profs])
    gv = np.stack([
        np.stack([gains_values(PIGains.from_model(p, e, tau_obj))
                  for e in eps]) for p in profs])
    # policy values grid (P, A, PARAM_DIM), built at the eps[0] design
    # point per profile (the PI-RLS values: kl_ref/tau_obj depend only
    # on the profile)
    av = np.stack([
        np.stack([pol.policy_values(
            p_, p, PIGains.from_model(p, eps[0], tau_obj), kind=k)
            for p_, k in zip(pls, kinds)]) for p in profs])
    with obs_trace.get_tracer().span("sweep/scenario"):
        if workloads is None:
            sv, squeeze_w = None, None
        else:
            squeeze_w = isinstance(workloads, PhaseSchedule)
            wls = [workloads] if squeeze_w else list(workloads)
            if not wls:
                raise ValueError("workloads= needs at least one "
                                 "PhaseSchedule")
            # schedule leaves stacked (P, W, ...): resolved per profile,
            # all packed to the grid's common row count (piecewise
            # chaining keeps long scripts in whole 16-row pieces)
            rows = max(chain_rows(len(w.phases)) for w in wls)
            sv = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs),
                *[jax.tree_util.tree_map(
                    lambda *ws: np.stack(ws),
                    *[w.resolve(p, rows) for w in wls])
                  for p in profs])
        det_grid = (detector is not None
                    and not isinstance(detector, DetectorConfig))
        if detector is None:
            dv = None
        elif det_grid:
            det_cfgs = list(detector)
            if not det_cfgs:
                raise ValueError("detector= needs at least one "
                                 "DetectorConfig")
            # detector hyperparameter grid (P, D, DET_PARAM_DIM): a new D
            # axis between [workloads] and seeds, like the policy axis
            dv = np.stack([np.stack([detector_values(d, p)
                                     for d in det_cfgs])
                           for p in profs])
        else:
            dv = np.stack([detector_values(detector, p) for p in profs])
        fault_grid = (faults is not None
                      and not isinstance(faults, flt.FaultSchedule))
        if faults is None:
            fv = None
        elif fault_grid:
            fault_scheds = list(faults)
            if not fault_scheds:
                raise ValueError("faults= needs at least one "
                                 "FaultSchedule")
            # fault-scenario axis (F, MAX_FAULT_ROWS): plant-independent
            # leaves stacked across schedules, the innermost grid axis
            # before seeds
            fv = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs),
                *[f.resolve() for f in fault_scheds])
        else:
            fv = faults.resolve()  # one schedule: no axis, like detector
        gvl = (None if not guard
               else flt.guard_values(None if guard is True else guard))
    return _GridValues(pv, gv, av, sv, dv, fv, gvl, squeeze_w, det_grid,
                       fault_grid)


def _count_scan_steps(steps: np.ndarray, chunk: int, n_devices: int,
                      max_steps: int, collect: bool) -> None:
    """``sweep_scan_steps_total{kind}``, per engine batch (a chunk, or
    one device's slice of it): ``run`` adds the steps its loop ran, the
    largest final step count of its runs (trace mode scans all
    ``max_steps``), and ``horizon`` the ``max_steps`` a scan runs. Pad
    rows repeat their chunk's first run, as the executor pads them."""
    n = len(steps)
    n_chunks = -(-n // chunk)
    padded = np.empty(n_chunks * chunk, steps.dtype)
    padded[:n] = steps
    padded[n:] = steps[(n_chunks - 1) * chunk]
    batches = n_chunks * max(n_devices, 1)
    ran = (batches * max_steps if collect
           else int(padded.reshape(batches, -1).max(axis=1).sum()))
    c = obs_metrics.get_registry().counter(
        "sweep_scan_steps_total",
        "scan engine steps per batch: run by the loop, or the horizon",
        labelnames=("kind",))
    c.inc(ran, kind="run")
    c.inc(batches * max_steps, kind="horizon")


def _sweep_impl(*args, **kwargs):
    """Shared implementation behind `sweep` / `sweep_resumable`, traced
    as one ``sweep`` span (`repro.obs.trace`); `_sweep_run` does the
    work."""
    with obs_trace.get_tracer().span("sweep"):
        return _sweep_run(*args, **kwargs)


def _sweep_run(profiles: Union[str, PlantProfile,
                               Sequence[Union[str, PlantProfile]]],
               epsilons: Sequence[float],
               seeds: Sequence[int],
               total_work: float,
               max_time: float = 3600.0,
               dt: float = 1.0,
               tau_obj: float = 10.0,
               policies: Union[None, pol.Policy,
                               Sequence[pol.Policy]] = None,
               collect_traces: bool = True,
               summary_warmup: int = 0,
               workloads: Union[None, PhaseSchedule,
                                Sequence[PhaseSchedule]] = None,
               detector: Union[None, DetectorConfig,
                               Sequence[DetectorConfig]] = None,
               faults: Union[None, flt.FaultSchedule,
                             Sequence[flt.FaultSchedule]] = None,
               guard: Union[None, bool, flt.GuardConfig] = None,
               record_events: Union[None, bool, int] = None,
               backend: str = "auto",
               chunk_size: Optional[int] = None,
               devices=None,
               consume=None,
               state=None,
               stop_after: Optional[int] = None,
               durable=None,
               campaign=None):
    """`_sweep_impl`'s work: normalizes the grid (``sweep/grid``, with
    ``sweep/keys``, ``sweep/scenario`` and ``sweep/rows``), runs its
    per-run rows through `repro.core.executor`, counts a scan-engine
    result's runs in ``closed_loop_runs_total{path="scan"}`` and its
    steps in ``sweep_scan_steps_total``, and assembles the result
    (``sweep/summary``).
    Returns (SweepResult | None, ExecState | None)."""
    tracer = obs_trace.get_tracer()
    with tracer.span("sweep/grid"):
        single = isinstance(profiles, (str, PlantProfile))
        profs = [_resolve(p) for p in ([profiles] if single else profiles)]
        eps = [float(e) for e in epsilons]
        seeds = [int(s) for s in seeds]
        if not (profs and eps and seeds):
            raise ValueError("sweep needs at least one profile, epsilon and "
                             "seed")
        if policies is None:
            pls, squeeze_pol = [PIPolicy()], True
        else:
            squeeze_pol = isinstance(policies, pol.Policy)
            pls = [policies] if squeeze_pol else list(policies)
            if not pls:
                raise ValueError("policies= needs at least one Policy")
        branches, kinds = pol.resolve_kinds(pls)
        with tracer.span("sweep/keys"):
            keys = seed_keys(seeds)
        (pv, gv, av, sv, dv, fv, gvl, squeeze_w, det_grid,
         fault_grid) = _grid_values(profs, eps, pls, kinds, tau_obj,
                                    workloads=workloads, detector=detector,
                                    faults=faults, guard=guard)
        n_events = _resolve_n_events(record_events)
        if backend not in ("scan", "pallas", "auto"):
            raise ValueError(f"unknown backend {backend!r}; choose "
                             "'scan', 'pallas' or 'auto'")
        # capability dispatch: the mega-kernel carry has no recorder ring
        # (documented fallback — recorded grids ride the scan engine)
        pallas_ok = (branches == ("pi",) and sv is None and dv is None
                     and fv is None and gvl is None and n_events == 0)
        if backend == "auto":
            # capability dispatch: the mega-kernel covers the flagship
            # fixed-gain PI path and pays off where it lowers natively; the
            # interpreted kernel is for correctness work, not speed
            backend = ("pallas" if pallas_ok
                       and jax.default_backend() == "tpu" else "scan")
        elif backend == "pallas" and not pallas_ok:
            raise ValueError(
                "backend='pallas' covers the fixed-gain PI path only "
                "(static plant, no detector, no faults/guard, no flight "
                "recorder); this grid "
                f"needs branches={branches}, workloads={sv is not None}, "
                f"detector={dv is not None}, faults={fv is not None}, "
                f"guard={gvl is not None}, record_events={n_events > 0} — "
                "use backend='scan'")
        max_steps = _bucket_steps(int(np.ceil(max_time / dt)))
        P, E, A, S = len(profs), len(eps), len(pls), len(seeds)
        W = (1 if sv is None
             else jax.tree_util.tree_leaves(sv)[0].shape[1])
        D = dv.shape[1] if det_grid else 1
        F = (jax.tree_util.tree_leaves(fv)[0].shape[0] if fault_grid
             else 1)
        shape7 = (P, E, A, W, D, F, S)
        n_runs = int(np.prod(shape7))
        with tracer.span("sweep/rows"):
            # flatten the grid to per-run rows (grid-nest order, so the
            # merged leading axis reshapes straight back to
            # (P,E,A,[W],[D],[F],S))
            (ip, ie, ia, iw, idet, ifl,
             is_) = np.indices(shape7).reshape(7, n_runs)
            batched = {"prof": pv[ip], "gains": gv[ip, ie],
                       "pvals": av[ip, ia], "key": keys[is_]}
            if sv is not None:
                batched["sched"] = jax.tree_util.tree_map(
                    lambda x: x[ip, iw], sv)
            if dv is not None:
                batched["det"] = dv[ip, idet] if det_grid else dv[ip]
            if fv is not None:
                # fault rows always ride the per-run rows here (a single
                # schedule broadcasts), so chunk slicing stays uniform
                batched["faults"] = jax.tree_util.tree_map(
                    lambda x: (x[ifl] if fault_grid
                               else np.broadcast_to(
                                   x, (n_runs,) + np.shape(x)).copy()),
                    fv)
        if backend == "pallas":
            # the op jits itself; with devices= the executor places one
            # slice of every chunk on each device
            fn = _flat_core_pallas(collect_traces)
            shared = (float(total_work), float(max_time), float(dt),
                      float(summary_warmup))
            wrap = "none"
        else:
            fn = _flat_core(max_steps, branches, collect_traces,
                            sv is not None, dv is not None,
                            gvl is not None, n_events)
            shared = (np.float32(total_work), np.float32(max_time),
                      np.float32(dt), np.float32(summary_warmup))
            if gvl is not None:
                shared = shared + (gvl,)
            wrap = "jit"
    if durable is not None:
        # journaled, retried, quarantine-capable campaign path — same
        # grid, same per-run rows, so the merged result is bit-for-bit
        # the plain run_grid one
        from repro.core import supervisor
        merged, report = supervisor.run_durable(
            fn, batched, shared, n_runs, dir=durable,
            chunk_size=chunk_size, devices=devices, wrap=wrap,
            consume=consume, config=campaign)
        exec_state = report.state
        if report.dead:
            # dead-lettered chunks leave their rows unfilled: a result
            # assembled from them would be garbage
            raise RuntimeError(
                f"campaign {durable}: chunks {report.dead} were "
                "dead-lettered, so their runs were never computed "
                "(the journal there records each error)")
    else:
        merged, exec_state = executor.run_grid(
            fn, batched, shared, n_runs, chunk_size=chunk_size,
            devices=devices, wrap=wrap, consume=consume, state=state,
            stop_after=stop_after)
    if merged is None:  # consume hook ran, or stop_after cut short
        return None, exec_state
    traces, final = merged
    if backend == "scan":
        # the kernel op counts its own runs (native, interpret, ref)
        from repro.kernels.closed_loop.ops import runs_counter
        runs_counter().inc(n_runs, path="scan")
        _count_scan_steps(final.steps, exec_state.chunk,
                          len(executor.resolve_devices(devices)),
                          max_steps, collect_traces)
    with tracer.span("sweep/summary"):
        if backend == "pallas":
            final = _carry_from_kernel_final(final)
        out_shape = ((P, E, A) + ((W,) if sv is not None else ())
                     + ((D,) if det_grid else ())
                     + ((F,) if fault_grid else ()) + (S,))
        reshape = lambda x: x.reshape(out_shape + x.shape[1:])
        traces = (None if traces is None
                  else jax.tree_util.tree_map(reshape, traces))
        final = jax.tree_util.tree_map(reshape, final)
        edges = {k: np.stack([_hist_edges(p)[k] for p in profs])
                 for k in ("progress_edges", "pcap_edges")}
        summary = _summary_dict(final, edges)

        def squeeze(tree, axis):
            return jax.tree_util.tree_map(
                lambda x: x[(slice(None),) * axis + (0,)]
                if hasattr(x, "ndim") and x.ndim > axis else x, tree)

        if squeeze_w:  # single PhaseSchedule: drop the W axis (P, E, A, W, S)
            traces, final = squeeze(traces, 3), squeeze(final, 3)
            summary = {k: v if k.endswith("_edges") else squeeze(v, 3)
                       for k, v in summary.items()}
        if squeeze_pol:
            traces, final = squeeze(traces, 2), squeeze(final, 2)
            summary = {k: v if k.endswith("_edges") else squeeze(v, 2)
                       for k, v in summary.items()}
        if single:
            traces, final = squeeze(traces, 0), squeeze(final, 0)
            summary = squeeze(summary, 0)
        return SweepResult(traces=traces,
                           exec_time=final.t,
                           energy=final.plant.energy,
                           work=final.plant.work,
                           completed=final.plant.work >= total_work,
                           n_steps=final.steps,
                           summary=summary,
                           detections=(None if final.det is None
                                       else final.det[..., DET_N_DETECT]),
                           guard_state=final.guard,
                           events=final.events
                           ), exec_state


def sweep(profiles, epsilons, seeds, total_work, max_time=3600.0,
          dt=1.0, tau_obj=10.0, policies=None,
          collect_traces=True, summary_warmup=0, workloads=None,
          detector=None, faults=None, guard=None,
          record_events=None, *,
          backend: str = "auto",
          chunk_size: Optional[int] = None, devices=None,
          consume=None, durable=None, campaign=None
          ) -> Optional[SweepResult]:
    """Vmapped closed-loop grid: profiles x epsilons [x policies]
    [x workloads] x seeds.

    The compiled function is cached by scan length, mode and the POLICY
    BRANCH SET only — plant, gain and policy hyperparameters are all
    traced — so repeated sweeps over different profiles, epsilon grids,
    RLS hyperparameter grids or policy weight sets reuse the same
    executable; a heterogeneous ``policies=[PIPolicy(...),
    OfflineRLPolicy(...), DutyCyclePolicy(...)]`` list runs through one
    `lax.switch`-dispatched engine, one compile per scan-length bucket.

    Pass `policies=` a single Policy (axis squeezed) or a sequence
    (inserts an A axis between epsilons and seeds); an RLS
    hyperparameter grid is ``policies=[PIPolicy(adaptive=cfg) for cfg
    in ...]`` (a profile-dependent policy's `values` are built at the
    epsilon[0] design point — the PI-RLS values only use the
    epsilon-independent k_i). `collect_traces=False` switches to the
    O(grid)-memory summary mode for very large grids. `summary_warmup`
    excludes each run's first steps (the descent transient) from the
    online summary reductions only.

    Pass `workloads=` a single `PhaseSchedule` (axis squeezed) or a
    sequence (inserts a W axis between policies and seeds): each
    schedule resolves against EVERY profile on the profile axis (its
    deltas/scales script that profile's plant over time), and phased
    grids share one compiled engine per scan-length bucket — the
    schedule arrays are traced. `detector=` runs the change-point
    detector in every run (design model = each profile);
    `SweepResult.detections` then carries per-run alarm counts. A
    SEQUENCE of DetectorConfigs sweeps the detector hyperparameters
    (threshold, min_gap, drift, ...) as their own grid axis — a D axis
    between [workloads] and seeds — for threshold/ROC tuning in one
    compiled call.

    `faults=` scripts telemetry/actuator failures inside every run
    (`repro.core.faults.FaultSchedule`): a single schedule applies to
    every run with no new axis; a SEQUENCE sweeps fault scenarios as
    their own F axis between [detectors] and seeds — degradation curves
    vs fault severity in one compiled call. `guard=` (GuardConfig, or
    True for the defaults) arms the guarded-degradation layer in every
    run's `plane_step`; `SweepResult.guard_state` then carries the
    per-run watchdog counters (time-in-failsafe, rejected signals,
    forced resets). `sweep(faults=None, guard=None)` is bit-for-bit the
    pre-faults engine — the fault RNG folds off a separate key and None
    arguments carry no pytree leaves, so the compiled graph is the
    pre-existing one. `record_events=` (True or a ring size) arms the
    flight recorder in every run; `SweepResult.events` then carries the
    per-run packed rings (decode with `repro.obs.events.decode_grid`) —
    recorder-off sweeps keep the exact recorder-free executable under
    the same None-leaves contract.

    Execution layer (`repro.core.executor`): every grid is flattened to
    per-run rows and run by `executor.run_grid`; with the default
    ``chunk_size=None`` that is one chunk of all runs. ``chunk_size=``
    cuts the rows into bounded-memory tiles (buffer donation between
    tiles, streaming merge on host — a 1M-run summary grid no longer
    has to fit in one vmap); ``devices=`` ("all", an int, or a device
    list) shards tiles across devices via pmap with a single-device
    fallback. Each run's parameters and RNG stream ride in its own row,
    so every layout computes the same per-run model: counts agree
    exactly, floats within a few ulp across chunk shapes (two chunk
    shapes are two XLA executables). ``backend="pallas"`` dispatches to
    the fused closed-loop Pallas
    mega-kernel (`repro.kernels.closed_loop`; fixed-gain PI, static
    plant, no detector — same model, its own per-run noise stream;
    with ``devices=`` each chunk is split over the devices). The
    default ``backend="auto"`` picks the kernel when the grid is
    capable and the platform compiles it natively (TPU), else scan.
    So on a TPU a fixed-gain PI grid draws from the same model as on a
    CPU but not the same samples: the kernel has its own noise streams
    and approximates each step's heartbeat count by a rounded Gaussian
    where the scan draws a Poisson, which moves per-(plant, epsilon)
    medians by up to ~2% (ROADMAP D2). Pass ``backend="scan"`` for the
    CPU's exact samples on any platform.
    ``consume=`` streams per-chunk results to a callback ``consume(lo,
    hi, (traces, final))`` instead of accumulating them (the offline-RL
    dataset harvester) — `sweep` then returns None.

    ``durable=dir`` runs the grid under the campaign supervisor
    (`repro.core.supervisor`): every chunk is write-ahead journaled and
    checkpointed into ``dir``, transient failures retry with backoff,
    failing devices are quarantined, and after ANY crash
    `supervisor.resume_campaign(dir)` reopens the campaign and returns
    the bit-for-bit uninterrupted result. ``campaign=`` tunes the
    `supervisor.CampaignConfig` ladder. The sweep arguments are pickled
    into ``dir`` as the campaign spec, so pass ``devices=`` as
    None/int/"all" (picklable forms), not raw device objects.
    """
    if durable is not None and consume is None:
        # first writer wins: a resume re-entering through sweep() keeps
        # the original spec. consume= callbacks are not picklable —
        # callers owning one (harvest_dataset) save their own spec.
        from repro.core import supervisor
        supervisor.save_campaign_spec(durable, "sweep", dict(
            profiles=profiles, epsilons=list(epsilons),
            seeds=list(seeds), total_work=total_work, max_time=max_time,
            dt=dt, tau_obj=tau_obj, policies=policies,
            collect_traces=collect_traces, summary_warmup=summary_warmup,
            workloads=workloads, detector=detector, faults=faults,
            guard=guard, record_events=record_events, backend=backend,
            chunk_size=chunk_size, devices=devices, campaign=campaign))
    res, _ = _sweep_impl(profiles, epsilons, seeds, total_work,
                         max_time, dt, tau_obj, policies,
                         collect_traces, summary_warmup, workloads,
                         detector, faults, guard, record_events,
                         backend=backend,
                         chunk_size=chunk_size, devices=devices,
                         consume=consume, durable=durable,
                         campaign=campaign)
    return res


def sweep_resumable(profiles, epsilons, seeds, total_work,
                    max_time=3600.0, dt=1.0, tau_obj=10.0,
                    policies=None, collect_traces=True,
                    summary_warmup=0, workloads=None, detector=None,
                    faults=None, guard=None, record_events=None, *,
                    backend: str = "auto", chunk_size: int,
                    devices=None, state=None,
                    stop_after: Optional[int] = None):
    """Chunked sweep that can stop and resume ACROSS chunk boundaries:
    returns (SweepResult | None, `executor.ExecState`). ``stop_after=``
    processes at most that many chunks per call (result is None until
    the grid completes); pass the returned state — plain numpy, it
    pickles — back via ``state=`` to continue where the previous call
    (or process) left off. Same grid semantics as `sweep`."""
    return _sweep_impl(profiles, epsilons, seeds, total_work, max_time,
                       dt, tau_obj, policies, collect_traces,
                       summary_warmup, workloads, detector, faults,
                       guard, record_events, backend=backend,
                       chunk_size=chunk_size, devices=devices,
                       state=state, stop_after=stop_after)


@functools.lru_cache(maxsize=None)
def _jit_replay():
    def replay(profile_vals, pcaps, dt):
        profile = _unpack_profile(profile_vals)
        pl = pcap_linearize(profile, pcaps)
        w = dt / (dt + profile.tau)

        def body(y, u):
            y = profile.K_L * w * u + (1.0 - w) * y
            return y, y

        _, ys = jax.lax.scan(body, pl[0] * profile.K_L, pl)
        return ys + profile.K_L

    return jax.jit(replay)


def replay_model(profile: Union[str, PlantProfile], pcaps, dt: float = 1.0
                 ) -> jnp.ndarray:
    """Deterministic Eq. 3 replay of a pcap schedule (noise-free model
    prediction, the Fig. 5 accuracy baseline)."""
    profile = _resolve(profile)
    return _jit_replay()(profile_values(profile),
                         jnp.asarray(pcaps, jnp.float32), jnp.float32(dt))
