"""Fault scripts as scan citizens + the guarded-degradation layer.

The paper evaluates the Eq. 4 PI loop under clean telemetry, but the
premise — a production feedback loop on heterogeneous HPC nodes — makes
heartbeat loss, frozen RAPL meters and stuck powercap actuators the
steady state, not the exception. This module scripts those failures the
same way `repro.core.workloads` scripts phases: as fixed-width packed
rows (`FaultSchedule` -> `FaultValues`) evaluated INSIDE the jitted
engine step, so `sweep(faults=[...])` vmaps whole fault scenarios as one
more grid axis, and the live `NRM` can wrap any `PowerActuator` in a
`FaultyActuator` driven by the same schedule.

Channels (`FaultWindow.kind`):

* ``hb_dropout``   — fraction p1 of this period's heartbeats are lost.
* ``hb_stale``     — the aggregator's output freezes at its last value
  (late delivery: beats arrive, the report doesn't).
* ``meter_freeze`` — the power meter repeats its last healthy reading.
* ``meter_bias``   — additive bias of p1 watts on the reading.
* ``meter_spike``  — with per-step probability p1 the reading is
  replaced by p2 (p2=0 means NaN — the classic poisoned register).
* ``act_stuck``    — the cap actuator ignores commands and holds p1
  watts (p1=0: holds whatever was last applied).
* ``act_quant``    — commands quantize to a p1-watt grid above pcap_min.
* ``act_delay``    — commands take effect one control period late.
* ``crash``        — tenant crash: no progress, no beats, idle power;
  the plant restarts cold when the window ends.

Sensor-side channels corrupt only what the CONTROLLER observes; the
plant's own work/energy integrals stay truthful, which is what lets
`benchmarks.fig9_chaos` measure true degradation under lying telemetry.

The guard layer (`GuardConfig`, consumed by `repro.core.plane.
plane_step`) is packed here too: a stale-signal watchdog (no fresh
progress within ``hold_k`` periods -> hold the applied cap, past
``failsafe_k`` -> fail safe to pcap_max, performance-safe by
construction), non-finite/outlier sentinels on progress and power, and
a policy-state divergence guard that routes through the existing
`on_change` estimator-reset hook. With every trigger expressed as
`jnp.where(trigger, ..., clean)`, a no-trigger run is bit-for-bit the
unguarded one.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics

FAULT_KINDS = ("none", "hb_dropout", "hb_stale", "meter_freeze",
               "meter_bias", "meter_spike", "act_stuck", "act_quant",
               "act_delay", "crash")
(K_NONE, K_HB_DROPOUT, K_HB_STALE, K_METER_FREEZE, K_METER_BIAS,
 K_METER_SPIKE, K_ACT_STUCK, K_ACT_QUANT, K_ACT_DELAY,
 K_CRASH) = range(len(FAULT_KINDS))

#: fixed row count every resolved schedule packs to, so heterogeneous
#: `sweep(faults=[...])` lists stack into one (F, MAX_FAULT_ROWS) grid
MAX_FAULT_ROWS = 8

# kinds whose primary parameter has a meaningful "unset" default
_DEFAULT_P1 = {"hb_dropout": 1.0, "meter_spike": 1.0}


class FaultValues(NamedTuple):
    """Packed fault rows: f32 host arrays from `FaultSchedule.resolve`,
    every leaf traced inside the engines (scan/vmap citizens)."""
    start: np.ndarray    # (R,) window start [s]
    end: np.ndarray      # (R,) window end [s] (+inf on padding rows)
    kind: np.ndarray     # (R,) index into FAULT_KINDS (0 = none)
    p1: np.ndarray       # (R,) primary parameter (kind-specific)
    p2: np.ndarray       # (R,) secondary parameter (kind-specific)
    period: np.ndarray   # () scalar; > 0 makes the script cyclic


@dataclasses.dataclass(frozen=True)
class FaultWindow:
    """One scripted failure window: `kind` active on [start, start+duration)."""
    kind: str
    start: float
    duration: float
    p1: float = 0.0
    p2: float = 0.0

    def __post_init__(self):
        if self.kind not in FAULT_KINDS or self.kind == "none":
            raise ValueError(f"unknown fault kind {self.kind!r}; choose "
                             f"from {FAULT_KINDS[1:]}")
        if self.duration <= 0:
            raise ValueError("fault window duration must be positive")


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """A fault script: windows on the run clock (cyclic if period > 0).

    `resolve()` packs to fixed-width `FaultValues` rows exactly like
    `PhaseSchedule.resolve` packs phases, so schedules ride the scan
    carry and stack into a `sweep(faults=[...])` axis.
    """
    windows: Tuple[FaultWindow, ...] = ()
    period: float = 0.0
    name: str = ""

    def __post_init__(self):
        object.__setattr__(self, "windows", tuple(self.windows))
        if len(self.windows) > MAX_FAULT_ROWS:
            raise ValueError(f"{len(self.windows)} fault windows > "
                             f"MAX_FAULT_ROWS={MAX_FAULT_ROWS}")
        if self.period > 0:
            for w in self.windows:
                if w.start + w.duration > self.period:
                    raise ValueError("cyclic fault window overruns the "
                                     "period")

    def resolve(self) -> FaultValues:
        R = MAX_FAULT_ROWS
        start = np.full(R, np.inf, np.float32)
        end = np.full(R, np.inf, np.float32)
        kind = np.zeros(R, np.float32)
        p1 = np.zeros(R, np.float32)
        p2 = np.zeros(R, np.float32)
        for i, w in enumerate(self.windows):
            start[i] = w.start
            end[i] = w.start + w.duration
            kind[i] = FAULT_KINDS.index(w.kind)
            p1[i] = w.p1 if w.p1 else _DEFAULT_P1.get(w.kind, 0.0)
            p2[i] = w.p2
        return FaultValues(start, end, kind, p1, p2,
                           np.asarray(self.period, np.float32))

    # host-side view (FaultyActuator + tests)
    def active(self, t: float) -> Tuple[FaultWindow, ...]:
        t_eff = float(t) % self.period if self.period > 0 else float(t)
        return tuple(w for w in self.windows
                     if w.start <= t_eff < w.start + w.duration)


class ActiveFaults(NamedTuple):
    """Per-channel activation at one instant (all traced scalars)."""
    hb_drop: jnp.ndarray        # fraction of beats lost this period
    hb_stale: jnp.ndarray       # 0/1: hold last observed progress
    meter_freeze: jnp.ndarray   # 0/1: hold last healthy power reading
    meter_bias: jnp.ndarray     # additive watts on the reading
    meter_spike_p: jnp.ndarray  # per-step spike probability
    meter_spike_v: jnp.ndarray  # spike value (0 -> NaN)
    act_stuck_on: jnp.ndarray   # 0/1: actuator ignores commands
    act_stuck_val: jnp.ndarray  # stuck value (0 -> hold last applied)
    act_quant: jnp.ndarray      # command quantum in watts (0 = off)
    act_delay: jnp.ndarray      # 0/1: one-period command delay
    crash: jnp.ndarray          # 0/1: tenant down


def fault_channels(fv: FaultValues, t: jnp.ndarray) -> ActiveFaults:
    """Reduce the packed rows to per-channel activations at time t."""
    t_eff = jnp.where(fv.period > 0,
                      jnp.mod(t, jnp.maximum(fv.period, 1e-9)), t)
    on = (t_eff >= fv.start) & (t_eff < fv.end)

    def peak(kidx, v):
        return jnp.max(jnp.where(on & (fv.kind == kidx), v, 0.0))

    return ActiveFaults(
        hb_drop=peak(K_HB_DROPOUT, fv.p1),
        hb_stale=peak(K_HB_STALE, 1.0),
        meter_freeze=peak(K_METER_FREEZE, 1.0),
        meter_bias=jnp.sum(jnp.where(on & (fv.kind == K_METER_BIAS),
                                     fv.p1, 0.0)),
        meter_spike_p=peak(K_METER_SPIKE, fv.p1),
        meter_spike_v=peak(K_METER_SPIKE, fv.p2),
        act_stuck_on=peak(K_ACT_STUCK, 1.0),
        act_stuck_val=peak(K_ACT_STUCK, fv.p1),
        act_quant=peak(K_ACT_QUANT, fv.p1),
        act_delay=peak(K_ACT_DELAY, 1.0),
        crash=peak(K_CRASH, 1.0),
    )


# ---- per-run fault state (rides the scan carry) ---------------------------

FAULT_STATE_DIM = 6
(F_LAST_PROGRESS,   # last delivered (non-stale) aggregated progress
 F_LAST_POWER,      # last healthy power reading (freeze anchor)
 F_PREV_CMD,        # previous period's cap command (act_delay)
 F_PREV_APPLIED,    # previous period's applied cap (act_stuck hold)
 F_CRASHED,         # 0/1: was down last period (restart edge)
 F_SPARE) = range(FAULT_STATE_DIM)


def fault_state_init(profile) -> jnp.ndarray:
    """Initial fault state: runs start uncapped at full power."""
    pmax = jnp.float32(profile.pcap_max)
    return jnp.stack([jnp.float32(0.0),
                      jnp.float32(profile.power_of_pcap(profile.pcap_max)),
                      pmax, pmax, jnp.float32(0.0), jnp.float32(0.0)])


def apply_actuator(af: ActiveFaults, fstate: jnp.ndarray,
                   pcap_cmd: jnp.ndarray, pcap_min) -> jnp.ndarray:
    """Distort the controller's cap command the way a sick actuator
    would; identity (bit-for-bit) when no actuator channel is active."""
    cmd = jnp.where(af.act_delay > 0, fstate[F_PREV_CMD], pcap_cmd)
    q = af.act_quant
    cmd = jnp.where(
        q > 0,
        pcap_min + jnp.round((cmd - pcap_min) / jnp.maximum(q, 1e-9)) * q,
        cmd)
    stuck = jnp.where(af.act_stuck_val > 0, af.act_stuck_val,
                      fstate[F_PREV_APPLIED])
    return jnp.where(af.act_stuck_on > 0, stuck, cmd)


# ---- guarded degradation (consumed by repro.core.plane.plane_step) --------

GUARD_PARAM_DIM = 6


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Guarded-degradation knobs for `plane_step(guard_vals=...)`.

    hold_k / failsafe_k count consecutive control periods without a
    fresh, in-range progress signal: past hold_k the row HOLDS its
    applied cap (no decisions on stale data), past failsafe_k it fails
    safe to pcap_max — the one cap that can never violate the paper's
    performance contract, whatever the plant is really doing.
    outlier_mult bounds accepted signals (progress <= mult * setpoint,
    power <= mult * power(pcap_max)); anything outside counts as stale.
    recover_reset routes the first fresh signal after a fail-safe
    through the policy's `on_change` hook, so estimators re-converge
    from the reset covariance instead of the poisoned one.
    """
    hold_k: int = 3
    failsafe_k: int = 12
    outlier_mult: float = 8.0
    recover_reset: bool = True


def guard_values(cfg: Optional[GuardConfig] = None) -> np.ndarray:
    cfg = cfg or GuardConfig()
    return np.array([cfg.hold_k, cfg.failsafe_k, cfg.outlier_mult,
                     1.0 if cfg.recover_reset else 0.0, 0.0, 0.0],
                    np.float32)


GUARD_STATE_DIM = 8
(G_STALE,          # consecutive periods without a valid progress signal
 G_MODE,           # 0 normal / 1 hold / 2 fail-safe
 G_LAST_PROGRESS,  # last accepted progress (substituted while stale)
 G_LAST_POWER,     # last accepted power reading
 G_N_INVALID,      # cumulative rejected-signal count (observability)
 G_N_FAILSAFE,     # cumulative periods spent in fail-safe
 G_N_RESETS,       # cumulative forced estimator resets
 G_SPARE) = range(GUARD_STATE_DIM)

GUARD_NORMAL, GUARD_HOLD, GUARD_FAILSAFE = 0.0, 1.0, 2.0


def guard_init() -> jnp.ndarray:
    return jnp.zeros(GUARD_STATE_DIM, jnp.float32)


# ---- live-runtime fault injection (NRM path) ------------------------------

class FaultyActuator:
    """Wrap any `PowerActuator` with a `FaultSchedule` evaluated on the
    host clock: stuck/quantized/delayed caps on `set_pcap`, frozen/
    biased/spiked readings on `read_power`. Drive the clock with
    `tick(t)` each control period (the NRM's `_t`). Crash windows read
    as zero power and swallow commands. Duck-typed: everything else
    delegates to the wrapped actuator. Every perturbation actually
    applied increments the per-kind ``faults_injected_total`` counter
    in the process metrics registry."""

    def __init__(self, inner, schedule: FaultSchedule, seed: int = 0):
        self.inner = inner
        self.schedule = schedule
        self._t = 0.0
        self._rng = np.random.default_rng(seed)
        self._prev_cmd: Optional[float] = None
        self._last_applied: Optional[float] = None
        self._frozen: Optional[float] = None
        # per-kind injection counter, cached so the per-period hot path
        # is one dict op, not a registry lookup under the lock
        self._injected = obs_metrics.get_registry().counter(
            "faults_injected_total",
            "fault perturbations actually applied by FaultyActuator",
            labelnames=("kind",))

    def tick(self, t: float) -> None:
        self._t = float(t)

    def _chan(self, kind: str) -> Optional[FaultWindow]:
        for w in self.schedule.active(self._t):
            if w.kind == kind:
                return w
        return None

    def set_pcap(self, pcap: float) -> None:
        cmd = float(pcap)
        if self._chan("act_delay") is not None:
            cmd, self._prev_cmd = (
                self._prev_cmd if self._prev_cmd is not None else cmd,
                float(pcap))
            self._injected.inc(kind="act_delay")
        else:
            self._prev_cmd = float(pcap)
        w = self._chan("act_quant")
        if w is not None:
            lo = getattr(getattr(self.inner, "profile", None),
                         "pcap_min", 0.0)
            cmd = lo + round((cmd - lo) / max(w.p1, 1e-9)) * w.p1
            self._injected.inc(kind="act_quant")
        w = self._chan("act_stuck")
        if w is not None:
            cmd = (w.p1 if w.p1 else
                   self._last_applied if self._last_applied is not None
                   else cmd)
            self._injected.inc(kind="act_stuck")
        if self._chan("crash") is not None:
            self._injected.inc(kind="crash")
            return  # a crashed tenant's runtime takes no commands
        self._last_applied = cmd
        self.inner.set_pcap(cmd)

    def read_power(self) -> float:
        if self._chan("crash") is not None:
            return 0.0
        true = float(self.inner.read_power())
        w = self._chan("meter_freeze")
        if w is not None:
            self._injected.inc(kind="meter_freeze")
            return self._frozen if self._frozen is not None else true
        self._frozen = true
        v = true
        w = self._chan("meter_bias")
        if w is not None:
            v += w.p1
            self._injected.inc(kind="meter_bias")
        w = self._chan("meter_spike")
        if w is not None and self._rng.random() < (w.p1 or 1.0):
            v = w.p2 if w.p2 else float("nan")
            self._injected.inc(kind="meter_spike")
        return v

    def drop_heartbeat(self) -> bool:
        """Should the workload shim drop this heartbeat right now?"""
        if self._chan("crash") is not None:
            return True
        w = self._chan("hb_dropout")
        if w is not None and self._rng.random() < (w.p1 or 1.0):
            self._injected.inc(kind="hb_dropout")
            return True
        return False

    def __getattr__(self, name):
        return getattr(self.inner, name)
