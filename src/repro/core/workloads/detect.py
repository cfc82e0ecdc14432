"""Online phase-change detection on progress-model residuals.

The detector replays the DESIGN model (the Eq. 3 first-order plant the
PI gains were placed on) alongside the real plant: each control period
it advances a deterministic prediction of linearized progress from the
applied cap and forms the residual r = progress - prediction. A
phase change moves the residual's LEVEL; the detector therefore runs a
two-sided Page-Hinkley / CUSUM test on the normalized deviation from a
slow EWMA of the residual,

    z = (r - level) / sigma,
    sigma^2 = noise_ref^2 + max(prediction, 1) / dt,

so a plant that merely differs from its design model (persistent bias)
is absorbed into the level while a CHANGE — knee shift, gain shift,
data/compute movement — accumulates and alarms. The sigma model covers
both the plant's heteroscedastic measurement noise (noise_ref, §4.3)
and the Poisson heartbeat-synthesis variance of the Eq. 1 median
(~rate/dt), so thresholds are in comparable sigma units across
profiles.

On an alarm the level jumps to the new residual, the statistics reset,
and a refractory window (`min_gap`) re-arms the detector; the scan
engine forwards the alarm to the active policy's `on_change` hook (RLS
covariance reset + immediate gain re-placement for adaptive PI) and
exposes it to every policy via `PolicyObs.phase_change`.

State and parameters pack into fixed-width f32 vectors so the detector
threads through the scan carry exactly like `RLSState` — traced, vmapped
and checkpointable.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.plant import PlantProfile

# Canonical packing order of the traced detector parameters.
DET_PARAM_FIELDS = ("kl_ref", "tau_ref", "noise_ref", "drift",
                    "threshold", "min_gap", "level_eta", "level_slack")
DET_PARAM_DIM = len(DET_PARAM_FIELDS)
# state slots: model replay, residual level, the two PH statistics, the
# refractory countdown and two counters
DET_PRED_L, DET_LEVEL, DET_M_POS, DET_M_NEG, DET_COOLDOWN, \
    DET_N_DETECT, DET_SINCE = range(7)
DET_STATE_DIM = 8  # one spare slot


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    """Page-Hinkley knobs, in residual-sigma units.

    ``drift`` is the per-period slack subtracted from |z| (tolerated
    wander); ``threshold`` the alarm level of the accumulated statistic;
    ``min_gap`` the refractory window in control periods — also the
    initial arming delay, so the PH statistic never accumulates the
    (re)start transient. ``level_eta`` is the EWMA gain of the residual
    level tracker: slow enough (<< 1/detection horizon) that a real
    shift alarms before it is absorbed, fast enough that a persistent
    plant/design mismatch stops ringing the alarm. ``level_slack``
    widens sigma by that fraction of the tracked level: a plant already
    far from its design model wanders with the moving cap (the mismatch
    is cap-dependent), so tolerance scales with the mismatch while a
    matched plant (level ~ 0) keeps full sensitivity."""
    drift: float = 0.25
    threshold: float = 12.0
    min_gap: int = 10
    level_eta: float = 0.05
    level_slack: float = 0.5


def detector_values(cfg: DetectorConfig, design: PlantProfile
                    ) -> np.ndarray:
    """Pack (config, design model) -> (len(DET_PARAM_FIELDS),) f32 host
    vector, which the engines trace."""
    noise_ref = design.noise_scale * float(np.sqrt(design.n_sockets))
    return np.asarray([design.K_L, design.tau, noise_ref, cfg.drift,
                       cfg.threshold, float(cfg.min_gap),
                       cfg.level_eta, cfg.level_slack], np.float32)


def detect_init(vals, gains, pcap0=None) -> jnp.ndarray:
    """Fresh detector state: model anchored at the starting cap's
    steady state (every run starts at pcap_max, like the plant), level
    at zero, refractory window running."""
    kl = vals[0]
    pcap0 = gains.pcap_max if pcap0 is None else pcap0
    state = jnp.zeros((DET_STATE_DIM,), jnp.float32)
    return (state.at[DET_PRED_L].set(kl * gains.linearize(pcap0))
            .at[DET_COOLDOWN].set(vals[5]))


def detect_step(vals, state, progress, pcap_l, dt
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One control period: advance the model, accumulate PH, maybe alarm.

    ``pcap_l`` is the cap applied THIS period, linearized through the
    design transform (`gains.linearize`). Pure and scan/vmap-safe.
    Returns (new_state, detected: bool)."""
    kl, tau, sig0, drift, thresh, min_gap, eta, slack = (
        vals[i] for i in range(8))
    w = dt / (dt + tau)
    pred_l = kl * w * pcap_l + (1.0 - w) * state[DET_PRED_L]
    pred = pred_l + kl
    resid = progress - pred
    level0 = state[DET_LEVEL]
    sigma = jnp.sqrt(sig0 * sig0 + jnp.maximum(pred, 1.0) / dt
                     + (slack * level0) ** 2)
    z = (resid - state[DET_LEVEL]) / jnp.maximum(sigma, 1e-6)
    armed = state[DET_COOLDOWN] <= 0.0
    # the PH statistics only run while armed: the refractory window
    # (post-alarm or post-init) feeds the level tracker, not the alarm
    m_pos = jnp.where(armed,
                      jnp.maximum(0.0, state[DET_M_POS] + z - drift), 0.0)
    m_neg = jnp.where(armed,
                      jnp.maximum(0.0, state[DET_M_NEG] - z - drift), 0.0)
    detected = armed & ((m_pos > thresh) | (m_neg > thresh))
    det_f = detected.astype(jnp.float32)
    level = jnp.where(detected, resid,
                      (1.0 - eta) * state[DET_LEVEL] + eta * resid)
    new = jnp.stack([
        pred_l,
        level,
        m_pos * (1.0 - det_f),
        m_neg * (1.0 - det_f),
        jnp.where(detected, min_gap,
                  jnp.maximum(state[DET_COOLDOWN] - 1.0, 0.0)),
        state[DET_N_DETECT] + det_f,
        jnp.where(detected, 0.0, state[DET_SINCE] + 1.0),
        jnp.float32(0.0),
    ]).astype(jnp.float32)
    return new, detected
