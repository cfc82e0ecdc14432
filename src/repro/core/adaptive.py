"""Adaptive control (beyond the paper; its §5.2 'natural direction').

The paper's PI gains are fixed by the offline-identified (K_L, tau). Under
phase changes (compute-bound <-> memory-bound) the true static gain drifts
and fixed gains become too aggressive or too sluggish. We close that gap
with recursive least squares (RLS, forgetting factor lambda) on the
first-order model in the *linearized* coordinates:

    progress_L[i+1] = theta1 * pcap_L[i] + theta2 * progress_L[i]

which gives online estimates tau_hat = dt*theta2/(1-theta2) and
K_L_hat = theta1*(dt+tau_hat)/dt; the PI gains are re-placed each period
(gain scheduling) with clamping and a dwell time to avoid chattering.

Two implementations of the same estimator:

* `RLSState`/`rls_init`/`rls_step` — pure-JAX, threaded through the scan
  engine's carry so adaptive runs live inside the jitted closed loop
  (`repro.core.sim`, `adaptive=` argument) and hyperparameter grids
  vmap alongside profiles x epsilons x seeds.
* `RLSAdapter` — the original numpy per-step version, kept ONLY as the
  equivalence oracle (tests drive both with identical input sequences).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.controller import PIGains
from repro.core.plant import PlantProfile

# Clip bounds for theta2 when converting to (tau_hat, K_L_hat); shared by
# both implementations so they stay bit-for-bit comparable.
_TH2_LO, _TH2_HI = 1e-3, 1.0 - 1e-3


@dataclasses.dataclass(frozen=True)
class RLSConfig:
    """Estimator hyperparameters — the sweep axis of the adaptive grid."""
    lam: float = 0.995      # forgetting factor
    dwell: int = 5          # min periods between gain re-placements
    kl_clamp: float = 4.0   # K_L_hat within [K_L_ref/c, K_L_ref*c]
    # divergence guard: cap on trace(P). A spike-corrupted regressor can
    # inflate the covariance geometrically (1/lam per period) until the
    # gain computation overflows f32; rescaling P back to this trace
    # bounds the estimator's worst-case step without touching theta.
    p_trace_max: float = 1e6


# Canonical packing order for traced RLS parameters (mirrors the
# profile/gain packing in repro.core.sim). `kl_ref` is the DESIGN model's
# K_L (the adapter linearizes against the model the gains were placed on,
# not the true plant); `tau_obj` is the closed-loop time constant implied
# by the original design, tau_obj = 1 / (kl_ref * k_i0).
RLS_FIELDS = ("lam", "dwell", "kl_clamp", "kl_ref", "tau_obj",
              "p_trace_max")


def rls_values(cfg: RLSConfig, design: PlantProfile, gains0: PIGains
               ) -> np.ndarray:
    tau_obj = 1.0 / (design.K_L * gains0.k_i)
    return np.asarray([cfg.lam, float(cfg.dwell), cfg.kl_clamp,
                       design.K_L, tau_obj, cfg.p_trace_max], np.float32)


class RLSState(NamedTuple):
    """Estimator + scheduled-gain state carried through the scan."""
    theta: jnp.ndarray         # (2,) [theta1, theta2]
    P: jnp.ndarray             # (2, 2) inverse-covariance
    prev_phi: jnp.ndarray      # (2,) regressor [pcap_L, progress_L] at i-1
    has_prev: jnp.ndarray      # bool: a regressor has been recorded
    since_update: jnp.ndarray  # periods since the last gain re-placement
    k_p: jnp.ndarray           # scheduled proportional gain
    k_i: jnp.ndarray           # scheduled integral gain
    tau_hat: jnp.ndarray       # current time-constant estimate [s]
    kl_hat: jnp.ndarray        # current static-gain estimate [Hz]


def rls_init(rls_vals, gains_vals_kp, gains_vals_ki) -> RLSState:
    """Fresh estimator around the design model packed in `rls_vals`."""
    kl_ref = rls_vals[3]
    tau0 = rls_vals[4] * kl_ref * gains_vals_kp  # tau = k_p * kl * tau_obj
    return RLSState(theta=jnp.stack([kl_ref * 0.5, jnp.float32(0.5)]),
                    P=jnp.eye(2, dtype=jnp.float32) * 1e2,
                    prev_phi=jnp.zeros((2,), jnp.float32),
                    has_prev=jnp.array(False),
                    since_update=jnp.float32(0.0),
                    k_p=jnp.float32(gains_vals_kp),
                    k_i=jnp.float32(gains_vals_ki),
                    tau_hat=jnp.asarray(tau0, jnp.float32),
                    kl_hat=jnp.asarray(kl_ref, jnp.float32))


def rls_step(rls_vals, s: RLSState, progress, pcap_l, dt) -> RLSState:
    """One RLS update + dwell-gated gain re-placement (pure, scan-safe).

    Mirrors `RLSAdapter.update` exactly: the regressor lags one period,
    theta is stored unclipped, theta2 is clipped only for the
    (tau_hat, K_L_hat) conversion, and gains move every `dwell`-th call.
    """
    lam, dwell, kl_clamp, kl_ref, tau_obj, p_max = (rls_vals[i]
                                                    for i in range(6))
    y = progress - kl_ref  # progress_L against the design model
    phi = s.prev_phi
    err = y - phi @ s.theta
    denom = lam + phi @ s.P @ phi
    k = (s.P @ phi) / denom
    theta = jnp.where(s.has_prev, s.theta + k * err, s.theta)
    P = jnp.where(s.has_prev, (s.P - jnp.outer(k, phi @ s.P)) / lam, s.P)
    # covariance trace clamp (divergence guard): a corrupt regressor
    # stream inflates P geometrically until the gain math overflows f32;
    # rescaling preserves the covariance's shape while bounding its
    # magnitude. The untriggered branch returns P itself, bit-for-bit.
    tr = P[0, 0] + P[1, 1]
    P = jnp.where(tr > p_max, P * (p_max / tr), P)

    th2 = jnp.clip(theta[1], _TH2_LO, _TH2_HI)
    tau_hat = dt * th2 / (1.0 - th2)
    kl_hat = jnp.clip(theta[0] * (dt + tau_hat) / dt,
                      kl_ref / kl_clamp, kl_ref * kl_clamp)

    since = s.since_update + 1.0
    place = since >= dwell
    k_p = jnp.where(place, tau_hat / (kl_hat * tau_obj), s.k_p)
    k_i = jnp.where(place, 1.0 / (kl_hat * tau_obj), s.k_i)
    since = jnp.where(place, 0.0, since)
    return RLSState(theta=theta, P=P,
                    prev_phi=jnp.stack([jnp.asarray(pcap_l, jnp.float32),
                                        jnp.asarray(y, jnp.float32)]),
                    has_prev=jnp.array(True),
                    since_update=since,
                    k_p=jnp.asarray(k_p, jnp.float32),
                    k_i=jnp.asarray(k_i, jnp.float32),
                    tau_hat=jnp.asarray(tau_hat, jnp.float32),
                    kl_hat=jnp.asarray(kl_hat, jnp.float32))


# Flat packing of RLSState for the uniform policy-state vector carried by
# the scan engine (repro.core.policies): theta(2) P(4) prev_phi(2)
# has_prev(1) since_update(1) k_p k_i tau_hat kl_hat.
RLS_STATE_SIZE = 14


def rls_pack(s: RLSState) -> jnp.ndarray:
    """RLSState -> (RLS_STATE_SIZE,) f32 vector (policy-state packing)."""
    return jnp.concatenate([
        jnp.asarray(s.theta, jnp.float32),
        jnp.asarray(s.P, jnp.float32).reshape(4),
        jnp.asarray(s.prev_phi, jnp.float32),
        jnp.stack([jnp.asarray(s.has_prev, jnp.float32),
                   jnp.asarray(s.since_update, jnp.float32),
                   jnp.asarray(s.k_p, jnp.float32),
                   jnp.asarray(s.k_i, jnp.float32),
                   jnp.asarray(s.tau_hat, jnp.float32),
                   jnp.asarray(s.kl_hat, jnp.float32)])])


def rls_unpack(v) -> RLSState:
    """Inverse of `rls_pack` (has_prev round-trips through a 0/1 float)."""
    return RLSState(theta=v[0:2], P=v[2:6].reshape(2, 2),
                    prev_phi=v[6:8], has_prev=v[8] > 0.5,
                    since_update=v[9], k_p=v[10], k_i=v[11],
                    tau_hat=v[12], kl_hat=v[13])


@dataclasses.dataclass
class RLSAdapter:
    """Numpy reference estimator (equivalence oracle for `rls_step`)."""
    gains0: PIGains
    profile: PlantProfile
    lam: float = 0.995          # forgetting factor
    dwell: int = 5              # min periods between gain updates
    kl_clamp: float = 4.0       # K_L_hat within [K_L/c, K_L*c]
    p_trace_max: float = 1e6    # covariance trace clamp (divergence guard)

    def __post_init__(self):
        self.theta = np.array([self.profile.K_L * 0.5, 0.5])
        self.P = np.eye(2) * 1e2
        self._prev: tuple | None = None
        self._since_update = 0
        self.tau_hat = self.profile.tau
        self.kl_hat = self.profile.K_L

    def on_change(self) -> None:
        """Phase-change reaction (mirrors the engine-side pi_rls
        `on_change` hook): the identified model is stale, so blow the
        covariance back to its fresh-init value, drop the old-phase
        regressor, and re-place the gains at the very next update."""
        self.P = np.eye(2) * 1e2
        self._prev = None
        self._since_update = self.dwell

    def update(self, gains: PIGains, progress: float, pcap_l: float,
               dt: float) -> PIGains:
        y = progress - self.profile.K_L  # progress_L
        if self._prev is not None:
            phi = np.array(self._prev)  # [pcap_L, progress_L] at i-1
            err = y - phi @ self.theta
            denom = self.lam + phi @ self.P @ phi
            k = (self.P @ phi) / denom
            self.theta = self.theta + k * err
            self.P = (self.P - np.outer(k, phi @ self.P)) / self.lam
            tr = float(np.trace(self.P))
            if tr > self.p_trace_max:
                self.P = self.P * (self.p_trace_max / tr)
        self._prev = (pcap_l, y)

        th1, th2 = self.theta
        th2 = float(np.clip(th2, _TH2_LO, _TH2_HI))
        tau_hat = dt * th2 / (1.0 - th2)
        kl_hat = th1 * (dt + tau_hat) / dt
        lo, hi = (self.profile.K_L / self.kl_clamp,
                  self.profile.K_L * self.kl_clamp)
        kl_hat = float(np.clip(kl_hat, lo, hi))
        self.tau_hat, self.kl_hat = tau_hat, kl_hat

        self._since_update += 1
        if self._since_update < self.dwell:
            return gains
        self._since_update = 0
        # re-place poles with the adapted model, keep tau_obj implied by the
        # original design: tau_obj = 1 / (K_L0 * K_I0)
        tau_obj = 1.0 / (self.profile.K_L * self.gains0.k_i)
        return dataclasses.replace(
            gains,
            k_p=tau_hat / (kl_hat * tau_obj),
            k_i=1.0 / (kl_hat * tau_obj),
        )
