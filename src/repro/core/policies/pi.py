"""PI and adaptive-PI (RLS gain-scheduled) policies — the paper's Eq. 4
controller as a policy-branch citizen.

Two branches share the PI slots of the packed state vector:

* ``pi``      — fixed gains. State: [prev_error, prev_pcap_l, 0...].
* ``pi_rls``  — RLS gain scheduling (§5.2 extension). State: PI slots +
  the 14-slot packed `RLSState` (see `repro.core.adaptive.rls_pack`).
  Param slots [1:7] carry `rls_values` (lam, dwell, kl_clamp, kl_ref,
  tau_obj, p_trace_max).

The step functions call the SAME `pi_step` / `rls_step` primitives in the
SAME order as the pre-policy engine did, so PI-via-policy reproduces the
old engine's trajectories bit-for-bit (tests assert exact equality).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core.adaptive import (RLSConfig, rls_init, rls_pack, rls_step,
                                 rls_unpack, rls_values)
from repro.core.controller import PIGains, PIState, pi_init, pi_step
from repro.core.plant import PlantProfile
from repro.core.adaptive import RLS_STATE_SIZE
from repro.core.policies.base import (BRANCH_TAG_SLOT, POLICY_STATE_DIM,
                                      Policy, pack_values, register_branch)

# state layout: [0]=prev_error [1]=prev_pcap_l, then the packed RLSState
# block, then the branch tag. `repro.core.sim` imports these (as
# PI_RLS_LO/HI and pi_pack) for the resume path — this module owns the
# layout, with the widths derived from their single sources of truth.
PI_RLS_LO = 2
PI_RLS_HI = PI_RLS_LO + RLS_STATE_SIZE
assert PI_RLS_HI == BRANCH_TAG_SLOT, \
    "PI+RLS slots must end exactly at the branch tag slot"
_RLS_LO, _RLS_HI = PI_RLS_LO, PI_RLS_HI


def pi_pack(pi: PIState, rls_block=None) -> jnp.ndarray:
    v = jnp.zeros((POLICY_STATE_DIM,), jnp.float32)
    v = v.at[0].set(pi.prev_error).at[1].set(pi.prev_pcap_l)
    if rls_block is not None:
        v = v.at[_RLS_LO:_RLS_HI].set(rls_block)
    return v


def _pi_step(vals, state, obs):
    pi = PIState(prev_error=state[0], prev_pcap_l=state[1])
    pi2, pcap = pi_step(obs.gains, pi, obs.progress, obs.dt)
    return pi_pack(pi2, state[_RLS_LO:_RLS_HI]), pcap


def _pi_init(vals, gains):
    return pi_pack(pi_init(gains))


def _pi_rls_step(vals, state, obs):
    # same call order as the fused engine always had: the estimator sees
    # the PREVIOUS linearized command (prev_pcap_l) alongside this
    # period's aggregated progress, then the PI runs on the (possibly
    # re-placed) gains
    rls = rls_unpack(state[_RLS_LO:_RLS_HI])
    rls = rls_step(vals[1:7], rls, obs.progress, state[1], obs.dt)
    g = obs.gains.with_gains(rls.k_p, rls.k_i)
    pi2, pcap = pi_step(g, PIState(prev_error=state[0],
                                   prev_pcap_l=state[1]),
                        obs.progress, obs.dt)
    return pi_pack(pi2, rls_pack(rls)), pcap


def _pi_rls_init(vals, gains):
    rls = rls_init(vals[1:7], gains.k_p, gains.k_i)
    return pi_pack(pi_init(gains), rls_pack(rls))


def _pi_rls_extras(state):
    r = rls_unpack(state[_RLS_LO:_RLS_HI])
    return {"k_p": r.k_p, "k_i": r.k_i, "tau_hat": r.tau_hat,
            "kl_hat": r.kl_hat, "theta1": r.theta[0],
            "theta2": r.theta[1]}


def _pi_rls_on_change(vals, state):
    # phase change detected: the identified model is stale. Blow the
    # covariance back to its fresh-init value (the estimator re-converges
    # at init speed), drop the old-phase regressor, and force the next
    # rls_step to re-place the PI gains immediately (since_update >=
    # dwell) instead of waiting out the dwell window.
    rls = rls_unpack(state[_RLS_LO:_RLS_HI])
    rls = rls._replace(P=jnp.eye(2, dtype=jnp.float32) * 1e2,
                       has_prev=jnp.array(False),
                       since_update=vals[2])  # vals[1:7][1] = dwell
    return state.at[_RLS_LO:_RLS_HI].set(rls_pack(rls))


register_branch("pi", _pi_step, _pi_init)
register_branch("pi_rls", _pi_rls_step, _pi_rls_init, _pi_rls_extras,
                on_change=_pi_rls_on_change)

# default probe length for the runtime re-identification recipe below
REEXCITE_K = 4


def reexcite_cap(pcap: float, step_i: int, frac: float,
                 lo: float, hi: float) -> float:
    """Post-alarm re-excitation: the runtime half of the
    re-identification recipe whose in-engine half is `_pi_rls_on_change`.

    The on_change hook blows the covariance and forces re-placement, but
    a freshly-reset estimator staring at steady-state operation learns
    nothing — the regressor barely moves. For the first few healthy
    windows after an alarm, alternate the commanded cap +/- ``frac`` of
    the actuation range (persistent excitation), clipped to the
    actuator's limits. `NRM.control_step` applies this for
    ``reexcite=`` windows after each detector alarm."""
    span = float(frac) * (float(hi) - float(lo))
    sign = 1.0 if int(step_i) % 2 == 0 else -1.0
    return float(min(max(float(pcap) + sign * span, float(lo)),
                     float(hi)))


@dataclasses.dataclass(frozen=True)
class PIPolicy(Policy):
    """Eq. 4 PI, optionally RLS gain-scheduled (`adaptive=RLSConfig()`).

    ``design`` names the plant model the initial gains were placed on
    (gain-shift scenarios); the estimator linearizes against it. Defaults
    to the profile the policy runs on.
    """
    adaptive: Optional[RLSConfig] = None
    design: Optional[PlantProfile] = None

    @property
    def branch(self) -> str:
        return "pi_rls" if self.adaptive is not None else "pi"

    def values(self, profile: PlantProfile, gains: PIGains) -> np.ndarray:
        if self.adaptive is None:
            return pack_values()
        return pack_values(*rls_values(self.adaptive,
                                       self.design or profile, gains))
