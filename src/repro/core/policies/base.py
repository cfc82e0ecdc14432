"""Pluggable power-policy subsystem: the uniform scan-citizen contract.

The paper's PI controller (Eq. 4) is one point in a space of power-capping
policies (offline-RL power control, duty-cycle modulation, ...). This
package turns "which controller runs inside the closed loop" into data the
scan engine (`repro.core.sim`) dispatches through, instead of a fork of
`engine_step` per policy.

Contract (all pure JAX, vmap/scan-safe):

* ``policy_values(policy, profile, gains) -> (POLICY_PARAM_DIM,) f32`` —
  the policy's hyperparameters packed on the host into a fixed-width
  numpy vector that the engine traces (slot 0 is the dispatch kind,
  assigned by the caller for heterogeneous grids). Packing makes no
  device call; because params are traced, hyperparameter grids vmap
  without recompiling.
* ``policy_init(policy, vals, gains) -> (POLICY_STATE_DIM,) f32`` — the
  policy's initial state packed into a fixed-width vector. A UNIFORM
  state width is what lets heterogeneous policies share one compiled
  engine: every policy's carry has the same pytree structure.
* ``policy_step(policy, vals, state, obs) -> (state, pcap)`` — one
  control period: observe (aggregated progress, measured power, dt, the
  actuator/setpoint context in ``obs.gains``) and emit the next power
  cap in watts.

Policies are *branches*: a branch is the static compute graph (step/init/
extras functions over the packed vectors), registered by name in
``BRANCHES``; a ``Policy`` dataclass instance is the host-side config that
names its branch and packs its traced values. Two instances of the same
branch differ only in traced data — no recompile. A heterogeneous policy
list compiles to ONE engine via ``lax.switch`` over the branch tuple with
the kind index traced (``branch_step``), so `sweep(policies=[...])` stays
one executable per scan-length bucket.

Adding a custom policy is ~10 lines — see README "Policies".
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple, \
    Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import PIGains
from repro.core.plant import PlantProfile

# Fixed widths of the packed policy vectors. STATE must hold the largest
# policy state (PI + the 14-slot RLS estimator block = 16) plus the
# branch tag; PARAM must hold kind + the largest hyperparameter/weight
# set (offline-RL: 6 feature weights).
POLICY_STATE_DIM = 17
POLICY_PARAM_DIM = 10
# Slot stamped with the producing branch's registry id (`branch_tag`) at
# init and preserved by every step, so a packed state resumed under a
# DIFFERENT branch is detectable instead of silently misread. 0 means
# untagged (hand-built vectors skip the check).
BRANCH_TAG_SLOT = 16


class PolicyObs(NamedTuple):
    """Per-period observation handed to `policy_step`.

    ``gains`` carries the shared actuator/setpoint context (Eq. 2
    transform, pcap range, setpoint) as a pytree of traced scalars — all
    policies cap against the same plant model the PI was designed on.
    """
    progress: jnp.ndarray  # Eq. 1 aggregated heart-rate [Hz]
    power: jnp.ndarray     # measured power this period [W]
    dt: jnp.ndarray        # control period [s]
    gains: PIGains
    # 1.0 on periods where the engine's change-point detector fired
    # (repro.core.workloads.detect); 0.0 otherwise / detector off
    phase_change: Union[jnp.ndarray, float] = 0.0


class Branch(NamedTuple):
    """Static compute graph of one policy kind."""
    step: Callable       # (vals, state, obs) -> (state, pcap)
    init: Callable       # (vals, gains) -> state
    extras: Callable     # (state) -> dict of per-step trace extras
    on_change: Callable  # (vals, state) -> state, on a detected phase change


BRANCHES: Dict[str, Branch] = {}


def register_branch(name: str, step: Callable, init: Callable,
                    extras: Optional[Callable] = None,
                    on_change: Optional[Callable] = None) -> None:
    """Register a policy branch (the extension point for custom policies).

    ``on_change`` is applied to the packed state when the engine's
    change-point detector fires (default: identity) — e.g. adaptive PI
    resets its RLS covariance there so gains re-converge fast."""
    for other in BRANCHES:
        if other != name and branch_tag(other) == branch_tag(name):
            raise ValueError(f"branch tag collision: '{name}' and "
                             f"'{other}' hash alike; pick another name")
    BRANCHES[name] = Branch(step=step, init=init,
                            extras=extras or (lambda state: {}),
                            on_change=on_change
                            or (lambda vals, state: state))


@dataclasses.dataclass(frozen=True)
class Policy:
    """Host-side policy config: names a branch, packs traced values."""

    @property
    def branch(self) -> str:
        raise NotImplementedError

    def values(self, profile: PlantProfile, gains: PIGains) -> np.ndarray:
        """Policy hyperparameters at slots [1:]; slot 0 (kind) is left 0."""
        return np.zeros((POLICY_PARAM_DIM,), np.float32)


def pack_values(*params) -> np.ndarray:
    """Pack params into slots [1:1+len] of a zeroed PARAM host vector."""
    v = np.zeros((POLICY_PARAM_DIM,), np.float32)
    v[1:1 + len(params)] = np.asarray(params, np.float32)
    return v


# ---- module-level contract functions --------------------------------------

BranchSpec = Union[str, Tuple[str, ...], Policy]


def as_branches(policy: BranchSpec) -> Tuple[str, ...]:
    if isinstance(policy, Policy):
        return (policy.branch,)
    if isinstance(policy, str):
        return (policy,)
    return tuple(policy)


def policy_values(policy: Policy, profile: PlantProfile, gains: PIGains,
                  kind: int = 0) -> np.ndarray:
    """The contract's `policy_values`: the f32 host param vector, with
    the dispatch kind (index into the active branch tuple) at slot 0. A
    `Policy.values` that returns a `jax.Array` is copied to the host."""
    v = np.array(policy.values(profile, gains), np.float32)
    v[0] = kind
    return v


def branch_tag(name: str) -> int:
    """Stable numeric id of a branch, derived from its NAME (not the
    registry order) so tags in checkpointed state vectors survive across
    sessions and import orders. 0 is reserved for 'untagged'; values fit
    exactly in a float32 slot. `register_branch` rejects collisions."""
    return zlib.crc32(name.encode()) % 65521 + 1


def tag_branch(tag: int) -> Optional[str]:
    """Inverse of `branch_tag` over the registered branches; None for
    0/unknown tags."""
    for name in BRANCHES:
        if branch_tag(name) == tag:
            return name
    return None


def branch_step(policy: BranchSpec) -> Callable:
    """(vals, state, obs) -> (state, pcap); `lax.switch` on vals[0] when
    more than one branch is active (heterogeneous grids). The branch tag
    slot is carried through unchanged."""
    bs = [BRANCHES[b] for b in as_branches(policy)]
    if len(bs) == 1:
        inner = bs[0].step
    else:
        def inner(vals, state, obs):
            idx = jnp.clip(vals[0].astype(jnp.int32), 0, len(bs) - 1)
            return jax.lax.switch(idx, [b.step for b in bs], vals, state,
                                  obs)

    def step(vals, state, obs):
        new, pcap = inner(vals, state, obs)
        return new.at[BRANCH_TAG_SLOT].set(state[BRANCH_TAG_SLOT]), pcap

    return step


def branch_init(policy: BranchSpec) -> Callable:
    names = as_branches(policy)
    bs = [BRANCHES[b] for b in names]
    tags = jnp.asarray([float(branch_tag(b)) for b in names],
                       jnp.float32)
    if len(bs) == 1:
        def init(vals, gains):
            return bs[0].init(vals, gains).at[BRANCH_TAG_SLOT].set(
                tags[0])
    else:
        def init(vals, gains):
            idx = jnp.clip(vals[0].astype(jnp.int32), 0, len(bs) - 1)
            state = jax.lax.switch(idx, [b.init for b in bs], vals,
                                   gains)
            return state.at[BRANCH_TAG_SLOT].set(tags[idx])

    return init


def branch_on_change(policy: BranchSpec) -> Callable:
    """(vals, state) -> state, the phase-change reaction; `lax.switch` on
    vals[0] for heterogeneous sets. The branch tag is preserved."""
    bs = [BRANCHES[b] for b in as_branches(policy)]
    if len(bs) == 1:
        inner = bs[0].on_change
    else:
        def inner(vals, state):
            idx = jnp.clip(vals[0].astype(jnp.int32), 0, len(bs) - 1)
            return jax.lax.switch(idx, [b.on_change for b in bs], vals,
                                  state)

    def on_change(vals, state):
        new = inner(vals, state)
        return new.at[BRANCH_TAG_SLOT].set(state[BRANCH_TAG_SLOT])

    return on_change


def branch_extras(policy: BranchSpec) -> Callable:
    """Per-step trace extras. Heterogeneous branch sets emit none (the
    trace dict structure is static and must match across lanes)."""
    names = as_branches(policy)
    if len(set(names)) == 1:
        return BRANCHES[names[0]].extras
    return lambda state: {}


def policy_step(policy: BranchSpec, vals, state, obs: PolicyObs):
    """The contract's `policy_step(vals, state, obs) -> (state, pcap)`."""
    return branch_step(policy)(vals, state, obs)


def policy_init(policy: BranchSpec, vals, gains: PIGains):
    """The contract's `policy_init(vals) -> PolicyState` (needs the gains
    context: e.g. PI seeds its carried command at the actuator max)."""
    return branch_init(policy)(vals, gains)


def resolve_kinds(policies: Sequence[Policy]
                  ) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """Dedup the branch set (order of first appearance) and map each
    policy to its kind index within it."""
    branches = tuple(dict.fromkeys(p.branch for p in policies))
    kinds = tuple(branches.index(p.branch) for p in policies)
    return branches, kinds
