"""Offline-RL power control (cf. Raj et al., "Offline Reinforcement-
Learning-Based Power Control"): a fitted-Q, linear-in-features policy
trained on transition datasets harvested from closed-loop sweeps.

Pipeline (everything after harvesting is pure JAX and jits):

1. ``build_dataset(traces, profile, epsilon)`` — turn `sweep(...,
   collect_traces=True)` traces into (s, a, r, s') transitions. The state
   is setpoint-relative progress s = progress/setpoint; the action is the
   normalized cap u = (pcap-min)/(max-min); the reward trades normalized
   power against performance debt: r = -power_norm - rho*max(0, 1 - s').
2. ``fit_offline_rl(dataset)`` — fitted Q-iteration on the quadratic
   feature map phi(s,u) = [1, s, s^2, u, u^2, s*u]: each sweep solves the
   ridge-regularized least squares to the Bellman targets, the max over
   next actions taken on the discrete candidate grid.
3. ``OfflineRLPolicy(weights=...)`` — at deployment the greedy policy
   evaluates Q on ``N_ACTIONS`` candidate caps spanning the actuator
   range and applies the argmax. Weights live in the traced param vector,
   so an ensemble of trained policies vmaps down the sweep's policy axis.

State: [0] = previous normalized action (traced for analysis; the greedy
policy itself is memoryless).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import PIGains
from repro.core.plant import PlantProfile
from repro.core.policies.base import (POLICY_STATE_DIM, Policy, pack_values,
                                      register_branch)

N_FEATURES = 6
N_ACTIONS = 9  # candidate caps spanning [pcap_min, pcap_max]


def features(s, u):
    """phi(s, u) = [1, s, s^2, u, u^2, s*u], broadcasting over s/u."""
    s, u = jnp.broadcast_arrays(jnp.asarray(s, jnp.float32),
                                jnp.asarray(u, jnp.float32))
    return jnp.stack([jnp.ones_like(s), s, s * s, u, u * u, s * u],
                     axis=-1)


def _rl_step(vals, state, obs):
    w = vals[1:1 + N_FEATURES]
    s = obs.progress / jnp.maximum(obs.gains.setpoint, 1e-9)
    us = jnp.linspace(0.0, 1.0, N_ACTIONS)
    q = features(s, us) @ w
    u = us[jnp.argmax(q)]
    g = obs.gains
    pcap = g.pcap_min + u * (g.pcap_max - g.pcap_min)
    return state.at[0].set(u), pcap


def _rl_init(vals, gains):
    # start at full power like every other policy
    return jnp.zeros((POLICY_STATE_DIM,), jnp.float32).at[0].set(1.0)


def _rl_extras(state):
    return {"action": state[0]}


register_branch("offline_rl", _rl_step, _rl_init, _rl_extras)


@dataclasses.dataclass(frozen=True)
class OfflineRLPolicy(Policy):
    """Greedy fitted-Q policy; ``weights`` is the phi-coefficient tuple."""
    weights: Tuple[float, ...] = (0.0,) * N_FEATURES

    @property
    def branch(self) -> str:
        return "offline_rl"

    def values(self, profile: PlantProfile, gains: PIGains) -> np.ndarray:
        if len(self.weights) != N_FEATURES:
            raise ValueError(f"OfflineRLPolicy needs {N_FEATURES} feature "
                             f"weights, got {len(self.weights)}")
        return pack_values(*self.weights)


# ---- dataset harvesting (host-side, numpy) --------------------------------

def transitions_from_traces(prog, pcap, power, valid, setpoint, p_lo,
                            p_hi, cap_lo, cap_rng, rho: float = 3.0
                            ) -> Dict[str, np.ndarray]:
    """(s, a, r, s') rows from trace arrays shaped (..., T), with the
    normalizers (setpoint, power range, cap range) scalars OR per-run
    arrays broadcasting over the leading axes — the generalization that
    lets one call convert a heterogeneous (profile x epsilon) chunk.
    Consecutive live steps become transitions; ``valid`` gates both
    endpoints."""
    prog = np.asarray(prog, np.float32)
    pcap = np.asarray(pcap, np.float32)
    power = np.asarray(power, np.float32)
    valid = np.asarray(valid, bool)
    per_run = lambda x: np.asarray(x, np.float32)[..., None]

    s = prog / np.maximum(per_run(setpoint), 1e-9)
    a = (pcap - per_run(cap_lo)) / np.maximum(per_run(cap_rng), 1e-9)
    pw = ((power - per_run(p_lo))
          / np.maximum(per_run(p_hi) - per_run(p_lo), 1e-9))

    # a[t] is the command computed at t and applied over period t+1, so
    # the transition is (s[t], a[t]) -> s[t+1] with the reward measured
    # on the NEXT period's outcome
    m = (valid[..., :-1] & valid[..., 1:]).reshape(-1)
    s_t = s[..., :-1].reshape(-1)[m]
    a_t = a[..., :-1].reshape(-1)[m]
    s_n = s[..., 1:].reshape(-1)[m]
    pw_n = pw[..., 1:].reshape(-1)[m]
    r = -pw_n - rho * np.maximum(0.0, 1.0 - s_n)
    return {"s": s_t, "a": a_t, "r": r.astype(np.float32), "s2": s_n}


def build_dataset(traces: Dict[str, np.ndarray], profile: PlantProfile,
                  epsilon: float, rho: float = 3.0) -> Dict[str, np.ndarray]:
    """Transitions from closed-loop traces of ONE profile.

    ``traces`` holds arrays shaped (..., T) — a `sweep(...,
    collect_traces=True)` result's traces (or one `simulate_closed_loop`
    run's, with T only). Returns flat arrays {s, a, r, s2} of equal
    length N. For grids too large to hold in trace form, use
    `harvest_dataset`, which streams chunks through the executor.
    """
    prog = np.asarray(traces["progress"], np.float32)
    valid = traces.get("valid", np.ones_like(prog, bool))
    return transitions_from_traces(
        prog, traces["pcap"], traces["power"], valid,
        (1.0 - epsilon) * profile.progress_max,
        float(profile.power_of_pcap(profile.pcap_min)),
        float(profile.power_of_pcap(profile.pcap_max)),
        profile.pcap_min, profile.pcap_max - profile.pcap_min, rho)


def harvest_dataset(profiles, epsilons, seeds, *, total_work: float,
                    max_time: float = 3600.0, dt: float = 1.0,
                    tau_obj: float = 10.0, rho: float = 3.0,
                    chunk_size: int = 1024, devices=None,
                    backend: str = "scan", durable=None,
                    campaign=None) -> Dict[str, np.ndarray]:
    """Bounded-memory transition harvest over a (profiles x epsilons x
    seeds) PI grid: the full-trace sweep streams through the chunked
    executor (`sweep(consume=...)`) and each chunk is converted to
    (s, a, r, s') rows on the fly — only O(chunk * T) trace memory ever
    exists, so paper-scale training sets no longer require the whole
    sweep's traces at once. Row order and values match concatenating
    `build_dataset` over per-(profile, epsilon) one-shot sweeps.

    ``durable=dir`` makes the harvest crash-safe end to end: each
    chunk's transitions are spooled atomically to
    ``dir/parts/part_<lo>.npz`` BEFORE the supervisor journal-commits
    the chunk, so `supervisor.resume_campaign(dir)` recomputes only the
    uncommitted chunks and reassembles the full dataset from disk —
    the in-memory accumulation a crash would lose is bypassed
    entirely."""
    from repro.core import sim  # late: policies must not import sim

    profs = [sim._resolve(p) for p in
             ([profiles] if isinstance(profiles, (str, PlantProfile))
              else profiles)]
    eps = [float(e) for e in epsilons]
    E, S = len(eps), len(seeds)
    setp = np.asarray([[(1.0 - e) * p.progress_max for e in eps]
                       for p in profs], np.float32)
    p_lo = np.asarray([p.power_of_pcap(p.pcap_min) for p in profs],
                      np.float32)
    p_hi = np.asarray([p.power_of_pcap(p.pcap_max) for p in profs],
                      np.float32)
    cap_lo = np.asarray([p.pcap_min for p in profs], np.float32)
    cap_rng = np.asarray([p.pcap_max - p.pcap_min for p in profs],
                         np.float32)

    def _chunk_transitions(lo, hi, traces):
        idx = np.arange(lo, hi)
        ip, ie = idx // (E * S), (idx // S) % E
        return transitions_from_traces(
            traces["progress"], traces["pcap"], traces["power"],
            traces["valid"], setp[ip, ie], p_lo[ip], p_hi[ip],
            cap_lo[ip], cap_rng[ip], rho)

    keys = ("s", "a", "r", "s2")
    if durable is not None:
        import os
        from pathlib import Path

        from repro.core import supervisor
        supervisor.save_campaign_spec(durable, "harvest_dataset", dict(
            profiles=profiles, epsilons=eps, seeds=list(seeds),
            total_work=total_work, max_time=max_time, dt=dt,
            tau_obj=tau_obj, rho=rho, chunk_size=chunk_size,
            devices=devices, backend=backend, campaign=campaign))
        part_dir = Path(durable) / "parts"
        part_dir.mkdir(parents=True, exist_ok=True)

        def consume(lo, hi, out):
            traces, _final = out
            d = _chunk_transitions(lo, hi, traces)
            # atomic spool BEFORE the journal commit: a committed chunk
            # always has its part on disk; a replayed chunk rewrites the
            # identical bytes
            p = part_dir / f"part_{lo:010d}.npz"
            tmp = p.with_name(p.name + ".tmp")
            with open(tmp, "wb") as fh:
                np.savez(fh, **d)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, p)

        sim.sweep(profs, eps, seeds, total_work=total_work,
                  max_time=max_time, dt=dt, tau_obj=tau_obj,
                  collect_traces=True, backend=backend,
                  chunk_size=chunk_size, devices=devices,
                  consume=consume, durable=durable, campaign=campaign)
        out: Dict[str, list] = {k: [] for k in keys}
        for p in sorted(part_dir.glob("part_*.npz")):
            with np.load(p) as z:
                for k in keys:
                    out[k].append(z[k])
        return {k: np.concatenate(v) if v
                else np.zeros((0,), np.float32)
                for k, v in out.items()}

    parts: Dict[str, list] = {k: [] for k in keys}

    def consume(lo, hi, out):
        traces, _final = out
        d = _chunk_transitions(lo, hi, traces)
        for k in parts:
            parts[k].append(d[k])

    sim.sweep(profs, eps, seeds, total_work=total_work,
              max_time=max_time, dt=dt, tau_obj=tau_obj,
              collect_traces=True, backend=backend,
              chunk_size=chunk_size, devices=devices, consume=consume)
    return {k: np.concatenate(v) if v else np.zeros((0,), np.float32)
            for k, v in parts.items()}


# ---- fitted Q-iteration (pure JAX) ----------------------------------------

@functools.partial(jax.jit, static_argnames=("n_iters",))
def _fqi(s, a, r, s2, gamma, ridge, n_iters: int):
    phi = features(s, a)                                   # (N, F)
    us = jnp.linspace(0.0, 1.0, N_ACTIONS)
    phi2 = features(s2[:, None], us[None, :])              # (N, L, F)
    A = phi.T @ phi + ridge * jnp.eye(N_FEATURES, dtype=jnp.float32)

    def body(w, _):
        q2 = (phi2 @ w).max(-1)                            # (N,)
        y = r + gamma * q2
        w = jnp.linalg.solve(A, phi.T @ y)
        return w, None

    w, _ = jax.lax.scan(body, jnp.zeros((N_FEATURES,), jnp.float32),
                        None, length=n_iters)
    return w


def fit_offline_rl(dataset: Dict[str, np.ndarray], gamma: float = 0.9,
                   ridge: float = 1e-3, n_iters: int = 50
                   ) -> OfflineRLPolicy:
    """Fitted Q-iteration over a harvested transition set -> policy."""
    if len(dataset["s"]) == 0:
        raise ValueError("empty transition dataset")
    w = _fqi(jnp.asarray(dataset["s"]), jnp.asarray(dataset["a"]),
             jnp.asarray(dataset["r"]), jnp.asarray(dataset["s2"]),
             jnp.float32(gamma), jnp.float32(ridge), int(n_iters))
    return OfflineRLPolicy(weights=tuple(float(x) for x in w))
