"""Simulated power-to-progress plants (paper §4.3–4.4 physics).

The plant is the paper's identified model of a cluster node running a
memory-bound workload under a RAPL powercap:

* actuator error  : power = a * pcap + b                     (§4.3)
* static char.    : progress* = K_L * (1 - exp(-alpha*(power - beta)))
* dynamics        : first-order with time constant tau       (Eq. 3)
* noise           : heteroscedastic with socket count        (§4.3, Fig. 3)
* disturbances    : sporadic exogenous drops to ~10 Hz       (§5.2, yeti)

Profiles `gros`, `dahu`, `yeti` carry the exact Table 2 parameters — the
identification benchmarks must recover them. The TPU-flavoured profiles
(`v5e-chip`, `v5e-host`) transplant the same physics onto chip-level power
ranges; their knees are seeded from the per-cell dominant roofline term
(memory-bound cells saturate earlier — see repro.core.phases).

Everything is a pure function of (state, rng) so plants vmap across a
simulated fleet (repro.core.hierarchy).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


# Canonical packing order for traced plant parameters. Owned here (the
# module that defines the fields) and shared by repro.core.sim's packed
# engine arguments and repro.core.workloads' phase-schedule rows, so a
# packed row means the same thing everywhere.
PROFILE_FIELDS = ("a", "b", "alpha", "beta", "K_L", "tau", "pcap_min",
                  "pcap_max", "n_sockets", "noise_scale", "power_noise",
                  "drop_prob", "drop_exit_prob", "drop_level")


@dataclasses.dataclass(frozen=True)
class PlantProfile:
    name: str
    a: float          # RAPL slope
    b: float          # RAPL offset [W]
    alpha: float      # power-to-progress curvature [1/W]
    beta: float       # power offset [W]
    K_L: float        # linear gain [Hz]
    tau: float = 1.0 / 3.0  # time constant [s]
    pcap_min: float = 40.0
    pcap_max: float = 120.0
    n_sockets: int = 1
    noise_scale: float = 0.6   # progress noise stddev per sqrt(socket) [Hz]
    power_noise: float = 1.0   # measured power noise [W]
    drop_prob: float = 0.0     # per-step probability of an exogenous drop
    drop_exit_prob: float = 0.3
    drop_level: float = 10.0   # Hz during a drop event (paper: ~10 Hz)

    # ---- static characteristic -------------------------------------------
    def power_of_pcap(self, pcap):
        return self.a * pcap + self.b

    def static_progress(self, pcap):
        power = self.power_of_pcap(pcap)
        return self.K_L * (1.0 - jnp.exp(-self.alpha * (power - self.beta)))

    @functools.cached_property
    def progress_max(self) -> float:
        """Static progress at pcap_max, one float32 device evaluation
        per profile: the profile is frozen, so the first value is kept
        and every later read (each `PIGains.from_model` of a grid) is a
        host attribute lookup."""
        return float(self.static_progress(self.pcap_max))


# Table 2 of the paper, verbatim.
PROFILES = {
    "gros": PlantProfile("gros", a=0.83, b=7.07, alpha=0.047, beta=28.5,
                         K_L=25.6, n_sockets=1, noise_scale=0.45),
    "dahu": PlantProfile("dahu", a=0.94, b=0.17, alpha=0.032, beta=34.8,
                         K_L=42.4, n_sockets=2, noise_scale=1.4),
    "yeti": PlantProfile("yeti", a=0.89, b=2.91, alpha=0.023, beta=33.7,
                         K_L=78.5, n_sockets=4, noise_scale=3.2,
                         drop_prob=0.02),
    # TPU-flavoured plants (hardware adaptation; see DESIGN.md §2). Power
    # range is chip TDP-ish; K_L is a tokens/s-scaled rate; the knee (alpha,
    # beta) reflects a memory-bound cell saturating well under TDP.
    "v5e-chip": PlantProfile("v5e-chip", a=0.97, b=2.0, alpha=0.035,
                             beta=55.0, K_L=1200.0, tau=0.5, pcap_min=90.0,
                             pcap_max=250.0, n_sockets=1, noise_scale=18.0),
    "v5e-host": PlantProfile("v5e-host", a=0.95, b=12.0, alpha=0.018,
                             beta=180.0, K_L=4500.0, tau=0.8, pcap_min=350.0,
                             pcap_max=1000.0, n_sockets=4, noise_scale=120.0,
                             drop_prob=0.01, drop_level=500.0),
}


class PlantState(NamedTuple):
    progress_l: jnp.ndarray  # linearized progress state (Eq. 2/3)
    dropped: jnp.ndarray     # bool: inside an exogenous drop event
    energy: jnp.ndarray      # accumulated energy [J]
    work: jnp.ndarray        # accumulated work units (integral of progress)


def plant_init(profile: PlantProfile, pcap0: Optional[float] = None
               ) -> PlantState:
    pcap0 = profile.pcap_max if pcap0 is None else pcap0
    p0 = profile.static_progress(pcap0)
    return PlantState(progress_l=jnp.float32(p0 - profile.K_L),
                      dropped=jnp.array(False),
                      energy=jnp.float32(0.0),
                      work=jnp.float32(0.0))


def pcap_linearize(profile: PlantProfile, pcap):
    """Eq. 2: pcap_L = -exp(-alpha (a pcap + b - beta)) (negative, in (-1,0])."""
    return -jnp.exp(-profile.alpha
                    * (profile.a * pcap + profile.b - profile.beta))


def plant_step(profile: PlantProfile, state: PlantState, pcap, dt,
               key) -> Tuple[PlantState, dict]:
    """One control period: apply pcap for dt seconds, observe (progress, power).

    Pure function — vmap/scan friendly. Returns (new_state, measurements).
    """
    kn, kp, kd, ke = jax.random.split(key, 4)
    pcap = jnp.clip(pcap, profile.pcap_min, profile.pcap_max)
    pl = pcap_linearize(profile, pcap)
    # Eq. 3 first-order dynamics in the linearized coordinates
    w = dt / (dt + profile.tau)
    new_pl = profile.K_L * w * pl + (1.0 - w) * state.progress_l

    # exogenous drop events (two-state Markov chain; §5.2)
    enter = jax.random.bernoulli(kd, profile.drop_prob)
    exit_ = jax.random.bernoulli(ke, profile.drop_exit_prob)
    dropped = jnp.where(state.dropped, ~exit_, enter)

    clean = new_pl + profile.K_L
    noise = (profile.noise_scale * jnp.sqrt(jnp.float32(profile.n_sockets))
             * jax.random.normal(kn))
    progress = jnp.maximum(0.0, jnp.where(dropped, profile.drop_level,
                                          clean) + noise)

    power_true = profile.power_of_pcap(pcap)
    power_meas = power_true + profile.power_noise * jax.random.normal(kp)
    new_state = PlantState(
        progress_l=new_pl,
        dropped=dropped,
        energy=state.energy + power_true * dt,
        work=state.work + progress * dt,
    )
    meas = {"progress": progress, "power": power_meas, "pcap": pcap,
            "progress_clean": clean}
    return new_state, meas


def simulate(profile: PlantProfile, pcaps: jnp.ndarray, dt: float,
             key) -> dict:
    """Open-loop simulation over a pcap schedule [T] -> traces dict."""

    def body(state, xs):
        pcap, k = xs
        state, meas = plant_step(profile, state, pcap, dt, k)
        return state, meas

    keys = jax.random.split(key, len(pcaps))
    state, traces = jax.lax.scan(body, plant_init(profile, pcaps[0]),
                                 (pcaps, keys))
    traces["energy"] = state.energy
    traces["work"] = state.work
    return traces
