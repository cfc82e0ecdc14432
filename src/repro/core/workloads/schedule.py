"""Phase-scripted, time-varying plants (paper §2: workload phases).

A `PhaseSchedule` scripts the plant's identified parameters over the run:
each `Phase` holds a duration and what the plant looks like during it —
an absolute `PlantProfile`, field overrides (`delta`) and/or field
multipliers (`scale`) applied to the run's base profile. `resolve(base)`
packs the script into `ScheduleValues`: fixed-width traced arrays
(`MAX_PHASES` rows in `repro.core.plant.PROFILE_FIELDS` order) that the
scan engine gathers from by carried sim-time, so ONE compiled engine
serves every schedule and schedule grids vmap like any other traced
parameter (`sweep(workloads=[...])`).

Semantics: phase i is active for t in [ends[i-1], ends[i]) (half-open, a
boundary step belongs to the NEW phase). A non-cyclic schedule holds its
last phase forever once the scripted segments are exhausted; a `cyclic`
schedule wraps sim-time modulo its total duration (the STREAM<->DGEMM
alternation runs indefinitely from two segments).

Generators:

* `stream_dgemm_schedule` — alternates a memory-bound (STREAM: sharp
  knee, large energy headroom) and a compute-bound (DGEMM: shallow knee,
  little headroom) variant of a base profile, via the same saturation ->
  knee mapping `repro.core.phases` uses for roofline cells.
* `roofline_schedule` — phases taken from dry-run roofline terms through
  `phases.profile_for_cell` (data/compute movement between devices).
* `markov_schedule` — a randomized phase chain (geometric dwell times,
  uniform jumps) for property tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, NamedTuple, Optional, Sequence, Tuple, \
    Union

import jax.numpy as jnp
import numpy as np

from repro.core.phases import knee_for_saturation, profile_for_cell
from repro.core.plant import PROFILE_FIELDS, PROFILES, PlantProfile

# Piece size of the packed schedule arrays: schedules pack into a WHOLE
# number of MAX_PHASES-row pieces (16 rows covers every paper scenario
# in one piece; longer scripts chain further pieces — `chain_rows`), so
# heterogeneous schedule grids share one engine per row-count bucket.
MAX_PHASES = 16

_N_FIELDS = len(PROFILE_FIELDS)


def chain_rows(n_phases: int) -> int:
    """Packed row count for an n-phase schedule: the smallest whole
    number of MAX_PHASES-row pieces that holds it. Scripts up to 16
    phases keep their original single-piece (16-row) shapes — and the
    compiled engines those shapes key; longer scripts chain 32, 48, ...
    row variants (a new scan-engine structure per bucket, shared by
    every schedule in that bucket)."""
    return MAX_PHASES * max(1, -(-n_phases // MAX_PHASES))


class ScheduleValues(NamedTuple):
    """Packed form of a PhaseSchedule (the engine-facing contract): f32
    host arrays from `PhaseSchedule.resolve`, traced inside the engines.

    ``ends`` is the cumulative end time of each phase (+inf padding past
    the last scripted phase); ``profiles`` the per-phase plant rows in
    `PROFILE_FIELDS` order (padding repeats the last row); ``period`` the
    cycle length in seconds, 0 for non-cyclic schedules. ``rows`` is
    `chain_rows` of the phase count — every schedule in one grid packs
    to a common row count (`PhaseSchedule.resolve(rows=...)`)."""
    ends: np.ndarray      # (rows,) f32
    profiles: np.ndarray  # (rows, len(PROFILE_FIELDS)) f32
    period: np.ndarray    # () f32; 0 = hold the last phase forever


def active_profile(sched: ScheduleValues, t):
    """(profile row, phase index) active at sim-time ``t`` (traced).

    Half-open segments: searchsorted(side='right') sends a boundary time
    to the NEXT phase, matching the engine's half-open control windows."""
    t_eff = jnp.where(sched.period > 0,
                      jnp.mod(t, jnp.maximum(sched.period, 1e-9)), t)
    idx = jnp.clip(jnp.searchsorted(sched.ends, t_eff, side="right"),
                   0, sched.ends.shape[-1] - 1)
    return sched.profiles[idx], idx


def _profile_row(p: PlantProfile) -> np.ndarray:
    return np.asarray([getattr(p, f) for f in PROFILE_FIELDS], np.float32)


def _as_items(m) -> Tuple[Tuple[str, float], ...]:
    items = tuple(m.items()) if isinstance(m, Mapping) else tuple(m)
    for f, _ in items:
        if f not in PROFILE_FIELDS:
            raise ValueError(f"unknown plant field {f!r}; choose from "
                             f"{PROFILE_FIELDS}")
    return items


@dataclasses.dataclass(frozen=True)
class Phase:
    """One schedule segment: how long, and what the plant looks like.

    ``profile`` (absolute) replaces the base for this phase; ``delta``
    overrides individual fields; ``scale`` multiplies them — applied in
    that order, so a phase can e.g. take the DGEMM profile and still
    scale its noise."""
    duration: float
    profile: Optional[PlantProfile] = None
    delta: Tuple[Tuple[str, float], ...] = ()
    scale: Tuple[Tuple[str, float], ...] = ()

    def __post_init__(self):
        if self.duration <= 0:
            raise ValueError("phase duration must be positive")
        object.__setattr__(self, "delta", _as_items(self.delta))
        object.__setattr__(self, "scale", _as_items(self.scale))

    def resolve(self, base: PlantProfile) -> PlantProfile:
        p = self.profile or base
        kw: Dict[str, float] = dict(self.delta)
        for f, s in self.scale:
            kw[f] = kw.get(f, getattr(p, f)) * s
        return dataclasses.replace(p, **kw) if kw else p


@dataclasses.dataclass(frozen=True)
class PhaseSchedule:
    """A time-ordered script of plant phases (host-side config)."""
    phases: Tuple[Phase, ...]
    cyclic: bool = False
    name: str = ""

    def __post_init__(self):
        object.__setattr__(self, "phases", tuple(self.phases))
        if not self.phases:
            raise ValueError("a PhaseSchedule needs at least one phase")

    @property
    def duration(self) -> float:
        return float(sum(p.duration for p in self.phases))

    def boundaries(self) -> np.ndarray:
        """Scripted phase-change times within one cycle (test helper)."""
        return np.cumsum([p.duration for p in self.phases[:-1]])

    def resolve(self, base: Union[str, PlantProfile],
                rows: Optional[int] = None) -> ScheduleValues:
        """Pack against a base profile -> engine-facing f32 host arrays
        (no device call).

        ``rows`` overrides the packed row count (must be a whole number
        of MAX_PHASES pieces >= the phase count): grids stacking short
        and long schedules pass the common `chain_rows` maximum so every
        leaf shares one traced shape. Scripts longer than one piece —
        e.g. a 40-phase cyclic chain — pack by PIECEWISE CHAINING into
        ceil(n/16) pieces instead of raising; the engine's gather is
        row-count agnostic."""
        base = PROFILES[base] if isinstance(base, str) else base
        n = len(self.phases)
        n_rows = chain_rows(n) if rows is None else int(rows)
        if n_rows < n or n_rows % MAX_PHASES:
            raise ValueError(f"rows={n_rows} cannot hold {n} phases in "
                             f"whole {MAX_PHASES}-row pieces")
        ends = np.full((n_rows,), np.inf, np.float32)
        ends[:n] = np.cumsum([p.duration for p in self.phases])
        rows_ = np.zeros((n_rows, _N_FIELDS), np.float32)
        for i, ph in enumerate(self.phases):
            rows_[i] = _profile_row(ph.resolve(base))
        rows_[n:] = rows_[n - 1]
        if self.cyclic:
            period = float(ends[n - 1])
        else:
            period = 0.0
            ends[n - 1] = np.inf  # hold the last phase forever
        return ScheduleValues(ends=ends, profiles=rows_,
                              period=np.asarray(period, np.float32))


# ---- generators -----------------------------------------------------------

# Saturation ratios fed to the roofline knee mapping: STREAM is strongly
# memory-bound (early knee, deep energy headroom), DGEMM strongly
# compute-bound (near-linear power-to-progress).
STREAM_SAT = 3.0
DGEMM_SAT = 0.3


def stream_dgemm_schedule(base: Union[str, PlantProfile] = "gros",
                          dwell: float = 200.0, n_cycles: int = 1,
                          cyclic: bool = False,
                          dgemm_kl_scale: float = 1.0) -> PhaseSchedule:
    """STREAM <-> DGEMM alternation (paper §5.2's two regimes).

    Each cycle is one STREAM dwell followed by one DGEMM dwell; with
    ``cyclic=True`` two phases alternate forever. ``dgemm_kl_scale``
    optionally shifts the compute phase's absolute rate too (a kernel
    that is faster/slower, not just differently bounded)."""
    base = PROFILES[base] if isinstance(base, str) else base
    stream = knee_for_saturation(base, STREAM_SAT)
    dgemm = knee_for_saturation(base, DGEMM_SAT)
    if dgemm_kl_scale != 1.0:
        dgemm = dataclasses.replace(dgemm, K_L=dgemm.K_L * dgemm_kl_scale)
    pair = [Phase(dwell, profile=stream), Phase(dwell, profile=dgemm)]
    phases = pair if cyclic else pair * n_cycles
    return PhaseSchedule(tuple(phases), cyclic=cyclic,
                         name=f"stream-dgemm-{base.name}")


def roofline_schedule(cells: Sequence[Dict[str, float]],
                      durations: Sequence[float],
                      base: str = "v5e-chip") -> PhaseSchedule:
    """Phases from roofline terms (`phases.roofline_terms` dicts): each
    cell's boundedness becomes that phase's plant knee — the
    data/compute-movement-between-devices scenario."""
    if len(cells) != len(durations):
        raise ValueError("one duration per roofline cell")
    phases = tuple(Phase(d, profile=profile_for_cell(c, base))
                   for c, d in zip(cells, durations))
    return PhaseSchedule(phases, name=f"roofline-{base}")


def markov_schedule(seed: int, base: Union[str, PlantProfile] = "gros",
                    states: Optional[Sequence[PlantProfile]] = None,
                    mean_dwell: float = 100.0, n_phases: int = 6
                    ) -> PhaseSchedule:
    """Randomized phase chain for property tests: geometric-ish dwell
    times (exponential, floored at one control period) and uniform jumps
    to a DIFFERENT state each boundary."""
    base = PROFILES[base] if isinstance(base, str) else base
    if states is None:
        states = [knee_for_saturation(base, s) for s in
                  (STREAM_SAT, 1.0, DGEMM_SAT)]
    rng = np.random.default_rng(seed)
    cur = int(rng.integers(len(states)))
    phases = []
    for _ in range(n_phases):
        dwell = max(1.0, float(rng.exponential(mean_dwell)))
        phases.append(Phase(dwell, profile=states[cur]))
        if len(states) > 1:
            cur = (cur + 1 + int(rng.integers(len(states) - 1))) \
                % len(states)
    return PhaseSchedule(tuple(phases), name=f"markov-{seed}")
