"""Hierarchical fleet power control (beyond the paper; scales to 1000+ nodes).

Two levels:

* **node level** — the paper's full control period, one per node: the
  scan engine's fused plant/heartbeat/policy step (`repro.core.sim.
  engine_step`) vmapped across the fleet with PER-NODE traced plant,
  gain and policy parameters. Fleets can therefore be heterogeneous in
  both hardware (a mix of plant-profile classes — gros next to dahu
  next to TPU hosts) and control policy (`repro.core.policies`: PI on
  one class, duty-cycle or offline-RL on another), while every node
  still runs through the single-node engine's compiled dynamics.
* **cluster level** — a slow outer loop that splits a global power budget
  across nodes every `reallocate_every` periods. Water-filling on the
  previous period's SETPOINT-RELATIVE progress: nodes lagging the fleet
  median get more budget (straggler mitigation falls out naturally), and
  because the fill respects per-node actuator bounds, budget SHIFTS
  across profile classes — a saturated low-demand class's surplus flows
  to the class that can still convert watts into progress (the EcoShift
  heterogeneous power-shifting scenario). The allocation enters each
  node's period as `cap_limit`: the applied command is min(policy
  command, allocation).

The per-node controller remains exactly its policy's law (Eq. 4 for PI) —
the cluster level only moves each node's cap budget, so the paper's
stability analysis still applies within a reallocation window.

The whole two-level run is one jitted scan, cached by (n_nodes, horizon
bucket, budgeted, policy branch set, n_classes) only — plant, gain,
policy, budget and reallocation cadence are traced — so e.g. the
1024-node benchmark compiles once per machine.
`_simulate_fleet_reference` keeps the hand-rolled per-node step as the
equivalence oracle for tests (per-node parameters included).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policies as pol
from repro.core import sim
from repro.core.controller import PIGains, pi_init, pi_step
from repro.core.plant import PlantProfile, plant_step
from repro.core.policies.pi import PIPolicy
from repro.core.workloads.schedule import Phase, PhaseSchedule, \
    chain_rows


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    n_nodes: int
    epsilon: float = 0.10
    tau_obj: float = 10.0
    dt: float = 1.0
    power_budget: float = 0.0   # total W across nodes; 0 = uncapped
    reallocate_every: int = 10
    # water-filling weight gain on relative lag: weights = 1 + boost*lag;
    # 1.0 reproduces the original (unparameterized) behaviour
    straggler_boost: float = 1.0


def _water_fill_bounds(lo, hi, budget, weights: jnp.ndarray) -> jnp.ndarray:
    """Split `budget` watts over nodes proportionally to weights, clipped
    to PER-NODE actuator bounds `lo`/`hi` (arrays or scalars).

    Starts from the clipped proportional target, then iteratively refines
    the CARRIED allocation: each round measures the remaining deficit (or
    surplus) and redistributes it over the nodes with room in that
    direction, so the total converges to the budget whenever it is
    feasible (sum(lo) <= budget <= sum(hi)) and saturates at the nearest
    bound otherwise. With heterogeneous bounds this is what shifts budget
    across profile classes: a class pinned at its bound stops absorbing
    the redistribution and the remainder flows to the class with room."""
    w = weights / jnp.maximum(weights.sum(), 1e-9)
    alloc = jnp.clip(budget * w, lo, hi)

    def body(alloc, _):
        leftover = budget - alloc.sum()
        room = jnp.where(leftover >= 0, hi - alloc, alloc - lo)
        share = room / jnp.maximum(room.sum(), 1e-9)
        alloc = jnp.clip(alloc + leftover * share, lo, hi)
        return alloc, None

    alloc, _ = jax.lax.scan(body, alloc, None, length=8)
    return alloc


def _water_fill(profile: PlantProfile, budget: float, n: int,
                weights: jnp.ndarray) -> jnp.ndarray:
    """Homogeneous-bounds convenience wrapper around `_water_fill_bounds`."""
    return _water_fill_bounds(jnp.full((n,), profile.pcap_min),
                              jnp.full((n,), profile.pcap_max),
                              budget, weights)


# packed-field indices, derived from sim's canonical packing order
_F_PCAP_MIN = sim._PROFILE_FIELDS.index("pcap_min")
_F_PCAP_MAX = sim._PROFILE_FIELDS.index("pcap_max")
_G_SETPOINT = sim._GAIN_FIELDS.index("setpoint")


@functools.lru_cache(maxsize=None)
def _fleet_core(n: int, scan_len: int, budgeted: bool,
                branches=("pi",), n_classes: int = 1):
    """The two-level fleet run as a pure function (jitted by
    `_jit_fleet`, vmapped over seeds by `fleet_sweep`'s executor core) —
    every scalar parameter, per-node plant/gain row and policy value is
    traced."""

    def run(profile_vals, gains_vals, policy_vals, class_ids, sched,
            budget, realloc_every, boost, steps, dt, key):
        max_time = steps * dt  # freeze (engine early-exit) past the horizon
        total_work = jnp.float32(jnp.inf)
        lo = profile_vals[:, _F_PCAP_MIN]
        hi = profile_vals[:, _F_PCAP_MAX]
        setpoints = gains_vals[:, _G_SETPOINT]
        seg = lambda x: jax.ops.segment_sum(x, class_ids,
                                            num_segments=n_classes)
        counts = jnp.maximum(seg(jnp.ones((n,))), 1.0)

        # sched is None (static plants) or a per-node ScheduleValues
        # pytree with leading (n,) leaves; jit separates the variants by
        # structure, so schedule-free fleets keep the pre-phases graph
        if sched is None:
            nodes0 = jax.vmap(
                lambda pv, gv, av: sim._default_init(
                    sim._unpack_profile(pv), sim._unpack_gains(gv),
                    branches, av))(profile_vals, gains_vals, policy_vals)
        else:
            nodes0 = jax.vmap(
                lambda pv, gv, av, sv: sim._default_init(
                    sim._unpack_profile(pv), sim._unpack_gains(gv),
                    branches, av, schedule=sv))(
                profile_vals, gains_vals, policy_vals, sched)

        def node_step(pv, gv, av, sv, c, k, lim):
            return sim.engine_step(
                sim._unpack_profile(pv), sim._unpack_gains(gv), c,
                total_work, max_time, dt, k, policy=branches,
                policy_vals=av, cap_limit=lim, schedule=sv)

        v_step = jax.vmap(node_step,
                          in_axes=(0, 0, 0,
                                   None if sched is None else 0, 0, 0,
                                   0 if budgeted else None))

        def step(carry, xs):
            nodes, alloc, prev_prog = carry
            t, k = xs

            if budgeted:
                # cluster level: periodic water-filling on the previous
                # period's setpoint-relative progress; stragglers (below
                # the fleet median) weigh more and receive a larger share
                def reallocate(_):
                    rel = prev_prog / jnp.maximum(setpoints, 1e-9)
                    med = jnp.median(rel)
                    lag = jnp.maximum(
                        0.0, (med - rel) / jnp.maximum(med, 1e-9))
                    return _water_fill_bounds(lo, hi, budget,
                                              1.0 + boost * lag)

                alloc = jax.lax.cond(t % realloc_every == 0, reallocate,
                                     lambda _: alloc, None)
            nodes, out = v_step(profile_vals, gains_vals, policy_vals,
                                sched, nodes, jax.random.split(k, n),
                                alloc if budgeted else None)

            row = {"progress_mean": out["progress"].mean(),
                   "progress_med": jnp.median(out["progress"]),
                   "power": out["power"].sum(),
                   "pcap_mean": out["pcap"].mean(),
                   "power_class": seg(out["power"]),
                   "progress_class": seg(out["progress"]) / counts,
                   "pcap_class": seg(out["pcap"]) / counts}
            if budgeted:
                row["alloc_class"] = seg(alloc) / counts
            if sched is not None:
                # mean active phase per class: phase-staggered fleets
                # make the cross-class movement observable
                row["phase_class"] = seg(out["phase"].astype(jnp.float32)
                                         ) / counts
            return (nodes, alloc, out["progress"]), row

        keys = jax.random.split(key, scan_len)
        (nodes, _, _), traces = jax.lax.scan(
            step, (nodes0, hi, jnp.zeros((n,))),
            (jnp.arange(scan_len), keys))
        traces["energy_total"] = nodes.plant.energy.sum()
        traces["work_total"] = nodes.plant.work.sum()
        traces["energy_class"] = seg(nodes.plant.energy)
        return traces

    return run


@functools.lru_cache(maxsize=None)
def _jit_fleet(n: int, scan_len: int, budgeted: bool,
               branches=("pi",), n_classes: int = 1):
    """One-seed fleet run, compiled once per (fleet size, horizon
    bucket, budgeted, policy branch set, class count)."""
    return jax.jit(_fleet_core(n, scan_len, budgeted, branches,
                               n_classes))


@functools.lru_cache(maxsize=None)
def _fleet_seed_core(n: int, scan_len: int, budgeted: bool,
                     branches=("pi",), n_classes: int = 1):
    """Executor-facing fleet engine: the same `_fleet_core` vmapped over
    a batch of seeds (batched = {'key': (S, 2)}), for chunked/sharded
    multi-seed campaigns."""
    run = _fleet_core(n, scan_len, budgeted, branches, n_classes)

    def flat(batched, pv, gv, av, cls, sv, budget, realloc, boost,
             steps, dt):
        return jax.vmap(lambda k: run(pv, gv, av, cls, sv, budget,
                                      realloc, boost, steps, dt, k)
                        )(batched["key"])

    return flat


def _fleet_layout(profile, fc: FleetConfig, node_class):
    """Normalize (profile(s), node_class) -> (profiles, per-node class)."""
    profs = ([profile] if isinstance(profile, PlantProfile)
             else list(profile))
    n = fc.n_nodes
    if node_class is None:
        cls = np.arange(n) % len(profs)
    else:
        cls = np.asarray(node_class, np.int32)
        if cls.shape != (n,):
            raise ValueError(f"node_class must have shape ({n},)")
        if cls.min() < 0 or cls.max() >= len(profs):
            raise ValueError("node_class indexes outside the profile list")
    return profs, cls


def _fleet_policies(policies, n_profiles: int, n: int, cls):
    """Normalize policies= to one Policy per node: a single Policy (all
    nodes), one per node, or one per profile class. When n_nodes equals
    the class count the list is ambiguous; the PER-NODE reading wins
    (``policies[i]`` is node i's policy, regardless of node_class)."""
    if policies is None:
        policies = PIPolicy()
    if isinstance(policies, pol.Policy):
        return [policies] * n
    pls = list(policies)
    if len(pls) == n:
        return pls
    if len(pls) == n_profiles:
        return [pls[c] for c in cls]
    raise ValueError(f"policies= must be one Policy, {n_profiles} "
                     f"(per class) or {n} (per node); got {len(pls)}")


def _fleet_schedules(schedules, profs, n: int, cls):
    """Normalize schedules= to a per-node ScheduleValues pytree with
    leading (n,) leaves, or None. Accepts a single PhaseSchedule (every
    node, resolved against its class profile), one per class, or one per
    node — same precedence rules as policies= (per-node reading wins
    when n_nodes == n_classes). None entries mean 'static plant' and
    become a one-phase hold of the node's class profile."""
    if schedules is None:
        return None
    if isinstance(schedules, PhaseSchedule):
        per_node = [schedules] * n
    else:
        scheds = list(schedules)
        if len(scheds) == n:
            per_node = scheds
        elif len(scheds) == len(profs):
            per_node = [scheds[c] for c in cls]
        else:
            raise ValueError(f"schedules= must be one PhaseSchedule, "
                             f"{len(profs)} (per class) or {n} (per "
                             f"node); got {len(scheds)}")
    static_hold = PhaseSchedule((Phase(1.0),))  # holds base forever
    per_node = [s or static_hold for s in per_node]
    rows = max(chain_rows(len(s.phases)) for s in per_node)
    resolved = [s.resolve(profs[cls[i]], rows)
                for i, s in enumerate(per_node)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *resolved)


def simulate_fleet(profile, fc: FleetConfig, steps: int, seed: int = 0, *,
                   node_class: Optional[Sequence[int]] = None,
                   policies: Union[None, pol.Policy,
                                   Sequence[pol.Policy]] = None,
                   schedules: Union[None, PhaseSchedule,
                                    Sequence[Optional[PhaseSchedule]]]
                   = None) -> dict:
    """Run the two-level controller over a (possibly heterogeneous) fleet.

    ``profile`` is a single PlantProfile or a sequence of profile CLASSES
    with ``node_class`` mapping each node to its class (default:
    round-robin). ``policies`` assigns the per-node control policy —
    a single Policy, one per class, or one per node. ``schedules``
    scripts per-node TIME-VARYING plants (`repro.core.workloads`): a
    single PhaseSchedule, one per class, or one per node (None entries =
    static), each resolved against the node's class profile — so
    phase-staggered fleets exercise cross-class budget shifting when one
    class goes compute-bound while another idles at its knee. Returns
    traces aggregated per step: fleet progress mean/median, power, caps,
    plus per-class power/progress/cap (and allocation, when budgeted;
    mean active phase, when scheduled) so cross-class budget shifting is
    observable; ``class_counts`` gives the node count per class."""
    profs, cls, branches, args = _fleet_args(profile, fc, node_class,
                                             policies, schedules)
    scan_len = sim._bucket_steps(steps)
    traces = _jit_fleet(fc.n_nodes, scan_len, fc.power_budget > 0,
                        branches, len(profs))(
        *args, jnp.float32(fc.power_budget),
        jnp.int32(fc.reallocate_every), jnp.float32(fc.straggler_boost),
        jnp.float32(steps), jnp.float32(fc.dt), jax.random.PRNGKey(seed))
    # trim only the TIME axis: per-step traces are (scan_len, ...);
    # per-run reductions like energy_class are (n_classes,) and must
    # pass through untouched
    out = {k: (v[:steps] if getattr(v, "ndim", 0)
               and v.shape[0] == scan_len else v)
           for k, v in traces.items()}
    out["class_counts"] = np.bincount(cls, minlength=len(profs))
    return out


def _fleet_args(profile, fc: FleetConfig, node_class, policies,
                schedules):
    """Shared per-node argument packing for `simulate_fleet` /
    `fleet_sweep`: (profs, cls, branches, (pv, gv, av, cls, sv))."""
    profs, cls = _fleet_layout(profile, fc, node_class)
    n = fc.n_nodes
    gains = [PIGains.from_model(p, fc.epsilon, fc.tau_obj) for p in profs]
    node_pols = _fleet_policies(policies, len(profs), n, cls)
    branches, kinds = pol.resolve_kinds(node_pols)

    pv = np.stack([sim.profile_values(p) for p in profs])[cls]
    gv = np.stack([sim.gains_values(g) for g in gains])[cls]
    av = np.zeros((n, pol.POLICY_PARAM_DIM), np.float32)
    cache = {}
    for i, (p_, k_) in enumerate(zip(node_pols, kinds)):
        ck = (int(cls[i]), p_, k_)
        if ck not in cache:
            cache[ck] = pol.policy_values(
                p_, profs[cls[i]], gains[cls[i]], kind=k_)
        av[i] = cache[ck]
    sv = _fleet_schedules(schedules, profs, n, cls)
    return profs, cls, branches, (jnp.asarray(pv), jnp.asarray(gv),
                                  jnp.asarray(av),
                                  jnp.asarray(cls, jnp.int32), sv)


def fleet_sweep(profile, fc: FleetConfig, steps: int,
                seeds: Sequence[int], *,
                node_class: Optional[Sequence[int]] = None,
                policies: Union[None, pol.Policy,
                                Sequence[pol.Policy]] = None,
                schedules: Union[None, PhaseSchedule,
                                 Sequence[Optional[PhaseSchedule]]]
                = None,
                chunk_size: Optional[int] = None,
                devices=None, durable=None, campaign=None) -> dict:
    """Multi-seed fleet campaign on the chunked/sharded executor: the
    `simulate_fleet` engine vmapped over independent seed realizations,
    cut into ``chunk_size`` tiles and spread over ``devices`` like any
    `sweep` grid (`repro.core.executor`), so 30-rep fleet evaluations at
    1024 nodes no longer need one giant batch (or one device). Returns
    `simulate_fleet`'s traces dict with a leading seed axis on every
    per-step series and per-run reduction.

    ``durable=dir`` journals the campaign through
    `repro.core.supervisor` (write-ahead chunk journal, retry/backoff,
    device quarantine); `supervisor.resume_campaign(dir)` reopens it
    after a crash and returns the identical traces dict. ``campaign=``
    tunes the `supervisor.CampaignConfig` ladder."""
    from repro.core import executor

    if durable is not None:
        from repro.core import supervisor
        supervisor.save_campaign_spec(durable, "fleet_sweep", dict(
            profile=profile, fc=fc, steps=steps, seeds=list(seeds),
            node_class=(None if node_class is None else list(node_class)),
            policies=policies, schedules=schedules,
            chunk_size=chunk_size, devices=devices, campaign=campaign))
    profs, cls, branches, args = _fleet_args(profile, fc, node_class,
                                             policies, schedules)
    scan_len = sim._bucket_steps(steps)
    fn = _fleet_seed_core(fc.n_nodes, scan_len, fc.power_budget > 0,
                          branches, len(profs))
    shared = args + (jnp.float32(fc.power_budget),
                     jnp.int32(fc.reallocate_every),
                     jnp.float32(fc.straggler_boost),
                     jnp.float32(steps), jnp.float32(fc.dt))
    keys = sim.seed_keys(seeds)
    if durable is not None:
        from repro.core import supervisor
        merged, _report = supervisor.run_durable(
            fn, {"key": keys}, shared, len(seeds), dir=durable,
            chunk_size=chunk_size, devices=devices, config=campaign)
    else:
        merged, _ = executor.run_grid(fn, {"key": keys}, shared,
                                      len(seeds), chunk_size=chunk_size,
                                      devices=devices)
    out = {k: (v[:, :steps] if getattr(v, "ndim", 0) >= 2
               and v.shape[1] == scan_len else v)
           for k, v in merged.items()}
    out["class_counts"] = np.bincount(cls, minlength=len(profs))
    return out


def _simulate_fleet_reference(profile, fc: FleetConfig, steps: int,
                              seed: int = 0,
                              node_class: Optional[Sequence[int]] = None
                              ) -> dict:
    """Hand-rolled per-node fleet step (plant_step + pi_step on raw
    measured progress, no heartbeat aggregation), generalized to per-node
    profile classes. Kept ONLY as the statistical-equivalence oracle for
    the engine-backed simulate_fleet."""
    profs, cls = _fleet_layout(profile, fc, node_class)
    n = fc.n_nodes
    gains = [PIGains.from_model(p, fc.epsilon, fc.tau_obj) for p in profs]
    pv = jnp.asarray(np.stack([sim.profile_values(p) for p in profs])[cls])
    gv = jnp.asarray(np.stack([sim.gains_values(g) for g in gains])[cls])
    class_ids = jnp.asarray(cls, jnp.int32)
    n_classes = len(profs)
    lo, hi = pv[:, _F_PCAP_MIN], pv[:, _F_PCAP_MAX]
    setpoints = gv[:, _G_SETPOINT]
    seg = lambda x: jax.ops.segment_sum(x, class_ids,
                                        num_segments=n_classes)
    counts = jnp.maximum(seg(jnp.ones((n,))), 1.0)

    plant_states = jax.vmap(
        lambda pvals: sim.plant_init(sim._unpack_profile(pvals)))(pv)
    pi_states = jax.vmap(
        lambda gvals: pi_init(sim._unpack_gains(gvals)))(gv)

    v_plant = jax.vmap(
        lambda pvals, s, cap, k: plant_step(
            sim._unpack_profile(pvals), s, cap, fc.dt, k),
        in_axes=(0, 0, 0, 0))
    v_pi = jax.vmap(
        lambda gvals, s, prog: pi_step(
            sim._unpack_gains(gvals), s, prog, fc.dt),
        in_axes=(0, 0, 0))

    def step(carry, xs):
        plant_s, pi_s, caps = carry
        t, key = xs
        keys = jax.random.split(key, n)
        plant_s, meas = v_plant(pv, plant_s, caps, keys)
        progress = meas["progress"]

        def reallocate(args):
            pi_s, caps = args
            rel = progress / jnp.maximum(setpoints, 1e-9)
            med = jnp.median(rel)
            lag = jnp.maximum(0.0, (med - rel) / jnp.maximum(med, 1e-9))
            weights = 1.0 + fc.straggler_boost * lag
            if fc.power_budget > 0:
                caps = _water_fill_bounds(lo, hi, fc.power_budget, weights)
            return pi_s, caps

        pi_s, caps = jax.lax.cond(
            (fc.power_budget > 0) & (t % fc.reallocate_every == 0),
            reallocate, lambda a: a, (pi_s, caps))

        pi_s, pi_caps = v_pi(gv, pi_s, progress)
        caps = jnp.where(fc.power_budget > 0,
                         jnp.minimum(pi_caps, caps), pi_caps)
        out = {
            "progress_mean": progress.mean(),
            "progress_med": jnp.median(progress),
            "power": meas["power"].sum(),
            "pcap_mean": caps.mean(),
            "power_class": seg(meas["power"]),
            "progress_class": seg(progress) / counts,
        }
        return (plant_s, pi_s, caps), out

    caps0 = hi
    keys = jax.random.split(jax.random.PRNGKey(seed), steps)
    (plant_s, _, _), traces = jax.lax.scan(
        step, (plant_states, pi_states, caps0),
        (jnp.arange(steps), keys))
    traces["energy_total"] = plant_s.energy.sum()
    traces["work_total"] = plant_s.work.sum()
    traces["energy_class"] = seg(plant_s.energy)
    return traces
