"""Generic fleet driver: service periods of `repro.core.plane.
ControlPlane`, replayed back to back on the plane's own clock.

The configuration gives the fleet: the tenant count, the plants (every
Table 2 parameter, literally) and their shares, epsilon, the control
period, the policy mix with each policy's settings, the detector, the
heartbeat ring and the capacity bucket. The traffic gives how heartbeats
are made and how many tenants the reference replays.

A period: each tenant's beat count is Poisson with the mean its plant
makes in one period at the cap the plane last applied to it (the
static map, paper Eq. 2), the beat times uniform in the period. The
timed service period runs from handing that batch to
`ControlPlane.ingest` to `ControlPlane.tick` returning its decisions.
"""
from __future__ import annotations

import time

import ml_dtypes  # noqa: F401  (registers the bfloat16 dtype)
import numpy as np

from common import PLANT_KEYS, derive_seed, registry_sample, rel_gap
from reference import plane as ref


def static_progress(plants: dict, cap: np.ndarray) -> np.ndarray:
    """Eq. 2's static map: beats per second of a plant held at ``cap``."""
    power = plants["a"] * cap + plants["b"]
    return plants["K_L"] * (1.0 - np.exp(-plants["alpha"]
                                         * (power - plants["beta"])))


def fleet_layout(config: dict):
    """Per-tenant plant name, policy group and detector flag, in the
    order tenants are added: each plant takes an equal share of the
    fleet, and inside a plant each policy group its share (the first
    group takes what rounding leaves)."""
    plants, groups = list(config["plants"]), config["policy_mix"]
    n = int(config["tenants"])
    per_plant = [n // len(plants) + (i < n % len(plants))
                 for i in range(len(plants))]
    rows = []
    for name, m in zip(plants, per_plant):
        sizes = [int(m * g["share"]) for g in groups]
        sizes[0] = m - sum(sizes[1:])
        for gi, size in enumerate(sizes):
            rows.append((name, gi, size))
    return rows


class Driver:
    def __init__(self, *, cell, config, traffic, seed, spans, log):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.spans, self.log = seed, spans, log
        self.dt = float(config["dt"])
        self.period = 0
        self.records = []      # per period: (n_beats, ingest_s, total_s)
        self.failures = 0
        self.tick_hist = None
        layout = [(name, gi) for name, gi, size in fleet_layout(config)
                  for _ in range(size)]
        groups = config["policy_mix"]
        self.names = np.asarray([n for n, _ in layout])
        self.group = np.asarray([g for _, g in layout])
        self.kinds = np.asarray([groups[g]["policy"] for _, g in layout])
        self.detect = np.asarray([bool(groups[g].get("detector"))
                                  for _, g in layout])
        self.params = {k: np.asarray([config["plants"][n][k]
                                      for n in self.names], np.float64)
                       for k in PLANT_KEYS}
        self.applied = self.params["pcap_max"].copy()

    # ---- the system under test -----------------------------------------
    def _policy(self, group: dict):
        from repro.core.adaptive import RLSConfig
        from repro.core.policies import DutyCyclePolicy, PIPolicy
        c = self.config
        if group["policy"] == ref.PI:
            return PIPolicy()
        if group["policy"] == ref.PI_RLS:
            r = c["rls"]
            return PIPolicy(adaptive=RLSConfig(
                lam=r["lam"], dwell=int(r["dwell"]),
                kl_clamp=r["kl_clamp"], p_trace_max=r["p_trace_max"]))
        if group["policy"] == ref.DUTY:
            d = c["dutycycle"]
            return DutyCyclePolicy(
                n_levels=int(d["n_levels"]), min_level=int(d["min_level"]),
                deadband=d["deadband"], down_step=d["down_step"],
                up_step=d["up_step"])
        raise ValueError(f"no reference for policy {group['policy']!r}")

    def setup(self):
        from repro.core.plane import ControlPlane
        from repro.core.plant import PlantProfile
        from repro.core.workloads.detect import DetectorConfig

        t0 = time.perf_counter()
        c = self.config
        d = c["detector"]
        det = DetectorConfig(drift=d["drift"], threshold=d["threshold"],
                             min_gap=int(d["min_gap"]),
                             level_eta=d["level_eta"],
                             level_slack=d["level_slack"])
        profs = {name: PlantProfile(name, **{k: v[k] for k in PLANT_KEYS})
                 for name, v in c["plants"].items()}
        first = next(iter(profs.values()))
        self.plane = ControlPlane(profile=first, epsilon=c["epsilon"],
                                  dt=self.dt, capacity=int(c["capacity"]),
                                  max_beats=int(c["max_beats"]))
        groups = c["policy_mix"]
        slots = []
        for name, gi, size in fleet_layout(c):
            g = groups[gi]
            slots += self.plane.add_tenants(
                size, policy=self._policy(g), profile=profs[name],
                detector=det if g.get("detector") else False)
        self.slots = np.asarray(slots, np.int64)
        # the tenants the reference replays: an equal number from every
        # (plant, policy group) drawn from the seed
        rng = derive_seed(self.seed, 3)
        per = int(self.traffic["check_tenants_per_group"])
        keys = np.char.add(np.char.add(self.names, "/"),
                           np.char.add(self.kinds,
                                       np.where(self.detect, "+det", "")))
        pick = []
        for k in np.unique(keys):
            idx = np.nonzero(keys == k)[0]
            pick += list(rng.choice(idx, size=min(per, len(idx)),
                                    replace=False))
        self.sample = np.sort(np.asarray(pick, np.int64))
        self.in_sample = np.full(len(self.slots), -1, np.int64)
        self.in_sample[self.sample] = np.arange(len(self.sample))
        self.sampled = []      # per period: (beat tenant, times, applied,
        #                        alarm, progress) of the sampled tenants
        t1 = time.perf_counter()
        with self.spans("warm"):
            for _ in range(int(self.traffic["warm_periods"])):
                self.step(timed=False)
        self.log(f"setup: fleet built in {t1 - t0:.3f} s, warm periods "
                 f"{time.perf_counter() - t1:.3f} s")
        self.tick_hist = registry_sample("plane_tick_seconds")

    def beats(self, k: int):
        """Period ``k``'s heartbeats: (tenant index, time) sorted by
        tenant, then time."""
        rng = derive_seed(self.seed, 4, k)
        lam = static_progress(self.params, self.applied) * self.dt
        n = rng.poisson(np.maximum(lam, 0.0))
        tenant = np.repeat(np.arange(len(n)), n)
        u = np.minimum(rng.random(len(tenant)), 1.0 - 1e-9)
        key = np.sort(tenant + u)
        return tenant, k * self.dt + (key - tenant) * self.dt

    def step(self, timed: bool = True):
        k = self.period
        with self.spans("generate"):
            tenant, times = self.beats(k)
            ids = self.slots[tenant]
        t0 = time.perf_counter()
        with self.spans("ingest"):
            self.plane.ingest(ids, times)
        t1 = time.perf_counter()
        with self.spans("tick"):
            try:
                out = self.plane.tick(now=(k + 1) * self.dt)
            except Exception as e:
                self.log(f"tick of period {k} failed: {e!r}")
                out = None
        t2 = time.perf_counter()
        ok = out is not None and (t2 - t0) <= float(
            self.traffic["period_limit_s"])
        if out is not None:
            applied = np.asarray(out["applied"], np.float64)[self.slots]
            p = self.params
            ok &= bool(np.all(np.isfinite(applied)
                              & (applied >= p["pcap_min"])
                              & (applied <= p["pcap_max"])))
            self.applied = np.where(np.isfinite(applied), applied,
                                    p["pcap_max"])
            s = self.slots[self.sample]
            m = self.in_sample[tenant] >= 0
            self.sampled.append((
                self.in_sample[tenant[m]], times[m], applied[self.sample],
                np.asarray(out["phase_change"])[s] > 0.5,
                np.asarray(out["progress"], np.float64)[s]))
        else:
            self.sampled.append(None)
        if timed:
            self.records.append((len(times), t1 - t0, t2 - t0))
            self.failures += not ok
        self.period += 1

    # ---- the traced run reads only the tick histogram, kept anyway -----
    def trace_on(self):
        pass

    def trace_off(self):
        pass

    # ---- results ---------------------------------------------------------
    def window_result(self) -> dict:
        from common import percentile
        total = np.asarray([r[2] for r in self.records]) * 1e3
        late = int(np.sum(total > 1e3 * float(self.traffic["period_limit_s"])))
        self.log(f"service periods: {len(total)} samples, "
                 f"{int(np.mean([r[0] for r in self.records]))} beats per "
                 f"period on average, longest {total.max():.1f} ms, "
                 f"{late} over the period limit")
        return {"attempted": len(self.records), "failed": self.failures,
                "metrics": {"plane_period_p50_ms": percentile(total, 50),
                            "plane_period_p95_ms": percentile(total, 95)}}

    def tick_ms(self):
        """Mean tick wall time over the window from the program's
        ``plane_tick_seconds`` histogram."""
        now = registry_sample("plane_tick_seconds")
        if now is None or self.tick_hist is None:
            return None
        n = now["count"] - self.tick_hist["count"]
        return (now["sum"] - self.tick_hist["sum"]) / n * 1e3 if n else None

    def check(self, control: bool = False) -> dict:
        """The sampled tenants' decisions in the window against the
        reference replay. ``control`` puts the reference at the next
        precision below the configuration's in the program's place:
        float32 heartbeat rates and a bfloat16 control law."""
        lim = self.traffic["limits"]
        gaps = (compare(self, rate_dtype=np.float32,
                        law_dtype=np.dtype("bfloat16")) if control
                else compare(self))
        return {"progress_gap_max": {"value": gaps["progress_max"],
                                     "limit": lim["progress_gap_max"]},
                "cap_gap_median": {"value": gaps["cap_median"],
                                   "limit": lim["cap_gap_median"]},
                "cap_off_share": {"value": gaps["cap_off_share"],
                                  "limit": lim["cap_off_share"]},
                "alarm_mismatch_share": {"value": gaps["alarm_share"],
                                         "limit": lim["alarm_mismatch_share"]}}


CAP_OFF = 1e-3  # a cap further than this from the reference is off


def compare(drv: Driver, rate_dtype=np.float64,
            law_dtype=np.float64) -> dict:
    """Replay the sampled tenants from their heartbeats in the
    reference, from the first period on, and compare each period of the
    window with what the program decided (or, for the control, the
    reference at lower precision put in its place)."""
    c = drv.config
    cfg = {"epsilon": c["epsilon"], "tau_obj": c["tau_obj"],
           "rls": c["rls"], "dutycycle": c["dutycycle"],
           "detector": c["detector"]}
    s = drv.sample
    plants = {k: v[s] for k, v in drv.params.items()}
    want = ref.Fleet(plants, drv.kinds[s], drv.detect[s], cfg)
    low = (ref.Fleet(plants, drv.kinds[s], drv.detect[s], cfg,
                     dtype=law_dtype)
           if np.dtype(law_dtype) != np.float64 else None)
    anchor = np.full(len(s), np.nan)
    low_anchor = anchor.copy()
    first = len(drv.sampled) - len(drv.records)
    prog_gap, cap_gap, alarm_off = [], [], []
    for k, rec in enumerate(drv.sampled):
        if rec is None:
            if k >= first:
                cap_gap.append(np.full(len(s), np.inf))
                alarm_off.append(np.ones(len(s), bool))
                prog_gap.append(np.full(len(s), np.inf))
            continue
        tenant, times, applied, alarm, progress = rec
        p_ref, anchor = ref.eq1_progress(times, tenant, len(s), anchor)
        cap_ref, alarm_ref = want.step(p_ref, drv.dt)
        if low is not None:
            progress, low_anchor = ref.eq1_progress(
                times, tenant, len(s), low_anchor, dtype=rate_dtype)
            applied, alarm = low.step(progress, drv.dt)
        if k >= first:
            prog_gap.append(rel_gap(progress, p_ref))
            cap_gap.append(rel_gap(applied, cap_ref))
            alarm_off.append(np.asarray(alarm) != alarm_ref)
    prog_gap = np.concatenate(prog_gap)
    cap_gap = np.concatenate(cap_gap)
    alarm_off = np.concatenate(alarm_off)
    return {"progress_max": float(prog_gap.max()),
            "cap_median": float(np.median(cap_gap)),
            "cap_off_share": float(np.mean(cap_gap > CAP_OFF)),
            "alarm_share": float(np.mean(alarm_off)),
            "n": int(len(cap_gap))}
