"""Generic campaign driver: repeated `repro.core.sim.sweep` calls over a
deployment's grid of plants x epsilons x policies x seeds.

The configuration gives the plants (every Table 2 parameter, literally),
the epsilon grid, the work, the horizon and the controller's tau_obj.
The traffic gives the seeds per call and how the call is made
(``devices``, ``chunk_size``, ``collect_traces``), the scenario the runs
share (``epsilons`` from the configuration's grid, ``policies``,
cyclic ``phases``, ``detector``, ``faults``, ``guard``; each absent one
is left out of the call), the counter that proves which engine path
ran, the reference the runs are checked against and the size of that
sample. Each timed call takes a fresh block of seeds derived from
``--seed`` and the call's index.

References: ``closed_loop`` (`reference/closed_loop.py`, the kernel
path's fixed-gain PI on its noise contract) and ``scan``
(`reference/scan_loop.py`, the scan engine's phased, faulted, guarded
runs on its key contract).
"""
from __future__ import annotations

import time

import ml_dtypes  # noqa: F401  (registers the bfloat16 dtype)
import numpy as np

from common import PLANT_KEYS, derive_seed, rate, registry_sample, rel_gap
from reference import closed_loop
from reference import noise as ref_noise
from reference import scan_loop

SEED_SPACE = 2 ** 31  # run seeds stay inside 32 unsigned bits
REFERENCES = {"closed_loop": closed_loop, "scan": scan_loop}
# guard counters the scan reference keeps, by the program's state slot
GUARD_FIELDS = {"invalid_signals": "G_N_INVALID",
                "failsafe_periods": "G_N_FAILSAFE",
                "guard_resets": "G_N_RESETS"}


class Driver:
    def __init__(self, *, cell, config, traffic, seed, spans, log):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.spans, self.log = seed, spans, log
        self.reference = traffic.get("reference", "closed_loop")
        self.ref = REFERENCES[self.reference]
        self.plants = list(config["plants"])
        self.eps = [float(e) for e in traffic.get("epsilons",
                                                  config["epsilons"])]
        if not set(self.eps) <= set(config["epsilons"]):
            raise ValueError("the traffic's epsilons must come from the "
                             "configuration's grid")
        self.policies = traffic.get("policies", [{"policy": "pi"}])
        if self.reference == "closed_loop" and (
                [p["policy"] for p in self.policies] != ["pi"]):
            raise ValueError("the closed_loop reference covers fixed-gain "
                             "PI grids only")
        self.n_seeds = int(traffic["seeds_per_call"])
        self.grid = (len(self.plants), len(self.eps), len(self.policies),
                     self.n_seeds)
        self.runs_per_call = int(np.prod(self.grid))
        self.calls = []           # (t0, t1, seed block, outputs, ok)
        # first run seed of the warm-up block; later blocks follow it
        self.seed0 = int(derive_seed(seed, 1).integers(0, SEED_SPACE // 2))

    # ---- the system under test -----------------------------------------
    def _profiles(self):
        from repro.core.plant import PlantProfile
        return [PlantProfile(name, **{k: self.config["plants"][name][k]
                                      for k in PLANT_KEYS})
                for name in self.plants]

    def _scenario(self) -> dict:
        """The sweep's scenario arguments, built from the traffic."""
        from repro.core import faults as flt
        from repro.core.adaptive import RLSConfig
        from repro.core.policies import DutyCyclePolicy, PIPolicy
        from repro.core.workloads.detect import DetectorConfig
        from repro.core.workloads.schedule import Phase, PhaseSchedule

        t, kw = self.traffic, {}
        if "policies" in t:
            pols = []
            for p in t["policies"]:
                if p["policy"] == "pi":
                    pols.append(PIPolicy())
                elif p["policy"] == "pi_rls":
                    r = p["rls"]
                    pols.append(PIPolicy(adaptive=RLSConfig(
                        lam=r["lam"], dwell=int(r["dwell"]),
                        kl_clamp=r["kl_clamp"],
                        p_trace_max=r["p_trace_max"])))
                elif p["policy"] == "dutycycle":
                    d = p["dutycycle"]
                    pols.append(DutyCyclePolicy(
                        n_levels=int(d["n_levels"]),
                        min_level=int(d["min_level"]),
                        deadband=d["deadband"], down_step=d["down_step"],
                        up_step=d["up_step"]))
                else:
                    raise ValueError(f"unknown policy {p['policy']!r}")
            kw["policies"] = pols
        if "phases" in t:
            kw["workloads"] = PhaseSchedule(tuple(
                Phase(ph["duration"], scale=tuple(ph["scale"].items()))
                for ph in t["phases"]), cyclic=True)
        if "detector" in t:
            d = t["detector"]
            kw["detector"] = DetectorConfig(
                drift=d["drift"], threshold=d["threshold"],
                min_gap=int(d["min_gap"]), level_eta=d["level_eta"],
                level_slack=d["level_slack"])
        if "faults" in t:
            f = t["faults"]
            kw["faults"] = flt.FaultSchedule(tuple(
                flt.FaultWindow(w["kind"], w["start"], w["duration"],
                                p1=w.get("p1", 0.0))
                for w in f["windows"]), period=f["period"])
        if "guard" in t:
            g = t["guard"]
            kw["guard"] = flt.GuardConfig(
                hold_k=int(g["hold_k"]), failsafe_k=int(g["failsafe_k"]),
                outlier_mult=g["outlier_mult"],
                recover_reset=bool(g["recover_reset"]))
        return kw

    def _seeds(self, call: int) -> np.ndarray:
        """Seed block of call ``call`` (-1: the warm-up call)."""
        start = self.seed0 + (call + 1) * self.n_seeds
        return np.arange(start, start + self.n_seeds) % SEED_SPACE

    def _call(self, seeds):
        import jax
        from repro.core import faults as flt
        from repro.core.sim import sweep

        t = self.traffic
        c = self.config
        res = sweep(self.profs, self.eps, seeds.tolist(),
                    total_work=c["total_work"], max_time=c["max_time"],
                    dt=c["dt"], tau_obj=c["tau_obj"],
                    collect_traces=t.get("collect_traces", False),
                    chunk_size=t.get("chunk_size"),
                    devices=t.get("devices"),
                    **({"backend": t["backend"]} if t.get("backend")
                       else {}), **self.scenario)
        out = {"exec_time": res.exec_time, "energy": res.energy,
               "work": res.work,
               "progress_mean": res.summary["progress_mean"],
               "power_mean": res.summary["power_mean"]}
        if "detections" in self.ref.FIELDS:
            out["detections"] = res.detections
        for name, slot in GUARD_FIELDS.items():
            if name in self.ref.FIELDS:
                out[name] = res.guard_state[..., getattr(flt, slot)]
        return jax.block_until_ready(out)

    def _counter(self) -> float:
        spec = self.traffic.get("path_counter")
        if not spec:
            return 0.0
        return registry_sample(spec["name"], spec["labels"]) or 0.0

    def setup(self):
        self.profs = self._profiles()
        self.scenario = self._scenario()
        t0 = time.perf_counter()
        with self.spans("warm"):
            self._call(self._seeds(-1))
        self.log(f"setup: warm-up call {time.perf_counter() - t0:.3f} s")
        self.counter0 = self._counter()

    def step(self):
        seeds = self._seeds(len(self.calls))
        t0 = time.perf_counter()
        with self.spans("sweep"):
            try:
                out = self._call(seeds)
                ok = True
            except Exception as e:  # a lost chunk fails the call's runs
                self.log(f"sweep call {len(self.calls)} failed: {e!r}")
                out, ok = None, False
        self.calls.append((t0, time.perf_counter(), seeds, out, ok))

    # ---- program spans for the traced run -------------------------------
    def trace_on(self):
        from repro.obs import trace as obs_trace
        self.program_trace = obs_trace.enable(True)
        self.program_trace.clear()
        self.trace_epoch = time.perf_counter()

    def trace_off(self):
        from repro.obs import trace as obs_trace
        self.traced_calls = len(self.calls)
        self.program_spans = [
            (e["name"], self.trace_epoch + e["ts"] * 1e-6,
             self.trace_epoch + (e["ts"] + e["dur"]) * 1e-6)
            for e in self.program_trace.events() if e.get("ph") == "X"]
        obs_trace.enable(False)

    # ---- results ---------------------------------------------------------
    def window_result(self) -> dict:
        runs = self.runs_per_call * len(self.calls)
        failed = 0
        self.outputs = []
        for t0, t1, seeds, out, ok in self.calls:
            if not ok:
                failed += self.runs_per_call
                self.outputs.append(None)
                continue
            host = {k: np.asarray(v, np.float64).reshape(-1)
                    for k, v in out.items()}
            bad = ~np.all([np.isfinite(v) for v in host.values()], axis=0)
            failed += int(bad.sum())
            self.outputs.append(host)
        each = sorted(t1 - t0 for t0, t1, *_ in self.calls)
        self.log(f"sweep calls: {len(each)}, {self.runs_per_call} runs each, "
                 f"{each[0]:.3f} / {each[len(each) // 2]:.3f} / "
                 f"{each[-1]:.3f} s fastest / median / slowest")
        self.path_runs = self._counter() - self.counter0
        return {"attempted": runs, "failed": failed,
                "metrics": {"campaign_runs_per_s": rate(
                    runs, self.calls[0][0], self.calls[-1][1])}}

    def check(self, control: bool = False) -> dict:
        """A sample of the window's runs, drawn from the seed, against
        the float64 reference on the same random draws. ``control`` puts
        the reference at the next precision below the configuration's
        float32 (bfloat16) in the program's place."""
        t = self.traffic
        lim = t["limits"]
        checks = {}
        done = [i for i, o in enumerate(self.outputs) if o is not None]
        if t.get("path_counter"):
            want = (self.runs_per_call * len(self.calls)
                    * float(t["path_counter"].get("per_run", 1)))
            checks["path_runs_missing"] = {
                "value": float(abs(want - self.path_runs)), "limit": 0}
        if not done:
            checks["calls_completed"] = {"value": 1, "limit": 0}
            return checks
        # the program's device outputs are freed before the reference runs
        self.calls = [(a, b, s, None, ok) for a, b, s, _, ok in self.calls]
        gap = compare(self, done, int(t["check_runs"]),
                      dtype=CONTROL_DTYPE if control else np.float64)
        for name, limit in lim.items():
            checks[name] = {"value": gap[name], "limit": limit}
        return checks

    # ---- sampling shared with the control -------------------------------
    def sample(self, done, n: int):
        """(call index, flat run index) of ``n`` runs drawn from the
        seed over the completed calls of the window."""
        rng = derive_seed(self.seed, 2)
        pick = rng.choice(len(done) * self.runs_per_call,
                          size=min(n, len(done) * self.runs_per_call),
                          replace=False)
        return (np.asarray(done)[pick // self.runs_per_call],
                pick % self.runs_per_call)

    def run_inputs(self, calls, flat):
        """Plants, gains, policy kinds and seeds of the sampled runs,
        from the configuration and the traffic (not from the program)."""
        ip, ie, ia, is_ = np.unravel_index(flat, self.grid)
        cfg = self.config
        plants = {k: np.asarray([cfg["plants"][self.plants[i]][k]
                                 for i in ip], np.float64)
                  for k in PLANT_KEYS}
        lin_max = -np.exp(-plants["alpha"] * (plants["a"]
                                              * plants["pcap_max"]
                                              + plants["b"]
                                              - plants["beta"]))
        tau_obj = cfg["tau_obj"]
        gains = {"k_p": plants["tau"] / (plants["K_L"] * tau_obj),
                 "k_i": 1.0 / (plants["K_L"] * tau_obj),
                 "setpoint": (1.0 - np.asarray(self.eps)[ie])
                 * plants["K_L"] * (1.0 + lin_max)}
        kinds = np.asarray([scan_loop.POLICIES[self.policies[a]["policy"]]
                            for a in ia])
        seeds = np.asarray([self._seeds(int(c))[s]
                            for c, s in zip(calls, is_)])
        return plants, gains, kinds, seeds


BLOCK = 4096  # runs per reference block: bounds the host memory
CONTROL_DTYPE = "bfloat16"
OFF = 1e-3    # a run whose widest gap is above this is off


def _settings(drv: Driver, policy: str) -> dict:
    """The traffic's settings of one policy ({} when it is not run)."""
    key = {"pi_rls": "rls", "dutycycle": "dutycycle"}[policy]
    return next((p[key] for p in drv.policies if p["policy"] == policy), {})


def _reference_runs(drv: Driver, plants, gains, kinds, seeds, dtype):
    """The reference's outputs for one block of runs."""
    cfg, t = drv.config, drv.traffic
    kw = dict(total_work=cfg["total_work"], max_time=cfg["max_time"],
              dt=cfg["dt"])
    if drv.reference == "closed_loop":
        noise = ref_noise.draw(seeds, ref_noise.horizon(cfg["max_time"],
                                                        cfg["dt"]))
        return closed_loop.run(plants, gains, noise, dtype=dtype, **kw)
    runs = scan_loop.Runs(
        plants, gains["setpoint"], kinds,
        {"tau_obj": cfg["tau_obj"], "rls": _settings(drv, "pi_rls"),
         "dutycycle": _settings(drv, "dutycycle"),
         "detector": t["detector"], "guard": t["guard"]},
        [(ph["duration"], ph["scale"]) for ph in t["phases"]], t["faults"],
        dtype=dtype)
    return runs.run(ref_noise.ScanDraws(seeds, cfg["max_time"], cfg["dt"]),
                    **kw)


def compare(drv: Driver, done, n: int, dtype=np.float64) -> dict:
    """Per-run relative gaps between the program's outputs (or, for the
    control, ``dtype`` reference runs put in its place) and the float64
    reference, over ``n`` sampled runs: the widest gap, the median over
    runs of each run's widest field gap, and the share of runs whose
    widest gap is above `OFF`."""
    calls, flat = drv.sample(done, n)
    plants, gains, kinds, seeds = drv.run_inputs(calls, flat)
    fields = drv.ref.FIELDS
    worst = np.zeros(len(flat))
    for lo in range(0, len(flat), BLOCK):
        sl = slice(lo, lo + BLOCK)
        args = ({k: v[sl] for k, v in plants.items()},
                {k: v[sl] for k, v in gains.items()}, kinds[sl], seeds[sl])
        want = _reference_runs(drv, *args, dtype=np.float64)
        if np.dtype(dtype) == np.float64:
            got = {k: np.asarray([drv.outputs[c][k][f] for c, f in
                                  zip(calls[sl], flat[sl])])
                   for k in fields}
        else:
            got = _reference_runs(drv, *args, dtype=np.dtype(dtype))
        for k in fields:
            worst[sl] = np.maximum(worst[sl], rel_gap(
                got[k], want[k], drv.ref.COUNTS.get(k, 0.0)))
    return {"run_gap_max": float(worst.max()),
            "run_gap_median": float(np.median(worst)),
            "run_off_share": float(np.mean(worst > OFF)),
            "runs": int(len(flat))}
