"""Beyond-paper benchmarks: adaptive RLS control under phase change (now
fully inside the jitted scan engine), an RLS hyperparameter grid in
trace-free summary mode, and hierarchical fleet budget control at 1000+
nodes riding the same engine step."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.common import Row, timed
from repro.configs.base import PowerControlConfig
from repro.core.adaptive import RLSConfig
from repro.core.controller import PIGains
from repro.core.hierarchy import FleetConfig, simulate_fleet
from repro.core.nrm import NRM, SimulatedPowerActuator
from repro.core.plant import PROFILES
from repro.core.policies import PIPolicy
from repro.core.sim import simulate_closed_loop, sweep


def run(quick: bool = True):
    rows: list[Row] = []
    # adaptive vs fixed under 2x gain shift (compute->memory phase change)
    design = PROFILES["gros"]
    shifted = dataclasses.replace(design, K_L=design.K_L * 2)
    work = 6000.0  # paper horizon (10k-iteration scale) in both modes
    fixed_gains = PIGains.from_model(design, 0.1)

    # fixed gains: designed on the unshifted model, run on the shifted
    # plant — one jitted scan via the batch engine
    fixed = simulate_closed_loop(shifted, gains=fixed_gains,
                                 total_work=work, max_time=1024.0, seed=6)
    # adaptive (RLS): the estimator now lives INSIDE the scan carry, so
    # this is the same single-compile engine (no per-step Python loop)
    adaptive_kw = dict(gains=fixed_gains, total_work=work,
                      max_time=1024.0, seed=6,
                      adaptive=RLSConfig(), design=design)
    simulate_closed_loop(shifted, **adaptive_kw)  # warm the compile
    t0 = time.time()
    adap = simulate_closed_loop(shifted, **adaptive_kw)
    engine_s = time.time() - t0
    # oracle per-step Python loop, timed for the speedup headline
    nrm = NRM(PowerControlConfig(epsilon=0.1, plant_profile="gros",
                                 adaptive=True))
    nrm.actuator = SimulatedPowerActuator(shifted, seed=5)
    t0 = time.time()
    tr = nrm._run_simulated_python(total_work=work, seed=6)
    loop_s = time.time() - t0
    rows.append(("beyond/adaptive_gain_shift", engine_s * 1e6,
                 f"fixed_time={fixed.exec_time:.0f}s;"
                 f"adaptive_time={adap.exec_time:.0f}s;"
                 f"loop_time={float(tr['t'][-1]):.0f}s;"
                 f"engine_speedup={loop_s / max(engine_s, 1e-9):.0f}x"))

    # RLS hyperparameter grid: profiles x eps x lambda x seeds in ONE
    # vmapped call, trace-free (summary mode) so the grid scales to 100k
    # runs (--full) without materializing per-step buffers
    if quick:
        profs, eps, seeds = "gros", (0.05, 0.1, 0.2), range(25)
        lams = (0.97, 0.99, 0.995, 0.999)
    else:
        profs, eps, seeds = ("gros", "dahu"), \
            (0.02, 0.05, 0.1, 0.15, 0.2), range(1000)
        lams = (0.9, 0.95, 0.97, 0.98, 0.99, 0.992, 0.995, 0.997,
                0.999, 0.9995)
    cfgs = [RLSConfig(lam=l) for l in lams]
    t0 = time.time()
    res = sweep(profs, eps, seeds, total_work=1200.0, max_time=1024.0,
                policies=[PIPolicy(adaptive=c) for c in cfgs],
                collect_traces=False)
    grid_s = time.time() - t0
    n_runs = int(np.asarray(res.exec_time).size)
    # mean completion time per lambda, pooled over the other axes
    et = np.asarray(res.exec_time).reshape(-1, len(cfgs),
                                           len(list(seeds)))
    per_lam = et.mean(axis=(0, 2))
    best = int(per_lam.argmin())
    rows.append(("beyond/adaptive_grid", grid_s * 1e6 / n_runs,
                 f"runs={n_runs};runs_per_sec={n_runs / grid_s:.0f};"
                 f"best_lam={lams[best]};"
                 f"best_mean_time={per_lam[best]:.0f}s"))

    # fleet: budget adherence + straggler mitigation at scale (node level
    # is the engine's fused step vmapped across nodes)
    for n in (64, 1024):
        prof = PROFILES["dahu"]
        peak = float(prof.power_of_pcap(prof.pcap_max)) * n
        fc = FleetConfig(n_nodes=n, epsilon=0.1, power_budget=0.7 * peak)
        us, tr = timed(lambda: simulate_fleet(prof, fc, steps=60, seed=0),
                       reps=1)
        power = np.asarray(tr["power"])[20:].mean()
        rows.append((f"beyond/fleet_{n}", us,
                     f"power={power/1e3:.1f}kW;budget={0.7*peak/1e3:.1f}kW;"
                     f"median_progress="
                     f"{float(np.asarray(tr['progress_med'])[20:].mean()):.1f}Hz"))
    return rows
