"""The comparison that decides `correct`, at a size the CPU holds: a
sound run passes, the control (the reference at the next precision
below the configuration's) fails, and a run with the timed path broken
underneath comes out not correct."""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402

SMALL = {
    # the kernel path runs in the Pallas interpreter here
    "campaign_fixed_pi": (
        {"seeds_per_call": 2, "backend": "pallas", "check_runs": 128,
         "path_counter": {"name": "closed_loop_runs_total",
                          "labels": {"path": "interpret"}}}, {}),
    # the scan engine in chunks of 12 runs, as the cell's 1,536
    "campaign_phased_faulted": (
        {"seeds_per_call": 2, "chunk_size": 12, "check_runs": 36}, {}),
    "plane_frontier_fleet": (
        {"check_tenants_per_group": 3, "warm_periods": 3},
        {"tenants": 96, "capacity": 128}),
}
SEED = 2 ** 31 + 4242


def _execute(root, cell, seconds=0.5, **kw):
    traffic, config = SMALL[cell]
    return run.execute(cell, SEED, seconds, False, root=root,
                       require_tpu=False, overrides=traffic,
                       config_overrides=config, log=lambda m: None, **kw)


def _failing(out):
    return [k for k, c in out["checks"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell, checkout):
    out = _execute(checkout, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_at_lower_precision_fails(cell, checkout):
    out = _execute(checkout, cell, control=True)
    assert out["correct"], out["checks"]
    assert any(v > out["checks"][k]["limit"]
               for k, v in out["control"].items()), out["control"]


def _half(x, axis=-1):
    """The computed half of the seeds, and their mean in place of the
    half left out."""
    x = np.asarray(x)
    return np.concatenate([x, np.broadcast_to(x.mean(axis, keepdims=True),
                                              x.shape)], axis)


def _broken_sweep(real, fault):
    def sweep(profiles, epsilons, seeds, *a, **kw):
        seeds = list(seeds)
        if fault == "half_left_out":
            res = real(profiles, epsilons, seeds[:len(seeds) // 2], *a, **kw)
        else:
            res = real(profiles, epsilons, seeds, *a, **kw)
        fix = {"state_unchanged": np.zeros_like,
               "answer_altered": lambda x: np.asarray(x) * (1 + 1e-2),
               "half_left_out": _half}[fault]
        summary = dict(res.summary)
        for k in ("progress_mean", "power_mean"):
            summary[k] = fix(summary[k])
        extra = {}
        if res.detections is not None:  # the scan cell's counters
            extra["detections"] = fix(res.detections)
            extra["guard_state"] = (_half(res.guard_state, -2)
                                    if fault == "half_left_out"
                                    else fix(res.guard_state))
        return dataclasses.replace(res, exec_time=fix(res.exec_time),
                                   energy=fix(res.energy),
                                   work=fix(res.work), summary=summary,
                                   **extra)
    return sweep


def _broken_tick(real, fault):
    last = {}

    def tick(self, *a, **kw):
        out = real(self, *a, **kw)
        prev = last.get(id(self))
        last[id(self)] = dict(out, applied=np.array(out["applied"]))
        if prev is None:
            return out
        applied = np.array(out["applied"])
        if fault == "state_unchanged":
            applied = prev["applied"]
        elif fault == "half_left_out":
            applied[::2] = prev["applied"][::2]
        else:
            applied = applied * (1 - 1e-2)
        last[id(self)]["applied"] = np.array(applied)
        return dict(out, applied=applied)
    return tick


FAULTS = ("state_unchanged", "half_left_out", "answer_altered")


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch,
                                          checkout):
    if cell.startswith("campaign"):
        from repro.core import sim
        monkeypatch.setattr(sim, "sweep", _broken_sweep(sim.sweep, fault))
    else:
        from repro.core.plane import ControlPlane
        monkeypatch.setattr(ControlPlane, "tick",
                            _broken_tick(ControlPlane.tick, fault))
    out = _execute(checkout, cell)
    assert not out["correct"] and _failing(out), out["checks"]


FOUR = r"""
import json, sys
sys.path[:0] = [{bench!r}, {src!r}]
import numpy as np
import run
from repro.core import executor

small = dict(seeds_per_call=8, backend="pallas", check_runs=256,
             devices="all",
             path_counter=dict(name="closed_loop_runs_total",
                               labels=dict(path="interpret")))
quiet = lambda m: None
sound = run.execute("campaign_fixed_pi", {seed}, 0.5, False,
                    require_tpu=False, overrides=small, log=quiet)
real = executor._per_device

def one_slice_lost(fn, devs):
    inner = real(fn, devs)
    def wrapped(batched, *shared):
        traces, final = inner(batched, *shared)
        per = len(np.asarray(batched["key"])) // len(devs)
        # the last chip's slice never comes back: its rows repeat the
        # first chip's
        return traces, {{k: np.concatenate([v[:-per], v[:per]])
                        for k, v in final.items()}}
    return wrapped

executor._COMPILED.clear()
executor._per_device = one_slice_lost
broken = run.execute("campaign_fixed_pi", {seed}, 0.5, False,
                     require_tpu=False, overrides=small, log=quiet)
print(json.dumps([sound["correct"], broken["correct"]]))
"""


def test_four_chip_split_without_one_chips_slice_is_not_correct():
    """The exchange between chips left out: on four virtual CPU
    devices, a sound run is correct and one whose last chip's slice
    never merges back is not."""
    import json
    import os
    import subprocess

    code = FOUR.format(bench=str(BENCH), src=str(BENCH.parent / "src"),
                       seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == [True, False]


def test_traced_run_profiles_only_the_mix_s_trace_calls(checkout,
                                                        monkeypatch):
    """A mix with ``trace_calls`` stops the profiler after that many
    timed calls while the window runs on, and the host-span readers
    average over the traced calls alone."""
    import jax
    import trace_reduce
    from repro.core import sim

    calls, events = [], []
    real = sim.sweep

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(sim, "sweep", counted)
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: events.append(("start", len(calls))))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: events.append(("stop", len(calls))))
    reduced = {"busy_s": 0.01, "window_s": 0.1, "ops": {}, "modules": {},
               "n_spans": {"bench/sweep": 1},
               "breakdown": {"device_ops": [], "idle_gaps": []}}
    monkeypatch.setattr(trace_reduce, "reduce_dir",
                        lambda *a, **kw: reduced)
    traffic, config = SMALL["campaign_phased_faulted"]
    out = run.execute("campaign_phased_faulted", SEED, 1.0, True,
                      root=checkout, require_tpu=False,
                      overrides={**traffic, "trace_calls": 1},
                      config_overrides=config, log=lambda m: None)
    # the warm-up call, then one timed call under the profiler
    assert events == [("start", 1), ("stop", 2)]
    assert out["correct"]
    assert out["metrics"]["engine.device_ms"]["value"] == 10.0
    assert out["metrics"]["sweep.host_ms"]["value"] > 0
    assert out["metrics"]["executor.host_ms"]["value"] > 0
