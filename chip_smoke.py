#!/usr/bin/env python3
"""Smoke run of the campaign engine and the control plane on a TPU.

    python chip_smoke.py            # one chip: every phase below
    python chip_smoke.py --chips 4  # the paper campaign sharded over 4
                                    # chips vs the same grid on one chip

Phases (one chip), each through the entry points a user calls; the
first phase that fails ends the run with a non-zero exit code:

1. device — the first JAX device must be a TPU; there is no CPU
   fallback.
2. paper campaign, kernel path — Table 2 plants x Fig. 7's 11-value
   epsilon grid x 1,000 seeds (33,000 runs, a 2,048-step bucket,
   summary mode) through `sweep` with its default backend. It must run
   the natively compiled closed-loop Pallas kernel, not the
   interpreter and not the scan engine, and agree with
   (a) the kernel's jnp oracle on identical noise over a 512-run slice,
       and, in trace mode, over a 264-run slice step by step
   (b) the scan engine's per-(plant, epsilon) medians over 100 seeds.
3. campaign, scan path — the phased / detector / faulted / guarded /
   mixed-policy grid the kernel cannot take, chunked under the durable
   supervisor; no chunk may dead-letter and no device be quarantined.
4. control plane — 10,000 mixed-policy tenants, 5 service periods;
   every applied cap finite and inside its tenant's actuator range.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

PLANTS = ("gros", "dahu", "yeti")      # Table 2
N_SEEDS = 1000
TOTAL_WORK = 6000.0
MAX_TIME = 2000.0                      # 2,048-step bucket
ORACLE_SLICE = 512
SCAN_SEEDS = 100
SCAN_EPS = (0.1, 0.3)
SCAN_CAMPAIGN_SEEDS = 256
SCAN_CHUNK = 1536
PLANE_TENANTS = 10_000
PLANE_TICKS = 5

# (a) kernel vs its jnp oracle on identical noise: every run, and in
# trace mode every valid step, within ORACLE_RTOL. On a v5e the two
# agreed bit for bit on all 512 runs; the margin only admits exp/log
# lowered a few ulps apart by Mosaic and XLA. An ulp that moved a
# discrete event (a heartbeat count's rounding, a drop draw, the
# completion step) would fail it.
ORACLE_RTOL = 1e-4
TRACE_SEEDS = 8                        # trace-mode slice: 264 runs
# (b) kernel vs scan medians over 100 seeds. The standard error of such
# a median is <= 0.7% here, and the two engines draw heartbeat counts
# differently (rounded Gaussian vs Poisson), which biases yeti by up to
# 2.2% — both measured with the jnp oracle against the scan on a CPU.
MEDIAN_RTOL = 0.04


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info(expect_count: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == "tpu",
          f"no TPU: JAX found {info['platform']} devices")
    check(info["count"] >= expect_count,
          f"need {expect_count} chips, JAX found {info['count']}")
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    return info


def _eps_grid():
    from benchmarks.fig7_pareto import EPS_GRID
    return EPS_GRID


def _paper_sweep(n_seeds: int, collect_traces: bool = False, **kw):
    import jax
    from repro.core.sim import sweep

    res = sweep(PLANTS, _eps_grid(), range(n_seeds),
                total_work=TOTAL_WORK, max_time=MAX_TIME,
                collect_traces=collect_traces, **kw)
    jax.block_until_ready(res.exec_time)
    return res


def _kernel_runs(path: str) -> float:
    from repro.kernels.closed_loop.ops import runs_counter
    return runs_counter().value(path=path)


def _scan_compiles() -> tuple:
    from repro.core import executor, sim
    return (sim._flat_core.cache_info().misses,
            sum(v._cache_size() for v in executor._COMPILED.values()
                if hasattr(v, "_cache_size")))


def _noise_bytes(n_runs: int) -> int:
    from repro.kernels.closed_loop import ref as R
    steps = 64 * math.ceil(MAX_TIME / 64)  # the kernel's time chunk
    return n_runs * steps * R.N_NOISE * 4


def _finite(res) -> bool:
    import numpy as np
    return all(np.isfinite(np.asarray(x)).all()
               for x in (res.exec_time, res.energy, res.work))


def paper_campaign(n_seeds: int = N_SEEDS) -> None:
    """Phase 2: the Fig. 7 grid at scale through the kernel."""
    import numpy as np

    eps = _eps_grid()
    n_runs = len(PLANTS) * len(eps) * n_seeds
    print(f"kernel campaign: {n_runs} runs, pre-drawn noise "
          f"{_noise_bytes(n_runs)} bytes", flush=True)
    scan0 = _scan_compiles()
    native0 = _kernel_runs("native")
    t0 = time.perf_counter()
    res = _paper_sweep(n_seeds)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = _paper_sweep(n_seeds)
    warm = time.perf_counter() - t0
    print(f"kernel campaign: cold {cold:.3f} s (compile included), "
          f"warm {warm:.3f} s", flush=True)
    check(_kernel_runs("native") - native0 == 2 * n_runs,
          "the campaign did not run the natively compiled kernel")
    check(_kernel_runs("interpret") == 0, "the kernel was interpreted")
    check(_scan_compiles() == scan0, "the campaign fell back to the scan")
    check(np.asarray(res.exec_time).shape == (3, len(eps), n_seeds),
          f"unexpected result shape {np.asarray(res.exec_time).shape}")
    check(_finite(res), "non-finite campaign results")
    check(np.array_equal(np.asarray(res.energy), np.asarray(again.energy)),
          "two identical campaigns disagree")
    oracle_check(res, n_seeds)
    scan_check(res)
    trace_check()


def _oracle(ip, ie, is_, collect: bool):
    """The jnp oracle on runs (plant, epsilon, seed) = (ip, ie, is_) of
    the paper grid, rebuilt from the same per-run rows and keys as
    `sweep`'s (so identical noise): (traces | None, final)."""
    import jax
    import numpy as np
    from repro.core import sim
    from repro.core.controller import PIGains
    from repro.core.plant import PROFILES

    eps = _eps_grid()
    rows = {
        "prof": np.stack([np.asarray(sim.profile_values(
            PROFILES[PLANTS[p]])) for p in ip]),
        "gains": np.stack([np.asarray(sim.gains_values(PIGains.from_model(
            PROFILES[PLANTS[p]], eps[e]))) for p, e in zip(ip, ie)]),
        "key": np.stack([np.asarray(jax.random.PRNGKey(int(s)))
                         for s in is_]),
    }
    return sim._flat_core_pallas(collect, use_ref=True)(
        rows, TOTAL_WORK, MAX_TIME, 1.0, 0.0)


def _max_rel(got, want) -> float:
    """Largest |got - want| / |want|; exact agreement (zeros included)
    counts 0."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(diff == 0, 0.0, diff / np.abs(want))
    return float(np.max(rel, initial=0.0))


def _check_finals(res, fin, idx, what: str) -> str:
    """Every run's exec_time / energy / work against the oracle's."""
    import numpy as np

    worst = []
    for name, key in (("exec_time", "t"), ("energy", "energy"),
                      ("work", "work")):
        rel = _max_rel(np.asarray(getattr(res, name))[tuple(idx)],
                       fin[key])
        worst.append(f"{name} max rel {rel:.3g}")
        check(rel <= ORACLE_RTOL,
              f"{what} {name}: max rel {rel:.3g} > {ORACLE_RTOL}")
    return ", ".join(worst)


def oracle_check(res, n_seeds: int) -> None:
    """(a): a 512-run slice of the campaign against the jnp oracle."""
    import numpy as np

    shape = (len(PLANTS), len(_eps_grid()), n_seeds)
    flat = np.linspace(0, math.prod(shape) - 1, ORACLE_SLICE).astype(int)
    idx = np.unravel_index(flat, shape)
    _, fin = _oracle(*idx, collect=False)
    got = _check_finals(res, fin, idx, "kernel vs oracle")
    print(f"check (a) kernel vs oracle, {ORACLE_SLICE} runs: {got} "
          f"(limit {ORACLE_RTOL})", flush=True)


def trace_check(n_seeds: int = TRACE_SEEDS) -> None:
    """(a) in trace mode: `sweep`'s default backend on a slice of the
    paper grid with per-step traces, against the jnp oracle's traces."""
    import numpy as np
    from repro.kernels.closed_loop.ref import TRACE_KEYS

    shape = (len(PLANTS), len(_eps_grid()), n_seeds)
    n_runs = math.prod(shape)
    native0 = _kernel_runs("native")
    scan0 = _scan_compiles()
    res = _paper_sweep(n_seeds, collect_traces=True)
    check(_kernel_runs("native") - native0 == n_runs,
          "trace mode did not run the natively compiled kernel")
    check(_scan_compiles() == scan0, "trace mode fell back to the scan")
    idx = np.indices(shape).reshape(3, n_runs)
    traces, fin = _oracle(*idx, collect=True)
    got = _check_finals(res, fin, idx, "trace mode vs oracle")
    valid = np.asarray(traces["valid"])
    check(np.array_equal(np.asarray(res.traces["valid"]).reshape(
        valid.shape), valid), "trace mode vs oracle: valid steps differ")
    worst = 0.0
    for key in TRACE_KEYS:
        if key == "valid":
            continue
        mine = np.asarray(res.traces[key]).reshape(valid.shape)
        check(np.isfinite(mine[valid]).all(),
              f"trace mode: non-finite {key} on a valid step")
        rel = _max_rel(mine[valid], np.asarray(traces[key])[valid])
        worst = max(worst, rel)
        check(rel <= ORACLE_RTOL,
              f"trace mode vs oracle {key}: max rel {rel:.3g} > "
              f"{ORACLE_RTOL}")
    print(f"check (a) trace mode vs oracle, {n_runs} runs x "
          f"{valid.shape[1]} steps ({int(valid.sum())} valid): {got}, "
          f"traces max rel {worst:.3g} (limit {ORACLE_RTOL})", flush=True)


def scan_check(res) -> None:
    """(b): per-(plant, epsilon) medians against the scan engine."""
    import numpy as np

    scan = _paper_sweep(SCAN_SEEDS, backend="scan")
    worst = {}
    for name in ("exec_time", "energy"):
        k = np.median(np.asarray(getattr(res, name))[..., :SCAN_SEEDS], -1)
        s = np.median(np.asarray(getattr(scan, name)), -1)
        rel = np.abs(k - s) / np.abs(s)
        worst[name] = float(rel.max())
        check(rel.max() <= MEDIAN_RTOL,
              f"kernel vs scan median {name}: max rel {rel.max():.4f} at "
              f"(plant, eps) {np.unravel_index(rel.argmax(), rel.shape)}")
    print("check (b) kernel vs scan medians, 100 seeds: "
          + ", ".join(f"{n} max rel {v:.4f}" for n, v in worst.items())
          + f" (limit {MEDIAN_RTOL})", flush=True)


def scan_campaign_kwargs() -> dict:
    """The phased / detector / faulted / guarded / mixed-policy grid
    (the one the kernel cannot take) — shared with the chip-compile
    test."""
    from benchmarks.fig9_chaos import chaos_schedule
    from repro.core.adaptive import RLSConfig
    from repro.core.faults import GuardConfig
    from repro.core.policies import DutyCyclePolicy, PIPolicy
    from repro.core.workloads.detect import DetectorConfig
    from repro.core.workloads.schedule import stream_dgemm_schedule

    return dict(
        profiles=PLANTS, epsilons=SCAN_EPS, total_work=TOTAL_WORK,
        max_time=MAX_TIME, collect_traces=False,
        policies=[PIPolicy(), PIPolicy(adaptive=RLSConfig()),
                  DutyCyclePolicy()],
        workloads=stream_dgemm_schedule(cyclic=True),
        detector=DetectorConfig(), faults=chaos_schedule(0.10),
        guard=GuardConfig(), chunk_size=SCAN_CHUNK)


def scan_campaign(n_seeds: int = SCAN_CAMPAIGN_SEEDS) -> None:
    """Phase 3: the scan engine under the durable supervisor."""
    import numpy as np
    from repro.core import supervisor
    from repro.core.sim import sweep

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
        t0 = time.perf_counter()
        res = sweep(seeds=range(n_seeds), durable=d,
                    **scan_campaign_kwargs())
        wall = time.perf_counter() - t0
        records, _ = supervisor.read_journal(
            Path(d) / supervisor.JOURNAL_NAME)
    kinds = [r["k"] for r in records]
    check("dead" not in kinds and "quarantine" not in kinds,
          f"supervisor dead-lettered or quarantined: {kinds}")
    done = np.asarray(res.completed)
    check(_finite(res), "non-finite scan-campaign results")
    check(bool(done.any()), "no scan-campaign run completed")
    print(f"scan campaign: {done.size} runs in {wall:.3f} s (compile "
          f"included), {int(done.sum())} completed, "
          f"{kinds.count('retry')} retries", flush=True)


def control_plane(n: int = PLANE_TENANTS, ticks: int = PLANE_TICKS) -> None:
    """Phase 4: one 10k-tenant plane, ``ticks`` service periods."""
    import numpy as np
    from benchmarks.plane_load import drive, make_plane
    from repro.core.plane import GAIN_FIELDS

    plane = make_plane(n)
    drive(plane, 1)  # compiles the (branch set, capacity bucket) tick
    walls = []
    for _ in range(ticks):
        t0 = time.perf_counter()
        out = drive(plane, 1)
        walls.append(time.perf_counter() - t0)
        alive = plane._alive
        lo = plane._gains[alive, GAIN_FIELDS.index("pcap_min")]
        hi = plane._gains[alive, GAIN_FIELDS.index("pcap_max")]
        cap = out["applied"][alive]
        check(np.isfinite(cap).all(), "non-finite applied caps")
        check(((cap >= lo) & (cap <= hi)).all(),
              "applied caps outside [pcap_min, pcap_max]")
    print(f"control plane: {plane.n_tenants} tenants (capacity "
          f"{plane.capacity}), median service period (ingest + tick) "
          f"{float(np.median(walls)):.6f} s over {ticks}", flush=True)


def sharded_campaign(n_chips: int) -> None:
    """--chips 4: the paper campaign with devices="all" vs one chip of
    the same process; per-run results must be identical."""
    import numpy as np

    out = {}
    native0 = _kernel_runs("native")
    for label, devices in (("all chips", "all"), ("one chip", None)):
        t0 = time.perf_counter()
        out[label] = _paper_sweep(N_SEEDS, devices=devices)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        _paper_sweep(N_SEEDS, devices=devices)
        warm = time.perf_counter() - t0
        print(f"kernel campaign on {label}: cold {cold:.3f} s, warm "
              f"{warm:.3f} s", flush=True)
    a, b = out["all chips"], out["one chip"]
    n_runs = np.asarray(a.exec_time).size
    check(_kernel_runs("native") - native0 == 4 * n_runs,
          "the campaigns did not run the natively compiled kernel")
    check(_finite(a), "non-finite sharded results")
    for name in ("exec_time", "energy", "work", "n_steps"):
        check(np.array_equal(np.asarray(getattr(a, name)),
                             np.asarray(getattr(b, name))),
              f"{n_chips}-chip and 1-chip {name} differ")
    for name in ("progress_hist", "pcap_hist"):
        check(np.array_equal(np.asarray(a.summary[name]),
                             np.asarray(b.summary[name])),
              f"{n_chips}-chip and 1-chip {name} differ")
    print(f"sharded campaign: {n_runs} runs identical on {n_chips} chips "
          "and on one", flush=True)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the sharded campaign comparison")
    args = p.parse_args()

    from repro.core.sim import enable_compilation_cache

    try:
        info = device_info(args.chips)
        enable_compilation_cache()
        if args.chips == 4:
            sharded_campaign(4)
        else:
            paper_campaign()
            scan_campaign()
            control_plane()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main())
