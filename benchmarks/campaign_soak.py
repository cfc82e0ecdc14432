"""Campaign soak: what durability costs and what chaos it survives.

Two questions, one benchmark module:

* **Overhead** — the same synthetic grid (a vmapped scan engine, heavy
  enough that per-chunk compute dwarfs an fsync) runs once under bare
  `executor.run_grid` and once under `supervisor.run_durable` into a
  fresh campaign directory. The delta is everything durability adds:
  per-chunk write-ahead start/commit records (each fsync'd), the event
  stream, and periodic `ExecState` checkpoints. Headline
  ``soak_overhead_pct`` lands on this commit's BENCH_sim.json history
  row (acceptance: < 3%), and the merged buffers are asserted
  bit-identical to the bare run's.
* **Chaos** — `FlakyGridFn` injects transient faults into ~10% of
  chunks; the campaign must complete with ZERO lost runs (no
  dead-letters, merge bit-identical to a clean reference). The
  ``--chaos`` CLI mode (what CI runs as its own step) additionally
  scripts one SIGTERM mid-campaign via
  ``CampaignConfig.kill_after_commits`` in a subprocess — started
  before the parent touches JAX, so the child can hold the device —
  then resumes the journal directory in-process and asserts the
  finished result is bit-for-bit the uninterrupted one.

Registered in `benchmarks.run` as opt-in module ``soak``:

  PYTHONPATH=src python -m benchmarks.run --quick --only soak   # overhead
  PYTHONPATH=src python -m benchmarks.campaign_soak --chaos \\
      --dir experiments/chaos_campaign                          # CI step
"""
from __future__ import annotations

import argparse
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path
from typing import List

import numpy as np

from benchmarks.common import Row

# overhead arm: few, fat chunks — the supervisor's fsyncs amortize over
# real compute, which is exactly how a million-run campaign is shaped
N_RUNS_QUICK = 10_000
N_RUNS_FULL = 40_000
CHUNK = 2_000
STEPS = 4_000
DIM = 8
REPS = 3  # min-of-N per arm: wall-clock noise dwarfs the fsync cost
# chaos arm: many thin chunks so a 10% fault rate means several faults
N_CHAOS = 2_000
CHUNK_CHAOS = 100
FAULT_RATE = 0.10
KILL_AFTER = 3  # commits before the scripted SIGTERM


def _engine(rows, coef):
    import jax
    import jax.numpy as jnp

    def one(seed, x0):
        def body(c, _):
            c = c * 0.999 + 0.01 * jnp.sin(c @ coef + seed * 1e-3)
            return c, None

        y, _ = jax.lax.scan(body, x0, None, length=STEPS)
        return {"y": y, "norm": jnp.sum(y * y)}

    return jax.vmap(one)(rows["seed"], rows["x0"])


def _grid(n: int):
    rng = np.random.default_rng(0)
    rows = {"seed": np.arange(n, dtype=np.float32),
            "x0": rng.standard_normal((n, DIM)).astype(np.float32)}
    coef = (np.eye(DIM, dtype=np.float32) * 0.5
            + np.float32(0.1) * np.ones((DIM, DIM), np.float32))
    return rows, (coef,)


def _leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _identical(a, b) -> bool:
    la, lb = _leaves(a), _leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(x, y) for x, y in zip(la, lb))


def _chaos_failures(n_chunks: int, rate: float):
    """Script first-attempt faults for ~``rate`` of the chunks. The
    supervisor walks chunks in order and retries in place, so chunk
    ``c``'s first attempt is call ``c`` plus one per earlier injected
    fault — the running shift keeps each retry (the very next call)
    clean."""
    from repro.core import supervisor

    stride = max(2, int(round(1.0 / rate)))
    fails, shift = {}, 0
    for c in range(0, n_chunks, stride):
        fails[c + shift] = supervisor.TransientFault(
            f"injected fault on chunk {c}")
        shift += 1
    return fails


def _chaos_campaign(dir_, n: int, *, rate: float = FAULT_RATE):
    """One supervised campaign under injected transient faults; returns
    (merged, report, reference) with the clean reference computed bare."""
    from repro.core import executor, supervisor
    from repro.obs.retry import RetryPolicy

    rows, shared = _grid(n)
    ref, _ = executor.run_grid(_engine, rows, shared, n,
                               chunk_size=CHUNK_CHAOS)
    n_chunks = -(-n // CHUNK_CHAOS)
    flaky = supervisor.FlakyGridFn(_engine,
                                   failures=_chaos_failures(n_chunks, rate))
    cfg = supervisor.CampaignConfig(
        retry=RetryPolicy(max_retries=3, base_s=0.005, max_s=0.05))
    merged, report = supervisor.run_durable(
        flaky, rows, shared, n, dir=dir_, chunk_size=CHUNK_CHAOS,
        wrap="none", config=cfg)
    return merged, report, ref


def run(quick: bool = True) -> List[Row]:
    from benchmarks import telemetry
    from repro.core import executor, supervisor

    n = N_RUNS_QUICK if quick else N_RUNS_FULL
    rows, shared = _grid(n)
    out: List[Row] = []

    # warm the executable once: run_grid caches the wrapped fn per
    # (fn, devs, donate, wrap), so both timed arms below reuse it
    warm = {k: v[:CHUNK] for k, v in rows.items()}
    executor.run_grid(_engine, warm, shared, CHUNK, chunk_size=CHUNK)

    bare_s = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        ref, _ = executor.run_grid(_engine, rows, shared, n,
                                   chunk_size=CHUNK)
        bare_s = min(bare_s, time.perf_counter() - t0)

    durable_s = float("inf")
    for _ in range(REPS):
        with tempfile.TemporaryDirectory(prefix="campaign_soak_") as td:
            t0 = time.perf_counter()
            merged, report = supervisor.run_durable(
                _engine, rows, shared, n, dir=td, chunk_size=CHUNK)
            durable_s = min(durable_s, time.perf_counter() - t0)
            n_journal = len(supervisor.read_journal(
                Path(td) / supervisor.JOURNAL_NAME)[0])

    overhead_pct = 100.0 * (durable_s - bare_s) / max(bare_s, 1e-9)
    same = _identical(ref, merged)
    out.append((f"soak/overhead/n={n}", durable_s * 1e6,
                f"bare={bare_s:.3f}s;durable={durable_s:.3f}s;"
                f"overhead={overhead_pct:.2f}%;journal={n_journal}rec"))
    out.append(("soak/bit_identical", 0.0, str(same)))

    # quick chaos arm (no subprocess): 10% transient faults, zero lost
    with tempfile.TemporaryDirectory(prefix="campaign_chaos_") as td:
        c_merged, c_report, c_ref = _chaos_campaign(td, N_CHAOS)
    lost = len(c_report.dead)
    out.append((f"soak/chaos/n={N_CHAOS}", 0.0,
                f"retries={c_report.retries};dead={lost};"
                f"identical={_identical(c_ref, c_merged)}"))

    entry = {"n_runs": n, "chunk": CHUNK, "steps": STEPS,
             "bare_s": round(bare_s, 3),
             "durable_s": round(durable_s, 3),
             "overhead_pct": round(overhead_pct, 2),
             "journal_records": n_journal,
             "bit_identical": bool(same),
             "chaos": {"n_runs": N_CHAOS, "rate": FAULT_RATE,
                       "retries": c_report.retries, "dead": lost}}
    telemetry.append_entry("campaign_soak", entry)
    telemetry.merge_history_value("soak_overhead_pct",
                                  round(overhead_pct, 2), quick)
    out.append(("soak/written", 0.0, str(telemetry.BENCH_PATH)))
    if not same or lost:
        raise RuntimeError(
            f"soak failed: bit_identical={same}, lost_chunks={lost}")
    return out


# ----------------------------------------------------------- chaos CLI
def _child_kill(dir_: str, n: int) -> None:
    """Subprocess body for the SIGTERM cycle: run the campaign with the
    chaos crash injector armed — the process signals itself right after
    the Nth fsync'd commit, so it never returns."""
    from repro.core import supervisor

    rows, shared = _grid(n)
    cfg = supervisor.CampaignConfig(
        checkpoint_every=2, kill_after_commits=KILL_AFTER,
        kill_signal=int(signal.SIGTERM))
    supervisor.run_durable(_engine, rows, shared, n, dir=dir_,
                           chunk_size=CHUNK_CHAOS, config=cfg)
    raise SystemExit("chaos child survived its own kill signal")


def run_chaos(base_dir: str) -> List[Row]:
    """The CI chaos step: one SIGTERM/resume cycle + transient-fault
    campaign. Journal directories live under ``base_dir`` so a failing
    CI run can upload them as artifacts. Raises on any lost run or
    non-identical merge.

    The SIGTERM child runs first, while this process is still off JAX:
    a device belongs to one process at a time, so a child started after
    the parent had touched JAX could not get the chip."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "run_chaos must start before this process touches JAX: its "
            "SIGTERM child needs the device")
    base = Path(base_dir)
    base.mkdir(parents=True, exist_ok=True)
    out: List[Row] = []

    # phase 1: SIGTERM mid-campaign (subprocess), resumed below
    d2 = base / "sigterm"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.campaign_soak",
         "--child-kill", str(d2), "--n", str(N_CHAOS)],
        capture_output=True, text=True, timeout=600)
    killed = proc.returncode in (-signal.SIGTERM, 128 + signal.SIGTERM)

    from repro.core import executor, supervisor

    # phase 2: 10% of chunks fault transiently — complete with zero lost
    d1 = base / "faults"
    merged, report, ref = _chaos_campaign(d1, N_CHAOS)
    ok1 = _identical(ref, merged) and not report.dead
    out.append((f"chaos/faults/n={N_CHAOS}", 0.0,
                f"retries={report.retries};dead={len(report.dead)};"
                f"identical={_identical(ref, merged)}"))

    # resume the killed campaign here, against a bare reference
    rows, shared = _grid(N_CHAOS)
    ref2, _ = executor.run_grid(_engine, rows, shared, N_CHAOS,
                                chunk_size=CHUNK_CHAOS)
    merged2, report2 = supervisor.run_durable(
        _engine, rows, shared, N_CHAOS, dir=d2, chunk_size=CHUNK_CHAOS)
    ok2 = (killed and report2.resumed and _identical(ref2, merged2))
    out.append((f"chaos/sigterm/n={N_CHAOS}", 0.0,
                f"child_rc={proc.returncode};resumed={report2.resumed};"
                f"replayed={report2.replayed};"
                f"identical={_identical(ref2, merged2)}"))

    if not (ok1 and ok2):
        sys.stderr.write(proc.stdout + proc.stderr)
        raise RuntimeError(
            f"chaos campaign failed: faults_ok={ok1}, sigterm_ok={ok2} "
            f"(journals kept in {base})")
    return out


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--chaos", action="store_true",
                   help="run the CI chaos step (faults + SIGTERM/resume)")
    p.add_argument("--dir", default="experiments/chaos_campaign",
                   help="campaign directory root for --chaos journals")
    p.add_argument("--full", action="store_true")
    p.add_argument("--child-kill", default=None, metavar="DIR",
                   help=argparse.SUPPRESS)  # internal: SIGTERM child body
    p.add_argument("--n", type=int, default=N_CHAOS,
                   help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.child_kill:
        _child_kill(args.child_kill, args.n)
        return
    rows = run_chaos(args.dir) if args.chaos else run(quick=not args.full)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
