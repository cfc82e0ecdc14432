"""Machine-readable perf telemetry: times the engine's flagship workloads
and writes BENCH_sim.json at the repo root, so the perf trajectory stays
comparable across PRs without parsing benchmark stdout.

Entries (each with first-call and warm wall time plus runs/sec):

* ``fig7_sweep``     — the quick Fig. 7 grid (2 profiles x 5 eps x 3
  seeds, summary mode).
* ``adaptive_grid``  — an RLS hyperparameter grid (eps x lambda x seeds,
  summary mode) through the adaptive scan engine.
* ``fleet_64`` / ``fleet_1024`` — the two-level fleet run at both scales.
* ``plane_tick_10k``  — one full multi-tenant ControlPlane service
  period (heartbeat ingest + Eq. 1 aggregation + the vmapped control
  tick) at 10k mixed-policy tenants; runs/sec is tenant-ticks/sec.
* ``sweep_throughput`` — the headline metric: warm runs/sec of one
  summary-mode PI grid through each execution layout (unchunked scan,
  chunked+donated scan, the scan sharded over every local device when
  there are several, and the Pallas closed-loop kernel — in interpret
  mode on a reduced grid off the chip). ``improvement`` is
  best-alternative vs unchunked (``scan_oneshot``: one chunk).

"cold" is the first in-process call: with a warm persistent XLA cache it
measures trace + cache load, not a from-scratch compile.

Observability plumbing: every numeric entry field and headline scalar is
published into the process metrics registry (`repro.obs.metrics`) as
``bench_entry{entry=,field=}`` / ``bench_headline{key=}`` gauges, and the
history row / BENCH file values are read back OUT of a registry snapshot
— the registry is the source of truth, the JSON files are exports. Each
`run()` also arms the span tracer and writes the registry snapshot
(``BENCH_metrics.json``) and the chrome trace of the run's executor
chunk spans (``BENCH_trace.json``) next to BENCH_sim.json, both
schema-validated before the write (a malformed export fails the
benchmark loudly)."""
from __future__ import annotations

import json
import platform
import time
from pathlib import Path

from benchmarks.common import Row
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_sim.json"


def _metrics_path() -> Path:
    # derived from BENCH_PATH (not cached) so tests that monkeypatch
    # BENCH_PATH get all three exports in the same sandbox dir
    return BENCH_PATH.with_name("BENCH_metrics.json")


def _trace_path() -> Path:
    return BENCH_PATH.with_name("BENCH_trace.json")


def _publish_entry(name: str, payload: dict) -> None:
    """Mirror an entry's numeric fields into the registry
    (``bench_entry{entry=,field=}``)."""
    g = obs_metrics.get_registry().gauge(
        "bench_entry", "numeric benchmark entry fields",
        labelnames=("entry", "field"))
    for k, v in payload.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            g.set(float(v), entry=name, field=k)


def _entry_fields_from_snapshot(snap: dict, field: str) -> dict:
    """{entry: value} for one field of every published bench_entry."""
    m = snap.get("metrics", {}).get("bench_entry")
    if m is None:
        return {}
    return {s["labels"]["entry"]: s["value"] for s in m["samples"]
            if s["labels"].get("field") == field}


def _timed_entry(fn, n_runs: int) -> dict:
    """fn must return a device array tied to the workload's output; we
    block on it so async dispatch doesn't fake the wall time."""
    import jax

    t0 = time.time()
    jax.block_until_ready(fn())
    cold = time.time() - t0
    t0 = time.time()
    jax.block_until_ready(fn())
    warm = time.time() - t0
    return {"cold_s": round(cold, 4), "warm_s": round(warm, 4),
            "runs": n_runs,
            "runs_per_sec": round(n_runs / max(warm, 1e-9), 2)}


def collect(quick: bool = True) -> dict:
    import jax

    from repro.core.hierarchy import FleetConfig, simulate_fleet
    from repro.core.adaptive import RLSConfig
    from repro.core.plant import PROFILES
    from repro.core.policies import PIPolicy
    from repro.core.sim import sweep

    entries = {}
    eps = (0.0, 0.05, 0.1, 0.15, 0.3)
    reps = 3 if quick else 30
    entries["fig7_sweep"] = _timed_entry(
        lambda: sweep(("gros", "dahu"), eps, range(reps),
                      total_work=6000.0, max_time=2000.0,
                      collect_traces=False).exec_time,
        2 * len(eps) * reps)

    pls = [PIPolicy(adaptive=RLSConfig(lam=l))
           for l in (0.97, 0.99, 0.995, 0.999)]
    seeds = 25 if quick else 250
    entries["adaptive_grid"] = _timed_entry(
        lambda: sweep("gros", (0.05, 0.1, 0.2), range(seeds),
                      total_work=1200.0, max_time=1024.0, policies=pls,
                      collect_traces=False).exec_time,
        3 * len(pls) * seeds)

    for n in (64, 1024):
        prof = PROFILES["dahu"]
        peak = float(prof.power_of_pcap(prof.pcap_max)) * n
        fc = FleetConfig(n_nodes=n, epsilon=0.1, power_budget=0.7 * peak)
        entries[f"fleet_{n}"] = _timed_entry(
            lambda: simulate_fleet(prof, fc, steps=60, seed=0)["power"],
            n)

    # the control plane's headline: one full service period at 10k
    # mixed-policy tenants (plane_load carries the 1k/100k scaling
    # record; this row is what accumulates in the history trajectory)
    from benchmarks.plane_load import HEADLINE, drive, make_plane
    plane = make_plane(HEADLINE)
    entries["plane_tick_10k"] = _timed_entry(
        lambda: drive(plane, 1)["applied"], HEADLINE)

    entries["sweep_throughput"] = _sweep_throughput(quick)

    return {
        "schema": 1,
        "quick": quick,
        "platform": platform.platform(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "entries": entries,
    }


def _sweep_throughput(quick: bool = True) -> dict:
    """Warm runs/sec of ONE summary-mode PI grid through every execution
    layout (`repro.core.sim.sweep` backends / `repro.core.executor`).
    The grid is identical across layouts, so the ratios are honest; the
    recorded ``improvement`` is best-alternative vs the unchunked scan
    (one chunk of every run). Off the chip the Pallas kernel rides a
    reduced grid — the interpreter executes the kernel body op by op, so
    its number is a correctness-path record, not a horse race."""
    import jax

    from repro.core.sim import sweep

    eps = (0.0, 0.05, 0.1, 0.15, 0.3)
    # big enough that per-chunk dispatch amortizes and the device split
    # has real work to parallelize (the sharded win needs scale)
    seeds = 2000 if quick else 5000
    n_runs = len(eps) * seeds
    kw = dict(total_work=1200.0, max_time=500.0, collect_traces=False)
    chunk = n_runs // 2

    def timed(variant_kw, n):
        fn = lambda: sweep("gros", eps, range(seeds), **kw,
                           **variant_kw).exec_time
        jax.block_until_ready(fn())
        t0 = time.time()
        jax.block_until_ready(fn())
        warm = time.time() - t0
        return {"warm_s": round(warm, 4),
                "runs_per_sec": round(n / max(warm, 1e-9), 2)}

    scan = {"backend": "scan"}
    backends = {
        "scan_oneshot": timed(scan, n_runs),
        "scan_chunked": timed({**scan, "chunk_size": chunk}, n_runs),
    }
    # sharded: ONE chunk split across every local device — chunking pays
    # its dispatch cost only when it buys memory or parallelism, so the
    # sharded entry uses the layout that buys parallelism. The device
    # count is fixed when JAX starts, so one device means no such row.
    n_dev = len(jax.local_devices())
    if n_dev > 1:
        backends[f"scan_sharded_{n_dev}dev"] = timed(
            {**scan, "chunk_size": n_runs, "devices": "all"}, n_runs)
    native = jax.default_backend() == "tpu"
    if native:
        backends["pallas_native"] = timed({"backend": "pallas"}, n_runs)
    elif quick:
        # reduced grid: interpret mode is the correctness path on CPU
        pallas_seeds = 4
        pk = dict(kw)
        pk["max_time"] = 128.0
        fnp = lambda: sweep("gros", eps[:2], range(pallas_seeds),
                            backend="pallas", **pk).exec_time
        jax.block_until_ready(fnp())
        t0 = time.time()
        jax.block_until_ready(fnp())
        warm = time.time() - t0
        backends["pallas_interpret"] = {
            "warm_s": round(warm, 4),
            "runs_per_sec": round(2 * pallas_seeds / max(warm, 1e-9), 2),
            "note": "reduced grid; interpret mode (no TPU)"}
    one = backends["scan_oneshot"]
    alts = {k: v for k, v in backends.items()
            if k not in ("scan_oneshot", "pallas_interpret")}
    best = max(alts, key=lambda k: alts[k]["runs_per_sec"])
    return {"runs": n_runs,
            "cold_s": 0.0,  # layouts share the warmed engines above
            "warm_s": alts[best]["warm_s"],
            "runs_per_sec": alts[best]["runs_per_sec"],
            "best": best,
            "devices": n_dev,
            "improvement": round(alts[best]["runs_per_sec"]
                                 / max(one["runs_per_sec"], 1e-9), 3),
            "backends": backends}


def _read_bench() -> dict:
    """Current BENCH_sim.json contents (empty skeleton if missing or
    corrupt) — the single reader both writers below go through."""
    if BENCH_PATH.exists():
        try:
            return json.loads(BENCH_PATH.read_text())
        except json.JSONDecodeError:
            pass
    return {"schema": 1, "entries": {}}


def append_entry(name: str, payload: dict) -> None:
    """Merge one named entry into BENCH_sim.json (creating it if needed)
    without disturbing the other entries — the hook other benchmark
    modules (e.g. policy_faceoff) use to persist machine-readable
    results. Numeric fields flow through the metrics registry: they are
    published as ``bench_entry`` gauges and the written values are read
    back out of a registry snapshot, so the JSON file and the exported
    metrics snapshot can never disagree."""
    _publish_entry(name, payload)
    snap = obs_metrics.get_registry().snapshot()
    fields = {s["labels"]["field"]: s["value"]
              for s in snap["metrics"]["bench_entry"]["samples"]
              if s["labels"]["entry"] == name} \
        if "bench_entry" in snap.get("metrics", {}) else {}
    data = _read_bench()
    data.setdefault("entries", {})[name] = {
        k: fields.get(k, v) if isinstance(v, (int, float))
        and not isinstance(v, bool) else v
        for k, v in payload.items()}
    BENCH_PATH.write_text(json.dumps(data, indent=2) + "\n")


_OWNED_PREFIXES = ("fig7_sweep", "adaptive_grid", "fleet_",
                   "plane_tick", "sweep_throughput")
_HISTORY_CAP = 50


def _git_rev() -> str:
    import subprocess
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=BENCH_PATH.parent).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _merge_history(history: list, row: dict,
                   cap: int = _HISTORY_CAP) -> list:
    """Append one trajectory row, DEDUPED per (git rev, quick/full
    mode): re-running the benchmarks on the same commit in the same
    mode replaces that commit's row in place (keeping its position in
    the trajectory) instead of appending a duplicate that pushes real
    history out of the cap. Quick and full rows measure different
    workload scales, so they never overwrite each other."""
    rev = row.get("rev")
    out = list(history)
    for i, h in enumerate(out):
        if (rev != "unknown" and h.get("rev") == rev
                and h.get("quick") == row.get("quick")):
            out[i] = row
            break
    else:
        out.append(row)
    return out[-cap:]


def merge_history_value(key: str, value, quick: bool = True) -> None:
    """Set ONE extra field on THIS commit's history row (rev+quick
    deduped via `_merge_history`, creating the row if the telemetry
    snapshot has not run yet) — how benchmark modules (fig9_chaos's
    ``chaos_guard_gain``) record a headline scalar in the cross-PR
    trajectory without owning the whole row. Numeric headlines are
    published as ``bench_headline{key=}`` gauges and the stored value is
    read back from a registry snapshot (the registry is the source of
    truth; non-numeric values bypass it)."""
    import datetime

    if isinstance(value, (int, float)) and not isinstance(value, bool):
        reg = obs_metrics.get_registry()
        reg.gauge("bench_headline", "headline benchmark scalars",
                  labelnames=("key",)).set(float(value), key=key)
        value = next(
            s["value"] for s in
            reg.snapshot()["metrics"]["bench_headline"]["samples"]
            if s["labels"]["key"] == key)
    data = _read_bench()
    rev = _git_rev()
    hist = list(data.get("history", []))
    row = next((dict(h) for h in hist
                if h.get("rev") == rev and h.get("quick") == quick),
               None)
    if row is None:
        row = {"rev": rev,
               "date": datetime.datetime.now(datetime.timezone.utc)
               .strftime("%Y-%m-%dT%H:%M:%SZ"),
               "quick": quick}
    row[key] = value
    data["history"] = _merge_history(hist, row)
    BENCH_PATH.write_text(json.dumps(data, indent=2) + "\n")


def run(quick: bool = True):
    import datetime

    # arm the span tracer for the whole collection pass: the chunked /
    # sharded sweep layouts ride repro.core.executor.run_grid, whose
    # per-chunk prepare/compute/transfer/merge spans become the
    # BENCH_trace.json export
    tracer = obs_trace.get_tracer()
    tracer.clear()
    obs_trace.enable(True)
    t_pass = time.perf_counter()
    try:
        data = collect(quick)
    finally:
        obs_trace.enable(False)
    # total collection wall time flows through the registry like every
    # other headline (published BEFORE the snapshot below, read back out
    # of it for the history row — no ad-hoc timer value lands in JSON)
    obs_metrics.get_registry().gauge(
        "bench_runtime_seconds",
        "wall-clock seconds of the full telemetry collection pass"
    ).set(round(time.perf_counter() - t_pass, 3))
    fresh = data["entries"]
    for name, e in fresh.items():
        _publish_entry(name, e)
    # keep entries appended by OTHER modules; prune stale/renamed
    # telemetry-owned names so the record stays a snapshot of this run
    prev_data = _read_bench()
    prev = {k: v for k, v in prev_data.get("entries", {}).items()
            if not k.startswith(_OWNED_PREFIXES)}
    data["entries"] = {**prev, **fresh}
    # the trajectory: one compact row per benchmark run (warm seconds of
    # every timed entry), keyed by commit — this is what accumulates
    # across PRs instead of being clobbered by each snapshot
    rev = _git_rev()
    hist_prev = list(prev_data.get("history", []))
    # headline plumbing reads from the registry SNAPSHOT, not the raw
    # collect() dict: the history row records exactly what the exported
    # metrics say
    snap = obs_metrics.get_registry().snapshot()
    warm_from_snap = _entry_fields_from_snapshot(snap, "warm_s")
    rps_from_snap = _entry_fields_from_snapshot(snap, "runs_per_sec")
    runtime_s = next(
        (s["value"] for s in snap["metrics"]
         ["bench_runtime_seconds"]["samples"]), 0.0) \
        if "bench_runtime_seconds" in snap.get("metrics", {}) else 0.0
    row = {"rev": rev,
           "date": datetime.datetime.now(datetime.timezone.utc)
           .strftime("%Y-%m-%dT%H:%M:%SZ"),
           "quick": quick,
           "runtime_s": runtime_s,
           "warm_s": {k: warm_from_snap[k] for k in fresh},
           "runs_per_sec": {k: rps_from_snap[k] for k in fresh
                            if k in rps_from_snap}}
    # keep extra fields other modules set on this commit's row via
    # merge_history_value (chaos_guard_gain): the snapshot refreshes its
    # own keys without clobbering theirs
    prev_row = next((h for h in hist_prev
                     if h.get("rev") == rev
                     and h.get("quick") == quick), None)
    if prev_row is not None:
        row = {**prev_row, **row}
    data["history"] = _merge_history(hist_prev, row)
    BENCH_PATH.write_text(json.dumps(data, indent=2) + "\n")
    # self-verify: the append must be OBSERVABLE in the file we just
    # wrote; a silent skip (unwritable path, serialization surprise)
    # becomes a loud benchmark failure (benchmarks.run exits non-zero)
    check = _read_bench()
    hist = check.get("history", [])
    if not hist or (rev != "unknown"
                    and not any(h.get("rev") == rev for h in hist)):
        raise RuntimeError(
            f"telemetry append skipped: no history row for rev {rev} "
            f"in {BENCH_PATH}")
    # export the observability twins next to BENCH_sim.json, both
    # validated BEFORE writing — a malformed export is a loud benchmark
    # failure, same contract as the history self-verify above
    obs_metrics.validate_snapshot(snap)
    obs_metrics.get_registry().write_snapshot(_metrics_path())
    trace_doc = tracer.to_chrome()
    obs_trace.validate_chrome_trace(trace_doc, require_spans=True)
    _trace_path().write_text(json.dumps(trace_doc) + "\n")
    n_spans = sum(1 for e in trace_doc["traceEvents"]
                  if e.get("ph") == "X")
    rows: list[Row] = []
    for name, e in fresh.items():
        rows.append((f"telemetry/{name}", e["warm_s"] * 1e6,
                     f"cold={e['cold_s']}s;warm={e['warm_s']}s;"
                     f"runs_per_sec={e['runs_per_sec']}"))
    rows.append(("telemetry/written", 0.0, str(BENCH_PATH)))
    rows.append(("telemetry/metrics_snapshot", 0.0,
                 f"{_metrics_path().name}:"
                 f"{len(snap.get('metrics', {}))}metrics"))
    rows.append(("telemetry/trace", 0.0,
                 f"{_trace_path().name}:{n_spans}spans"))
    return rows
