#!/usr/bin/env python3
"""Benchmark harness of the power-management control system.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in BENCHMARK.json and finds its files by name:
``bench/configs/<config>.json`` (the deployment), ``bench/traffic/
<traffic>.json`` (the mix; its ``driver`` names the generic driver in
``bench/drivers/``) and ``bench/metrics/<metric>.py`` (one reader per
per-layer metric; see `reader_file`). It needs a TPU with as many chips
as the cell asks for and never falls back to the CPU.

A run: set-up (build, warm every shape the window uses), a window of
``--seconds`` of timed calls with no compilation inside it, then the
check of what the window produced against the plain reference in
``bench/reference/``. With ``--trace 1`` the window runs under the JAX
profiler and the run reports the per-layer metrics, with ``--trace 0``
the end-to-end ones; a mix with ``trace_calls`` profiles only that many
timed calls of the window. The last line of standard output is one
JSON object; the numbers compared and their limits are the last lines
of standard error and the result's last key, ``checks``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as fh:
        return json.load(fh)


def resolve_cell(name: str, root: Path = ROOT) -> dict:
    """The cell, its configuration, its traffic and its metrics, all
    found by name under ``bench/``."""
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = read_json(root / cfg_entry["file"])
    traffic = read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer,
            "driver": root / "bench" / "drivers" / f"{traffic['driver']}.py",
            "metric_files": {m["name"]: reader_file(root, m["name"])
                             for m in layer}}


def reader_file(root: Path, metric: str) -> Path:
    """``bench/metrics/<metric>.py``; a metric split by the end-to-end
    metric it moves (``<quantity>.<part>``) may share the reader
    ``<quantity>.py`` of its quantity."""
    own = root / "bench" / "metrics" / f"{metric}.py"
    if own.is_file() or "." not in metric:
        return own
    return root / "bench" / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"


class CompileCounter:
    """Executables built (compiled or loaded from the persistent cache)
    while armed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1


class Spans:
    """The benchmark's own host spans: kept in memory, and written into
    the profiler's trace as annotations when it records."""

    def __init__(self):
        self.items = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"bench/{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def configure_cache() -> None:
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR
    when set, else a fixed directory inside the checkout (the path is
    part of the cache key, so it never moves); a process that already
    chose one keeps it. Every program is kept."""
    import jax
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR)))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, require_tpu: bool = True,
            overrides: dict | None = None,
            config_overrides: dict | None = None,
            t_start: float | None = None, keep_trace: str | None = None,
            control: bool = False, log=print) -> dict:
    """One run of a cell; returns the result object. ``overrides`` and
    ``config_overrides`` (tests, at a size a CPU holds) replace keys of
    the traffic and of the configuration; ``require_tpu=False`` (tests)
    skips the look for a chip. ``control`` (`control.py`) also reads the
    control, the reference at the next precision below the
    configuration's put in the program's place, on the same sample, into
    the result's ``control`` key."""
    found = resolve_cell(workload, root)
    found["traffic"] = {**found["traffic"], **(overrides or {})}
    found["config"] = {**found["config"], **(config_overrides or {})}
    cell = found["cell"]
    configure_cache()
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root / "bench"))
    t_ref = T_PROCESS if t_start is None else t_start
    devs = devices_for(cell["chips"], require_tpu)
    import jax
    log(f"setup: JAX and its {len(devs)} devices ready at "
        f"{time.perf_counter() - t_ref:.3f} s")

    compiles = CompileCounter()
    spans = Spans()
    driver_mod = load_module(found["driver"], f"bench_driver_"
                             f"{found['traffic']['driver']}")
    drv = driver_mod.Driver(cell=cell, config=found["config"],
                            traffic=found["traffic"], seed=seed,
                            spans=spans, log=log)
    drv.setup()
    setup_s = time.perf_counter() - t_ref
    log(f"setup: {setup_s:.3f} s")
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace \
        else None
    # a mix whose calls run many device operations each (a scan's loop
    # body is traced once per step) traces only its first timed calls
    trace_calls = int(found["traffic"].get("trace_calls", 0))
    tracing = trace
    if trace:
        drv.trace_on()
        # host annotations only (level 1), no Python function tracing:
        # the benchmark's spans are all the host side the reduction reads
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    compiles.armed = True
    t0 = time.perf_counter()
    n_steps = 0
    while True:
        drv.step()
        n_steps += 1
        if tracing and n_steps == trace_calls:
            jax.profiler.stop_trace()
            drv.trace_off()
            tracing = False
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    compiles.armed = False
    if tracing:
        jax.profiler.stop_trace()
        drv.trace_off()
    log(f"window: {t1 - t0:.3f} s, {n_steps} timed calls, "
        f"{compiles.count} compiles inside the window")
    result = drv.window_result()
    used = devs[:cell["chips"]]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(used)}
    checks = drv.check()
    if control:
        out_control = {k: c["value"] for k, c in
                       drv.check(control=True).items()}
    if compiles.count:
        checks["compiles_in_window"] = {"value": compiles.count,
                                        "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"]}
    if trace:
        import trace_reduce
        reduced = trace_reduce.reduce_dir(tmp.name, n_chips=cell["chips"])
        if keep_trace:
            import shutil
            shutil.copy(trace_reduce.find_trace(tmp.name), keep_trace)
        tmp.cleanup()
        ctx = {"cell": cell, "config": found["config"],
               "device_kind": used[0].device_kind,
               "traffic": found["traffic"], "driver": drv,
               "trace": reduced}
        metrics = {}
        for m in found["per_layer"]:
            reader = load_module(found["metric_files"][m["name"]],
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = reduced["breakdown"]
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in found["end_to_end"]}
        out["device"] = device
    if control:
        out["control"] = out_control
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--keep-trace", metavar="FILE",
                   help="also copy the raw .xplane.pb of a traced run here")
    args = p.parse_args(argv)
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace), keep_trace=args.keep_trace, log=log)
    except NoChip as e:
        log(f"bench: {e}")
        return 3
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
