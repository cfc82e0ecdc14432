#!/usr/bin/env python3
"""The program's own spans (`repro.obs.trace`) in a profiler trace: what
each part of `sweep` and of the plane's tick costs, and what the host
was doing while the device sat idle.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

runs one cell as `run.py` does (set-up, then a window of timed calls)
with the program's tracer and the JAX profiler on for the whole window,
and prints one JSON object: the cell's end-to-end metrics as this traced
run saw them (against a `run.py --trace 0` run of the same seed they
give the cost of tracing), the window's device busy time, and the
reduction below. ``--keep-trace FILE`` also keeps the ``.xplane.pb``.

The reduction (`reduce_program_spans`) reads the program's spans
(``sweep``, ``sweep/*``, ``executor/*``, ``plane/*``, ``signals/*``,
``python/gc``), which the tracer writes as profiler annotations on the
device's clock, beside the benchmark's ``bench/*`` spans:

- ``span_s`` / ``span_n``: program-span seconds and counts inside the
  window, by name;
- ``idle_by_span``: the device's idle seconds inside the window (averaged
  over the cell's chips, like busy time), by the innermost span open on
  the host at the time, program or benchmark; the values add up to the
  window less busy time;
- ``idle_gaps``: the longest idle gaps, each named by the innermost span
  open at its middle;
- ``parts``: per outermost program span (``sweep`` call, ``plane/tick``,
  ``plane/ingest``), the seconds of each span inside it by interval,
  over all of them and over the slowest 5%, with the ``python/gc`` count.
"""
from __future__ import annotations

import argparse
import bisect
import json
import sys
import tempfile
import time
from collections import defaultdict

import trace_reduce

PROGRAM = ("sweep/", "executor/", "plane/", "signals/")
OUTER = ("sweep", "plane/tick", "plane/ingest")
GC = "python/gc"
OUTSIDE = "between benchmark spans"


def is_program(name: str) -> bool:
    return name in ("sweep", GC) or name.startswith(PROGRAM)


def read_program_spans(path: str) -> list:
    """[(name, start, end)] of the program's spans on the host planes of
    a ``.xplane.pb``, in nanoseconds of the trace clock, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if is_program(ev.name)]
    return sorted(out, key=lambda s: s[1])


def innermost_segments(spans, w0, w1) -> list:
    """The window cut where the innermost open span changes: [(start,
    end, name)], the innermost being the open span that started last
    (of two that started together, the one that ends first)."""
    cuts = sorted({w0, w1} | {t for _, s, e in spans for t in (s, e)
                             if w0 < t < w1})
    by_start = sorted(spans, key=lambda s: s[1])
    out, active, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            active.append(by_start[i])
            i += 1
        active = [s for s in active if s[2] > a]
        name = (max(active, key=lambda s: (s[1], -s[2]))[0] if active
                else OUTSIDE)
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def _idle(lines, w0, w1) -> list:
    busy = trace_reduce._union(
        [(max(s, w0), min(e, w1)) for _, s, e in
         lines.get(trace_reduce.OPS_LINE, []) if e > w0 and s < w1])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def reduce_program_spans(planes: dict, program: list, n_chips: int) -> dict:
    """``planes`` as `trace_reduce.read_planes` gives them, ``program``
    as `read_program_spans` does; the window is `trace_reduce`'s."""
    bench = [s for s in planes["spans"]
             if s[0] not in trace_reduce.SETUP_SPANS]
    if not bench:
        raise ValueError("the trace holds no timed benchmark span")
    w0, w1 = bench[0][1], max(s[2] for s in bench)
    devs = sorted(planes["devices"])[:n_chips]
    if not devs:
        raise ValueError("the trace holds no device plane")
    inside = [(n, max(s, w0), min(e, w1)) for n, s, e in program
              if e > w0 and s < w1]
    span_s, span_n = defaultdict(float), defaultdict(int)
    for n, s, e in inside:
        span_s[n] += (e - s) * 1e-9
        span_n[n] += 1
    segs = innermost_segments(bench + inside, w0, w1)
    idle_by, gaps = defaultdict(float), []
    for dev in devs:
        j = 0
        for a, b in _idle(planes["devices"][dev], w0, w1):
            gaps.append((a, b))
            while segs[j][1] <= a:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < b:
                lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
                idle_by[segs[k][2]] += (hi - lo) * 1e-9 / len(devs)
                k += 1
    top = sorted(gaps, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    starts = [s[0] for s in segs]
    named = [[segs[bisect.bisect_right(starts, (a + b) / 2) - 1][2],
              (b - a) * 1e-9] for a, b in top]
    return {"window_s": (w1 - w0) * 1e-9, "span_s": dict(span_s),
            "span_n": dict(span_n), "idle_by_span": dict(idle_by),
            "idle_gaps": named, "parts": parts(program, w0, w1)}


def parts(program: list, w0: float, w1: float, slow: float = 0.05) -> dict:
    """Per outermost span in the window: its seconds, the seconds of
    every span lying inside it by name, and its ``python/gc`` count;
    the mean over all of them and over the slowest ``slow`` share."""
    program = sorted(program, key=lambda s: s[1])
    starts = [s[1] for s in program]
    out = {}
    for outer in OUTER:
        rows = []
        for i, (n, s, e) in enumerate(program):
            if n != outer or s < w0 or e > w1:
                continue
            row = defaultdict(float, total=(e - s) * 1e-9)
            for m, a, b in program[i + 1:bisect.bisect_right(starts, e)]:
                if b <= e:
                    row[m] += (b - a) * 1e-9
                    row["n_gc"] += m == GC
            rows.append(row)
        if not rows:
            continue
        rows.sort(key=lambda r: -r["total"])
        k = max(1, round(len(rows) * slow))
        out[outer] = {"n": len(rows), "mean": _mean(rows),
                      "slowest": _mean(rows[:k]), "n_slowest": k}
    return out


def _mean(rows) -> dict:
    keys = sorted({k for r in rows for k in r})
    return {k: sum(r.get(k, 0.0) for r in rows) / len(rows) for k in keys}


def per_call_ms(drv, name: str):
    """Mean time of the program span ``name`` per traced `sweep` call,
    in ms, from the campaign driver's copy of the tracer's spans (host
    clock); None where no traced call holds one."""
    calls = [(t0, t1) for t0, t1, *_ in
             drv.calls[:getattr(drv, "traced_calls", 0)]]
    spans = getattr(drv, "program_spans", None) or []
    per = [sum(e - s for n, s, e in spans if n == name and s >= t0
               and e <= t1) for t0, t1 in calls]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per)


def profile(workload: str, seed: int, seconds: float,
            keep_trace: str | None = None, log=print) -> dict:
    """One traced run of a cell with the program's tracer on through the
    window; see the module's docstring."""
    import run

    found = run.resolve_cell(workload)
    cell = found["cell"]
    run.configure_cache()
    sys.path.insert(0, str(run.ROOT / "src"))
    devs = run.devices_for(cell["chips"])
    import jax
    from repro.obs import trace as obs_trace

    drv = run.load_module(found["driver"], "bench_driver").Driver(
        cell=cell, config=found["config"], traffic=found["traffic"],
        seed=seed, spans=run.Spans(), log=log)
    drv.setup()
    tmp = tempfile.TemporaryDirectory(prefix="bench_spans_")
    drv.trace_on()
    obs_trace.enable(True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0
    jax.profiler.start_trace(tmp.name, profiler_options=opts)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        drv.step()
    jax.profiler.stop_trace()
    obs_trace.enable(False)
    drv.trace_off()
    result = drv.window_result()
    path = trace_reduce.find_trace(tmp.name)
    planes = trace_reduce.read_planes(path)
    base = trace_reduce.reduce_planes(planes, cell["chips"])
    spans = reduce_program_spans(planes, read_program_spans(path),
                                 cell["chips"])
    if keep_trace:
        import shutil
        shutil.copy(path, keep_trace)
    tmp.cleanup()
    return {"device": {"kind": devs[0].device_kind, "count": len(devs)},
            "end_to_end_traced": result["metrics"],
            "busy_s": base["busy_s"], "n_spans": base["n_spans"],
            **spans}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--keep-trace", metavar="FILE")
    args = p.parse_args(argv)
    import run
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        out = profile(args.workload, args.seed, args.seconds,
                      args.keep_trace, log)
    except run.NoChip as e:
        log(f"bench: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
