"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time and the traced window, device time per
operation and per XLA module, and the longest idle gaps of the device,
each named by the benchmark span the host was in at the time.

Busy is the union of the intervals in which an operation ran on a
device (the device plane's ``XLA Ops`` line), averaged over the chips
the cell uses. The window runs from the start of the first timed
benchmark span (``bench/<name>`` annotations, set-up's ``bench/warm``
left out) to the end of the last, on the trace's own clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
SETUP_SPANS = ("bench/warm",)
TOP = 10


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(event_name: str) -> str:
    """An XLA op event's name without its HLO text: ``%fusion.3 = f32[..]
    fusion(..)`` -> ``fusion.3``."""
    return event_name.split(" = ")[0].lstrip("%")


def read_planes(path: str) -> dict:
    """{"devices": {plane name: {line name: [(name, start, end)]}},
    "spans": [(name, start, end)]} in nanoseconds of the trace clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            devices[plane.name] = {
                name: [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for ev in line.events]
                for name, line in lines.items()}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def reduce_planes(planes: dict, n_chips: int) -> dict:
    spans = [s for s in planes["spans"] if s[0] not in SETUP_SPANS]
    if not spans:
        raise ValueError("the trace holds no timed benchmark span")
    w0, w1 = spans[0][1], max(s[2] for s in spans)
    devs = sorted(planes["devices"])[:n_chips]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy_ns, ops, modules, gaps = 0.0, defaultdict(float), \
        defaultdict(float), []
    for dev in devs:
        lines = planes["devices"][dev]
        evs = [(n, max(s, w0), min(e, w1)) for n, s, e in
               lines.get(OPS_LINE, []) if e > w0 and s < w1]
        for n, s, e in evs:
            ops[op_name(n)] += (e - s) * 1e-9
        for n, s, e in lines.get(MODULES_LINE, []):
            if e > w0 and s < w1:
                modules[n] += (min(e, w1) - max(s, w0)) * 1e-9
        merged = _union([(s, e) for _, s, e in evs])
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((host_span_at(spans, (a + b) / 2), (b - a) * 1e-9))
    busy_s = busy_ns * 1e-9 / len(devs)
    window_s = (w1 - w0) * 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window_s,
            "ops": dict(ops), "modules": dict(modules),
            "n_spans": {name: sum(1 for s in spans if s[0] == name)
                        for name in {s[0] for s in spans}},
            "breakdown": {"device_ops": [[n, v] for n, v in top_ops],
                          "idle_gaps": [[n, v] for n, v in top_gaps]}}


def host_span_at(spans, t) -> str:
    """The innermost benchmark span open at time ``t``."""
    inner = None
    for name, s, e in spans:
        if s <= t <= e and (inner is None or s >= inner[1]):
            inner = (name, s)
    return inner[0] if inner else "between benchmark spans"


def find_trace(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_dir(directory: str, n_chips: int = 1) -> dict:
    """Reduce the newest trace under ``directory``."""
    return reduce_planes(read_planes(find_trace(directory)), n_chips)
