"""Streaming telemetry sinks: bounded-memory, bounded-disk JSONL export.

A million-run campaign cannot keep its telemetry in host lists — the
PR-8 registry/EventLog layer is in-process and pull-based. This module
is the push side:

  * `JsonlSink` — append-only, size-rotated JSONL writer (thread-safe).
    When the active file would exceed ``max_bytes`` it rotates
    ``path -> path.1 -> ... -> path.{max_files-1}`` (oldest deleted), so
    a week-long run holds at most ``max_bytes * max_files`` on disk.
  * `MetricsSampler` — background daemon thread writing periodic
    registry snapshots as compact rows with **per-counter deltas** since
    the previous sample (rates without a TSDB).
  * `decision_consumer` — adapts a sink to the ``consume(lo, hi, out)``
    hook `executor.run_grid` / `sim.sweep` / `ControlPlane.tick`
    already expose: per-chunk summary rows (or full per-run rows) go to
    disk and the chunk arrays are dropped, keeping campaign memory
    O(chunk).
  * ``EventLog(sink=...)`` (in `repro.obs.events`) streams every decoded
    decision-stream event through the same writer before eviction.

Everything here is stdlib + numpy only — importing a sink can never
perturb jax tracing.
"""
from __future__ import annotations

import json
import os
import threading
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.obs import metrics as obs_metrics


class JsonlSink:
    """Append-only JSONL writer with size rotation.

    ``write(obj)`` serializes one row; when the active file would grow
    past ``max_bytes`` it is rotated first (``path.1`` newest rotated,
    higher suffixes older, beyond ``max_files`` deleted). ``written`` /
    ``rotations`` count activity; all methods are thread-safe.
    """

    def __init__(self, path, max_bytes: int = 32 << 20,
                 max_files: int = 4):
        if max_bytes < 1 or max_files < 1:
            raise ValueError("max_bytes and max_files must be >= 1")
        self.path = Path(path)
        self.max_bytes = int(max_bytes)
        self.max_files = int(max_files)
        self.written = 0
        self.rotations = 0
        self._lock = threading.Lock()
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = open(self.path, "a", encoding="utf-8")
        self._size = self.path.stat().st_size

    def _rotate_locked(self) -> None:
        self._fh.close()
        oldest = self.path.with_name(
            f"{self.path.name}.{self.max_files - 1}")
        if oldest.exists():
            oldest.unlink()
        for i in range(self.max_files - 2, 0, -1):
            src = self.path.with_name(f"{self.path.name}.{i}")
            if src.exists():
                src.rename(self.path.with_name(f"{self.path.name}.{i + 1}"))
        if self.max_files > 1:
            self.path.rename(self.path.with_name(f"{self.path.name}.1"))
        else:
            self.path.unlink()
        self._fh = open(self.path, "a", encoding="utf-8")
        self._size = 0
        self.rotations += 1

    def write(self, obj: Any) -> None:
        line = json.dumps(obj, separators=(",", ":"),
                          default=_jsonable) + "\n"
        with self._lock:
            if self._size and self._size + len(line) > self.max_bytes:
                self._rotate_locked()
            self._fh.write(line)
            self._size += len(line)
            self.written += 1

    def write_many(self, objs: Sequence[Any]) -> None:
        for o in objs:
            self.write(o)

    def flush(self) -> None:
        with self._lock:
            self._fh.flush()

    def close(self) -> None:
        with self._lock:
            self._fh.close()

    def files(self) -> List[Path]:
        """Active file + rotated generations, newest first."""
        out = [self.path]
        for i in range(1, self.max_files):
            p = self.path.with_name(f"{self.path.name}.{i}")
            if p.exists():
                out.append(p)
        return out

    def __enter__(self) -> "JsonlSink":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _jsonable(v):
    if isinstance(v, (np.floating, np.integer)):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    return str(v)


def read_jsonl(path) -> List[dict]:
    """Parse one JSONL file (tests / analysis helper)."""
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


# ----------------------------------------------------------- flattening
def _flat_key(name: str, labels: Dict[str, str]) -> str:
    if not labels:
        return name
    body = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
    return f"{name}{{{body}}}"


def snapshot_row(snap: dict,
                 prev_counters: Optional[Dict[str, float]] = None
                 ) -> dict:
    """Flatten one registry snapshot into a compact sample row:
    ``gauges``/``counters`` keyed ``name{label=value,...}``, histograms
    reduced to (count, sum), and ``deltas`` = counter increments since
    ``prev_counters`` (a fresh counter's delta is its value)."""
    row: dict = {"t": snap.get("unix_time"), "gauges": {},
                 "counters": {}, "histograms": {}, "deltas": {}}
    for name, m in snap.get("metrics", {}).items():
        for s in m["samples"]:
            key = _flat_key(name, s["labels"])
            if m["type"] == "gauge":
                row["gauges"][key] = s["value"]
            elif m["type"] == "counter":
                row["counters"][key] = s["value"]
            else:
                row["histograms"][key] = {"count": s["count"],
                                          "sum": s["sum"]}
    if prev_counters is not None:
        for key, v in row["counters"].items():
            row["deltas"][key] = round(v - prev_counters.get(key, 0.0), 9)
    return row


class MetricsSampler:
    """Periodic background snapshot sampler -> JSONL sink.

    ``start()`` launches a daemon thread that writes one `snapshot_row`
    immediately and then every ``period_s``; ``stop()`` joins it and
    writes one final row, so even a short run exports at least two
    samples (start + end state) and every counter's total delta.
    """

    def __init__(self, sink: JsonlSink,
                 registry: Optional[obs_metrics.MetricsRegistry] = None,
                 period_s: float = 5.0):
        self.sink = sink
        self.registry = registry or obs_metrics.get_registry()
        self.period_s = float(period_s)
        self.samples = 0
        self._prev: Dict[str, float] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def sample(self) -> dict:
        """Take one sample now (also usable without the thread)."""
        with self._lock:
            row = snapshot_row(self.registry.snapshot(), self._prev)
            self._prev = dict(row["counters"])
            self.sink.write(row)
            self.samples += 1
            return row

    def _loop(self) -> None:
        self.sample()
        while not self._stop.wait(self.period_s):
            self.sample()

    def start(self) -> "MetricsSampler":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="repro-obs-sampler", daemon=True)
            self._thread.start()
        return self

    def stop(self, final: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(self.period_s * 2, 5))
            self._thread = None
        if final:
            self.sample()

    def __enter__(self) -> "MetricsSampler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


# ------------------------------------------------------- consume= hooks
def _walk_arrays(out: Any, prefix: str = "") -> List[tuple]:
    """Flatten a (possibly nested) dict of arrays to (dotted_key, array)
    leaves; non-dict payloads land under their prefix (or 'out')."""
    if isinstance(out, dict):
        leaves: List[tuple] = []
        for k, v in out.items():
            key = f"{prefix}.{k}" if prefix else str(k)
            leaves.extend(_walk_arrays(v, key))
        return leaves
    return [(prefix or "out", np.asarray(out))]


def decision_consumer(sink: JsonlSink, mode: str = "summary",
                      fields: Optional[Sequence[str]] = None
                      ) -> Callable[[int, int, Any], None]:
    """Build a ``consume(lo, hi, out)`` hook that streams chunk results
    to ``sink`` and drops them — plug into ``ControlPlane.tick``,
    ``sim.sweep`` or ``executor.run_grid`` directly.

    ``mode="summary"`` writes ONE row per chunk with mean/min/max per
    field (bounded output regardless of campaign size);
    ``mode="rows"`` writes one row per run/tenant (full-resolution
    decision stream, still O(chunk) memory). ``fields`` restricts which
    (dotted) keys are exported."""
    if mode not in ("summary", "rows"):
        raise ValueError(f"mode must be 'summary' or 'rows', got {mode!r}")

    def consume(lo: int, hi: int, out: Any) -> None:
        leaves = [(k, np.asarray(a, dtype=np.float64))
                  for k, a in _walk_arrays(out)
                  if fields is None or k in fields]
        if mode == "summary":
            row: dict = {"lo": int(lo), "hi": int(hi), "n": int(hi - lo)}
            for k, a in leaves:
                a = a.reshape(a.shape[0], -1) if a.ndim > 1 else a
                row[k] = {"mean": float(np.mean(a)),
                          "min": float(np.min(a)),
                          "max": float(np.max(a))}
            sink.write(row)
        else:
            n = hi - lo
            for j in range(n):
                row = {"i": int(lo + j)}
                for k, a in leaves:
                    if a.shape and a.shape[0] >= n:
                        v = a[j]
                        row[k] = (float(v) if np.ndim(v) == 0
                                  else np.asarray(v).tolist())
                sink.write(row)

    return consume
