"""Span tracing on the profiler's clock, with Chrome trace-event export.

The program's spans are named ``<layer>/<part>``: ``sweep`` and its
``sweep/grid`` (with ``sweep/keys``, ``sweep/scenario`` and
``sweep/rows``) and ``sweep/summary`` in `repro.core.sim`;
``plane/ingest``, ``plane/tick`` and its ``plane/aggregate``,
``plane/pack``, ``plane/events`` and ``plane/publish`` in
`repro.core.plane`; ``signals/median`` and
``signals/shift`` inside the aggregation (`repro.core.signals`); and
the executor's per-chunk ``executor/prepare|compute|transfer|merge``
(device ids in args), which nest inside whichever of those called
`executor.run_grid`. While enabled the tracer also records every Python
garbage collection as a ``python/gc`` span (its generation in args).

Every span is recorded twice while the tracer is enabled:

* in memory, as a Chrome trace-event (``ph: "X"``) with ``ts``/``dur``
  in ``perf_counter`` microseconds since :attr:`Tracer.epoch` — open the
  file :meth:`Tracer.write` exports in chrome://tracing or
  https://ui.perfetto.dev;
* as a ``jax.profiler.TraceAnnotation`` of the same name, which lands in
  the profiler's ``.xplane.pb`` on the same clock as the device's ops
  when a profile is being taken (and costs next to nothing when not).

Both carry ``parent`` (the enclosing span's name) and ``call`` (an id
shared by every span under one outermost span: one `sweep` call, one
plane period) in their args / metadata.

The process-wide tracer starts **disabled**: ``span()`` is then a no-op
context manager — no timestamp, no annotation, no list growth, no gc
hook — so the hot loops pay nothing until someone calls ``enable()``.
``jax.profiler`` is imported on first enabled use only.
"""
from __future__ import annotations

import contextlib
import gc
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List

GC_SPAN = "python/gc"
_TraceAnnotation = None  # jax.profiler.TraceAnnotation, once enabled


class Tracer:
    def __init__(self, enabled: bool = False):
        self._lock = threading.RLock()
        self._local = threading.local()   # .stack: open (name, call)
        self._epoch = time.perf_counter()
        self._events: List[Dict[str, Any]] = []
        self._calls = itertools.count(1)
        self._gc_hook = self._on_gc
        self._enabled = False
        self.enabled = enabled

    @property
    def enabled(self) -> bool:
        return self._enabled

    @enabled.setter
    def enabled(self, flag: bool) -> None:
        flag = bool(flag)
        if flag and not self._enabled:
            global _TraceAnnotation
            if _TraceAnnotation is None:  # first enabled use
                from jax.profiler import TraceAnnotation as _TraceAnnotation
            gc.callbacks.append(self._gc_hook)
        elif self._enabled and not flag:
            gc.callbacks.remove(self._gc_hook)
        self._enabled = flag

    @property
    def epoch(self) -> float:
        """Absolute ``perf_counter`` seconds at which ``ts`` is 0 (set
        at construction and by every `clear`)."""
        return self._epoch

    # ------------------------------------------------------------ record
    def _ts_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @staticmethod
    def _annotation(name: str, args: Dict[str, Any]):
        """The profiler annotation of a span: its args as metadata
        (scalars as they are, anything else as text, None left out)."""
        return _TraceAnnotation(name, **{
            k: v if isinstance(v, (str, int, float, bool)) else str(v)
            for k, v in args.items() if v is not None})

    def _record(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, tid: int = 0, **args):
        if not self._enabled:
            yield
            return
        stack = self._stack()
        parent, call = stack[-1] if stack else (None, next(self._calls))
        args = {k: _jsonable(v) for k, v in args.items()}
        args.update(parent=parent, call=call)
        stack.append((name, call))
        t0 = self._ts_us()
        try:
            with self._annotation(name, args):
                yield
        finally:
            t1 = self._ts_us()
            stack.pop()
            self._record({"name": name, "ph": "X", "ts": t0,
                          "dur": t1 - t0, "pid": os.getpid(),
                          "tid": int(tid), "args": args})

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` hook: one ``python/gc`` span per collection,
        in the span context of the thread that triggered it."""
        if phase == "start":
            stack = self._stack()
            parent, call = stack[-1] if stack else (None, None)
            args = {"generation": info.get("generation"),
                    "parent": parent, "call": call}
            ann = self._annotation(GC_SPAN, args)
            ann.__enter__()
            self._local.gc = (self._ts_us(), args, ann)
            return
        pending = getattr(self._local, "gc", None)
        if pending is None:
            return
        self._local.gc = None
        t0, args, ann = pending
        ann.__exit__(None, None, None)
        self._record({"name": GC_SPAN, "ph": "X", "ts": t0,
                      "dur": self._ts_us() - t0, "pid": os.getpid(),
                      "tid": 0, "args": args})

    def instant(self, name: str, tid: int = 0, **args) -> None:
        if not self._enabled:
            return
        self._record({
            "name": name, "ph": "i", "s": "t", "ts": self._ts_us(),
            "pid": os.getpid(), "tid": int(tid),
            "args": {k: _jsonable(v) for k, v in args.items()},
        })

    # ------------------------------------------------------------ export
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._epoch = time.perf_counter()

    def to_chrome(self) -> Dict[str, Any]:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def write(self, path) -> Dict[str, Any]:
        doc = self.to_chrome()
        validate_chrome_trace(doc)
        with open(path, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True)
            fh.write("\n")
        return doc


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return str(v)


def validate_chrome_trace(doc: Any, require_spans: bool = False) -> None:
    """Raise ValueError unless ``doc`` is a well-formed Chrome trace-event
    document (CI runs this against the exported BENCH_trace.json)."""
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        raise ValueError("chrome trace must be a dict with a "
                         "'traceEvents' list")
    n_spans = 0
    for ev in doc["traceEvents"]:
        if not isinstance(ev, dict):
            raise ValueError(f"trace event must be a dict, got {ev!r}")
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                raise ValueError(f"trace event missing {field!r}: {ev!r}")
        if ev["ph"] == "X":
            n_spans += 1
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                raise ValueError(f"complete event needs dur >= 0: {ev!r}")
    if require_spans and n_spans == 0:
        raise ValueError("trace contains no complete ('X') spans")


# --------------------------------------------------------------- default
_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _TRACER


def enable(flag: bool = True) -> Tracer:
    _TRACER.enabled = bool(flag)
    return _TRACER
