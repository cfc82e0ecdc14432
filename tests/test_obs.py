"""Observability layer (repro.obs): the in-scan flight recorder, the
process metrics registry and the span tracer — plus their wiring into
sim, plane, executor, NRM, faults and the benchmark telemetry.

The two contracts worth the most scrutiny:

1. NEUTRALITY — a recorder-off run must be bit-for-bit the pre-recorder
   engine (the ring is a None carry field, no pytree leaves), and a
   recorder-ON run must not perturb the simulation numerics either (the
   ring only observes; every trace/summary value matches exactly).
2. FIDELITY — under a scripted fault storm the decoded timeline must
   agree with the guard's own counters and with the host-side
   `FaultSchedule.active(t)` windows.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from tests._hypothesis import given, settings, st  # noqa: E402

from repro.core import faults as flt  # noqa: E402
from repro.core.sim import simulate_closed_loop, sweep  # noqa: E402
from repro.obs import events as evt  # noqa: E402
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402


# ---------------------------------------------------------------------------
# event ring primitives
# ---------------------------------------------------------------------------

def test_ring_append_decode_roundtrip():
    vec = evt.ring_init(4)
    vec = evt.ring_append(vec, True, 1.5, evt.EV_GUARD_HOLD,
                          evt.SRC_GUARD, 3.0, 40.0)
    vec = evt.ring_append(vec, True, 2.5, evt.EV_FAULT_ENTER,
                          evt.SRC_FAULTS, 0.0, 1.0, 0.0)
    out = evt.decode_ring(vec)
    assert [e.name for e in out] == ["guard_hold", "fault_enter"]
    assert out[0].t == 1.5 and out[0].source_name == "guard"
    assert out[0].payload == (3.0, 40.0, 0.0, 0.0)
    assert out[1].code == evt.EV_FAULT_ENTER
    assert evt.ring_total(vec) == 2
    d = out[0].as_dict()
    assert d["name"] == "guard_hold" and d["payload"][0] == 3.0


def test_ring_append_fire_false_is_bit_noop():
    vec = evt.ring_init(2)
    vec = evt.ring_append(vec, True, 1.0, evt.EV_DETECTOR_ALARM,
                          evt.SRC_DETECTOR)
    after = evt.ring_append(vec, False, 9.0, evt.EV_GUARD_FAILSAFE,
                            evt.SRC_GUARD, 7.0)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(vec))


@settings(max_examples=25, deadline=None)
@given(cap=st.integers(min_value=1, max_value=7),
       n=st.integers(min_value=0, max_value=40))
def test_ring_overflow_evicts_oldest_total_monotonic(cap, n):
    """Property: after n appends into a cap-slot ring, `total` == n
    exactly (monotonic, counts evictions) and the decoded survivors are
    the LAST min(n, cap) events, oldest surviving first."""
    vec = evt.ring_init(cap)
    for i in range(n):
        vec = evt.ring_append(vec, True, float(i), evt.EV_DETECTOR_ALARM,
                              evt.SRC_DETECTOR, float(i))
    assert evt.ring_total(vec) == n
    out = evt.decode_ring(vec)
    assert len(out) == min(n, cap)
    want = list(range(n))[-min(n, cap):]
    assert [int(e.payload[0]) for e in out] == want
    assert [e.t for e in out] == [float(w) for w in want]


def test_decode_ring_rejects_grids_decode_grid_accepts_them():
    grid = np.stack([np.asarray(evt.ring_init(3))] * 2)
    with pytest.raises(ValueError, match="decode_grid"):
        evt.decode_ring(grid)
    decoded = evt.decode_grid(grid.reshape(2, 1, -1))
    assert decoded.shape == (2, 1)
    assert decoded[0, 0] == []


def test_event_log_eviction_and_state_roundtrip():
    log = evt.EventLog(capacity=3)
    for i in range(5):
        log.append(float(i), evt.EV_TENANT_ADDED, evt.SRC_PLANE, (i,))
    assert log.total == 5 and len(log) == 3
    assert [e.t for e in log.events()] == [2.0, 3.0, 4.0]
    clone = evt.EventLog()
    clone.load_state_dict(log.state_dict())
    assert clone.total == 5 and clone.capacity == 3
    assert [e.as_dict() for e in clone.events()] == \
        [e.as_dict() for e in log.events()]
    got = evt.filter_events(log.events(), code=evt.EV_TENANT_ADDED,
                            source=evt.SRC_PLANE)
    assert len(got) == 3


# ---------------------------------------------------------------------------
# recorder neutrality (the recorder observes, never perturbs)
# ---------------------------------------------------------------------------

_CHAOS = dict(
    total_work=1e9, max_time=150.0,
    faults=flt.FaultSchedule(
        (flt.FaultWindow("hb_dropout", 30.0, 40.0, p1=1.0),),
        period=150.0, name="dropout"),
    guard=flt.GuardConfig(hold_k=3, failsafe_k=12))


def test_recorder_on_is_bitwise_neutral_trace_mode():
    off = simulate_closed_loop("gros", 0.1, **_CHAOS)
    on = simulate_closed_loop("gros", 0.1, record_events=True, **_CHAOS)
    for k in off.traces:
        np.testing.assert_array_equal(off.traces[k], on.traces[k],
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(off.guard_state),
                                  np.asarray(on.guard_state))
    assert off.events is None and off.event_state is None
    assert on.events and on.n_events_total > 0


def test_recorder_on_is_bitwise_neutral_summary_and_empty_ring():
    # clean run, no event sources armed: the ring stays empty AND the
    # summary reductions still match the recorder-off run exactly
    kw = dict(total_work=3000.0, max_time=400.0, collect_traces=False)
    off = simulate_closed_loop("gros", 0.1, **kw)
    on = simulate_closed_loop("gros", 0.1, record_events=8, **kw)
    assert on.events == [] and on.n_events_total == 0
    for k in off.summary:
        np.testing.assert_array_equal(off.summary[k], on.summary[k],
                                      err_msg=k)


def test_recorder_neutral_on_sweep_axis_and_chunked():
    kw = dict(total_work=2000.0, max_time=300.0, collect_traces=False,
              faults=_CHAOS["faults"], guard=_CHAOS["guard"])
    eps = (0.05, 0.1)
    off = sweep("gros", eps, range(3), **kw)
    on = sweep("gros", eps, range(3), record_events=16, **kw)
    chunked = sweep("gros", eps, range(3), record_events=16,
                    chunk_size=2, **kw)
    for k in off.summary:
        np.testing.assert_array_equal(off.summary[k], on.summary[k],
                                      err_msg=k)
        np.testing.assert_array_equal(off.summary[k],
                                      chunked.summary[k], err_msg=k)
    assert off.events is None
    assert on.events.shape == (2, 3, evt.ring_dim(16))
    np.testing.assert_array_equal(np.asarray(on.events),
                                  np.asarray(chunked.events))
    decoded = evt.decode_grid(on.events)
    assert decoded.shape == (2, 3)
    # every faulted run saw the storm: enter events in every cell
    for idx in np.ndindex(*decoded.shape):
        assert evt.filter_events(decoded[idx], code=evt.EV_FAULT_ENTER)


def test_recorder_excluded_from_fast_paths():
    with pytest.raises(ValueError, match="typed_pi"):
        sweep("gros", (0.1,), range(2), total_work=500.0,
              max_time=100.0, collect_traces=False, typed_pi=True,
              record_events=True)
    with pytest.raises(ValueError, match="record_events"):
        sweep("gros", (0.1,), range(2), total_work=500.0,
              max_time=100.0, collect_traces=False, backend="pallas",
              record_events=True)
    with pytest.raises(ValueError, match="record_events"):
        simulate_closed_loop("gros", 0.1, total_work=500.0,
                             max_time=100.0, record_events=-3)


# ---------------------------------------------------------------------------
# chaos-timeline fidelity (fig9-style storm)
# ---------------------------------------------------------------------------

def test_chaos_timeline_agrees_with_guard_counters_and_schedule():
    """Scripted dropout storm: the decoded alarm/HOLD/FAILSAFE/recovery
    timeline must be ordered per fault cycle, agree with the guard's own
    G_N_RESETS counter, and each enter/exit must land inside/outside the
    host-view `FaultSchedule.active(t)` windows."""
    sched = flt.FaultSchedule(
        (flt.FaultWindow("hb_dropout", 30.0, 40.0, p1=1.0),),
        period=150.0, name="storm")
    res = simulate_closed_loop(
        "gros", 0.1, total_work=1e9, max_time=400.0, faults=sched,
        guard=flt.GuardConfig(hold_k=3, failsafe_k=12),
        record_events=256)
    ev = res.events
    assert ev == sorted(ev, key=lambda e: e.t)
    enters = evt.filter_events(ev, code=evt.EV_FAULT_ENTER)
    exits = evt.filter_events(ev, code=evt.EV_FAULT_EXIT)
    holds = evt.filter_events(ev, code=evt.EV_GUARD_HOLD)
    fsafes = evt.filter_events(ev, code=evt.EV_GUARD_FAILSAFE)
    recovers = evt.filter_events(ev, code=evt.EV_GUARD_RECOVER)
    resets = evt.filter_events(ev, code=evt.EV_RECOVERY_RESET)
    # 400s / 150s period, window at +30: 3 full fault cycles
    assert len(enters) == len(exits) == 3
    assert len(holds) == len(fsafes) == len(recovers) == 3
    # the guard's own counter is the ground truth the ring must match
    assert len(resets) == int(res.guard_state[flt.G_N_RESETS])
    for en, ho, fs, ex, rc in zip(enters, holds, fsafes, exits,
                                  recovers):
        assert en.t < ho.t < fs.t < ex.t <= rc.t
        # host-view cross-check: enter during an active window, exit
        # after it cleared
        assert sched.active(en.t), f"no active window at enter t={en.t}"
        assert not sched.active(ex.t), f"window still active at {ex.t}"
    # payloads carry the watchdog staleness at escalation time
    assert all(h.payload[0] >= 3 for h in holds)      # >= hold_k
    assert all(f.payload[0] >= 12 for f in fsafes)    # >= failsafe_k
    assert all(e.source == evt.SRC_GUARD
               for e in holds + fsafes + recovers + resets)
    assert all(e.source == evt.SRC_FAULTS for e in enters + exits)


def test_recorder_resume_keeps_total_monotonic():
    from repro.configs.base import PowerControlConfig
    from repro.core.nrm import NRM
    cfg = PowerControlConfig(plant_profile="gros", epsilon=0.1)
    nrm = NRM(cfg, guard=flt.GuardConfig(hold_k=3, failsafe_k=12))
    nrm.run_simulated(1e9, max_time=150.0, faults=_CHAOS["faults"],
                      record_events=32)
    t1 = evt.ring_total(nrm._event_state)
    assert t1 > 0
    # second segment: recording continues implicitly, same ring
    nrm.run_simulated(1e9, max_time=150.0, faults=_CHAOS["faults"])
    t2 = evt.ring_total(nrm._event_state)
    assert t2 > t1
    assert evt.ring_capacity(nrm._event_state) == 32
    assert len(nrm.flight_events()) == min(t2, 32)
    # the ring checkpoints with the run
    d = nrm.state_dict()
    clone = NRM(cfg, guard=flt.GuardConfig(hold_k=3, failsafe_k=12))
    clone.load_state_dict(d)
    assert evt.ring_total(clone._event_state) == t2
    # record_events=False drops the ring for the next segment
    nrm.run_simulated(1e9, max_time=50.0, record_events=False)
    assert nrm._event_state is None and nrm.flight_events() == []


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram_and_labels():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("ticks_total", "ticks", labelnames=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="b")
    assert c.value(kind="a") == 1.0 and c.value(kind="b") == 2.5
    with pytest.raises(ValueError):
        c.inc(-1.0, kind="a")
    g = reg.gauge("depth", "queue depth")
    g.set(7.0)
    g.inc(-2.0)
    assert g.value() == 5.0
    h = reg.histogram("lat", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(50.0)
    v = h.value()
    assert v["count"] == 3 and v["counts"] == [1, 1, 1]
    assert v["sum"] == pytest.approx(50.55)
    # re-registration returns the same object; a kind clash raises
    assert reg.counter("ticks_total", "ticks",
                       labelnames=("kind",)) is c
    with pytest.raises(ValueError):
        reg.gauge("ticks_total", "oops")


def test_registry_snapshot_validates_and_prometheus_renders():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("runs_total", "runs", labelnames=("mode",)).inc(
        3, mode="quick")
    reg.histogram("tick_s", "tick seconds").observe(0.2)
    snap = reg.snapshot()
    obs_metrics.validate_snapshot(snap)  # must not raise
    text = reg.to_prometheus()
    assert "# TYPE runs_total counter" in text
    assert 'runs_total{mode="quick"} 3' in text
    assert "# TYPE tick_s histogram" in text
    for broken in [
        None,
        {},
        {"schema": 99, "metrics": {}},
        {"schema": 1, "metrics": {"x": {"type": "bogus", "help": "",
                                        "labelnames": [],
                                        "samples": []}}},
    ]:
        with pytest.raises(ValueError):
            obs_metrics.validate_snapshot(broken)


def test_registry_write_snapshot_roundtrip(tmp_path):
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("x", "x").set(1.5)
    path = tmp_path / "m.json"
    reg.write_snapshot(path)
    snap = json.loads(path.read_text())
    obs_metrics.validate_snapshot(snap)
    assert snap["metrics"]["x"]["samples"][0]["value"] == 1.5


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_is_noop_enabled_records_spans(tmp_path):
    tr = obs_trace.Tracer()
    with tr.span("off/span", chunk=0):
        pass
    assert tr.events() == []
    tr = obs_trace.Tracer(enabled=True)
    with tr.span("executor/compute", chunk=1, devices=[0]):
        pass
    tr.instant("marker", note="hi")
    doc = tr.to_chrome()
    obs_trace.validate_chrome_trace(doc, require_spans=True)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 1
    assert spans[0]["name"] == "executor/compute"
    assert spans[0]["dur"] >= 0
    assert spans[0]["args"]["chunk"] == 1
    tr.enabled = False
    path = tmp_path / "t.json"
    tr.write(path)
    obs_trace.validate_chrome_trace(json.loads(path.read_text()))
    with pytest.raises(ValueError, match="no complete"):
        obs_trace.validate_chrome_trace(
            {"traceEvents": []}, require_spans=True)
    with pytest.raises(ValueError):
        obs_trace.validate_chrome_trace({"nope": 1})


@pytest.fixture
def tracer():
    """The process tracer, enabled and empty; disabled again after."""
    tr = obs_trace.enable(True)
    tr.clear()
    try:
        yield tr
    finally:
        obs_trace.enable(False)
        tr.clear()


def _spans(tr):
    return [e for e in tr.events() if e["ph"] == "X"
            and e["name"] != obs_trace.GC_SPAN]


def test_tracer_records_parent_and_call_for_nested_spans():
    tr = obs_trace.Tracer(enabled=True)
    try:
        for _ in range(2):
            with tr.span("sweep"):
                with tr.span("sweep/grid"):
                    with tr.span("sweep/keys"):
                        pass
                with tr.span("executor/compute", chunk=0):
                    pass
    finally:
        tr.enabled = False
    got = [(e["name"], e["args"]["parent"], e["args"]["call"])
           for e in _spans(tr)]
    first, second = got[:4], got[4:]
    assert [g[:2] for g in first] == [
        ("sweep/keys", "sweep/grid"), ("sweep/grid", "sweep"),
        ("executor/compute", "sweep"), ("sweep", None)]
    assert len({g[2] for g in first}) == 1
    assert len({g[2] for g in second}) == 1
    assert first[0][2] != second[0][2]
    assert _spans(tr)[2]["args"]["chunk"] == 0


def test_disabled_tracer_builds_no_annotation_and_leaves_gc_alone(
        monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("a disabled tracer built an annotation")

    import gc
    hooks = list(gc.callbacks)
    tr = obs_trace.Tracer()
    monkeypatch.setattr(tr, "_annotation", refuse)
    with tr.span("sweep"):
        gc.collect()
    tr.instant("marker")
    assert tr.events() == []
    assert gc.callbacks == hooks


def test_enabled_tracer_records_gc_and_unhooks_when_disabled():
    import gc
    tr = obs_trace.Tracer(enabled=True)
    assert tr._gc_hook in gc.callbacks
    try:
        with tr.span("plane/tick"):
            gc.collect()
    finally:
        tr.enabled = False
    assert tr._gc_hook not in gc.callbacks
    tick = [e for e in tr.events() if e["name"] == "plane/tick"][0]
    gcs = [e for e in tr.events() if e["name"] == obs_trace.GC_SPAN
           and e["args"]["generation"] == 2]
    assert gcs and gcs[0]["args"]["parent"] == "plane/tick"
    assert gcs[0]["args"]["call"] == tick["args"]["call"]
    assert tick["ts"] <= gcs[0]["ts"] and \
        gcs[0]["ts"] + gcs[0]["dur"] <= tick["ts"] + tick["dur"]
    n = len(tr.events())
    gc.collect()
    assert len(tr.events()) == n


def test_tracer_epoch_is_the_perf_counter_origin_of_ts():
    import time
    tr = obs_trace.Tracer(enabled=True)
    try:
        tr.clear()
        t0 = time.perf_counter()
        with tr.span("sweep"):
            pass
        t1 = time.perf_counter()
    finally:
        tr.enabled = False
    ev = _spans(tr)[0]
    start = tr.epoch + ev["ts"] * 1e-6
    assert t0 <= start <= start + ev["dur"] * 1e-6 <= t1


def test_sweep_spans_nest_with_the_executor_s_in_one_call(tracer):
    sweep("gros", [0.1, 0.2], [1, 2, 3], total_work=50.0, max_time=64.0,
          collect_traces=False, chunk_size=4)
    ev = _spans(tracer)
    by = {}
    for e in ev:
        by.setdefault(e["name"], []).append(e)
    parent = {n: es[0]["args"]["parent"] for n, es in by.items()}
    assert parent == {"sweep": None, "sweep/grid": "sweep",
                      "sweep/keys": "sweep/grid",
                      "sweep/scenario": "sweep/grid",
                      "sweep/rows": "sweep/grid",
                      "sweep/summary": "sweep",
                      "executor/prepare": "sweep",
                      "executor/compute": "sweep",
                      "executor/transfer": "sweep",
                      "executor/merge": "sweep"}
    assert len(by["executor/compute"]) == 2  # 6 runs in chunks of 4
    assert len({e["args"]["call"] for e in ev}) == 1
    order = [e["name"] for e in sorted(ev, key=lambda e: e["ts"])]
    assert order.index("sweep/grid") < order.index("executor/prepare") \
        < order.index("sweep/summary")


def test_plane_period_spans_nest_under_ingest_and_tick(tracer):
    from repro.core.plane import ControlPlane
    plane = ControlPlane(profile="gros", capacity=16, max_beats=8)
    ids = plane.add_tenants(5)
    plane.ingest(np.repeat(ids, 3), np.tile([0.1, 0.4, 0.7], 5))
    plane.tick(now=1.0)
    parent = {e["name"]: e["args"]["parent"] for e in _spans(tracer)}
    assert parent == {"plane/ingest": None, "plane/tick": None,
                      "plane/aggregate": "plane/tick",
                      "signals/median": "plane/aggregate",
                      "signals/shift": "plane/aggregate",
                      "plane/pack": "plane/tick",
                      "executor/prepare": "plane/tick",
                      "executor/compute": "plane/tick",
                      "executor/transfer": "plane/tick",
                      "executor/merge": "plane/tick",
                      "plane/events": "plane/tick",
                      "plane/publish": "plane/tick"}
    calls = {e["name"]: e["args"]["call"] for e in _spans(tracer)}
    assert calls["plane/ingest"] != calls["plane/tick"] \
        == calls["executor/compute"] == calls["signals/median"]


@pytest.mark.parametrize("guarded", [False, True],
                         ids=["unguarded", "guarded"])
def test_the_plane_tick_module_is_named_plane_tick(monkeypatch, guarded):
    import jax
    from repro.core import executor
    from repro.core.plane import ControlPlane, tick_fn

    plane = ControlPlane(profile="gros", capacity=16,
                         guard=flt.GuardConfig() if guarded else None)
    plane.add_tenants(3)
    seen = {}

    def capture(fn, rows, shared, n, **kw):
        seen.update(fn=fn, rows=rows, shared=shared)
        raise RuntimeError("captured")

    monkeypatch.setattr(executor, "run_grid", capture)
    with pytest.raises(RuntimeError, match="captured"):
        plane.tick()
    assert seen["fn"] is tick_fn(plane._branches, guarded)
    text = jax.jit(seen["fn"]).lower(seen["rows"],
                                     *seen["shared"]).as_text()
    assert "module @jit_plane_tick" in text


def test_the_scan_chunk_module_is_named_sweep_scan(monkeypatch):
    import jax
    from repro.core import executor
    seen = {}

    def capture(fn, batched, shared, n, **kw):
        seen.update(fn=fn, batched=batched, shared=shared)
        raise RuntimeError("captured")

    monkeypatch.setattr(executor, "run_grid", capture)
    with pytest.raises(RuntimeError, match="captured"):
        sweep("gros", [0.1], [1, 2], total_work=50.0, max_time=64.0,
              collect_traces=False, chunk_size=2, backend="scan")
    text = jax.jit(seen["fn"]).lower(seen["batched"],
                                     *seen["shared"]).as_text()
    assert "module @jit_sweep_scan" in text


# ---------------------------------------------------------------------------
# wiring: executor counters + spans
# ---------------------------------------------------------------------------

def _runs_by_path():
    from repro.kernels.closed_loop.ops import runs_counter
    c = runs_counter()
    return {p: c.value(path=p) for p in ("scan", "interpret")}


SCAN_GRID = dict(faults=[flt.FaultSchedule(name="noop"),
                         flt.FaultSchedule((flt.FaultWindow(
                             "hb_dropout", 20.0, 15.0, p1=1.0),))],
                 guard=flt.GuardConfig())


@pytest.mark.parametrize("path", ["one_shot", "chunked", "durable",
                                  "kernel"])
def test_sweep_counts_its_runs_by_engine_path(path, tmp_path):
    """``closed_loop_runs_total{path="scan"}`` rises by the grid's runs
    on every path through the scan engine, and not at all when the
    kernel takes the grid (which counts under its own path)."""
    how = {"one_shot": dict(SCAN_GRID),
           "chunked": dict(SCAN_GRID, chunk_size=5),
           "durable": dict(SCAN_GRID, chunk_size=5, durable=tmp_path),
           "kernel": dict(backend="pallas")}[path]
    before = _runs_by_path()
    res = sweep(["gros", "dahu"], [0.1, 0.2], [1, 2, 3], total_work=50.0,
                max_time=64.0, collect_traces=False, **how)
    after = _runs_by_path()
    runs = int(np.size(res.energy))
    assert runs == (12 if path == "kernel" else 24)
    scan, kernel = (runs, 0) if path != "kernel" else (0, runs)
    assert after["scan"] - before["scan"] == scan
    assert after["interpret"] - before["interpret"] == kernel


def test_run_grid_publishes_counters_and_spans():
    import jax.numpy as jnp
    from repro.core import executor

    reg = obs_metrics.get_registry()
    before = reg.counter("executor_chunks_total",
                         "grid chunks executed").value()
    tracer = obs_trace.get_tracer()
    tracer.clear()
    obs_trace.enable(True)
    try:
        out, state = executor.run_grid(
            lambda b: {"y": b["x"] * 2.0}, {"x": jnp.arange(10.0)},
            (), 10, chunk_size=4)
    finally:
        obs_trace.enable(False)
    np.testing.assert_array_equal(out["y"], np.arange(10.0) * 2.0)
    after = reg.counter("executor_chunks_total",
                        "grid chunks executed").value()
    assert after - before == 3
    names = {e["name"] for e in tracer.events()}
    assert {"executor/prepare", "executor/compute",
            "executor/transfer", "executor/merge"} <= names
    compute = [e for e in tracer.events()
               if e["name"] == "executor/compute"]
    assert compute[0]["args"]["cold"] in (True, False)
    assert "devices" in compute[0]["args"]
    tracer.clear()


# ---------------------------------------------------------------------------
# wiring: control plane events + metrics
# ---------------------------------------------------------------------------

def test_plane_quarantine_events_and_snapshot_carry():
    from repro.core.plane import ControlPlane

    plane = ControlPlane(profile="gros", dt=1.0,
                         guard=flt.GuardConfig(hold_k=2, failsafe_k=5))
    plane.add_tenants(2, ids=["ok", "sick"])
    added = evt.filter_events(plane.events.events(),
                              code=evt.EV_TENANT_ADDED)
    assert len(added) == 1 and added[0].payload[0] == 2
    t = 0.0
    for k in range(10):
        t += 1.0
        for tid in (["ok"] if k >= 2 else ["ok", "sick"]):
            plane.ingest([tid] * 4,
                         [t - 1.0 + (j + 0.5) / 4 for j in range(4)])
        plane.tick()
    assert plane.quarantined() == ["sick"]
    evs = plane.events.events()
    q_in = evt.filter_events(evs, code=evt.EV_QUARANTINE_ENTER)
    assert len(q_in) == 1 and q_in[0].source == evt.SRC_PLANE
    assert int(q_in[0].payload[1]) == plane.slot("sick")
    # recovery clears the quarantine and logs the exit
    for k in range(3):
        t += 1.0
        for tid in ("ok", "sick"):
            plane.ingest([tid] * 4,
                         [t - 1.0 + (j + 0.5) / 4 for j in range(4)])
        plane.tick()
    assert plane.quarantined() == []
    assert evt.filter_events(plane.events.events(),
                             code=evt.EV_QUARANTINE_EXIT)
    # the decision stream survives a snapshot kill/resume
    snap = plane.snapshot()
    resumed = ControlPlane.restore(snap)
    assert [e.as_dict() for e in resumed.events.events()] == \
        [e.as_dict() for e in plane.events.events()]
    plane.remove_tenant("sick")
    assert evt.filter_events(plane.events.events(),
                             code=evt.EV_TENANT_REMOVED)
    # registry gauges track the plane
    reg = obs_metrics.get_registry()
    assert reg.gauge("plane_tenants",
                     "live tenants on the last tick").value() >= 1


def test_plane_old_snapshots_without_events_still_restore():
    import dataclasses as dc
    from repro.core.plane import ControlPlane

    plane = ControlPlane(profile="gros", dt=1.0)
    plane.add_tenants(1, ids=["a"])
    snap = dc.replace(plane.snapshot(), events=None)
    resumed = ControlPlane.restore(snap)
    assert resumed.slot("a") == plane.slot("a")


# ---------------------------------------------------------------------------
# wiring: NRM + faults + telemetry registry plumbing
# ---------------------------------------------------------------------------

def test_nrm_control_step_publishes_metrics():
    from repro.configs.base import PowerControlConfig
    from repro.core.nrm import NRM

    reg = obs_metrics.get_registry()
    c = reg.counter("nrm_control_steps_total",
                    "live control periods executed")
    before = c.value()
    nrm = NRM(PowerControlConfig(plant_profile="gros", epsilon=0.1))
    for _ in range(3):
        nrm.actuator.advance(nrm.cfg.sampling_period)
        nrm.heartbeat(t=nrm._t + 0.5)
        nrm.control_step()
    assert c.value() - before == 3
    assert reg.gauge("nrm_pcap_watts",
                     "cap applied by the last control period"
                     ).value() > 0


def test_faulty_actuator_counts_injections():
    from repro.configs.base import PowerControlConfig
    from repro.core.nrm import NRM, SimulatedPowerActuator

    reg = obs_metrics.get_registry()
    c = reg.counter(
        "faults_injected_total",
        "fault perturbations actually applied by FaultyActuator",
        labelnames=("kind",))
    before = c.value(kind="act_stuck")
    prof_cfg = PowerControlConfig(plant_profile="gros", epsilon=0.1)
    inner = SimulatedPowerActuator(NRM(prof_cfg).profile)
    sched = flt.FaultSchedule(
        (flt.FaultWindow("act_stuck", 0.0, 10.0, p1=55.0),),
        period=100.0, name="stuck")
    fa = flt.FaultyActuator(inner, sched)
    fa.tick(1.0)
    fa.set_pcap(90.0)
    assert c.value(kind="act_stuck") - before == 1
    assert inner._pcap == 55.0


def test_telemetry_headlines_flow_through_registry(tmp_path, monkeypatch):
    from benchmarks import telemetry

    monkeypatch.setattr(telemetry, "BENCH_PATH", tmp_path / "B.json")
    telemetry.merge_history_value("chaos_guard_gain", 42.25)
    telemetry.append_entry("faceoff", {"warm_s": 1.25, "note": "x"})
    reg = obs_metrics.get_registry()
    assert reg.gauge("bench_headline", "headline benchmark scalars",
                     labelnames=("key",)
                     ).value(key="chaos_guard_gain") == 42.25
    assert reg.gauge("bench_entry", "numeric benchmark entry fields",
                     labelnames=("entry", "field")
                     ).value(entry="faceoff", field="warm_s") == 1.25
    data = json.loads((tmp_path / "B.json").read_text())
    assert data["entries"]["faceoff"] == {"warm_s": 1.25, "note": "x"}
    assert data["history"][0]["chaos_guard_gain"] == 42.25
    # exports land next to (monkeypatched) BENCH_PATH
    assert telemetry._metrics_path().parent == tmp_path
    assert telemetry._trace_path().name == "BENCH_trace.json"
