"""The program's spans in a profiler trace: the reduction that names
idle time by the innermost span, the per-call readers of the campaign
cell, and the traced report, at a size the CPU holds."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import program_spans  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def _planes():
    ops = [("%fusion.1 = f32[4]{0} fusion()", 10 * MS, 20 * MS),
           ("%fusion.2 = f32[4]{0} fusion()", 100 * MS, 110 * MS)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops}},
            "spans": [("bench/warm", 0, 2 * MS),
                      ("bench/tick", 5 * MS, 150 * MS)]}


PROGRAM = [("plane/tick", 8 * MS, 140 * MS),
           ("plane/aggregate", 20 * MS, 62 * MS),
           ("signals/median", 20 * MS, 50 * MS),
           ("executor/compute", 90 * MS, 115 * MS),
           ("python/gc", 120 * MS, 125 * MS)]


def test_idle_time_goes_to_the_innermost_span():
    r = program_spans.reduce_program_spans(_planes(), PROGRAM, 1)
    ms = {k: round(v * 1e3, 6) for k, v in r["idle_by_span"].items()}
    assert ms == {"bench/tick": 13.0, "plane/tick": 50.0,
                  "signals/median": 30.0, "plane/aggregate": 12.0,
                  "executor/compute": 15.0, "python/gc": 5.0}
    # every idle second is named once: the window less busy time
    assert sum(ms.values()) == pytest.approx(145.0 - 20.0)
    assert r["window_s"] == pytest.approx(0.145)


def test_gaps_are_named_by_the_innermost_span_at_their_middle():
    r = program_spans.reduce_program_spans(_planes(), PROGRAM, 1)
    gaps = [(n, round(s * 1e3, 6)) for n, s in r["idle_gaps"]]
    assert gaps == [("plane/aggregate", 80.0), ("plane/tick", 40.0),
                    ("bench/tick", 5.0)]


def test_span_seconds_and_counts_are_cut_to_the_window():
    program = PROGRAM + [("plane/ingest", 0, 6 * MS),      # half inside
                         ("plane/ingest", 150 * MS, 160 * MS)]  # outside
    r = program_spans.reduce_program_spans(_planes(), program, 1)
    assert r["span_n"] == {"plane/tick": 1, "plane/aggregate": 1,
                           "signals/median": 1, "executor/compute": 1,
                           "python/gc": 1, "plane/ingest": 1}
    assert r["span_s"]["plane/ingest"] == pytest.approx(0.001)
    assert r["span_s"]["plane/tick"] == pytest.approx(0.132)


def test_without_program_spans_gaps_keep_the_benchmark_s_names():
    planes = _planes()
    planes["spans"].append(("bench/tick", 160 * MS, 170 * MS))
    mine = program_spans.reduce_program_spans(planes, [], 1)
    theirs = trace_reduce.reduce_planes(planes, 1)["breakdown"]["idle_gaps"]
    assert mine["idle_gaps"] == [[n, pytest.approx(s)] for n, s in theirs]
    assert set(mine["idle_by_span"]) == {"bench/tick",
                                         "between benchmark spans"}
    assert mine["span_s"] == {} and mine["parts"] == {}


def test_the_recorded_chip_trace_holds_no_program_span_yet():
    """Recorded before the program had spans: everything idle is in a
    benchmark span, and the reduction adds up to the window."""
    path = str(BENCH / "tests" / "data" /
               "plane_frontier_fleet.tpu_v5_lite.xplane.pb")
    assert program_spans.read_program_spans(path) == []
    planes = trace_reduce.read_planes(path)
    base = trace_reduce.reduce_planes(planes, 1)
    r = program_spans.reduce_program_spans(planes, [], 1)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    assert set(r["idle_by_span"]) <= {"bench/generate", "bench/ingest",
                                      "bench/tick",
                                      "between benchmark spans"}


def test_parts_split_each_tick_and_its_slowest_share():
    program = []
    for i in range(20):
        t0 = i * 100 * MS
        slow = i == 7
        end = t0 + (80 if slow else 50) * MS
        program += [("plane/tick", t0, end),
                    ("plane/aggregate", t0 + MS, t0 + 31 * MS),
                    ("executor/compute", t0 + 40 * MS, t0 + 45 * MS)]
        if slow:
            program.append(("python/gc", t0 + 50 * MS, t0 + 78 * MS))
    p = program_spans.parts(program, 0, 2000 * MS)["plane/tick"]
    assert p["n"] == 20 and p["n_slowest"] == 1
    assert p["slowest"]["total"] == pytest.approx(0.080)
    assert p["slowest"]["python/gc"] == pytest.approx(0.028)
    assert p["slowest"]["n_gc"] == 1
    assert p["mean"]["plane/aggregate"] == pytest.approx(0.030)
    assert p["mean"]["n_gc"] == pytest.approx(1 / 20)


class _Driver:
    def __init__(self, spans):
        self.calls = [(1.0, 2.0), (3.0, 4.0), (5.0, 6.0)]
        self.traced_calls = 2
        self.program_spans = spans


@pytest.mark.parametrize("metric,span", [("sweep.grid_ms", "sweep/grid"),
                                         ("sweep.summary_ms",
                                          "sweep/summary")])
def test_sweep_part_readers(metric, span):
    reader = run.load_module(run.reader_file(BENCH.parent, metric),
                             "reader_" + metric.replace(".", "_"))
    spans = [(span, 1.1, 1.4), ("executor/compute", 1.4, 1.9),
             (span, 3.2, 3.3), (span, 5.1, 5.9)]  # the last call untraced
    assert reader.read({"driver": _Driver(spans)}) == pytest.approx(200.0)
    # a program without the span (the parent) reads nothing
    old = [("executor/compute", 1.4, 1.9)]
    assert reader.read({"driver": _Driver(old)}) is None


@pytest.fixture
def profiled(tmp_path):
    """A traced run of a cell through the program's tracer and the JAX
    profiler on the CPU, then reduced from its ``.xplane.pb``."""
    import jax
    from repro.obs import trace as obs_trace

    def go(work):
        tr = obs_trace.enable(True)
        tr.clear()
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level, opts.python_tracer_level = 1, 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench/sweep"):
                work()
        finally:
            jax.profiler.stop_trace()
            obs_trace.enable(False)
        path = trace_reduce.find_trace(str(tmp_path))
        return trace_reduce.read_planes(path), \
            program_spans.read_program_spans(path)

    return go


def test_program_spans_land_in_the_profile_nested(profiled):
    from repro.core.sim import sweep

    planes, program = profiled(lambda: sweep(
        "gros", [0.1], [1, 2, 3], total_work=50.0, max_time=64.0,
        collect_traces=False, chunk_size=2))
    assert [s[0] for s in planes["spans"]] == ["bench/sweep"]
    names = [n for n, _, _ in program]
    for n in ("sweep", "sweep/grid", "sweep/keys", "sweep/rows",
              "sweep/summary", "executor/prepare", "executor/compute",
              "executor/transfer", "executor/merge"):
        assert n in names, n
    assert names.count("executor/compute") == 2

    def within(inner, outer):
        (_, a, b), = [s for s in program if s[0] == outer]
        return all(a <= s <= e <= b for n, s, e in program if n == inner)

    assert within("sweep/keys", "sweep/grid")
    assert within("sweep/rows", "sweep/grid")
    for n in ("sweep/grid", "sweep/summary", "executor/compute"):
        assert within(n, "sweep")
    (_, s0, e0), = planes["spans"]
    (_, s1, e1), = [s for s in program if s[0] == "sweep"]
    assert s0 <= s1 <= e1 <= e0  # one clock for both families


SEED = 2 ** 31 + 4242


def test_traced_campaign_run_reports_the_sweep_parts(checkout, monkeypatch):
    monkeypatch.setattr(trace_reduce, "reduce_dir", lambda *a, **kw: {
        "busy_s": 0.01, "window_s": 0.1, "ops": {}, "modules": {},
        "n_spans": {"bench/sweep": 1},
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    # the kernel path, in the Pallas interpreter here
    out = run.execute("campaign_fixed_pi", SEED, 0.5, True, root=checkout,
                      require_tpu=False, log=lambda m: None,
                      overrides={"seeds_per_call": 2, "backend": "pallas",
                                 "check_runs": 64, "path_counter": {
                                     "name": "closed_loop_runs_total",
                                     "labels": {"path": "interpret"}}})
    assert out["correct"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["sweep.grid_ms"] > 0 and m["sweep.summary_ms"] > 0
    assert m["sweep.grid_ms"] + m["sweep.summary_ms"] < m["sweep.host_ms"]


def test_the_report_runs_a_cell_end_to_end(monkeypatch):
    """`profile` on the CPU at a small fleet, with a stand-in device
    plane (the CPU's profile has none)."""
    import jax

    real_resolve, real_read = run.resolve_cell, trace_reduce.read_planes

    def small(name, root=run.ROOT):
        found = real_resolve(name, root)
        found["traffic"].update(check_tenants_per_group=3, warm_periods=3)
        found["config"].update(tenants=96, capacity=128)
        return found

    def with_device(path):
        planes = real_read(path)
        planes["devices"] = {"/device:TPU:0": {"XLA Ops": [
            ("%op = f32[] x()", s, s + 1000) for _, s, _ in
            planes["spans"]]}}
        return planes

    monkeypatch.setattr(run, "resolve_cell", small)
    monkeypatch.setattr(run, "devices_for",
                        lambda chips, require_tpu=True: jax.devices())
    monkeypatch.setattr(trace_reduce, "read_planes", with_device)
    out = program_spans.profile("plane_frontier_fleet", SEED, 0.5,
                                log=lambda m: None)
    json.dumps(out)
    assert set(out["end_to_end_traced"]) == {"plane_period_p50_ms",
                                             "plane_period_p95_ms"}
    tick = out["parts"]["plane/tick"]
    assert tick["n"] == out["span_n"]["plane/tick"] > 0
    for n in ("plane/aggregate", "signals/median", "signals/shift",
              "plane/pack", "executor/compute", "plane/events",
              "plane/publish"):
        assert tick["mean"][n] > 0, n
    assert np.isclose(sum(out["idle_by_span"].values()),
                      out["window_s"] - out["busy_s"])
