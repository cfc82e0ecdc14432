"""Reduction of a profiler trace to busy time, per-op and per-module
device time and named idle gaps."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def _planes(chips=1):
    ops = [("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 10 * MS, 20 * MS),
           ("%closed_loop = (f32[16,128]) custom-call()", 20 * MS, 60 * MS),
           ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 100 * MS, 110 * MS)]
    mods = [("jit__run(7)", 10 * MS, 60 * MS), ("jit_f(3)", 100 * MS,
                                                110 * MS)]
    devices = {f"/device:TPU:{i}": {"XLA Ops": ops, "XLA Modules": mods}
               for i in range(chips)}
    spans = [("bench/warm", 0, 5 * MS), ("bench/sweep", 8 * MS, 70 * MS),
             ("bench/sweep", 90 * MS, 120 * MS)]
    return {"devices": devices, "spans": spans}


def test_busy_window_ops_and_modules():
    r = trace_reduce.reduce_planes(_planes(), n_chips=1)
    assert r["window_s"] == pytest.approx(0.112)  # 8 ms .. 120 ms
    assert r["busy_s"] == pytest.approx(0.060)
    assert r["ops"]["fusion.1"] == pytest.approx(0.020)
    assert r["modules"]["jit__run(7)"] == pytest.approx(0.050)
    assert r["n_spans"] == {"bench/sweep": 2}
    assert r["breakdown"]["device_ops"][0] == ["closed_loop",
                                               pytest.approx(0.040)]


def test_idle_gaps_are_named_by_the_host_span():
    r = trace_reduce.reduce_planes(_planes(), n_chips=1)
    gaps = dict((round(s, 6), n) for n, s in r["breakdown"]["idle_gaps"])
    assert gaps[0.040] == "between benchmark spans"  # 60 .. 100 ms
    assert gaps[0.010] == "bench/sweep"              # 110 .. 120 ms
    assert gaps[0.002] == "bench/sweep"              # 8 .. 10 ms
    assert len(r["breakdown"]["idle_gaps"]) <= trace_reduce.TOP


def test_busy_is_averaged_over_the_cells_chips():
    planes = _planes(chips=4)
    planes["devices"]["/device:TPU:3"]["XLA Ops"] = []
    r = trace_reduce.reduce_planes(planes, n_chips=4)
    assert r["busy_s"] == pytest.approx(0.060 * 3 / 4)


def test_a_trace_without_a_device_or_a_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes({"devices": {}, "spans": _planes()[
            "spans"]}, 1)
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(dict(_planes(), spans=[]), 1)


# Recorded on a TPU v5 lite by a `--trace 1` run of plane_frontier_fleet
# and cut to its first six service periods: the host plane keeps the
# benchmark's spans, the device plane its XLA Ops and XLA Modules lines.
CHIP_TRACE = BENCH / "tests" / "data" / \
    "plane_frontier_fleet.tpu_v5_lite.xplane.pb"


def test_chip_trace_reduces_to_busy_spans_and_modules():
    planes = trace_reduce.read_planes(str(CHIP_TRACE))
    assert list(planes["devices"]) == ["/device:TPU:0"]
    r = trace_reduce.reduce_planes(planes, n_chips=1)
    assert r["n_spans"] == {"bench/generate": 6, "bench/ingest": 6,
                            "bench/tick": 6}
    assert r["window_s"] == pytest.approx(0.64343, rel=1e-4)
    assert r["busy_s"] == pytest.approx(2.79427e-4, rel=1e-4)
    tick = [v for k, v in r["modules"].items() if k.startswith("jit_fn(")]
    assert tick and tick[0] == pytest.approx(2.84935e-4, rel=1e-4)
    ops = r["breakdown"]["device_ops"]
    assert 0 < len(ops) <= trace_reduce.TOP and " = " not in ops[0][0]
    assert {n for n, _ in r["breakdown"]["idle_gaps"]} <= {
        "bench/generate", "bench/ingest", "bench/tick",
        "between benchmark spans"}
