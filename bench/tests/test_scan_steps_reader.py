"""`scan.steps_run_pct`: the share of the scan engine's horizon its
summary-mode loop ran, read from the program's
``sweep_scan_steps_total`` counter; nothing where the program has none."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402
from repro.obs import metrics  # noqa: E402

METRIC = "scan.steps_run_pct"


@pytest.fixture
def registry(monkeypatch):
    reg = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "get_registry", lambda: reg)
    return reg


def _read():
    return run.load_module(run.reader_file(BENCH.parent, METRIC),
                           "reader_scan_steps_run_pct").read({})


def test_steps_run_is_a_share_of_the_horizon(registry):
    c = registry.counter("sweep_scan_steps_total", labelnames=("kind",))
    for ran in (299, 236, 138):  # one call: a chunk per plant
        c.inc(ran, kind="run")
        c.inc(2048, kind="horizon")
    assert _read() == pytest.approx(100.0 * 673 / 6144)


def test_a_program_without_the_counter_reads_nothing(registry):
    assert _read() is None
    registry.counter("closed_loop_runs_total", labelnames=("path",)
                     ).inc(4608, path="scan")
    assert _read() is None


def test_the_metric_is_read_in_the_scan_cell_only():
    cells = {w["name"]: run.resolve_cell(w["name"])
             for w in run.read_json(BENCH.parent / "BENCHMARK.json")
             ["workloads"]}
    reading = {n for n, c in cells.items()
               if METRIC in {m["name"] for m in c["per_layer"]}}
    assert reading == {"campaign_phased_faulted"}
