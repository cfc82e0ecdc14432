"""The scan-engine cell `campaign_phased_faulted`: its mix against the
staged one, its run counter, and its two readers (`scan.step_us` on a
synthetic trace, `sweep.scenario_ms` on a synthetic driver), at a size
the CPU holds. Its sound run, control and broken timed paths are in
`test_bench_checks.py`."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402

CELL = "campaign_phased_faulted"
SEED = 2 ** 31 + 4242
# the scan engine in chunks of 12 runs, as the cell's 1,536
SMALL = {"seeds_per_call": 2, "chunk_size": 12, "check_runs": 36}


def _reader(metric):
    return run.load_module(run.reader_file(BENCH.parent, metric),
                           "reader_" + metric.replace(".", "_"))


def test_the_mix_is_the_staged_scenario_counted_on_the_scan():
    mix = run.resolve_cell(CELL)["traffic"]
    staged = json.loads((BENCH / "traffic"
                         / "phased_faulted_256_seeds.json").read_text())
    differ = {k for k in staged if staged[k] != mix.get(k)}
    assert set(mix) == set(staged)
    assert differ <= {"path_counter", "limits"}
    assert mix["path_counter"] == {"name": "closed_loop_runs_total",
                                   "labels": {"path": "scan"},
                                   "per_run": 1}
    assert set(mix["limits"]) == {"run_gap_max", "run_gap_median",
                                  "run_off_share"}


def test_scan_runs_the_counter_misses_fail_on_path_runs_missing(
        monkeypatch, checkout):
    """Runs that reach the scan engine without being counted (as in a
    program that counts only the kernel's runs) fail the run on
    ``path_runs_missing`` alone."""
    from repro.kernels.closed_loop import ops

    class Uncounted:
        def inc(self, amount, path):
            pass

    monkeypatch.setattr(ops, "runs_counter", Uncounted)
    out = run.execute(CELL, SEED, 0.5, False, root=checkout,
                      require_tpu=False, overrides=SMALL, log=lambda m: None)
    failing = [k for k, c in out["checks"].items()
               if c["value"] > c["limit"]]
    assert not out["correct"] and failing == ["path_runs_missing"], \
        out["checks"]
    assert out["checks"]["path_runs_missing"]["value"] == out["attempted"]


def _step_us(modules, n_calls, **traffic):
    cell = run.resolve_cell(CELL)
    trace = {"modules": modules, "n_spans": {"bench/sweep": n_calls}}
    return _reader("scan.step_us").read({
        "config": cell["config"], "traffic": {**cell["traffic"], **traffic},
        "trace": trace})


def test_scan_step_is_module_time_per_call_chunk_and_step():
    # 4,608 runs in chunks of 1,536; 2,000 periods bucket to 2,048 steps
    cell = run.resolve_cell(CELL)
    reader = _reader("scan.step_us")
    assert reader.chunks_per_call(cell["config"], cell["traffic"]) == 3
    assert reader.scan_steps(cell["config"]) == 2048
    assert reader.scan_steps({"max_time": 64.0, "dt": 1.0}) == 256
    us = _step_us({"jit_sweep_scan(41)": 0.030, "jit__run(7)": 1.0}, 1)
    assert us == pytest.approx(4.8828125)  # 30 ms / (1 x 3 x 2,048)
    two = _step_us({"jit_sweep_scan(41)": 0.030,
                    "jit_sweep_scan(42)": 0.030}, 2)
    assert two == pytest.approx(us)
    # one chunk per call when the mix names no chunk size
    assert _step_us({"jit_sweep_scan(41)": 0.030}, 1,
                    chunk_size=None) == pytest.approx(us * 3)


@pytest.mark.parametrize("modules,n_calls", [
    ({"jit__run(7)": 1.0}, 1), ({"jit_sweep_scan(41)": 0.030}, 0)],
    ids=["no_scan_module", "no_traced_call"])
def test_scan_step_reads_nothing_without_the_module_or_a_call(modules,
                                                              n_calls):
    assert _step_us(modules, n_calls) is None


class _Driver:
    def __init__(self, spans):
        self.calls = [(1.0, 2.0), (3.0, 4.0), (5.0, 6.0)]
        self.traced_calls = 2
        self.program_spans = spans


def test_scenario_reader_is_its_span_per_traced_call():
    reader = _reader("sweep.scenario_ms")
    spans = [("sweep/grid", 1.1, 1.5), ("sweep/scenario", 1.2, 1.3),
             ("sweep/scenario", 3.2, 3.5),
             ("sweep/scenario", 5.1, 5.9)]  # the last call untraced
    assert reader.read({"driver": _Driver(spans)}) == pytest.approx(200.0)
    # a program without the span (the parent) reads nothing
    old = [("sweep/grid", 1.1, 1.5)]
    assert reader.read({"driver": _Driver(old)}) is None

