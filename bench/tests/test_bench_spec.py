"""BENCHMARK.json against the benchmark's contract, and every cell,
those staged for the tests too, resolved to its files by name."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
STAGED = run.load_module(BENCH / "tests" / "conftest.py",
                         "bench_tests_conftest").STAGED
ALL_CELLS = CELLS + [c["name"] for c, _ in STAGED if c["name"] not in CELLS]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_keys():
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.add(m["name"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    everything = ([c["name"] for c in SPEC["configs"]] + CELLS
                  + [m["name"] for m in SPEC["end_to_end"]
                     + SPEC["per_layer"]])
    assert len(everything) == len(set(everything))
    assert all(NAME.match(n) for n in everything)


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_cell_resolves_to_its_files(cell, checkout):
    found = run.resolve_cell(cell, checkout)
    assert found["driver"].is_file()
    assert all(p.is_file() for p in found["metric_files"].values())
    reported = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert found["per_layer"]
    for m in found["per_layer"]:
        assert m["moves"] in reported
    cfg = found["config"]
    assert cfg["reduced"] == [] and cfg["assumed"]
    assert set(found["traffic"]["limits"])


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_every_metric_reader_loads(cell, checkout):
    files = run.resolve_cell(cell, checkout)["metric_files"]
    for name, path in files.items():
        mod = run.load_module(path, "reader_" + name.replace(".", "_"))
        assert callable(mod.read)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        run.resolve_cell("no_such_cell")


def test_a_run_without_a_tpu_fails_and_prints_no_result(capsys):
    assert run.main(["--workload", "campaign_fixed_pi", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
