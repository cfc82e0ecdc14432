"""A checkout root for the tests that drive cells: BENCHMARK.json with
the cells built and rehearsed here but not yet in the benchmark
(`STAGED`, each with the metrics it reports), and the repository's
``bench/`` and ``src/`` linked in."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
STAGED = [({
    "name": "campaign_phased_faulted", "config": "table2-campaign",
    "traffic": "phased_faulted_256_seeds", "chips": 1,
    "why": "4,608 runs x 2,048 periods per sweep call in chunks of 1,536: "
           "phases, blackout, guard, detector and three policies, so the "
           "kernel is bypassed and the scan engine does the device work"},
    ("campaign_runs_per_s", "sweep.host_ms", "executor.host_ms",
     "engine.device_ms", "device_idle_pct.campaign"))]


@pytest.fixture(scope="session")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {w["name"] for w in spec["workloads"]}
    for cell, metrics in STAGED:
        if cell["name"] in listed:
            continue
        spec["workloads"].append(cell)
        for m in spec["end_to_end"] + spec["per_layer"]:
            if m["name"] in metrics and "workloads" in m:
                m["workloads"].append(cell["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for d in ("bench", "src"):
        (root / d).symlink_to(ROOT / d)
    return root
