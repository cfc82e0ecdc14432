"""Work counts, peaks, roofline shares, percentiles, rates and spreads."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import common  # noqa: E402
import run  # noqa: E402
import work  # noqa: E402

CAMPAIGN = run.resolve_cell("campaign_fixed_pi")


def test_work_follows_from_the_grid_alone():
    cfg, traffic = CAMPAIGN["config"], CAMPAIGN["traffic"]
    runs = len(cfg["plants"]) * len(cfg["epsilons"]) * traffic[
        "seeds_per_call"]
    assert work.campaign_runs(cfg, traffic) == runs == 33_000
    assert work.closed_loop_steps(cfg) == 2000
    assert work.closed_loop_bytes(cfg, traffic) == 33_000 * 2000 * 20
    doubled = dict(traffic, seeds_per_call=2 * traffic["seeds_per_call"])
    assert work.closed_loop_bytes(cfg, doubled) == 2 * 33_000 * 2000 * 20


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("cpu")
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_roofline_share_and_its_ceiling():
    n_bytes = 819e9 * 0.5
    assert work.roofline_pct(n_bytes, 1.0, "TPU v5 lite") == pytest.approx(50)
    assert work.roofline_pct(n_bytes, 0.48, "TPU v5 lite") < 105
    with pytest.raises(ValueError):
        work.roofline_pct(n_bytes, 0.47, "TPU v5 lite")


@pytest.mark.parametrize("q,want", [(50, 3.0), (95, 4.8), (0, 1.0),
                                    (100, 5.0)])
def test_percentile_is_linear_between_ranks(q, want):
    assert common.percentile([5, 1, 4, 2, 3], q) == pytest.approx(want)


def test_rate_spans_first_start_to_last_end():
    assert common.rate(33_000 * 3, 10.0, 13.3) == pytest.approx(30_000)


def test_rel_gap_counts_exact_zero_and_non_finite():
    gap = common.rel_gap([1.0, 0.0, np.nan, 2.0], [1.0, 0.0, 1.0, 1.0])
    assert list(gap) == [0.0, 0.0, np.inf, 1.0]


def test_rel_gap_of_a_count_has_the_floor_one():
    gap = common.rel_gap([1.0, 0.0, 3.0, 40.0], [0.0, 0.0, 2.0, 38.0], 1.0)
    assert list(gap) == pytest.approx([1.0, 0.0, 0.5, 2 / 38])


def test_a_split_metric_shares_its_quantitys_reader(tmp_path):
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    (metrics / "idle.py").write_text("")
    (metrics / "idle.serve.py").write_text("")
    assert run.reader_file(tmp_path, "idle.train") == metrics / "idle.py"
    assert run.reader_file(tmp_path, "idle.serve") == metrics / "idle.serve.py"
    assert run.reader_file(tmp_path, "busy") == metrics / "busy.py"


def test_seeds_take_any_whole_number():
    a = common.derive_seed(2 ** 31 + 12345, 4, 7).random(3)
    b = common.derive_seed(2 ** 31 + 12345, 4, 7).random(3)
    c = common.derive_seed(2 ** 31 + 12346, 4, 7).random(3)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
