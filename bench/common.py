"""Arithmetic shared by the harness, the drivers and the metric readers:
seeds, reads of the program's metrics registry, percentiles, rates and
relative gaps."""
from __future__ import annotations

import numpy as np

# a plant's parameters, as the configurations give them (paper Table 2)
PLANT_KEYS = ("a", "b", "alpha", "beta", "K_L", "tau", "pcap_min",
              "pcap_max", "n_sockets", "noise_scale", "power_noise",
              "drop_prob", "drop_exit_prob", "drop_level")


def derive_seed(seed: int, *words: int) -> np.random.Generator:
    """A generator for one purpose of a run, from ``--seed`` and that
    purpose's words; every whole ``--seed`` is accepted."""
    return np.random.default_rng([int(seed) % 2 ** 64, *words])


def registry_sample(name: str, labels: dict | None = None):
    """One sample of a metric in the program's registry (a counter's or
    gauge's value, a histogram's dict), or None when it is absent."""
    from repro.obs import metrics
    fam = metrics.get_registry().snapshot()["metrics"].get(name)
    if fam is None:
        return None
    for s in fam["samples"]:
        if s["labels"] == (labels or {}):
            return s if fam["type"] == "histogram" else s["value"]
    return None


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between closest ranks (numpy's
    default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def rate(count: float, t_first_start: float, t_last_end: float) -> float:
    """Work per second over the whole window, first start to last end."""
    return float(count) / (t_last_end - t_first_start)


def rel_gap(got, want, floor: float = 0.0) -> np.ndarray:
    """|got - want| / max(|want|, floor) elementwise; exact agreement is
    0 and a non-finite reading is an infinite gap. A count takes the
    floor 1, so one event more or less than none reads 1."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.abs(got - want)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where(diff == 0, 0.0, diff / np.maximum(np.abs(want),
                                                         floor))
    return np.where(np.isfinite(gap), gap, np.inf)
