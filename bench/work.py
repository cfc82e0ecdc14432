"""Work of the closed-loop kernel path, counted from the grid alone, and
its share of the chip's roofline.

Each closed-loop period of each run consumes five float32 random inputs
(progress noise, power noise, drop entry, drop exit, heartbeat noise):
20 bytes. A campaign call of R runs over a horizon of max_time at dt
needs R * ceil(max_time / dt) of them, whatever draws them: a noise
tensor read from HBM today, or numbers made inside the kernel later.
v5e publishes no VPU rate, so the bound is bytes over HBM bandwidth and
the share is a lower bound of how close the path runs to the chip.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

RANDOM_INPUTS_PER_STEP = 5
BYTES_PER_INPUT = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"
MAX_SHARE_PCT = 105.0


def campaign_runs(config: dict, traffic: dict) -> int:
    """Runs of one sweep call: plants x epsilons x seeds per call."""
    return (len(config["plants"]) * len(config["epsilons"])
            * int(traffic["seeds_per_call"]))


def closed_loop_steps(config: dict) -> int:
    return math.ceil(config["max_time"] / config["dt"])


def closed_loop_bytes(config: dict, traffic: dict) -> int:
    """Random-input bytes one sweep call consumes."""
    return (campaign_runs(config, traffic) * closed_loop_steps(config)
            * RANDOM_INPUTS_PER_STEP * BYTES_PER_INPUT)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    the table is an error."""
    with open(PEAKS) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def roofline_pct(n_bytes: float, seconds: float, device_kind: str) -> float:
    """Least time at the peak HBM bandwidth over the measured device
    time, in percent. Above 105% the bytes are counted too high or the
    time leaves out part of the work, and that is an error."""
    least = n_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    share = 100.0 * least / seconds
    if share > MAX_SHARE_PCT:
        raise ValueError(f"roofline share {share:.1f}% > {MAX_SHARE_PCT}%: "
                         "the work is over-counted or the time is short")
    return share
