"""scan.step_us: device time of one control period of the scan engine
across one chunk's runs: the device time of every ``jit_sweep_scan``
module in the traced window over traced calls x chunks per call x scan
steps, in us. Chunks per call are ceil(runs / ``chunk_size``) and scan
steps ceil(max_time / dt) rounded up to a power of two, at least 256,
as `repro.core.sim` buckets a scan's length."""

import math

MODULE = "jit_sweep_scan"
MIN_STEPS = 256


def scan_steps(config: dict) -> int:
    n = math.ceil(config["max_time"] / config["dt"])
    steps = MIN_STEPS
    while steps < n:
        steps *= 2
    return steps


def chunks_per_call(config: dict, traffic: dict) -> int:
    """One sweep call's chunks: plants x the mix's epsilons x policies x
    seeds per call, over the chunk size (one chunk without one)."""
    runs = (len(config["plants"])
            * len(traffic.get("epsilons", config["epsilons"]))
            * len(traffic.get("policies", [None]))
            * int(traffic["seeds_per_call"]))
    return math.ceil(runs / (traffic.get("chunk_size") or runs))


def read(ctx):
    t = ctx["trace"]
    seconds = sum(v for k, v in t["modules"].items()
                  if k.split("(")[0] == MODULE)
    calls = t["n_spans"].get("bench/sweep", 0)
    if seconds <= 0 or not calls:
        return None
    steps = (calls * chunks_per_call(ctx["config"], ctx["traffic"])
             * scan_steps(ctx["config"]))
    return 1e6 * seconds / steps
