#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers and the
control's, over many seeds, in one process on the chip.

    python3 bench/control.py --workload <cell> --seeds 12 --seconds 5 \\
        [--first-seed N] [--out FILE]

Each seed is one run of the cell (`run.execute`: set-up, a short window
at the cell's own load, the check of its outputs against the reference:
the program's reading) that also puts the reference at the next
precision below the configuration's in the program's place on the same
sample (the control's reading). One JSON line per seed. The benchmark's
own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=1_000_003)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--out")
    args = p.parse_args(argv)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        res = run.execute(args.workload, seed, args.seconds, False,
                          t_start=time.perf_counter(), control=True, log=log)
        line = {"workload": args.workload, "seed": seed,
                "attempted": res["attempted"], "failed": res["failed"],
                "program": {k: v["value"] for k, v in res["checks"].items()},
                "control": res["control"]}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
