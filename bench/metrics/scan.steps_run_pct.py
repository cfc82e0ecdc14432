"""scan.steps_run_pct: the share of the scan engine's horizon its
summary-mode loop ran, in %: 100 x the program's
``sweep_scan_steps_total{kind="run"}`` over ``{kind="horizon"}``,
summed over every engine batch (a chunk, or one device's slice of it)
of the process. None where the program keeps no such counter."""

from common import registry_sample


def read(ctx):
    ran = registry_sample("sweep_scan_steps_total", {"kind": "run"})
    horizon = registry_sample("sweep_scan_steps_total", {"kind": "horizon"})
    if not ran or not horizon:
        return None
    return 100.0 * ran / horizon
