"""closed_loop_roofline: the closed-loop path's share of the HBM
roofline. The work is the random inputs the grid's runs consume
(`work.closed_loop_bytes`), the time every device operation of the
kernel op's jitted call (``jit__run``: noise draw, kernel, unpack), so
the share reads the same work if the noise moves into the kernel."""

import work

MODULE = "jit__run"


def read(ctx):
    t = ctx["trace"]
    seconds = sum(v for k, v in t["modules"].items()
                  if k.split("(")[0] == MODULE)
    calls = t["n_spans"].get("bench/sweep", 0)
    if seconds <= 0 or not calls:
        return None
    n_bytes = calls * work.closed_loop_bytes(ctx["config"], ctx["traffic"])
    return work.roofline_pct(n_bytes, seconds, ctx["device_kind"])
