"""engine.device_ms: device busy time per `sweep` call in the traced
window (every operation the call ran on the device), in ms."""


def read(ctx):
    n = ctx["trace"]["n_spans"].get("bench/sweep", 0)
    if not n or ctx["trace"]["busy_s"] <= 0:
        return None
    return 1e3 * ctx["trace"]["busy_s"] / n
