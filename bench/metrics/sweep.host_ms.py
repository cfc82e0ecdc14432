"""sweep.host_ms: host time of `sweep` per call outside the executor:
the benchmark's span around each call minus the executor's
prepare/compute/transfer/merge spans inside it, over the traced
calls, in ms."""


def read(ctx):
    drv = ctx["driver"]
    calls = [(t0, t1) for t0, t1, *_ in
             drv.calls[:getattr(drv, "traced_calls", 0)]]
    spans = getattr(drv, "program_spans", None)
    if not calls or not spans:
        return None
    own = []
    for t0, t1 in calls:
        inner = sum(e - s for name, s, e in spans
                    if name.startswith("executor/") and s >= t0 and e <= t1)
        own.append((t1 - t0) - inner)
    return 1e3 * sum(own) / len(own)
