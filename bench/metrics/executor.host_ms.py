"""executor.host_ms: the executor's host time per `sweep` call: its
prepare, transfer and merge spans, over the traced calls, in ms."""

HOST = ("executor/prepare", "executor/transfer", "executor/merge")


def read(ctx):
    drv = ctx["driver"]
    calls = [(t0, t1) for t0, t1, *_ in
             drv.calls[:getattr(drv, "traced_calls", 0)]]
    spans = getattr(drv, "program_spans", None)
    if not calls or not spans:
        return None
    per = [sum(e - s for name, s, e in spans
               if name in HOST and s >= t0 and e <= t1) for t0, t1 in calls]
    return 1e3 * sum(per) / len(per)
