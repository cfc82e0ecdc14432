"""sweep.summary_ms: host time per `sweep` call from the executor's
return to the `SweepResult` (the program's ``sweep/summary`` span: the
kernel's final state as a carry, reshapes, histogram edges, the
summary), over the traced calls, in ms."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx["driver"], "sweep/summary")
