"""sweep.scenario_ms: host time per `sweep` call spent building the
scenario's values inside the grid (the program's ``sweep/scenario``
span: phase schedules resolved per plant, detector values, fault rows,
the guard vector), over the traced calls, in ms."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx["driver"], "sweep/scenario")
