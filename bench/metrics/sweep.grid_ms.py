"""sweep.grid_ms: host time per `sweep` call spent building the grid
before the engine runs (the program's ``sweep/grid`` span: value
stacks, per-seed keys, per-run rows), over the traced calls, in ms."""

import program_spans


def read(ctx):
    return program_spans.per_call_ms(ctx["driver"], "sweep/grid")
