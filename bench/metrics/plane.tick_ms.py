"""plane.tick_ms: mean `ControlPlane.tick` wall time over the window,
from the program's ``plane_tick_seconds`` histogram, in ms."""


def read(ctx):
    return ctx["driver"].tick_ms()
