"""Device: share of the traced window in which no operation ran on the
chip and no ``serve.step`` span of the program was open: the chip waiting
outside the scheduler's steps, on an empty engine or on the front door's
pump (``lib/spans.py``).  ``device_idle`` minus this is the idle inside
steps."""
from lib import spans


def read(ctx):
    red = spans.reduction(ctx)
    if red is None or red.window_s <= 0:
        return None
    idle = spans.idle_outside_s(red)
    return None if idle is None else 100.0 * idle / red.window_s
