"""The part of collective time in which no other operation runs on the
same device, as a share of the traced window (the step time)."""

LAYER = "collectives"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(r):
    if r.trace is None or r.cell.chips < 2:
        return None
    return 100.0 * r.trace.collective_exposed_s / r.trace.window_s
