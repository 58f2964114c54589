"""Device time of all-reduce / all-gather / reduce-scatter operations per
step on one device (mean over the devices), from the trace."""

LAYER = "collectives"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"


def read(r):
    if r.trace is None or r.cell.chips < 2 or not r.trace.steps:
        return None
    return 1e3 * r.trace.collective_s / r.trace.steps
