"""1 - (union of device-operation intervals / traced window), from the
profiler trace, mean over the devices."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
