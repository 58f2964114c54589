"""Share of device busy time in operations whose HLO ``op_name`` lies
under the program's ``optimizer_update`` named scope."""

LAYER = "step builder"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.category_s.get("optimizer_update", 0.0) / r.trace.busy_s
