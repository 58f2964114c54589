"""Share of device busy time in the expert layers: everything under
their ``moe`` scope (router, dispatch, the experts' activation, combine)
and the grouped expert products, which XLA's own kernel runs under the
name ``ragged-dot-*`` (``harness/scope_times.py``); forward, backward
and the forward pass re-run under ``model.remat``."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    if r.trace is None:
        return None
    sec = scope_times.seconds(r.trace.label_s, "moe")
    if sec > 0:
        sec += scope_times.ragged_dot_seconds(r.trace.label_s)
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
