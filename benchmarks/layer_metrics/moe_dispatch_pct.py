"""Share of device busy time under ``moe/router``, ``moe/dispatch`` and
``moe/combine``: what routing costs beside the expert products (scores
and top-k, the sort by expert, the two row gathers and the weighted sum).
Every pass is read from the trace, the forward pass re-run under
``model.remat`` included (``harness/scope_times.part_label_s``)."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    label_s = scope_times.part_label_s(__file__, r)
    if label_s is None:
        return None
    sec = scope_times.seconds(label_s, "moe",
                              ("router", "dispatch", "combine"))
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
