"""Share of device busy time in the Kimi Delta Attention layers:
everything under the program's ``layerN/kda`` scope (the q/k/v
projections, the short convolutions and the L2 norms, the decay and
write-rate gates, the chunked delta scan, the gated output norm, the
out-projection); forward, backward and the forward pass re-run under
``model.remat``. A program without the scope gives nothing."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "linear attention"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    if r.trace is None or not r.trace.busy_s:
        return None
    sec = scope_times.seconds(r.trace.label_s, "kda")
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
