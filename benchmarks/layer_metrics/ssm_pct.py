"""Share of device busy time in the Mamba-2 layers: everything under the
program's ``layerN/mamba`` scope (in-projection, convolution, the chunked
scan, the gated norm, out-projection); forward, backward and the forward
pass re-run under ``model.remat``. A program without the scope gives
nothing."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "state-space mixer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    if r.trace is None:
        return None
    sec = scope_times.seconds(r.trace.label_s, "mamba")
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
