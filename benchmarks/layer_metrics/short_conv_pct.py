"""Share of device busy time under ``short_conv/gate_conv``: the two
gates and the 3-tap depthwise convolution of the short-convolution
mixers: elementwise work, bound by bytes. Every pass is read from the
trace, the forward pass re-run under ``model.remat`` included
(``harness/scope_times.part_label_s``)."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "short convolution"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    label_s = scope_times.part_label_s(__file__, r)
    if label_s is None:
        return None
    sec = scope_times.seconds(label_s, "short_conv", ("gate_conv",))
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
