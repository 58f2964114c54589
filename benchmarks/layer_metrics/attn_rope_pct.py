"""Share of device busy time in what an attention layer does to its
queries and keys between their projections and the kernel: everything
under the program's ``qk_norm_rope`` scope (the rotation by the layer's
own rule, a partial YaRN rotation of a global layer or a whole-head one
of a window layer, after the q/k norm where the model has one, and the
cast to the kernels' dtype, with their gradients). Every pass is read
from the trace, the forward pass re-run under ``model.remat`` included
(``harness/scope_times.part_label_s``). A program without the scope
gives nothing."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "attention kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    label_s = scope_times.part_label_s(__file__, r)
    if not label_s or not r.trace.busy_s:
        return None
    sec = scope_times.seconds(label_s, "qk_norm_rope")
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
