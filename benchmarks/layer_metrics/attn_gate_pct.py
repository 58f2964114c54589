"""Share of device busy time in the attention layers' per-head output
gate: everything under the program's ``attn_gate`` scope inside an
attention scope (``layerN/attn/attn_gate``, ``layerN/attn_window/
attn_gate``: the gate's projection of the normed input, its sigmoid, the
product with the kernel's output, the counter's mean, and their
gradients). Every pass is read from the trace, the forward pass re-run
under ``model.remat`` included (``harness/scope_times.part_label_s``). A
program without the scope gives nothing."""

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
    sec = scope_times.seconds(label_s, "attn_gate")
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
