"""Share of device busy time under ``moe/router`` and ``moe/dispatch`` in
the forward pass that the backward pass runs again (``model.remat``): the
phase ``again`` of ``harness/scope_times.part_label_s``. A layer that
keeps what its routing decided (the logits, the chosen experts and their
scores, the sort by expert) from its first forward pass reads what the
backward pass still differentiates through: the scores, the weights'
normalisation and the tokens' cast; one that keeps nothing reads the
router's product, the top-k, the chosen scores' gather and the sorts once
more; one that re-runs no forward pass reads 0.0. A part of
``moe_dispatch_pct``; a program without expert layers gives nothing."""

from benchmarks.harness import loop_timeline, scope_times

LAYER = "expert layer"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    label_s = scope_times.part_label_s(__file__, r)
    if not label_s or not r.trace.busy_s \
            or not scope_times.seconds(label_s, "moe"):
        return None
    again = {label: sec for label, sec in label_s.items()
             if label.partition(":")[2].startswith(f"{scope_times.AGAIN}/")}
    return 100.0 * scope_times.seconds(
        again, "moe", ("router", "dispatch")) / r.trace.busy_s
