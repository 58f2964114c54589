"""Share of device busy time in what a latent attention layer does to
make its keys and values: everything under the program's ``mla_latent``
scope (the down-projection to the key/value latent and the shared rotary
key, the latent's RMSNorm, the up-projection to each head's key and
value, and the broadcast of the one rotated key into every head's key,
with their gradients). Every pass is read from the trace, the forward
pass re-run under ``model.remat`` included
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
    sec = scope_times.seconds(label_s, "mla_latent")
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
