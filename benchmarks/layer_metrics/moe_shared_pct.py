"""Share of device busy time in the dense work that rides with every
expert layer of a latent mixture: the shared expert (``moe/shared``) and
the projections into and out of the experts' latent (``moe/latent_in``,
``moe/latent_out``). Every pass is read from the trace, the forward pass
re-run under ``model.remat`` included
(``harness/scope_times.part_label_s``). A part of ``moe_pct``."""

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
                              ("shared", "latent_in", "latent_out"))
    return 100.0 * sec / r.trace.busy_s if sec > 0 else None
