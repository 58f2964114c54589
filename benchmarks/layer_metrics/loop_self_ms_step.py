"""Host time per step the loop spends on its own work, from the
program's loop timeline: the ``train_step`` span (the dispatch), the
``bookkeeping`` spans and every ``hook:*`` span but the benchmark's own
window hook, over the window's steps. ``loop_host_ms_step`` from inside
the program: no wait on the device (``backpressure``,
``metrics_fetch``), no wait on the input (``infeed``), no snapshot."""

from benchmarks.harness import loop_timeline

LAYER = "train loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"


@loop_timeline.reader
def read(r):
    found = loop_timeline.of_run(__file__, r)
    if found is None:
        return None
    spans, steps = found
    return loop_timeline.total_ns(
        spans, names=("train_step", "bookkeeping"), prefix="hook:"
    ) * 1e-6 / steps
