"""Host time per step during which the loop itself left the device's
queue empty: for every full sync in the window (the ``metrics_fetch``
span of the program's loop timeline), the time from the end of the fetch
to the end of the next ``train_step`` span, less the benchmark's own
hook; summed over the window, divided by its steps. Everything the loop
does there (the recovery snapshot, goodput, memory sampling, hooks, the
next batch's pull) runs with nothing queued behind it."""

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
    bubbles = loop_timeline.sync_bubbles(spans)
    return sum(end - start - instrument
               for start, end, instrument in bubbles) * 1e-6 / steps
