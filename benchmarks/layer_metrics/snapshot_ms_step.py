"""Host time per step under the loop's ``snapshot`` span: the recovery
ladder's device-to-host copy of the train state
(``resilience.snapshot_interval_steps``), summed over the window and
divided by its steps. A part of ``sync_bubble_ms_step``: the copy runs
at a full sync. 0 in a window that holds no snapshot."""

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
    return loop_timeline.total_ns(spans, names=("snapshot",)) * 1e-6 / steps
