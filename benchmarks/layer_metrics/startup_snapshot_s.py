"""Seconds under the loop's first ``snapshot`` span: the recovery
ladder's baseline copy of the train state to the host, taken at loop
entry before the first step (1.31 GB in the BERT cells). 0.0 where the
ladder is off. From the ``startup`` event's ``phases_s``."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "train loop"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"


@loop_timeline.reader
def read(r):
    return startup_timeline.phase_s(r, "snapshot")
