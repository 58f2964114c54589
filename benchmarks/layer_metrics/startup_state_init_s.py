"""Seconds under the loop's ``startup:init_state`` span:
``StepBuilder.init_state``, the ``jit`` of the state's creation with its
trace (a whole forward of the model), its compile or load from the
cache, and its run. From the ``startup`` event's ``phases_s``."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "step builder"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"


@loop_timeline.reader
def read(r):
    return startup_timeline.phase_s(r, "startup:init_state")
