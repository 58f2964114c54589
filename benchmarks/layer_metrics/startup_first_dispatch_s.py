"""Seconds under the loop's first ``train_step`` span (phase
``compile``): the step's Python trace, its lowering, the executable's
compile or load from the cache, and the dispatch. In a benchmark run
the harness has compiled the same step for its footprint before, so
the backend's part is a load at most. From the ``startup`` event's
``phases_s``."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "train loop"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"


@loop_timeline.reader
def read(r):
    return startup_timeline.phase_s(r, "train_step")
