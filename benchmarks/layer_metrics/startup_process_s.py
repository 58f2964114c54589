"""OS process start to the construction of the ``Trainer``: interpreter,
imports, backend start, configuration and, in a benchmark run, making
the traffic pool. The ``startup`` event's ``process_s``, which the
program reads from the OS's own record of when the process started
(``core/profiling.process_age_s``), so no entry point stamps a clock.
``startup_process_s + first_step_s`` is the restart up to the first
dispatch."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "entry points and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"


@loop_timeline.reader
def read(r):
    return startup_timeline.part_s(r, "process_s")
