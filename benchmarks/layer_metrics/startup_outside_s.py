"""What ``first_step_s`` holds that is not the program's: the ``startup``
event's ``outside_s``, its ``time_to_first_step_s`` less the seconds
under the loop's spans. Under ``train.py`` that is the few statements
between the spans; in a benchmark run it is the harness's footprint
compile and reference check, made while it holds the trainer between
``build()`` and ``train()`` (the detail file's
``marks_s["reference_checked"] - marks_s["trainer_built"]``)."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "entry points and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"


@loop_timeline.reader
def read(r):
    return startup_timeline.part_s(r, "outside_s")
