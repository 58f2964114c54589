"""The timed step's device footprint: ``compiled.memory_analysis()`` of
the very step the loop runs (arguments + outputs + temporaries -
aliased), per device. The allocator's live-array peak does not see a
program's temporaries on this runtime (PERF.md, PR 21)."""

LAYER = "step builder"
UNIT = "GiB"
BETTER = "lower"
SOURCE = "program_counter"


def read(r):
    return r.step_memory.get("step_gib")
