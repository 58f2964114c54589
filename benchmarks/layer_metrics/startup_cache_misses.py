"""Of the executables that JAX's persistent cache was asked for and could
hold, the share it did not have: ``cache_misses / (cache_hits +
cache_misses)`` of the ``startup`` event's ``compile``. 0.0 is a warm
restart, 1.0 a cold one. A program under the cache's thresholds compiles
on every start and is neither (JAX counts a miss only where it goes on
to write an entry). None where the cache was asked for nothing."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "entry points and compile cache"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"


@loop_timeline.reader
def read(r):
    asked = startup_timeline.compile_sum(r, "cache_hits", "cache_misses")
    if not asked:
        return None
    return startup_timeline.compile_sum(r, "cache_misses") / asked
