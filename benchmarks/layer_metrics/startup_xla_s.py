"""Seconds in the backend's ``compile_or_get_cached`` from the
``Trainer``'s construction to the first dispatch returning:
``xla_compile_s + cache_load_s`` of the ``startup`` event's ``compile``
(JAX's ``backend_compile_duration`` events; a span in which JAX
reported a cache hit is a load, any other a compile). Both halves, as
``startup_trace_s``."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "entry points and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"


@loop_timeline.reader
def read(r):
    return startup_timeline.compile_sum(r, "xla_compile_s", "cache_load_s")
