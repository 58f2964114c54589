"""Python time spent tracing functions to jaxprs and lowering jaxprs to
MLIR modules from the ``Trainer``'s construction to the first dispatch
returning: ``trace_s + lower_s`` of the ``startup`` event's ``compile``
(``core/profiling.CompileLog``, from JAX's own
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` events;
nested events counted once). Both halves: the program's spans and, in
a benchmark run, the harness's footprint compile and reference check
between them."""

from benchmarks.harness import loop_timeline, startup_timeline

LAYER = "step builder"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_counter"


@loop_timeline.reader
def read(r):
    return startup_timeline.compile_sum(r, "trace_s", "lower_s")
