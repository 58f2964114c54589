"""Time a step waits for its batch: the ``StepTimer`` ``infeed`` phase
mean over the window (the loop's pull from the prefetch queue)."""

LAYER = "input"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"


def read(r):
    return r.window["phase_ms_step"].get("infeed")
