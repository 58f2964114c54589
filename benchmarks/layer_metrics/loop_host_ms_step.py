"""Host time the loop itself spends per step: the ``StepTimer``
``dispatch`` phase (mean, from the program) plus the program's hooks'
``after_step`` time (the benchmark's span around each hook). The
``metrics_fetch`` and ``backpressure`` phases are waits on the device
under dispatch-ahead, so they follow the device's step time and are
left out; the run's detail line has the fetch waits."""

LAYER = "train loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"


def read(r):
    w = r.window
    if "dispatch" not in w["phase_ms_step"]:
        return None
    return w["phase_ms_step"]["dispatch"] + w["hooks_ms_step"]
