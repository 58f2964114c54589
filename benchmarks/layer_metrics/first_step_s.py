"""Restart to first completed step, as the program itself reports it:
the ``startup`` telemetry event's ``time_to_first_step_s`` (Trainer
construction to the first step's dispatch returning, compile or cache
load included)."""

LAYER = "entry points and compile cache"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"


def read(r):
    return r.startup.get("time_to_first_step_s")
