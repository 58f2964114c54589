"""The program's own record of its restart, as per-layer readers see it.

The ``startup`` telemetry event of the train loop (``train/loop.py``
``_emit_startup``) says where ``time_to_first_step_s`` went: seconds per
span of the loop's recorder up to the first dispatch returning
(``phases_s``), what lay between those spans (``outside_s``), what the
process had spent before the trainer was made (``process_s``), and what
JAX traced, lowered, compiled and loaded meanwhile (``compile``, from
``core/profiling.CompileLog``). The runner copies the event's ``extra``
into ``RunRecords.startup``, so these readers need nothing else of it.

A program whose event lacks the fields (this PR's parent) gives ``None``
here, and so does every reader built on this; each is wrapped in
``loop_timeline.reader`` besides, so that nothing it meets can end the
run. ``../STARTUP_TIMELINE.md`` has the span names and the metrics.
"""

from __future__ import annotations


def phase_s(records, span: str) -> float | None:
    """Seconds under ``span`` before the first dispatch returned. 0.0
    where the program recorded its phases and this one did not run."""
    phases = records.startup.get("phases_s")
    return None if phases is None else float(phases.get(span, 0.0))


def part_s(records, key: str) -> float | None:
    """``outside_s`` or ``process_s``, as the event has it."""
    value = records.startup.get(key)
    return None if value is None else float(value)


def compile_sum(records, *keys: str) -> float | None:
    """The sum of the named counters of the event's ``compile``."""
    counts = records.startup.get("compile")
    return None if counts is None else float(sum(counts[k] for k in keys))
