"""Tracing / profiling (SURVEY.md §5 "Tracing / profiling").

XPlane traces viewable in TensorBoard/Perfetto, plus the train loop's own
recorder:

  * ``trace(logdir)``  — context manager around a window of steps
                         (``jax.profiler.start_trace``/``stop_trace``)
  * ``StepTimer``      — the loop's recorder. ``phase(name)`` times one
                         stretch of an iteration on the host: it adds to
                         the per-phase totals (reported as ``time_*_ms`` in
                         the Trainer's logged metrics and folded into the
                         goodput ledger), appends the occurrence to a
                         bounded ring (the loop timeline), and enters a
                         ``jax.profiler.TraceAnnotation`` of the span's
                         name, so a profile taken by anyone shows the same
                         spans on its own timeline.
  * ``slow_iterations`` — which iterations of a block of ring spans took
                         far longer on the host than the block's median.

Step-window traces during training: ``--set train.profile_start=N
--set train.profile_stop=M`` via ProfileHook (train/hooks.py). Span names,
the timeline file and the ``slow_step`` event: docs/OBSERVABILITY.md
"Loop timeline".
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import statistics
import time
from typing import Iterable, Iterator

import jax

TIMELINE_SCHEMA = "dtf-loop-timeline/1"

# Spans in which the host waits on the device by design: under
# dispatch-ahead they follow the device's step time (a metrics fetch
# waits out every step in flight), so they say nothing of the host.
DEVICE_WAITS = ("backpressure", "metrics_fetch")


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """Capture an XPlane trace for everything inside the block."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class _Phase:
    """One occurrence of a phase; what ``StepTimer.phase`` returns."""

    __slots__ = ("_timer", "_name", "_span", "_annotation", "_start_ns",
                 "_t0")

    def __init__(self, timer: "StepTimer", name: str, span: str):
        self._timer = timer
        self._name = name
        self._span = span

    def __enter__(self) -> None:
        self._annotation = jax.profiler.TraceAnnotation(self._span)
        # Start on the profiler's clock (epoch nanoseconds, as its
        # ``profile_start_time`` and TraceMe events are); the duration
        # from the monotonic one.
        self._start_ns = time.time_ns()
        self._t0 = self._timer.clock_ns()
        self._annotation.__enter__()

    def __exit__(self, *exc) -> bool:
        self._annotation.__exit__(*exc)
        timer, name = self._timer, self._name
        dt = timer.clock_ns() - self._t0
        timer.totals[name] = timer.totals.get(name, 0.0) + dt * 1e-9
        timer.counts[name] = timer.counts.get(name, 0) + 1
        timer.spans.append((self._span, timer.step, self._start_ns, dt))
        return False


class StepTimer:
    """Host-side wall time per named phase, and each occurrence of it.

    ``totals``/``counts`` accumulate until ``reset()`` (the Trainer
    resets at every metrics fetch). ``spans`` is a ring of
    ``(span, step, start_ns, duration_ns)`` that ``reset()`` leaves
    alone: ``step`` is the iteration the caller last set on the timer,
    ``start_ns`` is ``time.time_ns()``. The ring holds the last
    ``RING_SPANS`` occurrences — at up to 16 spans an iteration, the
    last 1024 iterations and more. Durations come from ``clock_ns``
    (monotonic nanoseconds; a test that judges durations hands in one it
    controls).
    """

    RING_SPANS = 16 * 1024

    def __init__(self, clock_ns=time.perf_counter_ns):
        self.clock_ns = clock_ns
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.step = 0
        self.spans: collections.deque = collections.deque(
            maxlen=self.RING_SPANS)

    def phase(self, name: str, span: str | None = None) -> _Phase:
        """Context manager around one stretch. ``name`` keys ``totals``;
        ``span`` (default: the same) names the ring entry and the trace
        annotation — the dispatch is phase ``dispatch`` or ``compile``
        under the one span name ``train_step``."""
        return _Phase(self, name, span or name)

    def means(self) -> dict[str, float]:
        return {
            f"time_{k}_ms": 1000.0 * v / max(self.counts[k], 1)
            for k, v in self.totals.items()
        }

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()

    def dump(self, path: str, *, final_step: int) -> str | None:
        """Write the ring as a ``dtf-loop-timeline/1`` file (atomic
        rename). Returns the path, or None if the directory cannot be
        written: a forensic file must never take down the run."""
        doc = {
            "schema": TIMELINE_SCHEMA,
            "pid": os.getpid(),
            "clock": "time.time_ns",
            # What to add to ``start_ns`` to land on the profiler's
            # timeline (``profile_start_time`` + an event's offset).
            "offset_ns": 0,
            "final_step": int(final_step),
            "spans": [list(s) for s in self.spans],
        }
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                json.dump(doc, fh, separators=(",", ":"))
                fh.write("\n")
            os.replace(tmp, path)
        except OSError:
            return None
        return path


# An iteration is slow on the host past max(floor, factor x the median
# iteration of its block).
SLOW_FLOOR_MS = 100.0
SLOW_FACTOR = 10.0


def slow_iterations(spans: Iterable[tuple]) -> list[dict]:
    """Iterations of one block that were slow on the host.

    Groups ``spans`` (ring entries) by step and sums each iteration's
    time under spans other than ``DEVICE_WAITS``; an iteration is slow
    if that sum passes ``max(SLOW_FLOOR_MS, SLOW_FACTOR x the block's
    median)``. Each result names the step and every span of it in
    milliseconds, the waits included, so the reader sees what the host
    did and what it waited for.
    """
    by_step: dict[int, dict[str, int]] = {}
    for name, step, _, duration_ns in spans:
        per = by_step.setdefault(step, {})
        per[name] = per.get(name, 0) + duration_ns
    host_ns = {
        step: sum(d for n, d in per.items() if n not in DEVICE_WAITS)
        for step, per in by_step.items()}
    if not host_ns:
        return []
    median_ns = statistics.median(host_ns.values())
    limit_ns = max(SLOW_FLOOR_MS * 1e6, SLOW_FACTOR * median_ns)
    return [
        {"step": step,
         "host_ms": round(ns * 1e-6, 3),
         "block_median_ms": round(median_ns * 1e-6, 3),
         "spans_ms": {n: round(d * 1e-6, 3)
                      for n, d in by_step[step].items()}}
        for step, ns in host_ns.items() if ns > limit_ns]
