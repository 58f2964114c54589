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
  * ``CompileLog``     — every trace, lowering, compile and cache load of
                         the process, from JAX's own monitoring events
                         (``compile_log()`` is the one set of listeners).
  * ``process_age_s``  — seconds since the OS started this process.

Step-window traces during training: ``--set train.profile_start=N
--set train.profile_stop=M`` via ProfileHook (train/hooks.py). Span names,
the timeline file, the compile log and the ``slow_step`` and ``recompile``
events: docs/OBSERVABILITY.md "Loop timeline".
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import statistics
import threading
import time
from typing import Iterable, Iterator

import jax
import jax.monitoring

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


class _Span:
    """One stretch that lands in the ring and in a profile and in no
    total; what ``StepTimer.span`` returns."""

    __slots__ = ("_timer", "_span", "_annotation", "_start_ns", "_t0")

    def __init__(self, timer: "StepTimer", span: str):
        self._timer = timer
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
        timer = self._timer
        timer.spans.append((self._span, timer.step, self._start_ns,
                            timer.clock_ns() - self._t0))
        return False


class _Phase(_Span):
    """One occurrence of a phase; what ``StepTimer.phase`` returns."""

    __slots__ = ("_name",)

    def __init__(self, timer: "StepTimer", name: str, span: str):
        self._timer = timer  # (no super() call: made ten times an iteration)
        self._name = name
        self._span = span

    def __exit__(self, *exc) -> bool:
        self._annotation.__exit__(*exc)
        timer, name = self._timer, self._name
        dt = timer.clock_ns() - self._t0
        timer.totals[name] = timer.totals.get(name, 0.0) + dt * 1e-9
        timer.counts[name] = timer.counts.get(name, 0) + 1
        timer.spans.append((self._span, timer.step, self._start_ns, dt))
        return False


STARTUP_PREFIX = "startup:"


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

    ``startup`` is the restart's own timeline: the ring as it stood when
    ``freeze_startup`` was called (the Trainer calls it when its first
    dispatch returns), kept beside the ring so that a run of a million
    steps still dumps it. ``compiles`` is the process's compile log;
    what a timer reports of it is what was logged since the timer was
    made.
    """

    RING_SPANS = 16 * 1024

    def __init__(self, clock_ns=time.perf_counter_ns):
        self.clock_ns = clock_ns
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self.step = 0
        self.spans: collections.deque = collections.deque(
            maxlen=self.RING_SPANS)
        self.startup: list[tuple] = []
        self.compiles = compile_log()
        self._made_ns = time.time_ns()
        self._compiles_base = self.compiles.counts()

    def phase(self, name: str, span: str | None = None) -> _Phase:
        """Context manager around one stretch. ``name`` keys ``totals``;
        ``span`` (default: the same) names the ring entry and the trace
        annotation — the dispatch is phase ``dispatch`` or ``compile``
        under the one span name ``train_step``."""
        return _Phase(self, name, span or name)

    def span(self, span: str) -> _Span:
        """Context manager around one stretch that no total counts: a
        ring entry and a trace annotation, and nothing in ``totals`` or
        ``means()`` (the Trainer's ``startup:*`` stretches, whose wall
        the goodput ledger charges as one bucket)."""
        return _Span(self, span)

    def means(self) -> dict[str, float]:
        return {
            f"time_{k}_ms": 1000.0 * v / max(self.counts[k], 1)
            for k, v in self.totals.items()
        }

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()

    def freeze_startup(self, step: int) -> dict[str, float]:
        """Keep what the ring holds now as ``startup`` and give its
        seconds per span name. A ``startup:*`` entry gets ``step`` (the
        step the loop started from: such a span may have closed before a
        restore said which)."""
        self.startup = [
            (name, step if name.startswith(STARTUP_PREFIX) else at, start, dur)
            for name, at, start, dur in self.spans]
        phases_s: dict[str, float] = {}
        for name, _, _, dur in self.startup:
            phases_s[name] = phases_s.get(name, 0.0) + dur * 1e-9
        return phases_s

    def compile_summary(self) -> dict:
        """The compile log's counters since this timer was made, and the
        two sums an operator asks for — Python time tracing and lowering
        (``trace_lower_s``), seconds in XLA or loading from the cache
        (``xla_s``) — each split by whether the event began under one of
        ``startup``'s spans (``inside``) or between them (``outside``:
        the caller's own work while it held the timer's owner)."""
        now = self.compiles.counts()
        out: dict = {k: round(v - self._compiles_base[k], 6)
                     for k, v in now.items()}
        halves = {"trace_lower_s": {"inside": 0.0, "outside": 0.0},
                  "xla_s": {"inside": 0.0, "outside": 0.0}}
        for _, _, start_ns, duration_ns, _, xla_ns in self.compile_entries():
            where = ("inside" if any(s <= start_ns < s + d
                                     for _, _, s, d in self.startup)
                     else "outside")
            halves["xla_s"][where] += xla_ns * 1e-9
            halves["trace_lower_s"][where] += (duration_ns - xla_ns) * 1e-9
        for key, half in halves.items():
            out[key] = {k: round(v, 6) for k, v in half.items()}
        return out

    def compile_entries(self) -> list[tuple]:
        """The compile log's entries since this timer was made (as many
        of them as the log still holds)."""
        return self.compiles.since(self._made_ns)

    def dump(self, path: str, *, final_step: int) -> str | None:
        """Write the ring as a ``dtf-loop-timeline/1`` file (atomic
        rename), with the startup spans and the compile log beside it.
        Returns the path, or None if the directory cannot be written: a
        forensic file must never take down the run."""
        doc = {
            "schema": TIMELINE_SCHEMA,
            "pid": os.getpid(),
            "clock": "time.time_ns",
            # What to add to ``start_ns`` to land on the profiler's
            # timeline (``profile_start_time`` + an event's offset).
            "offset_ns": 0,
            "final_step": int(final_step),
            "spans": [list(s) for s in self.spans],
            "startup": [list(s) for s in self.startup],
            "compiles": [list(e) for e in self.compile_entries()],
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


# JAX's own monitoring events (jax/_src/dispatch.py ``log_elapsed_time``
# sends each as a time span with ``time.time()`` start and end and the
# function's name), and what the log calls them.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "xla",
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """Every trace, lowering and backend compile of this process, as JAX
    reports them: running counters, and the outermost events as
    ``(kind, fun_name, start_ns, duration_ns, cache_hit, xla_ns)`` in a
    bounded list, in the order they ended.

    ``kind`` is ``trace`` (Python tracing a function to a jaxpr),
    ``lower`` (jaxpr to an MLIR module) or ``xla`` (the backend's
    ``compile_or_get_cached``). ``start_ns`` is epoch nanoseconds, the
    ring's clock. ``cache_hit`` is None except on ``xla``: True where the
    persistent cache gave the executable (JAX's ``cache_hits`` event,
    sent inside the span: the whole span is then the load, key, read and
    deserialization, and its ``cache_retrieval_time_sec`` a part of it
    that is counted nowhere else), False where JAX compiled and went on
    to write an entry (``cache_misses``), None where it compiled and the
    cache took no notice: switched off, or a program under its
    thresholds of compile time and size, which compiles on every start.

    Events nest: a ``jnp`` function traced inside another's trace (a
    BERT start sends 12,700 traces, over four thousand of them inside
    one trace of the step), a constant's small compile inside a trace.
    JAX says when each begins too, so every thread has its stack of
    open events: the counters count each event's own time, its span
    less the events inside it, so that no second is counted twice and
    each is counted for the innermost thing that ran; the list takes
    the outermost only, whose ``xla_ns`` is the backend time inside it
    (all of an ``xla`` entry, the nested compiles of a ``trace``; the
    rest of its duration is tracing or lowering).

    The listeners run where JAX compiles, never in an iteration that does
    not. ``register`` adds them to JAX for the life of the process;
    ``compile_log()`` does so once.
    """

    LOG_ENTRIES = 4096

    def __init__(self):
        self.entries: collections.deque = collections.deque(
            maxlen=self.LOG_ENTRIES)
        self.logged = 0      # events ever counted: "anything new?" is one compare
        self._counts = {
            "traces": 0, "trace_s": 0.0, "lower_s": 0.0,
            "xla_compiles": 0, "xla_compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0, "cache_load_s": 0.0}
        self._lock = threading.Lock()
        # Per thread: ``stack``, one ``[inner_ns, xla_ns]`` per event that
        # has begun and not ended; ``cache_hit``, what the cache said of
        # the compile the thread is in the middle of.
        self._thread = threading.local()

    def register(self) -> "CompileLog":
        jax.monitoring.register_scalar_listener(self._on_begin)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_time_span_listener(self._on_span)
        return self

    def _stack(self) -> list:
        try:
            return self._thread.stack
        except AttributeError:
            self._thread.stack = []
            return self._thread.stack

    def _on_begin(self, event: str, value: float, **_) -> None:
        # ``log_elapsed_time`` sends an event's name as a scalar (its
        # start time) when it begins, and as a time span when it ends.
        if event in COMPILE_EVENTS:
            self._stack().append([0, 0])

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._thread.cache_hit = True
        elif event == CACHE_MISS_EVENT:
            self._thread.cache_hit = False

    def _on_span(self, event: str, start: float, end: float,
                 fun_name: str = "", **_) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        start_ns = int(start * 1e9)
        duration_ns = max(int(end * 1e9) - start_ns, 0)
        stack = self._stack()
        # (An event that began before the listeners were there has no
        # frame: it holds nothing that was seen.)
        inner_ns, xla_ns = stack.pop() if stack else (0, 0)
        cache_hit = None
        if kind == "xla":
            xla_ns = duration_ns
            cache_hit = getattr(self._thread, "cache_hit", None)
            self._thread.cache_hit = None
        if stack:
            stack[-1][0] += duration_ns
            stack[-1][1] += xla_ns
        own_s = max(duration_ns - inner_ns, 0) * 1e-9
        with self._lock:
            self.logged += 1
            if not stack:
                self.entries.append((kind, str(fun_name), start_ns,
                                     duration_ns, cache_hit, xla_ns))
            c = self._counts
            if kind == "trace":
                c["traces"] += 1
                c["trace_s"] += own_s
            elif kind == "lower":
                c["lower_s"] += own_s
            elif cache_hit:
                c["cache_hits"] += 1
                c["cache_load_s"] += own_s
            else:
                c["xla_compiles"] += 1
                c["xla_compile_s"] += own_s
                c["cache_misses"] += cache_hit is False

    def counts(self) -> dict:
        with self._lock:
            return dict(self._counts)

    def since(self, t_ns: int) -> list[tuple]:
        """The entries that ended at ``t_ns`` (epoch nanoseconds) or
        later, oldest first; as many of them as the list still holds."""
        with self._lock:
            out = []
            for entry in reversed(self.entries):
                if entry[2] + entry[3] < t_ns:
                    break
                out.append(entry)
        return out[::-1]


_COMPILE_LOG: CompileLog | None = None
_COMPILE_LOG_LOCK = threading.Lock()


def compile_log() -> CompileLog:
    """The process's one compile log, its listeners registered with JAX
    on the first call (JAX keeps listeners for the life of the process,
    so there is one set however many timers a process makes)."""
    global _COMPILE_LOG
    with _COMPILE_LOG_LOCK:
        if _COMPILE_LOG is None:
            _COMPILE_LOG = CompileLog().register()
        return _COMPILE_LOG


def process_age_s() -> float | None:
    """Seconds since the OS started this process, by the OS's own
    record: ``/proc/self/stat``'s ``starttime`` (clock ticks after boot)
    against ``CLOCK_BOOTTIME``. None where the OS does not say."""
    try:
        with open("/proc/self/stat") as fh:
            # The command's name may hold spaces and brackets: the fields
            # after it count from the last ")", field 3 first.
            fields = fh.read().rsplit(")", 1)[1].split()
        started_s = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started_s
    except (OSError, ValueError, IndexError, AttributeError):
        return None


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
