"""The program's own record of its loop, as per-layer readers see it.

The train loop's recorder (``core/profiling.StepTimer``) keeps every
stretch of every iteration as ``(span, step, start_ns, duration_ns)`` and
the loop writes the ring as ``loop_timeline-<pid>.json`` (schema
``dtf-loop-timeline/1``) into ``trace.dump_dir`` when it stops. The
runner sets that to ``.bench_out/<cell>/``, and the run ends by SIGTERM
at the window's close, so after every run the file of this process holds
the window: its last step is the loop's last step, and the window is the
``window["steps"]`` iterations before it.

A program without the recorder (this PR's parent) leaves no file; every
function here then gives ``None``, and so does every reader built on it.
Readers are called unguarded by the runner: ``reader`` wraps one so that
nothing it meets can end the run.

Span names (``docs/OBSERVABILITY.md`` "Loop timeline"): ``infeed``,
``backpressure``, ``train_step``, ``metrics_fetch``, ``bookkeeping``,
``snapshot``, ``rollback``, ``hook:<Class>``. In a benchmark run the
program's hooks arrive wrapped, so they are all ``hook:TimedHook``;
``hook:WindowHook`` is the instrument and belongs to no sum.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import traceback

from benchmarks.harness import trace_reduce

SCHEMA = "dtf-loop-timeline/1"
OUT_DIR = ".bench_out"            # runner.OUT_DIR: <root>/.bench_out/<cell>/
INSTRUMENT = "hook:WindowHook"
TASK_PLANE = "Task Environment"   # carries the profile's epoch start


def reader(read):
    """A reader that gives ``None`` where anything is missing or wrong,
    and says so on stderr: the runner calls ``read()`` unguarded."""

    @functools.wraps(read)
    def guarded(records):
        try:
            return read(records)
        except Exception:  # the boundary: one metric less, never a run less
            print(f"[bench] reader {read.__module__} found nothing to read:\n"
                  + traceback.format_exc(limit=3), file=sys.stderr, flush=True)
            return None

    return guarded


def root_of(reader_file: str) -> str:
    """``<root>/benchmarks/layer_metrics/<reader>.py`` -> ``<root>``."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))


def out_dir(root: str, cell_name: str) -> str:
    return os.path.join(root, OUT_DIR, cell_name)


def load(root: str, cell_name: str) -> dict | None:
    """This process's timeline file of the cell, or None."""
    path = os.path.join(out_dir(root, cell_name),
                        f"loop_timeline-{os.getpid()}.json")
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    if doc.get("schema") != SCHEMA or not isinstance(doc.get("spans"), list):
        return None
    return doc


def window_spans(doc: dict | None, window: dict) -> tuple[list, int] | None:
    """``(spans, steps)`` of the window's iterations, in the order they
    ran: steps ``(final_step - steps, final_step]``. None if the window's
    length is unknown or the ring no longer holds its first iteration."""
    steps = int(window.get("steps") or 0) if doc else 0
    if steps <= 0:
        return None
    last = int(doc["final_step"])
    spans = [s for s in doc["spans"] if last - steps < s[1] <= last]
    if not spans or min(s[1] for s in spans) != last - steps + 1:
        return None
    return spans, steps


def of_run(reader_file: str, records) -> tuple[list, int] | None:
    """The window's spans for the run a reader was handed."""
    return window_spans(
        load(root_of(reader_file), records.cell.name), records.window)


def sync_bubbles(spans: list) -> list[tuple[int, int, int]]:
    """``(start_ns, end_ns, instrument_ns)`` per full sync of the loop:
    from the end of a ``metrics_fetch`` (the host knows the device queue
    is empty) to the end of the next ``train_step`` (it holds work
    again). The last number is the part of it spent in the benchmark's
    own hook. A fetch that no dispatch follows (the loop's last) opens no
    bubble."""
    out = []
    start, instrument = None, 0
    for name, _, start_ns, duration_ns in spans:
        if name == "metrics_fetch":
            start, instrument = start_ns + duration_ns, 0
        elif start is not None and name == INSTRUMENT:
            instrument += duration_ns
        elif start is not None and name == "train_step":
            out.append((start, start_ns + duration_ns, instrument))
            start = None
    return out


def fetches(spans: list) -> list[tuple[int, int]]:
    """``(start_ns, end_ns)`` of every ``metrics_fetch`` span: the host
    waits for the newest step. Where a sync bubble opens, a fetch ends."""
    return [(s, s + d) for n, _, s, d in spans if n == "metrics_fetch"]


def total_ns(spans: list, *, names: tuple = (), prefix: str | None = None
             ) -> int:
    """Time under the spans called one of ``names`` or starting with
    ``prefix``; the instrument's never."""
    return sum(d for n, _, _, d in spans if n != INSTRUMENT and (
        n in names or (prefix is not None and n.startswith(prefix))))


def device_gaps(profile) -> tuple[list, float] | None:
    """``(gaps, window_ns)`` of the first device: its ten longest idle
    gaps (``trace_reduce.reduce_device``), sorted and disjoint, in the
    trace's own times, and its traced window. None for a trace without a
    device plane."""
    plane = next((p for p in profile.planes
                  if trace_reduce.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        return None
    device = trace_reduce.reduce_device(plane, None)
    return trace_reduce.union(device.gaps), device.window_s * 1e9


def idle_inside(gaps: list, intervals: list) -> float:
    """How much of ``gaps`` (sorted, disjoint) lies inside ``intervals``."""
    return trace_reduce.measure(gaps) - trace_reduce.measure(
        trace_reduce.subtract(gaps, trace_reduce.union(intervals)))


def profile_start_ns(profile) -> int | None:
    """The epoch nanosecond that a trace's event times count from (the
    ``profile_start_time`` stat of its ``Task Environment`` plane)."""
    for plane in profile.planes:
        if plane.name == TASK_PLANE:
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


def idle_pct_inside(reader_file: str, records, intervals_of) -> float | None:
    """The first device's ten longest idle gaps in the traced stretch,
    cut to ``intervals_of(spans)`` (epoch nanoseconds of the window's
    spans, moved onto the trace's clock by the file's ``offset_ns`` and
    the trace's ``profile_start_time``), as a share of that device's
    traced window. None without a trace, a timeline or a device plane."""
    if records.trace is None:
        return None
    root = root_of(reader_file)
    doc = load(root, records.cell.name)
    found = window_spans(doc, records.window)
    if found is None:
        return None
    profile = trace_reduce.load(trace_reduce.find_xplane(
        os.path.join(out_dir(root, records.cell.name), "trace")))
    t0 = profile_start_ns(profile)
    device = device_gaps(profile)
    if t0 is None or device is None:
        return None
    gaps, window_ns = device
    shift = int(doc.get("offset_ns", 0)) - t0   # epoch -> the trace's times
    inside = [(start + shift, end + shift)
              for start, end in intervals_of(found[0])]
    return 100.0 * idle_inside(gaps, inside) / window_ns
