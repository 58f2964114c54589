"""Is the loop timeline on the profiler's clock?

    python3 benchmarks/tools/clock_check.py <cell> [<out.json>]

After a ``--trace 1`` run of the cell in this checkout: pairs every
``train_step`` span of the newest ``loop_timeline-*.json`` under
``.bench_out/<cell>/`` that began inside the traced stretch with the
host event of the same name and ordinal in the trace (the program's own
``TraceAnnotation``, entered right after the span's ``start_ns`` was
stamped), and prints how far apart they start and end, in microseconds:
profiler minus timeline. PERF.md §6 (PR 24) holds the reading; the
readers trust ``start_ns`` + the file's ``offset_ns`` as the trace's clock.
"""

from __future__ import annotations

import glob
import json
import os
import statistics
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.harness import loop_timeline, trace_reduce  # noqa: E402


def host_events(profile, name: str) -> list[tuple[float, float]]:
    """``(start_ns, duration_ns)`` of the host events called ``name``, in
    the trace's own times, by start."""
    return sorted(
        (float(e.start_ns), float(e.duration_ns))
        for plane in profile.planes if plane.name == trace_reduce.HOST_PLANE
        for line in plane.lines for e in line.events if e.name == name)


def _spread(values: list[float]) -> dict:
    q = (statistics.quantiles(values, n=4) if len(values) > 1
         else [values[0]] * 3)
    return {"median": statistics.median(values), "min": min(values),
            "max": max(values), "iqr": q[2] - q[0]}


def pair(spans: list, profile, name: str = "train_step") -> dict:
    t0 = loop_timeline.profile_start_ns(profile)
    events = host_events(profile, name)
    if t0 is None or not events:
        return {"pairs": 0, "why": "no profile_start_time or no host event "
                                   f"called {name!r} in the trace"}
    # the stretch in which the profiler recorded annotations, with half
    # an event's length of room for a clock that is a little off
    room = 0.5 * min(d for _, d in events)
    # the timeline's epoch nanoseconds as the trace's times: in whole
    # numbers first, a float holds 1.8e18 to 256 ns only
    mine = [(s - t0, d) for n, _, s, d in spans if n == name
            and events[0][0] - room <= s - t0 <= events[-1][0] + room]
    if len(mine) != len(events):
        return {"pairs": 0, "why": f"{len(events)} events in the trace, "
                f"{len(mine)} spans of the timeline begin among them"}
    starts = [(es - s) * 1e-3 for (es, _), (s, _) in zip(events, mine)]
    ends = [(es + ed - s - d) * 1e-3
            for (es, ed), (s, d) in zip(events, mine)]
    return {"pairs": len(mine), "start_diff_us": _spread(starts),
            "end_diff_us": _spread(ends)}


def main(argv) -> int:
    cell = argv[0]
    out = loop_timeline.out_dir(_ROOT, cell)
    files = sorted(glob.glob(os.path.join(out, "loop_timeline-*.json")),
                   key=os.path.getmtime)
    if not files:
        print(f"clock_check: no loop_timeline-*.json under {out}",
              file=sys.stderr)
        return 1
    with open(files[-1]) as fh:
        doc = json.load(fh)
    profile = trace_reduce.load(
        trace_reduce.find_xplane(os.path.join(out, "trace")))
    found = {"cell": cell, "timeline": os.path.basename(files[-1]),
             "timeline_bytes": os.path.getsize(files[-1]),
             "timeline_spans": len(doc["spans"]),
             # which of the program's span names the trace holds as
             # host events, and how many of each
             "host_events": {n: len(host_events(profile, n))
                             for n in sorted({s[0] for s in doc["spans"]})},
             **pair(doc["spans"], profile)}
    print(json.dumps(found, indent=1))
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
        with open(argv[1], "w") as fh:
            json.dump(found, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
