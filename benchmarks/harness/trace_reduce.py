"""From the profiler's trace to numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` (nothing but JAX). A device is a plane named
``/device:TPU:<n>``; its operations are the events of its ``XLA Ops``
line, named by HLO instruction. No device plane, or no operation on one,
is an error: there is no fallback to host events.

- busy: the union of the intervals in which an operation ran;
- window: first operation's start to last operation's end, per device;
- idle gaps: the window minus busy, each named by what the host was
  doing meanwhile (the host event that overlaps it most);
- time by category and by scope label (``hlo_scopes``), as *self* time:
  where events nest, the innermost gets the time, so sums equal busy;
- collective time (synchronous ones from the ops line, asynchronous ones
  as their start..done span on the ``Async XLA Ops`` line), and the part
  of it in which no other operation ran on the same device (exposed).

All values are averages over the devices found, in seconds.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from benchmarks.harness.hlo_scopes import COLLECTIVE_OPCODES

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # start..done spans of asynchronous ops
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


class TraceError(RuntimeError):
    pass


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


# ------------------------------------------------------- interval algebra --
def union(intervals: list) -> list:
    """Sorted, disjoint cover of ``[(start, end), ...]``."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(cover: list) -> float:
    return float(sum(e - s for s, e in cover))


def subtract(cover: list, holes: list) -> list:
    """``cover`` minus ``holes``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in cover:
        while j < len(holes) and holes[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(holes) and holes[k][0] < e:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: list) -> list:
    """Per event ``(start, end)``, the time no later-started event
    covers. ``events`` sorted by (start, -end). Sums to the union."""
    own = [0.0] * len(events)
    stack: list = []          # (end, index); top is innermost
    t = events[0][0] if events else 0.0

    def advance(to):
        nonlocal t
        if to > t:
            if stack:
                own[stack[-1][1]] += to - t
            t = to

    for i, (s, e) in enumerate(events):
        while stack and stack[-1][0] <= s:
            advance(stack[-1][0])
            stack.pop()
        advance(s)
        stack.append((e, i))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return own


# ----------------------------------------------------------------- result --
@dataclasses.dataclass
class DeviceReduction:
    busy_s: float
    window_s: float
    category_s: dict
    label_s: dict
    kernel_s: dict            # kernel kind -> (calls, seconds)
    collective_s: float
    collective_exposed_s: float
    gaps: list                # [(start_ns, end_ns)], longest first
    modules: int              # executions of the step's module seen


@dataclasses.dataclass
class TraceReduction:
    devices: int
    busy_s: float
    window_s: float
    category_s: dict
    label_s: dict
    kernel_s: dict
    collective_s: float
    collective_exposed_s: float
    idle_gaps: list           # [(host activity, seconds)], longest first
    steps: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def reduce_device(plane, scopes) -> DeviceReduction:
    line = _line(plane, OPS_LINE)
    if line is None:
        raise TraceError(f"plane {plane.name} has no '{OPS_LINE}' line "
                         f"(has: {[ln.name for ln in plane.lines]})")
    evs = sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns),
                   e.name) for e in line.events if e.duration_ns > 0),
                 key=lambda x: (x[0], -x[1]))
    if not evs:
        raise TraceError(f"no operation ran on {plane.name}")
    own = self_times([(s, e) for s, e, _ in evs])
    category_s: dict = {}
    label_s: dict = {}
    kernel_s: dict = {}
    collective, compute = [], []
    named: dict = {}          # an op recurs every step: look it up once
    for (s, e, name), dt in zip(evs, own):
        if name not in named:
            instr = scopes.find(name) if scopes else None
            named[name] = (
                scopes.category(instr, name) if scopes else "other",
                scopes.label(instr, name) if scopes else name,
                scopes.kernel_kind(instr) if instr is not None else "")
        cat, label, kind = named[name]
        category_s[cat] = category_s.get(cat, 0.0) + dt * 1e-9
        label_s[label] = label_s.get(label, 0.0) + dt * 1e-9
        if cat == "attn_kernel":
            n, sec = kernel_s.get(kind, (0, 0.0))
            kernel_s[kind] = (n + 1, sec + (e - s) * 1e-9)
        (collective if cat == "collective" else compute).append((s, e))
    # An asynchronous collective's time on the wire is its start..done
    # span on the async line; on the ops line only its two ends show.
    async_line = _line(plane, ASYNC_LINE)
    for e in (async_line.events if async_line is not None else ()):
        name = e.name.strip().lstrip("%")
        if name.startswith(COLLECTIVE_OPCODES) and e.duration_ns > 0:
            collective.append((float(e.start_ns),
                               float(e.start_ns + e.duration_ns)))
    busy = union([(s, e) for s, e, _ in evs])
    window = (busy[0][0], busy[-1][1])
    coll = union(collective)
    gaps = sorted(subtract([window], busy), key=lambda g: g[0] - g[1])
    mods = _line(plane, MODULES_LINE)
    return DeviceReduction(
        busy_s=measure(busy) * 1e-9,
        window_s=(window[1] - window[0]) * 1e-9,
        category_s=category_s, label_s=label_s, kernel_s=kernel_s,
        collective_s=measure(coll) * 1e-9,
        collective_exposed_s=measure(subtract(coll, union(compute))) * 1e-9,
        gaps=gaps[:10],
        modules=sum(1 for _ in mods.events) if mods is not None else 0)


def _host_events(profile) -> list:
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    out.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns), e.name))
    return out


def name_gap(gap, host_events) -> str:
    """What the host was doing during a device idle gap: the host event
    that overlaps the gap most, ties to the shorter (more specific)."""
    s, e = gap
    best, best_key = "nothing recorded on the host", (0.0, 0.0)
    for hs, he, name in host_events:
        overlap = min(e, he) - max(s, hs)
        if overlap <= 0:
            continue
        key = (overlap, -(he - hs))
        if key > best_key:
            best, best_key = name, key
    return re.sub(r"\s+", " ", best)[:80]


def _mean(values) -> float:
    values = list(values)
    return sum(values) / len(values)


def _mean_dicts(dicts) -> dict:
    keys = {k for d in dicts for k in d}
    return {k: _mean(d.get(k, 0.0) for d in dicts) for k in keys}


def reduce(profile, scopes=None) -> TraceReduction:
    planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        raise TraceError(
            "the trace has no device plane (planes: "
            f"{[p.name for p in profile.planes]})")
    per = [reduce_device(p, scopes) for p in planes]
    host = _host_events(profile)
    named: dict = {}
    for g in per[0].gaps:         # name the gaps of the first device
        what = name_gap(g, host)
        named[what] = named.get(what, 0.0) + (g[1] - g[0]) * 1e-9
    kinds = {k for d in per for k in d.kernel_s}
    return TraceReduction(
        devices=len(per),
        busy_s=_mean(d.busy_s for d in per),
        window_s=_mean(d.window_s for d in per),
        category_s=_mean_dicts([d.category_s for d in per]),
        label_s=_mean_dicts([d.label_s for d in per]),
        kernel_s={k: (_mean(d.kernel_s.get(k, (0, 0.0))[0] for d in per),
                      _mean(d.kernel_s.get(k, (0, 0.0))[1] for d in per))
                  for k in kinds},
        collective_s=_mean(d.collective_s for d in per),
        collective_exposed_s=_mean(d.collective_exposed_s for d in per),
        idle_gaps=sorted(named.items(), key=lambda kv: -kv[1])[:10],
        steps=max(d.modules for d in per))


def breakdown(red: TraceReduction) -> dict:
    ops = sorted(red.label_s.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps]}


# ------------------------------------------------------------- inspection --
def describe(profile, events_per_line: int = 4) -> str:
    """The structure of a trace, for reading one by hand."""
    out = []
    for plane in profile.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:events_per_line]:
                stats = ", ".join(f"{k}={str(v)[:50]}"
                                  for k, v in list(e.stats)[:8])
                out.append(f"    {e.name[:70]!r} start={e.start_ns:.0f} "
                           f"dur={e.duration_ns:.0f} {stats}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(load(sys.argv[1])))
