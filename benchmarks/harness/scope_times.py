"""What the per-layer readers of a model's own mechanisms share: device
seconds by the program's named scopes, and the program's counters.

**Scopes.** ``trace_reduce`` keeps device self-time by label,
``<kind>:<phase>/<scope path>``, the path folded to three levels
(``hlo_scopes.scope_of``): ``custom-call:fwd/layerN/moe/experts``. Under
``model.remat`` the forward pass that the backward pass runs again
carries one more level, ``rematted_computation``, so in the run's own
labels its operations fold to ``...:bwd/rematted_computation/layerN/<module>``
and the part inside the module is cut off. A whole module's time needs no
more than that (``seconds`` over ``records.trace.label_s``). A part's
time does: ``part_label_s`` reduces the run's trace once more, from the
files the runner left beside it, with that level taken out of the path
and written as the phase ``again`` (``custom-call:again/layerN/moe/dispatch``),
so every pass of a part is read from the device and none is worked out.

**Grouped products.** XLA's TPU lowering of ``jax.lax.ragged_dot`` emits
its own Mosaic kernel and names the call ``ragged-dot-<mode>``, dropping
the program's scope, and the same name serves the forward pass, the
re-run forward pass and the backward pass: the label
``custom-call:ragged-dot-none``. ``ragged_dot_seconds`` reads it; a model
whose only ragged products are its expert layers' owns all of it.

**Counters.** The loop fetches the step's metrics every ``log_interval``
steps and ``LoggingHook`` writes them as ``train_step`` events; the
flight recorder holds the newest of them and dumps its ring when the
window's SIGTERM stops the job, as ``flightrec-<pid>.json`` beside the
trace. A program without the counter, or a run without the dump, gives
``None``.
"""

from __future__ import annotations

import functools
import json
import os

from benchmarks.harness import hlo_scopes, loop_timeline, trace_reduce

REMAT = "rematted_computation"
AGAIN = "again"                   # the phase of the re-run forward pass
RAGGED_DOT = "custom-call:ragged-dot"
PRODUCT_KINDS = ("custom-call", "convolution", "dot")


class _PartScopes(hlo_scopes.HloScopes):
    """Labels that keep a module's parts in the re-run forward pass."""

    def label(self, instr, event_name=""):
        label = super().label(instr, event_name)
        if instr is None or REMAT not in label:
            return label
        kind = label.partition(":")[0]
        phase, _, path = hlo_scopes.scope_of(
            instr.op_name.replace(f"/{REMAT}/", "/")).partition("/")
        return f"{kind}:{AGAIN}/{path if phase in ('fwd', 'bwd') else phase}"


@functools.lru_cache(maxsize=2)
def _reduce_again(out: str, pid: int) -> dict | None:
    try:
        with open(os.path.join(out, "step.hlo.txt")) as fh:
            scopes = _PartScopes(fh.read())
        profile = trace_reduce.load(
            trace_reduce.find_xplane(os.path.join(out, "trace")))
    except (OSError, trace_reduce.TraceError):
        return None
    return trace_reduce.reduce(profile, scopes).label_s


def part_label_s(reader_file: str, records) -> dict | None:
    """Device seconds by label with the parts of the re-run forward pass
    kept apart, from this run's trace and step HLO; None without them."""
    if records.trace is None:
        return None
    return _reduce_again(
        loop_timeline.out_dir(loop_timeline.root_of(reader_file),
                              records.cell.name), os.getpid())


def _parse(label: str) -> tuple[str, list]:
    kind, _, scope = label.partition(":")
    return kind, scope.split("/")


def seconds(label_s: dict, module: str, parts: tuple | None = None, *,
            kinds: tuple | None = None) -> float:
    """Device seconds under ``<module>`` (a scope name such as ``moe``),
    or under ``<module>/<part>`` for ``part`` in ``parts`` (from
    ``part_label_s``: the run's own labels cut the re-run pass's parts
    off). ``kinds`` keeps only labels of those kinds (an HLO opcode, or
    ``convolution`` / ``dot`` for a fusion around one)."""
    total = 0.0
    for label, sec in label_s.items():
        kind, path = _parse(label)
        if module not in path or (kinds is not None and kind not in kinds):
            continue
        at = path.index(module)
        if parts is None or (len(path) > at + 1 and path[at + 1] in parts):
            total += sec
    return total


def ragged_dot_seconds(label_s: dict) -> float:
    """Device seconds in XLA's grouped-matmul kernels, every pass."""
    return sum(sec for label, sec in label_s.items()
               if label.startswith(RAGGED_DOT))


def recomputes(label_s: dict, module: str) -> bool:
    """Whether the trace shows ``module``'s forward pass run again inside
    the backward pass (``model.remat``)."""
    return any(module in path and (REMAT in path or AGAIN in path)
               for _, path in map(_parse, label_s))


def window_counters(reader_file: str, records) -> list[dict] | None:
    """The ``metrics`` of the ``train_step`` events the window fetched,
    oldest first, from this process's flight-recorder dump."""
    path = os.path.join(
        loop_timeline.out_dir(loop_timeline.root_of(reader_file),
                              records.cell.name),
        f"flightrec-{os.getpid()}.json")
    try:
        with open(path) as fh:
            events = json.load(fh)["events"]
    except (OSError, ValueError, KeyError):
        return None
    steps = [(int(e["step"]), e["metrics"]) for e in events
             if e.get("kind") == "train_step" and e.get("metrics")
             and e.get("step") is not None]
    if not steps:
        return None
    last = max(s for s, _ in steps)
    n = int(records.window.get("steps") or 0)
    return [m for s, m in sorted(steps, key=lambda x: x[0])
            if n <= 0 or s > last - n] or None


def mean_counter(reader_file: str, records, name: str) -> float | None:
    fetched = window_counters(reader_file, records)
    values = [float(m[name]) for m in fetched or () if name in m]
    return sum(values) / len(values) if values else None
