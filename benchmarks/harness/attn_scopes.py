"""Device seconds of the attention kernels by the kind of layer that
called them, for the readers of the window layers' metrics.

``trace_reduce`` labels every Mosaic call under a ``jit(_flash_*)``
wrapper ``attn_kernel:<wrapper>`` and drops the program's scope, so the
run's own labels cannot tell a window layer's call from a global
layer's. The program names the two modules apart (``layerN/attn`` and
``layerN/attn_window``, models/lfm2.py), and the compiled step's HLO
keeps that path in each call's ``op_name``. ``window_kernel_s`` reduces
the run's trace once more, from the files the runner left beside it,
with the calls under ``attn_window`` labelled
``attn_kernel:attn_window/<wrapper>`` and the forward pass that
``model.remat`` runs again ``attn_kernel:attn_window/again/<wrapper>``.
A program without the scope has no such label and the readers give
``None``.
"""

from __future__ import annotations

import functools
import os

from benchmarks.harness import hlo_scopes, loop_timeline, trace_reduce

WINDOW_SCOPE = "attn_window"
REMAT = "rematted_computation"
PREFIX = f"attn_kernel:{WINDOW_SCOPE}/"


class _WindowScopes(hlo_scopes.HloScopes):
    def label(self, instr, event_name=""):
        label = super().label(instr, event_name)
        if (instr is None or not label.startswith("attn_kernel:")
                or f"/{WINDOW_SCOPE}/" not in instr.op_name):
            return label
        again = "again/" if f"/{REMAT}/" in instr.op_name else ""
        return PREFIX + again + label.partition(":")[2]


@functools.lru_cache(maxsize=2)
def _reduce(out: str, pid: int) -> dict | None:
    try:
        with open(os.path.join(out, "step.hlo.txt")) as fh:
            scopes = _WindowScopes(fh.read())
        profile = trace_reduce.load(
            trace_reduce.find_xplane(os.path.join(out, "trace")))
    except (OSError, trace_reduce.TraceError):
        return None
    return trace_reduce.reduce(profile, scopes).label_s


def window_kernel_s(reader_file: str, records) -> dict | None:
    """``{label: device seconds}`` of the window layers' kernels in this
    run's traced stretch (mean over devices), ``{}`` where the program
    has no such scope; None without a trace."""
    if records.trace is None:
        return None
    label_s = _reduce(
        loop_timeline.out_dir(loop_timeline.root_of(reader_file),
                              records.cell.name), os.getpid())
    if label_s is None:
        return None
    return {k: v for k, v in label_s.items() if k.startswith(PREFIX)}


def recomputes(window_s: dict) -> bool:
    """Whether the trace shows the window layers' forward kernel run
    again inside the backward pass."""
    return any(k.startswith(PREFIX + "again/") for k in window_s)
