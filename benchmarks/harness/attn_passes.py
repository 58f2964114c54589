"""Device seconds of the attention kernels with the forward kernel that
``model.remat`` runs again told apart, for ``attn_fwd_again_pct``.

``trace_reduce`` labels every Mosaic call under a ``jit(_flash_*)``
wrapper ``attn_kernel:<wrapper>``, whatever pass it belongs to. A layer
under ``jax.checkpoint`` runs its forward pass once more inside the
backward pass, and the compiled step's HLO keeps that in each call's
``op_name`` (``.../checkpoint/rematted_computation/layerN/attn/
jit(_flash_fwd)/pallas_call``). ``attention_kernel_s`` reduces the run's
trace once more, from the files the runner left beside it, with those
forward calls labelled ``attn_kernel:again/<wrapper>``: the labelling
``attn_scopes`` does for the window layers, for every attention call.
"""

from __future__ import annotations

import functools
import os

from benchmarks.harness import hlo_scopes, loop_timeline, trace_reduce
from benchmarks.harness.scope_times import AGAIN, REMAT

KERNELS = "attn_kernel:"
FORWARD = KERNELS + "_flash_fwd"
FORWARD_AGAIN = f"{KERNELS}{AGAIN}/"


class _PassScopes(hlo_scopes.HloScopes):
    def label(self, instr, event_name=""):
        label = super().label(instr, event_name)
        if (instr is None or not label.startswith(FORWARD)
                or f"/{REMAT}/" not in instr.op_name):
            return label
        return FORWARD_AGAIN + label.partition(":")[2]


@functools.lru_cache(maxsize=2)
def _reduce(out: str, pid: int) -> dict | None:
    try:
        with open(os.path.join(out, "step.hlo.txt")) as fh:
            scopes = _PassScopes(fh.read())
        profile = trace_reduce.load(
            trace_reduce.find_xplane(os.path.join(out, "trace")))
    except (OSError, trace_reduce.TraceError):
        return None
    return trace_reduce.reduce(profile, scopes).label_s


def attention_kernel_s(reader_file: str, records) -> dict | None:
    """``{label: device seconds}`` of the attention kernels in this run's
    traced stretch (mean over devices), ``{}`` for a program that runs
    none; None without a trace."""
    if records.trace is None:
        return None
    label_s = _reduce(
        loop_timeline.out_dir(loop_timeline.root_of(reader_file),
                              records.cell.name), os.getpid())
    if label_s is None:
        return None
    return {k: v for k, v in label_s.items() if k.startswith(KERNELS)}


def forward_again_s(kernel_s: dict) -> float:
    """Seconds of the forward kernel run again inside the backward pass."""
    return sum(v for k, v in kernel_s.items() if k.startswith(FORWARD_AGAIN))
