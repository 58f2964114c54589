"""Share of device busy time in the window layers' attention kernels:
Mosaic custom calls under a ``jit(_flash_*)`` wrapper whose HLO
``op_name`` lies under the program's ``attn_window`` scope
(``sliding_attention`` layers; ``harness/attn_scopes.py``), every pass
read from the trace: forward, the forward pass re-run under
``model.remat``, and backward. A part of ``attn_kernel_pct``."""

from benchmarks.harness import attn_scopes, loop_timeline

LAYER = "attention kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    window_s = attn_scopes.window_kernel_s(__file__, r)
    if not window_s or not r.trace.busy_s:
        return None
    return 100.0 * sum(window_s.values()) / r.trace.busy_s
