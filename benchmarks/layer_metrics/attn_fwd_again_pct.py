"""Share of device busy time in attention forward kernels that the
backward pass runs again: Mosaic custom calls under a ``jit(_flash_fwd*)``
wrapper whose HLO ``op_name`` lies under ``rematted_computation``
(``harness/attn_passes.py``). A layer under ``model.remat`` that keeps the
kernel's output and logsumexp from its first forward pass reads 0.0; one
that keeps nothing reads the forward kernel's whole time once more. A
part of ``attn_kernel_pct``; a program that runs no attention kernel
gives nothing."""

from benchmarks.harness import attn_passes, loop_timeline

LAYER = "attention kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    kernel_s = attn_passes.attention_kernel_s(__file__, r)
    if not kernel_s or not r.trace.busy_s:
        return None
    return 100.0 * attn_passes.forward_again_s(kernel_s) / r.trace.busy_s
