"""The window layers' attention kernels' share of their roofline: the
least time the chip could take for the operations and bytes those layers
need in one step (``benchmarks/flops/<family>.attention_kernel_work``'s
``window_*`` part, through its ``window_part``: pairs inside window,
diagonal and document only, whatever computes them; the larger of
operations / peak FLOP/s and bytes / peak bytes/s, forward and backward
each) divided by the device time per step of the kernels under the
program's ``attn_window`` scope (``harness/attn_scopes.py``). Where the
trace shows the forward kernel run again under ``model.remat`` it is in
the time, and its work is counted too."""

from benchmarks.harness import attn_scopes, loop_timeline, manifest

LAYER = "attention kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    window_s = attn_scopes.window_kernel_s(__file__, r)
    if not window_s or not r.trace.steps or r.attention_work is None:
        return None
    flops = manifest.load_family(loop_timeline.root_of(__file__), "flops",
                                 r.cell.config["flops"])
    if not hasattr(flops, "window_part"):
        return None
    work = flops.window_part(
        r.attention_work,
        recomputed_forward=attn_scopes.recomputes(window_s))
    least = sum(
        max(work[f"{part}_flops"] / r.peaks["bf16_flops_per_s"],
            work[f"{part}_bytes"] / r.peaks["hbm_bytes_per_s"])
        for part in ("forward", "backward"))
    spent = sum(window_s.values()) / r.trace.steps
    return 100.0 * least / spent if spent > 0 else None
