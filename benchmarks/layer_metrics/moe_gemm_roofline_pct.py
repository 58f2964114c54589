"""The grouped expert products' share of their roofline: the least time
the chip could take for their operations and bytes in one step
(``benchmarks/flops/<family>.moe_gemm_work``, fed the local assignments
the run COUNTED, ``moe_local_assignments``, not their expectation; the
larger of operations / peak FLOP/s and bytes / peak bytes/s, forward and
backward each, times the expert layers) divided by the device time per
step of the products themselves: XLA's ``ragged-dot-*`` kernels, and any
custom call, convolution or dot under ``moe/experts``. XLA's kernel
carries one name in every pass, so where the trace shows the forward
pass run again (``model.remat``) the re-run products are in the time,
and their operations are counted too (``recomputed_forward``)."""

from benchmarks.harness import loop_timeline, manifest, scope_times

LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    if r.trace is None or not r.trace.steps:
        return None
    local = scope_times.mean_counter(__file__, r, "moe_local_assignments")
    flops = manifest.load_family(loop_timeline.root_of(__file__), "flops",
                                 r.cell.config["flops"])
    if local is None or not hasattr(flops, "moe_gemm_work"):
        return None
    h = {**r.cell.config["published"], **r.cell.config["reference_hparams"]}
    layers = len(h["layer_types"]) - int(h["num_dense_layers"])
    again = scope_times.recomputes(r.trace.label_s, "moe")
    work = flops.moe_gemm_work(local, h, recomputed_forward=again)
    least = sum(
        max(work[f"{part}_flops"] / r.peaks["bf16_flops_per_s"],
            work[f"{part}_bytes"] / r.peaks["hbm_bytes_per_s"])
        for part in ("forward", "backward")) * layers
    label_s = scope_times.part_label_s(__file__, r)
    if label_s is None:
        return None
    spent = (scope_times.ragged_dot_seconds(label_s)
             + scope_times.seconds(
                 label_s, "moe", ("experts",),
                 kinds=scope_times.PRODUCT_KINDS)) / r.trace.steps
    return 100.0 * least / spent if spent > 0 else None
