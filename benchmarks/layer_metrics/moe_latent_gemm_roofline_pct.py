"""The grouped expert products' share of their roofline where the experts
work in a latent: as ``moe_gemm_roofline_pct``, with the expert layers
counted from the configuration's ``pattern`` (its ``E`` layers: a layer
here is a mixer or a feed-forward alone) and
``benchmarks/flops/<family>.moe_gemm_work`` of the two latent products,
fed the local assignments the run COUNTED (``moe_local_assignments``, a
mean over the layers that have experts). The time is XLA's
``ragged-dot-*`` kernels plus any product under ``moe/experts``; where
the trace shows the forward pass run again (``model.remat``) the re-run
products are in the time and their operations are counted too."""

from benchmarks.harness import loop_timeline, manifest, scope_times

LAYER = "expert layer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    if r.trace is None or not r.trace.steps:
        return None
    h = {**r.cell.config["published"], **r.cell.config["reference_hparams"]}
    local = scope_times.mean_counter(__file__, r, "moe_local_assignments")
    flops = manifest.load_family(loop_timeline.root_of(__file__), "flops",
                                 r.cell.config["flops"])
    label_s = scope_times.part_label_s(__file__, r)
    if (local is None or label_s is None or "pattern" not in h
            or "moe_latent_size" not in h
            or not hasattr(flops, "moe_gemm_work")):
        return None
    work = flops.moe_gemm_work(
        local, h, recomputed_forward=scope_times.recomputes(label_s, "moe"))
    least = manifest.load_reader(
        loop_timeline.root_of(__file__), "attn_roofline_pct").least_seconds(
            work, r.peaks)
    least = sum(sec for sec, _ in least.values()) * h["pattern"].count("E")
    spent = (scope_times.ragged_dot_seconds(label_s)
             + scope_times.seconds(
                 label_s, "moe", ("experts",),
                 kinds=scope_times.PRODUCT_KINDS)) / r.trace.steps
    return 100.0 * least / spent if spent > 0 else None
