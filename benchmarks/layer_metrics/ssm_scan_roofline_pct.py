"""The chunked scan's share of its roofline: the least time the chip
could take for the recurrence's operations and bytes in one step
(``benchmarks/flops/<family>.ssm_scan_work`` on the positions the step's
rows hold, at the heads, groups, state and chunk as run, whatever
implements it; the larger of operations / peak FLOP/s and bytes / peak
bytes/s, forward and backward each) divided by the device time per step
under the program's ``mamba/scan`` scope, every pass read from the trace
(``harness/scope_times.part_label_s``). Where the trace shows the forward
pass run again (``model.remat``) it is in the time, and its work is
counted too."""

from benchmarks.harness import loop_timeline, manifest, scope_times

LAYER = "state-space mixer"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


@loop_timeline.reader
def read(r):
    if r.trace is None or not r.trace.steps:
        return None
    label_s = scope_times.part_label_s(__file__, r)
    flops = manifest.load_family(loop_timeline.root_of(__file__), "flops",
                                 r.cell.config["flops"])
    if label_s is None or not hasattr(flops, "ssm_scan_work"):
        return None
    spent = scope_times.seconds(label_s, "mamba", ("scan",)) / r.trace.steps
    if spent <= 0:
        return None
    h = {**r.cell.config["published"], **r.cell.config["reference_hparams"]}
    tokens = r.cell.workload["per_chip_batch"] * r.cell.traffic["seq_len"]
    work = flops.ssm_scan_work(
        tokens, h, recomputed_forward=scope_times.recomputes(label_s, "mamba"))
    least = manifest.load_reader(
        loop_timeline.root_of(__file__), "attn_roofline_pct").least_seconds(
            work, r.peaks)
    return 100.0 * sum(sec for sec, _ in least.values()) / spent
