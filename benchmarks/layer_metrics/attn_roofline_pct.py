"""The attention kernels' share of their roofline: the least time the
chip could take for the operations and bytes attention needs in one step
(``benchmarks/flops/bert.attention_kernel_work``: the larger of
operations / peak FLOP/s and bytes / peak bytes/s, forward and backward
each) divided by the kernels' device time per step. ``least_seconds``
also says which side bounds: bytes for the packed rows of ``bert_s512``
(short documents leave little arithmetic), operations at 8192."""

LAYER = "attention kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


def least_seconds(work: dict, peaks: dict) -> dict:
    out = {}
    for part in ("forward", "backward"):
        by_flops = work[f"{part}_flops"] / peaks["bf16_flops_per_s"]
        by_bytes = work[f"{part}_bytes"] / peaks["hbm_bytes_per_s"]
        out[part] = (max(by_flops, by_bytes),
                     "flops" if by_flops >= by_bytes else "bytes")
    return out


def read(r):
    if r.trace is None or r.attention_work is None or not r.trace.steps:
        return None
    kernel_s = r.trace.category_s.get("attn_kernel", 0.0) / r.trace.steps
    if kernel_s <= 0:
        return None
    least = least_seconds(r.attention_work, r.peaks)
    return 100.0 * (least["forward"][0] + least["backward"][0]) / kernel_s
