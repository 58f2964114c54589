"""Share of device busy time in the attention kernels: Mosaic custom
calls (``tpu_custom_call``) that the HLO text places under a
``jit(_flash_*)`` wrapper."""

LAYER = "attention kernels"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"


def read(r):
    if r.trace is None or r.attention_work is None:
        return None
    return 100.0 * r.trace.category_s.get("attn_kernel", 0.0) / r.trace.busy_s
