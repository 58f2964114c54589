"""Share of device busy time in ``dot``/``convolution`` fusions outside
the attention kernels and outside the optimizer scope."""

LAYER = "models"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.category_s.get("gemm_conv", 0.0) / r.trace.busy_s
