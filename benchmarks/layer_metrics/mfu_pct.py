"""Model FLOP/s utilization: operations the forward and backward passes
require per real token or image (``benchmarks/flops``, from shapes, no
recomputation, block-diagonal attention for packed rows) x the run's
throughput per chip / the chip's bf16 peak."""

LAYER = "models"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"


def read(r):
    return 100.0 * r.model_flops_per_unit * r.window["rate_per_chip"] \
        / r.peaks["bf16_flops_per_s"]
