"""ResNet-50 v1.5 at a given image size, from shapes.

A convolution needs ``H_out*W_out*K_h*K_w*C_in*C_out`` multiply-adds.
The stem is the published 7x7/2 over 3 channels (the space-to-depth
form the system runs is an 8x8 whose extra row and column are the
system's, not the model's). The usual "~4.1 GFLOPs" for ResNet-50 v1.5 at
224 counts multiply-adds; operations are twice that.
"""

from __future__ import annotations

import numpy as np


def forward_macs(image_size: int, h: dict) -> float:
    stages, width = h["stage_sizes"], h["width"]
    exp, classes = h["bottleneck_expansion"], h["num_classes"]
    size = image_size // 2                      # stem, stride 2
    macs = size * size * 7 * 7 * 3 * width
    size //= 2                                  # max-pool, stride 2
    c_in = width
    for stage, blocks in enumerate(stages):
        mid = width * 2 ** stage
        out = mid * exp
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            macs += size * size * c_in * mid               # 1x1, before the stride
            size_out = size // stride
            macs += size_out * size_out * 9 * mid * mid    # 3x3 carries the stride (v1.5)
            macs += size_out * size_out * mid * out        # 1x1
            if c_in != out or stride != 1:
                macs += size_out * size_out * c_in * out   # projection shortcut
            size, c_in = size_out, out
    return float(macs + c_in * classes)


def train_flops(batch: dict, h: dict) -> float:
    images = np.asarray(batch["label"]).shape[0]
    size = np.asarray(batch["image"]).shape[1]
    return 3.0 * 2.0 * forward_macs(size, h) * images
