"""ResNet-50 v1.5 reference (He et al., arXiv:1512.03385; stride 2 in the
3x3 convolution of each down-sampling bottleneck, the v1.5 layout of the
MLPerf reference).

Stem convolution, BatchNorm, ReLU, 3x3/2 max-pool; stages of [3, 4, 6, 3]
bottlenecks (1x1, 3x3, 1x1 with 4x expansion, projection shortcut where
the shape changes); global average pool; dense. Loss: cross-entropy with
label smoothing, mean over the batch. Weight decay is the optimizer's,
not the loss's.

Departures, each because the system under test makes it
(``benchmarks/configs/resnet50.json``): the stem is the 8x8/2 convolution
with padding (2, 4) that the system's 4x4 convolution over 2x2
space-to-depth input computes exactly; its kernel is the system's
(4, 4, 4*C, 64) kernel re-indexed, pixel (2a+di, 2b+dj, c) <- s2d
(a, b, (2*di+dj)*C + c). BatchNorm is in training mode with the
statistics of the sample itself (8 images on the chip), biased variance,
epsilon 1e-5. The system computes activations in bfloat16; this is
float32 throughout, and the tolerance beside the check is what that
costs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import global_norm

BN_EPS = 1e-5


def conv(x, kernel, stride, padding):
    return jax.lax.conv_general_dilated(
        x, kernel, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]


def conv_bn(p, x, stride=1, padding="SAME", relu=True):
    y = batch_norm(p["BatchNorm_0"]["bn"],
                   conv(x, p["conv"]["kernel"], stride, padding))
    return jax.nn.relu(y) if relu else y


def stem_kernel_from_s2d(k):
    """(4, 4, 4*C, F) space-to-depth kernel -> the (8, 8, C, F) pixel
    kernel it stands for."""
    a, b, c4, f = k.shape
    c = c4 // 4
    k = k.reshape(a, b, 2, 2, c, f)          # (a, b, di, dj, c, f)
    k = k.transpose(0, 2, 1, 3, 4, 5)        # (a, di, b, dj, c, f)
    return k.reshape(2 * a, 2 * b, c, f)


def bottleneck(p, x, stride):
    y = conv_bn(p["conv1"], x)
    y = conv_bn(p["conv2"], y, stride=stride)
    y = conv_bn(p["conv3"], y, relu=False)
    if "proj" in p:
        x = conv_bn(p["proj"], x, stride=stride, relu=False)
    return jax.nn.relu(x + y)


def logits(params, images, stage_sizes):
    x = images.astype(jnp.float32)
    if "stem_s2d" in params:
        stem = params["stem_s2d"]
        kernel = stem_kernel_from_s2d(stem["conv"]["kernel"])
        y = conv(x, kernel, 2, ((2, 4), (2, 4)))
        x = jax.nn.relu(batch_norm(stem["BatchNorm_0"]["bn"], y))
    else:
        x = conv_bn(params["stem"], x, stride=2, padding=((3, 3), (3, 3)))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for stage, size in enumerate(stage_sizes):
        for block in range(size):
            stride = 2 if stage > 0 and block == 0 else 1
            x = bottleneck(params[f"stage{stage + 1}_block{block + 1}"], x,
                           stride)
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["classifier"]["kernel"] + params["classifier"]["bias"]


def loss(params, batch, stage_sizes, label_smoothing):
    z = logits(params, batch["image"], stage_sizes)
    n = z.shape[-1]
    target = jax.nn.one_hot(batch["label"], n) * (1.0 - label_smoothing) \
        + label_smoothing / n
    return jnp.mean(-jnp.sum(target * jax.nn.log_softmax(z, axis=-1), axis=-1))


@functools.partial(jax.jit, static_argnames=("stage_sizes", "label_smoothing"))
def _loss_and_grad_norm(params, batch, stage_sizes, label_smoothing):
    value, grads = jax.value_and_grad(loss)(
        params, batch, stage_sizes, label_smoothing)
    return value, global_norm(grads)


def loss_and_grad_norm(params, batch, hparams):
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    return _loss_and_grad_norm(
        params, batch, stage_sizes=tuple(hparams["stage_sizes"]),
        label_smoothing=float(hparams["label_smoothing"]))
