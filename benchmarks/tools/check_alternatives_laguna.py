"""``check_alternatives.py`` for the ``laguna`` family: what
``laguna_s_s16384``'s ``check_tolerance`` refuses beside a lower
precision. Same harness comparison, same options and output (that
tool's ``readings`` and ``main``); this file only brings the family's
list, because that tool names its families in code and a PR that adds a
configuration edits no file the benchmark has.

    python3 benchmarks/tools/check_alternatives_laguna.py --workload laguna_s_s16384 \\
        --seeds 7 [--alternatives a_gate_for_each_channel ...] [--control-dtype bfloat16]

The alternatives, each an ``assumed`` item of
``configs/laguna_s_2_1.json`` put in the PROGRAM's place: the gate's form
(one a channel), the rotated dims (interleaved pairs), the place of
``attention_factor`` (the softmax scale alone), a gate on the shared
expert, a sigmoid router. ``tests/test_laguna.py`` holds each of them,
and the rest of the list, leaf by leaf in float32 on the CPU.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmarks.tools import check_alternatives  # noqa: E402
from benchmarks.tools.check_alternatives import _attr  # noqa: E402


def laguna_alternatives() -> dict:
    """name -> a context manager that yields the overrides the program is
    built with while the alternative is in place."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_framework_tpu.models import lfm2, moe
    from distributed_tensorflow_framework_tpu.ops import flash_attention

    def a_gate_for_each_channel(out, u, kernel):
        # each head's column of W_g fans out to its channels, steeper
        # from one channel to the next
        d = out.shape[-1]
        wide = jnp.repeat(kernel, d, axis=1) * jnp.tile(
            jnp.linspace(0.5, 1.5, d), kernel.shape[1])
        g = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.float32), wide,
            precision=jax.lax.Precision.HIGHEST)).reshape(out.shape)
        return out.astype(jnp.float32) * g, g.mean(axis=-1)

    rotary = lfm2.rotary

    def interleaved_pairs(x, positions, theta, rule=None):
        # the same frequencies, turning the pairs (2i, 2i + 1) of the
        # rotated dims instead of (i, i + rot / 2)
        rule = rule or lfm2.RotaryRule()
        rot = int(x.shape[-1] * rule.fraction)
        first = jnp.concatenate([x[..., 0:rot:2], x[..., 1:rot:2]], axis=-1)
        halves = rotary(jnp.concatenate([first, x[..., rot:]], axis=-1),
                        positions, theta, rule)
        a, b = jnp.split(halves[..., :rot], 2, axis=-1)
        turned = jnp.stack([a, b], axis=-1).reshape(*x.shape[:-1], rot)
        return jnp.concatenate([turned, halves[..., rot:]], axis=-1)

    kernels = flash_attention.flash_attention

    with open(os.path.join(_ROOT, "benchmarks", "configs",
                           "laguna_s_2_1.json")) as fh:
        factor = json.load(fh)["rope_parameters"]["full_attention"][
            "attention_factor"]

    def scores_scaled(q, k, v, **kw):
        if "window" not in kw:           # a global layer's call
            q = (q.astype(jnp.float32) * factor ** 2).astype(q.dtype)
        return kernels(q, k, v, **kw)

    @contextlib.contextmanager
    def factor_on_the_softmax_scale():
        # cos and sin unscaled, the global layers' scores times the
        # factor's square instead
        with _attr(flash_attention, "flash_attention", scores_scaled):
            yield ("model.rope_attention_factor=1.0",)

    @contextlib.contextmanager
    def a_gate_on_the_shared_expert():
        def interceptor(next_fun, args, kwargs, context):
            out = next_fun(*args, **kwargs)
            if context.method_name == "__call__" and isinstance(
                    context.module, moe.SharedExpert):
                x = args[0].astype(jnp.float32)
                gate = jax.nn.sigmoid(x.sum(-1, keepdims=True) / 64.0)
                out = (gate * out).astype(out.dtype)
            return out

        with nn.intercept_methods(interceptor):
            yield ()

    def sigmoid_router(gate_logits, topk):
        scores = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
        chosen, experts = jax.lax.top_k(scores, topk)
        return experts.astype(jnp.int32), chosen / (
            chosen.sum(axis=-1, keepdims=True) + moe.ROUTER_NORM_EPS)

    return {
        "a_gate_for_each_channel": lambda: _attr(
            lfm2, "gate_heads", a_gate_for_each_channel),
        "interleaved_pairs_rotate": lambda: _attr(
            lfm2, "rotary", interleaved_pairs),
        "attention_factor_on_the_softmax_scale_alone":
            factor_on_the_softmax_scale,
        "a_gate_on_the_shared_expert": a_gate_on_the_shared_expert,
        "a_sigmoid_router": lambda: _attr(
            moe, "route_softmax_topk", sigmoid_router),
    }


def main(argv=None) -> int:
    check_alternatives.FAMILIES["laguna"] = laguna_alternatives
    return check_alternatives.main(argv)


if __name__ == "__main__":
    sys.exit(main())
