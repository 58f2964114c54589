"""LFM2-MoE: a causal decoder of gated short convolutions, grouped-query
attention and bias-routed experts (LiquidAI LFM2-8B-A1B, ``model_type:
lfm2_moe``).

Pre-norm blocks, ``h = x + mixer(RMSNorm(x))``, ``y = h + ffn(RMSNorm(h))``,
one mixer kind per layer (``ModelConfig.layer_types``):

  conv            gated short convolution: ``[B, C, u] = split(x W_in)``,
                  ``v = B ⊙ u``, a depthwise causal convolution of
                  ``conv_kernel`` taps over ``v``, ``out = (C ⊙ conv) W_out``.
  full_attention  q over ``num_heads``, k and v over ``num_kv_heads``, no
                  bias; RMSNorm over each head's dims on q and k; rotary
                  positions (half rotation) on the whole head; causal
                  softmax attention, each key/value head serving
                  ``num_heads / num_kv_heads`` query heads; ``W_o``.

The first ``num_dense_layers`` layers carry a dense SwiGLU feed-forward
(``mlp_dim``), the rest ``DroplessMoE`` (models/moe.py): sigmoid scores,
a selection bias, normalised top-k weights, no dropped token, and this
process's share of the experts. Token embedding in, a final RMSNorm and
a head tied to the embedding out.

Packed rows (``segment_ids``, 0 on padding): attention stays inside a
document, ``positions`` restart at each document, and a convolution tap
that would reach across a document boundary reads zero.

Scopes a trace can be read by (docs/OBSERVABILITY.md):
``layerN/short_conv/{in_proj,gate_conv,out_proj}``, ``layerN/attn/...``,
``layerN/{mlp_in,mlp_up,mlp_out}``,
``layerN/moe/{router,dispatch,experts,combine}``, ``lm_head``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_framework_tpu.models.layers import dense_kernel_init
from distributed_tensorflow_framework_tpu.models.moe import (
    DroplessMoE, held_experts)

LAYER_KINDS = ("conv", "full_attention")
# What every expert layer reports (DroplessMoE's counters), averaged over
# the model's expert layers and named ``moe_<key>`` in the step's metrics.
MOE_COUNTERS = ("local_assignments", "load_max_mean", "dropped",
                "local_share")


def document_positions(segment_ids: jax.Array) -> jax.Array:
    """Position of each token inside its own document."""
    idx = jnp.arange(segment_ids.shape[1], dtype=jnp.int32)[None, :]
    new_doc = jnp.concatenate(
        [jnp.ones_like(segment_ids[:, :1], bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)
    return idx - jax.lax.cummax(jnp.where(new_doc, idx, 0), axis=1)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x²) + eps) · scale`` over the last axis, computed
    and returned in float32 (consumers cast)."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * scale


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, kernel_init=dense_kernel_init,
                    name=name)


def causal_depthwise_conv(v, taps, segment_ids=None):
    """``c_t = Σ_j taps[j] ⊙ v_{t-j}`` per channel; ``v`` (B, S, C),
    ``taps`` (L, C). A tap that would read before the row's start, or a
    token of another document, reads zero."""
    s = v.shape[1]
    out = v * taps[0]
    for j in range(1, taps.shape[0]):
        shifted = jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :s]
        if segment_ids is not None:
            before = jnp.pad(segment_ids, ((0, 0), (j, 0)),
                             constant_values=-1)[:, :s]
            shifted = jnp.where((before == segment_ids)[..., None],
                                shifted, 0)
        out = out + shifted * taps[j]
    return out


class ShortConv(nn.Module):
    """The gated short convolution mixer."""

    kernel: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids=None):
        h = x.shape[-1]
        gates = _dense(3 * h, self.dtype, "in_proj")(x)
        taps = self.param("conv_kernel", dense_kernel_init,
                          (self.kernel, h), jnp.float32)
        with jax.named_scope("gate_conv"):
            b_gate, c_gate, u = jnp.split(gates, 3, axis=-1)
            conv = causal_depthwise_conv(
                b_gate * u, taps.astype(self.dtype), segment_ids)
            y = c_gate * conv
        return _dense(h, self.dtype, "out_proj")(y)


def rotary(x, positions, theta: float):
    """Half-rotation rotary embedding over the whole head: ``x`` (B, S,
    N, D), ``positions`` (B, S); float32."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (B,S,D/2)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention_xla(q, k, v, segment_ids=None, dtype=jnp.float32):
    """Plain XLA causal grouped-query attention, (B, S, N, D) layout:
    the ``attention_impl: xla`` path and the kernels' test oracle."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    allowed = jnp.tril(jnp.ones((s, s), bool))[None, None]
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, None, :, None]
                             == segment_ids[:, None, None, :])
    scores = jnp.where(allowed, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"
    mesh: Any = None

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        b, s, h = x.shape
        n, nkv, d = self.num_heads, self.num_kv_heads, h // self.num_heads
        q = _dense(n * d, self.dtype, "query")(x).reshape(b, s, n, d)
        k = _dense(nkv * d, self.dtype, "key")(x).reshape(b, s, nkv, d)
        v = _dense(nkv * d, self.dtype, "value")(x).reshape(b, s, nkv, d)
        with jax.named_scope("qk_norm_rope"):
            q = rotary(RMSNorm(self.norm_eps, name="q_norm")(q), positions,
                       self.rope_theta).astype(self.dtype)
            k = rotary(RMSNorm(self.norm_eps, name="k_norm")(k), positions,
                       self.rope_theta).astype(self.dtype)
        if self.attention_impl == "pallas":
            from distributed_tensorflow_framework_tpu.ops.flash_attention import (
                flash_attention,
            )

            out = flash_attention(q, k, v, segment_ids=segment_ids,
                                  causal=True, mesh=self.mesh)
        elif self.attention_impl == "xla":
            out = causal_attention_xla(q, k, v, segment_ids, self.dtype)
        else:
            raise ValueError(
                f"attention_impl {self.attention_impl!r} is not wired for "
                f"the lfm2 family (pallas | xla)")
        return _dense(h, self.dtype, "attn_out")(out.reshape(b, s, n * d))


class Lfm2Block(nn.Module):
    kind: str                    # one of LAYER_KINDS
    dense_ffn: bool
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    moe_mlp_dim: int
    num_experts: int
    expert_topk: int
    expert_groups: int = 1
    expert_group: int = 0
    conv_kernel: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"
    mesh: Any = None

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        normed = RMSNorm(self.norm_eps, name="mixer_norm")(x)
        if self.kind == "conv":
            mixed = ShortConv(self.conv_kernel, self.dtype,
                              name="short_conv")(normed, segment_ids)
        else:
            mixed = GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.rope_theta,
                self.norm_eps, self.dtype, self.attention_impl, self.mesh,
                name="attn",
            )(normed, segment_ids, positions)
        x = x + mixed
        normed = RMSNorm(self.norm_eps, name="ffn_norm")(x)
        # A type-stable counter dict either way (zeros under a dense
        # feed-forward): return values are all that crosses nn.remat.
        counters = {key: jnp.zeros((), jnp.float32) for key in MOE_COUNTERS}
        if self.dense_ffn:
            gate = _dense(self.mlp_dim, self.dtype, "mlp_in")(normed)
            up = _dense(self.mlp_dim, self.dtype, "mlp_up")(normed)
            y = _dense(x.shape[-1], self.dtype, "mlp_out")(nn.silu(gate) * up)
        else:
            y, counters = DroplessMoE(
                num_experts=self.num_experts, mlp_dim=self.moe_mlp_dim,
                topk=self.expert_topk, groups=self.expert_groups,
                group=self.expert_group, dtype=self.dtype, name="moe",
            )(normed)
        return x + y.astype(x.dtype), counters


class Lfm2ForCausalLM(nn.Module):
    vocab_size: int
    hidden_size: int
    layer_types: tuple
    num_dense_layers: int
    num_heads: int
    num_kv_heads: int
    mlp_dim: int
    moe_mlp_dim: int
    num_experts: int
    expert_topk: int
    expert_groups: int = 1
    expert_group: int = 0
    conv_kernel: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"
    mesh: Any = None
    remat: bool = False
    ckpt_policy: Any = None

    def expert_share(self) -> dict | None:
        """Which experts this process holds and of how many groups, for
        the run's opening record; None for a stack of dense layers."""
        if self.num_dense_layers >= len(self.layer_types):
            return None
        held = held_experts(self.num_experts, self.expert_groups,
                            self.expert_group)
        return {"num_experts": self.num_experts,
                "groups": self.expert_groups, "group": self.expert_group,
                "held": list(held), "topk": self.expert_topk}

    @nn.compact
    def __call__(self, input_ids, segment_ids=None, positions=None, *,
                 train: bool = True):
        del train  # no dropout in this family
        if segment_ids is None:
            segment_ids = jnp.ones_like(input_ids)
        if positions is None:
            positions = document_positions(segment_ids)
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         param_dtype=jnp.float32, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        x = embed(input_ids)
        block_cls = Lfm2Block
        if self.remat:
            kwargs = ({"policy": self.ckpt_policy}
                      if self.ckpt_policy is not None else {})
            block_cls = nn.remat(Lfm2Block, **kwargs)
        totals = {key: jnp.zeros((), jnp.float32) for key in MOE_COUNTERS}
        n_moe = 0
        for i, kind in enumerate(self.layer_types):
            dense_ffn = i < self.num_dense_layers
            x, counters = block_cls(
                kind=kind, dense_ffn=dense_ffn, num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                mlp_dim=self.mlp_dim, moe_mlp_dim=self.moe_mlp_dim,
                num_experts=self.num_experts, expert_topk=self.expert_topk,
                expert_groups=self.expert_groups,
                expert_group=self.expert_group,
                conv_kernel=self.conv_kernel, rope_theta=self.rope_theta,
                norm_eps=self.norm_eps, dtype=self.dtype,
                attention_impl=self.attention_impl, mesh=self.mesh,
                name=f"layer{i}",
            )(x, segment_ids, positions)
            if not dense_ffn:
                totals = {key: totals[key] + counters[key]
                          for key in MOE_COUNTERS}
                n_moe += 1
        x = RMSNorm(self.norm_eps, name="final_norm")(x)
        with jax.named_scope("lm_head"):
            # Tied head, in the compute dtype: the largest product of the
            # model; the loss takes its softmax in float32.
            logits = x.astype(self.dtype) @ embed.embedding.astype(
                self.dtype).T
        if not n_moe:
            return logits
        return {"logits": logits,
                **{f"moe_{key}": totals[key] / n_moe
                   for key in MOE_COUNTERS}}


def build(config, *, mesh=None, dtype=jnp.bfloat16, ckpt_policy=None):
    """``ModelConfig`` -> module, with the family's own checks."""
    kinds = tuple(config.layer_types)
    if len(kinds) != config.num_layers or set(kinds) - set(LAYER_KINDS):
        raise ValueError(
            f"model.layer_types must name one of {LAYER_KINDS} for each of "
            f"model.num_layers={config.num_layers} layers, got {kinds}")
    heads = config.num_heads
    kv_heads = config.num_kv_heads or heads
    if heads % kv_heads:
        raise ValueError(f"model.num_heads={heads} is no multiple of "
                         f"model.num_kv_heads={kv_heads}")
    has_experts = config.num_dense_layers < config.num_layers
    if has_experts and not (config.num_experts > 0 and config.moe_mlp_dim > 0
                            and 1 <= config.expert_topk <= config.num_experts):
        raise ValueError(
            "layers past model.num_dense_layers carry experts: set "
            "model.num_experts, model.moe_mlp_dim and 1 <= "
            "model.expert_topk <= model.num_experts")
    return Lfm2ForCausalLM(
        vocab_size=config.vocab_size, hidden_size=config.hidden_size,
        layer_types=kinds, num_dense_layers=config.num_dense_layers,
        num_heads=heads, num_kv_heads=kv_heads,
        mlp_dim=config.mlp_dim, moe_mlp_dim=config.moe_mlp_dim,
        num_experts=config.num_experts, expert_topk=config.expert_topk,
        expert_groups=config.expert_groups, expert_group=config.expert_group,
        conv_kernel=config.conv_kernel, rope_theta=config.rope_theta,
        norm_eps=config.norm_eps, dtype=dtype,
        attention_impl=config.attention_impl, mesh=mesh,
        remat=config.remat, ckpt_policy=ckpt_policy)
