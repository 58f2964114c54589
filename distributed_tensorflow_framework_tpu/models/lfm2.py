"""The causal decoder family: a stack whose mixer is chosen per layer,
over dropless experts. Two published models run through it, told apart
by ``ModelConfig`` settings alone: LFM2-MoE (LiquidAI LFM2-8B-A1B,
``model_type: lfm2_moe``: gated short convolutions, grouped-query
attention, bias-routed SwiGLU experts, a tied head; every default below
is what it runs) and SmallThinker (PowerInfer SmallThinker-21BA3B: global
layers without positions beside rotary window layers, a router that
reads the stream before attention, ReGLU experts, an untied head).

Pre-norm blocks, ``h = x + mixer(RMSNorm(x))``, ``y = h + ffn(RMSNorm(h))``,
one mixer kind per layer (``ModelConfig.layer_types``):

  conv               gated short convolution: ``[B, C, u] = split(x W_in)``,
                     ``v = B ⊙ u``, a depthwise causal convolution of
                     ``conv_kernel`` taps over ``v``,
                     ``out = (C ⊙ conv) W_out``.
  full_attention     q over ``num_heads``, k and v over ``num_kv_heads``
                     of ``head_dim`` dims, no bias; RMSNorm over each
                     head's dims on q and k (``qk_norm``); rotary
                     positions (half rotation) on the whole head where
                     ``rope_layout`` says so; causal softmax attention,
                     each key/value head serving ``num_heads /
                     num_kv_heads`` query heads; ``W_o``.
  sliding_attention  the same, and a query at ``i`` sees a key at ``j``
                     only if ``i - j < sliding_window``.

The first ``num_dense_layers`` layers carry a dense SwiGLU feed-forward
(``mlp_dim``), the rest ``DroplessMoE`` (models/moe.py): no dropped
token, this process's share of the experts, the router's scores
(``router_score``), what it reads (``router_input``) and the experts'
activation (``expert_activation``) as set. Token embedding in, a final
RMSNorm and a head out: the embedding transposed (``tie_embeddings``) or
a matrix of its own.

Packed rows (``segment_ids``, 0 on padding): attention stays inside a
document, ``positions`` restart at each document, and a convolution tap
that would reach across a document boundary reads zero.

``remat`` checkpoints each layer (``nn.remat``). With no policy handed
down (``precision.remat_policy: none``) a layer keeps, beside its input,
the two values its attention backward kernels read from the forward
kernel: the output and the logsumexp (ops/flash_attention.py's
``RESIDUAL_NAMES``; O(S·D) a layer, as large as the input), and the
re-run forward pass recomputes everything else. That engages wherever
the layer's attention is the direct Pallas call (one device, or inside
manual axes). Where there are no names to keep the layer re-runs whole,
to the same result: ``attention_impl: xla``, conv layers, and the
``shard_map`` the kernels wrap themselves in under a multi-device
``jit``, whose equation hides the names from the layer's checkpoint.
``precision.remat_policy: save_nothing`` is the full re-run,
``dots_saveable`` keeps the products' outputs.

Scopes a trace can be read by (docs/OBSERVABILITY.md):
``layerN/short_conv/{in_proj,gate_conv,out_proj}``, ``layerN/attn/...``
(``full_attention``), ``layerN/attn_window/...`` (``sliding_attention``),
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

LAYER_KINDS = ("conv", "full_attention", "sliding_attention")
# The attention module's name, and so its scope in a trace, by kind.
ATTENTION_SCOPES = {"full_attention": "attn",
                    "sliding_attention": "attn_window"}
ROUTER_INPUTS = ("ffn_norm", "stream")
# What every expert layer reports (DroplessMoE's counters), averaged over
# the model's expert layers and named ``moe_<key>`` in the step's metrics.
MOE_COUNTERS = ("local_assignments", "load_max_mean", "dropped",
                "local_share", "compact")


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


def causal_attention_xla(q, k, v, segment_ids=None, dtype=jnp.float32,
                         window=None):
    """Plain XLA causal grouped-query attention, (B, S, N, D) layout:
    the ``attention_impl: xla`` path and the kernels' test oracle.
    ``window``: a query sees only the ``window`` keys up to its own."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    allowed = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        allowed = allowed & ~jnp.tril(jnp.ones((s, s), bool), -window)
    allowed = allowed[None, None]
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
    head_dim: int = 0            # 0 = hidden // num_heads
    window: int | None = None    # a query sees this many keys, its own last
    rope: bool = True            # rotary positions on q and k
    qk_norm: bool = True         # RMSNorm over each head of q and of k

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        b, s, h = x.shape
        n, nkv = self.num_heads, self.num_kv_heads
        d = self.head_dim or h // n
        q = _dense(n * d, self.dtype, "query")(x).reshape(b, s, n, d)
        k = _dense(nkv * d, self.dtype, "key")(x).reshape(b, s, nkv, d)
        v = _dense(nkv * d, self.dtype, "value")(x).reshape(b, s, nkv, d)

        def normed_and_rotated(t, norm_name):
            if self.qk_norm:
                t = RMSNorm(self.norm_eps, name=norm_name)(t)
            if self.rope:
                t = rotary(t, positions, self.rope_theta)
            return t.astype(self.dtype)

        with jax.named_scope("qk_norm_rope"):
            q = normed_and_rotated(q, "q_norm")
            k = normed_and_rotated(k, "k_norm")
        # The keyword is given only where a window is set, so a model
        # without one calls (and traces) what it always did.
        window = {} if self.window is None else {"window": self.window}
        if self.attention_impl == "pallas":
            from distributed_tensorflow_framework_tpu.ops.flash_attention import (
                flash_attention,
            )

            out = flash_attention(q, k, v, segment_ids=segment_ids,
                                  causal=True, mesh=self.mesh, **window)
        elif self.attention_impl == "xla":
            out = causal_attention_xla(q, k, v, segment_ids, self.dtype,
                                       **window)
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
    head_dim: int = 0
    sliding_window: int = 0      # of a ``sliding_attention`` layer
    rope: bool = True            # this layer's attention rotates q and k
    qk_norm: bool = True
    router_input: str = "ffn_norm"   # one of ROUTER_INPUTS
    router_score: str = "sigmoid_bias"
    expert_activation: str = "silu"

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        stream = x               # what ``router_input: stream`` reads
        normed = RMSNorm(self.norm_eps, name="mixer_norm")(x)
        if self.kind == "conv":
            mixed = ShortConv(self.conv_kernel, self.dtype,
                              name="short_conv")(normed, segment_ids)
        else:
            sliding = self.kind == "sliding_attention"
            mixed = GroupedQueryAttention(
                self.num_heads, self.num_kv_heads, self.rope_theta,
                self.norm_eps, self.dtype, self.attention_impl, self.mesh,
                head_dim=self.head_dim,
                window=self.sliding_window if sliding else None,
                rope=self.rope, qk_norm=self.qk_norm,
                name=ATTENTION_SCOPES[self.kind],
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
            # The router reads what the experts read, or the stream as it
            # entered the layer (before the mixer and its norm).
            route_from = (stream,) if self.router_input == "stream" else ()
            y, counters = DroplessMoE(
                num_experts=self.num_experts, mlp_dim=self.moe_mlp_dim,
                topk=self.expert_topk, groups=self.expert_groups,
                group=self.expert_group, dtype=self.dtype,
                score=self.router_score, activation=self.expert_activation,
                name="moe",
            )(normed, *route_from)
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
    head_dim: int = 0
    sliding_window: int = 0
    rope_layout: tuple = ()      # per layer, 1 = rotates; () = every one
    qk_norm: bool = True
    tie_embeddings: bool = True
    embed_init_std: float = 0.02
    router_input: str = "ffn_norm"
    router_score: str = "sigmoid_bias"
    expert_activation: str = "silu"

    def window_block_share(self, seq_len: int) -> float | None:
        """Visited ÷ causal (q-block, k-block) visits of the window
        layers' kernels on rows of ``seq_len``, at the tiles the kernels
        take (``ops/flash_attention.window_block_counts``: a static
        count, segments aside); None for a model without such layers or
        without the kernels."""
        if ("sliding_attention" not in self.layer_types
                or self.attention_impl != "pallas"):
            return None
        from distributed_tensorflow_framework_tpu.ops import flash_attention

        tile = flash_attention.select_dispatch(
            seq_len, seq_len, self.dtype,
            self.head_dim or self.hidden_size // self.num_heads)
        visited, causal = flash_attention.window_block_counts(
            seq_len, seq_len, tile.bwd_block_q, tile.bwd_block_k,
            self.sliding_window)
        return visited / causal

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
                         embedding_init=nn.initializers.normal(
                             self.embed_init_std),
                         name="embed")
        x = embed(input_ids)
        block_cls = Lfm2Block
        if self.remat:
            policy = self.ckpt_policy
            if policy is None:
                # Keep what the attention backward kernels read, so the
                # forward kernel is not run again (module docstring).
                from distributed_tensorflow_framework_tpu.ops.flash_attention import (
                    RESIDUAL_NAMES,
                )

                policy = jax.checkpoint_policies.save_only_these_names(
                    *RESIDUAL_NAMES)
            block_cls = nn.remat(Lfm2Block, policy=policy)
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
                head_dim=self.head_dim, sliding_window=self.sliding_window,
                rope=bool(self.rope_layout[i]) if self.rope_layout else True,
                qk_norm=self.qk_norm, router_input=self.router_input,
                router_score=self.router_score,
                expert_activation=self.expert_activation,
                name=f"layer{i}",
            )(x, segment_ids, positions)
            if not dense_ffn:
                totals = {key: totals[key] + counters[key]
                          for key in MOE_COUNTERS}
                n_moe += 1
        x = RMSNorm(self.norm_eps, name="final_norm")(x)
        if self.tie_embeddings:
            head = embed.embedding
        else:
            head = self.param("lm_head", nn.initializers.normal(0.02),
                              (self.vocab_size, self.hidden_size),
                              jnp.float32)
        with jax.named_scope("lm_head"):
            # In the compute dtype: the largest product of the model; the
            # loss takes its softmax in float32.
            logits = x.astype(self.dtype) @ head.astype(self.dtype).T
        counters = {f"moe_{key}": totals[key] / n_moe
                    for key in MOE_COUNTERS} if n_moe else {}
        share = self.window_block_share(input_ids.shape[1])
        if share is not None:
            counters["attn_window_block_share"] = jnp.float32(share)
        if not counters:
            return logits
        return {"logits": logits, **counters}


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
    if "sliding_attention" in kinds and config.sliding_window < 1:
        raise ValueError(
            "a sliding_attention layer needs model.sliding_window >= 1 "
            f"(keys a query sees, its own included), got "
            f"{config.sliding_window}")
    rope_layout = tuple(int(r) for r in config.rope_layout)
    if rope_layout and (len(rope_layout) != config.num_layers
                        or set(rope_layout) - {0, 1}):
        raise ValueError(
            f"model.rope_layout must give 0 or 1 for each of "
            f"model.num_layers={config.num_layers} layers (1: a "
            f"full_attention or sliding_attention layer rotates its "
            f"queries and keys), or be empty for all of them, got "
            f"{rope_layout}")
    if config.router_input not in ROUTER_INPUTS:
        raise ValueError(f"model.router_input must be one of "
                         f"{ROUTER_INPUTS}, got {config.router_input!r}")
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
        remat=config.remat, ckpt_policy=ckpt_policy,
        head_dim=config.head_dim, sliding_window=config.sliding_window,
        rope_layout=rope_layout, qk_norm=config.qk_norm,
        tie_embeddings=config.tie_embeddings,
        embed_init_std=config.embed_init_std,
        router_input=config.router_input, router_score=config.router_score,
        expert_activation=config.expert_activation)
