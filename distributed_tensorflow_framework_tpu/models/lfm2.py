"""The causal decoder family: a stack whose mixer is chosen per layer,
over dropless experts. Four published models run through it, told apart
by ``ModelConfig`` settings alone: LFM2-MoE (LiquidAI LFM2-8B-A1B,
``model_type: lfm2_moe``: gated short convolutions, grouped-query
attention, bias-routed SwiGLU experts, a tied head; every default below
is what it runs), SmallThinker (PowerInfer SmallThinker-21BA3B: global
layers without positions beside rotary window layers, a router that
reads the stream before attention, ReGLU experts, an untied head) and
Nemotron-H (NVIDIA Nemotron-3-Super-120B-A12B, ``model_type:
nemotron_h``: layers of one sublayer each: Mamba-2 mixers, attention
without positions, a LatentMoE of ungated squared-ReLU experts beside a
shared expert) and Laguna (poolside Laguna-S-2.1, ``model_type:
laguna``: window layers with more query heads than the global layers
and a rotary rule of their own, YaRN frequencies over half of a global
layer's head, a per-head output gate, softmax-routed SwiGLU experts with
scaled weights beside a gated shared expert).

Pre-norm blocks, ``h = x + mixer(RMSNorm(x))``, ``y = h + ffn(RMSNorm(h))``,
one mixer kind per layer (``ModelConfig.layer_types``):

  conv               gated short convolution: ``[B, C, u] = split(x W_in)``,
                     ``v = B ⊙ u``, a depthwise causal convolution of
                     ``conv_kernel`` taps over ``v``,
                     ``out = (C ⊙ conv) W_out``.
  full_attention     q over ``num_heads``, k and v over ``num_kv_heads``
                     of ``head_dim`` dims, no bias; RMSNorm over each
                     head's dims on q and k (``qk_norm``); rotary
                     positions (half rotation) where ``rope_layout``
                     says so, by the layer's rule (``RotaryRule``: over
                     the whole head at ``rope_theta``, or over the first
                     ``rope_fraction`` of it, with YaRN frequencies
                     under ``rope_yarn_factor`` and cos and sin times
                     ``rope_attention_factor``); causal softmax
                     attention, each key/value head serving ``num_heads
                     / num_kv_heads`` query heads; with
                     ``attention_gate: per_head`` each head's result
                     times ``sigmoid(u W_g)``, one scalar a head and
                     token read from the layer's normed input
                     (``gate_heads``); ``W_o``.
  sliding_attention  the same, and a query at ``i`` sees a key at ``j``
                     only if ``i - j < sliding_window``; over
                     ``sliding_num_heads`` query heads where that is set,
                     and by a plain rule of its own at
                     ``sliding_rope_theta`` over ``sliding_rope_fraction``
                     of the head where that is set.

Or a layer is ONE sublayer, ``y = x + sublayer(RMSNorm(x))``
(``SoloBlock``):

  mamba2_only        the Mamba-2 mixer (``Mamba2Mixer``): in-projection
                     to gate ``z``, ``xBC`` and ``dt``; a depthwise causal
                     convolution of ``conv_kernel`` taps with bias and
                     SiLU over ``xBC``; the selective state-space
                     recurrence with one scalar decay a head as a chunked
                     scan (ops/ssm_scan.py, chunks of ``mamba_chunk``),
                     ``D`` skip; a gated RMSNorm per B/C group;
                     out-projection.
  attention_only     ``full_attention``'s mixer alone.
  experts_only       ``DroplessMoE`` alone, with what ``moe_latent_dim``
                     (experts in a latent of the stream),
                     ``moe_shared_dim`` (a shared expert beside them) and
                     ``routed_scaling`` say.

``tensor_groups`` / ``tensor_group`` give a process one tensor-parallel
share, as ``expert_groups`` gives it its experts, taken wherever a layer
has something to split: an attention layer of any kind holds its query
heads ``/ tensor_groups`` (``num_heads``, or ``sliding_num_heads`` in a
window layer) with the key/value heads they read and the gate's columns
of those heads, a Mamba-2 layer ``mamba_num_heads / tensor_groups`` heads
with ``mamba_groups / tensor_groups`` B/C groups (its gated norm is over
its own groups), a dense feed-forward ``mlp_dim / tensor_groups`` and a
shared expert ``moe_shared_dim / tensor_groups`` of their hidden units
(a unit, gated or not, is elementwise in them); each adds its heads' (or
units') part of the out-projection's sum and nothing stands in for the
others. A share that would split a B/C group, or a key/value head's
queries unevenly, is refused, and so is one over a ``conv`` layer (its
gated convolution has no such split). Routers, latent projections and
norms are whole on every chip.

The first ``num_dense_layers`` layers carry a dense SwiGLU feed-forward
(``mlp_dim``), the rest ``DroplessMoE`` (models/moe.py): no dropped
token, this process's share of the experts, the router's scores
(``router_score``), what it reads (``router_input``), the experts'
activation (``expert_activation``), the factor on their weights
(``routed_scaling``) and a shared expert of their form beside them
(``moe_shared_dim``) as set. Token embedding in, a final
RMSNorm and a head out: the embedding transposed (``tie_embeddings``) or
a matrix of its own.

Packed rows (``segment_ids``, 0 on padding): attention stays inside a
document, ``positions`` restart at each document, a convolution tap
that would reach across a document boundary reads zero, and a Mamba-2
layer's state starts from zero at each document.

What ``model.remat`` keeps: ``remat`` checkpoints each layer
(``nn.remat``). With no policy handed down (``precision.remat_policy:
none``) a layer keeps, beside its input, two sets of named values, and
the re-run forward pass recomputes everything else:

  * the two values its attention backward kernels read from the forward
    kernel, the output and the logsumexp (ops/flash_attention.py's
    ``RESIDUAL_NAMES``; O(S·D) a layer, as large as the input). That
    engages wherever the layer's attention is the direct Pallas call
    (one device, or inside manual axes); ``attention_impl: xla``, conv
    layers, and the ``shard_map`` the kernels wrap themselves in under a
    multi-device ``jit``, whose equation hides the names from the
    layer's checkpoint, have no such names, and that part of the layer
    re-runs whole, to the same result;
  * what its expert layer's routing decided (models/moe.py's
    ``ROUTING_NAMES``): the router's float32 logits (T × num_experts),
    the chosen experts and their scores (T × K each) and the sort by
    expert (``order``, ``inverse``: T·K int32 each; ``group_sizes``: one
    a held expert), so the router's product, ``lax.top_k``, the gather
    of the chosen scores, the two argsorts and the count run once a
    step; the scores and the weights are recomputed from the kept
    values, because the backward pass differentiates through them.

``precision.remat_policy: save_nothing`` is the full re-run,
``dots_saveable`` keeps the products' outputs and no name.

Scopes a trace can be read by (docs/OBSERVABILITY.md):
``layerN/short_conv/{in_proj,gate_conv,out_proj}``, ``layerN/attn/...``
(``full_attention``), ``layerN/attn_window/...`` (``sliding_attention``),
inside either ``qk_norm_rope`` (norm, rotation and cast of q and k) and
``attn_gate`` (the gate's projection and its product with the kernel's
result),
``layerN/{mlp_in,mlp_up,mlp_out}``,
``layerN/moe/{router,dispatch,experts,combine}`` and, around them,
``layerN/moe/{latent_in,latent_out,shared}``,
``layerN/mamba/{in_proj,conv,scan,gate_norm,out_proj}``, ``lm_head``.
Counters beside the logits: ``moe_*`` (the mean over the layers that
have experts), ``attn_window_block_share``, ``attn_window_grid_share``,
``ssm_resets`` and ``attn_gate_mean`` (the gate's mean over heads, tokens
and gated layers).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_framework_tpu.models.layers import dense_kernel_init
from distributed_tensorflow_framework_tpu.models.moe import (
    ROUTING_NAMES, DroplessMoE, check_expert_settings, held_experts,
    projection)

# A layer is a mixer and then a feed-forward ...
LAYER_KINDS = ("conv", "full_attention", "sliding_attention")
# ... or ONE sublayer, ``x + sublayer(RMSNorm(x))``: a mixer alone or the
# expert feed-forward alone.
SOLO_KINDS = ("mamba2_only", "attention_only", "experts_only")
# The attention module's name, and so its scope in a trace, by kind.
ATTENTION_SCOPES = {"full_attention": "attn",
                    "sliding_attention": "attn_window"}
ROUTER_INPUTS = ("ffn_norm", "stream")
# What every expert layer reports (DroplessMoE's counters), averaged over
# the model's expert layers and named ``moe_<key>`` in the step's metrics.
MOE_COUNTERS = ("local_assignments", "load_max_mean", "dropped",
                "local_share", "compact")
# What a gated attention layer reports beside them: the mean of its gate
# over heads and tokens, averaged over the gated layers and named
# ``attn_gate_mean`` in the step's metrics.
GATE_COUNTER = "attn_gate"
# How Mamba-2 draws the step size a head starts from (``dt_bias`` is its
# inverse softplus): log-uniform between the two, floored. The published
# ``time_step_min/max/floor``; they shape this init and nothing else.
MAMBA_DT_INIT = (1e-3, 1e-1, 1e-4)


def document_starts(segment_ids: jax.Array) -> jax.Array:
    """True at each row's first token and wherever the document changes."""
    return jnp.concatenate(
        [jnp.ones_like(segment_ids[:, :1], bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)


def document_positions(segment_ids: jax.Array) -> jax.Array:
    """Position of each token inside its own document."""
    idx = jnp.arange(segment_ids.shape[1], dtype=jnp.int32)[None, :]
    new_doc = document_starts(segment_ids)
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


def _mamba_dt_bias_init(key, shape, dtype=jnp.float32):
    low, high, floor = MAMBA_DT_INIT
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(low), math.log(high)))
    dt = jnp.maximum(dt, floor)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _mamba_a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _conv_init(taps: int):
    """Uniform within ``1/sqrt(taps)``, for a depthwise convolution's
    taps and its bias: its fan-in is its taps."""
    bound = 1.0 / math.sqrt(taps)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def step_size(dt, dt_bias):
    """``Δ = softplus(dt + dt_bias)``, float32, with no clamp."""
    return nn.softplus(dt.astype(jnp.float32) + dt_bias)


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm_group(y ⊙ silu(z)) ⊙ scale``: the gate first, then the
    norm with its mean square over each of ``groups`` runs of channels;
    float32."""
    lead, d = y.shape[:-1], y.shape[-1]
    y = (y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))).reshape(
        *lead, groups, d // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return y.reshape(*lead, d) * scale


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer (arXiv:2405.21060) over the heads and B/C groups
    this process holds: ``[z | xBC | dt] = u W_in``; ``xBC`` through a
    depthwise causal convolution with bias and SiLU; ``[x | B | C] =
    xBC``, head ``h`` of ``head_dim`` channels reading group ``h //
    (heads / groups)`` of ``state`` dims; ``Δ = softplus(dt + dt_bias)``,
    ``a = -exp(A_log)``, the recurrence ``S_t = exp(Δ_t a) S_{t-1} + Δ_t
    x_t ⊗ B_t``, ``y_t = S_t C_t + D x_t`` as a chunked scan
    (ops/ssm_scan.py); ``RMSNorm_group(y ⊙ silu(z)) ⊙ w`` with the mean
    square over each group's channels; ``W_out``. State and convolution
    start anew at each document (``segment_ids``)."""

    heads: int
    head_dim: int
    groups: int
    state: int
    kernel: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    out_init_std: float = 0.0        # 0: the fan-in rule
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids=None):
        from distributed_tensorflow_framework_tpu.ops.ssm_scan import (
            chunked_ssm_scan,
        )

        bsz, s, h = x.shape
        n_h, p, g, n = self.heads, self.head_dim, self.groups, self.state
        d_in, d_bc = n_h * p, g * n
        proj = _dense(2 * d_in + 2 * d_bc + n_h, self.dtype, "in_proj")(x)
        z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * d_bc], axis=-1)
        taps = self.param("conv_kernel", _conv_init(self.kernel),
                          (self.kernel, d_in + 2 * d_bc), jnp.float32)
        conv_bias = self.param("conv_bias", _conv_init(self.kernel),
                               (d_in + 2 * d_bc,), jnp.float32)
        with jax.named_scope("conv"):
            xbc = nn.silu(causal_depthwise_conv(
                xbc, taps.astype(self.dtype), segment_ids)
                + conv_bias.astype(self.dtype))
        dt_bias = self.param("dt_bias", _mamba_dt_bias_init, (n_h,),
                             jnp.float32)
        a_log = self.param("A_log", _mamba_a_log_init, (n_h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (n_h,), jnp.float32)
        with jax.named_scope("scan"):
            xs, b, c = jnp.split(xbc, [d_in, d_in + d_bc], axis=-1)
            xs = xs.reshape(bsz, s, n_h, p)
            y = chunked_ssm_scan(
                xs, step_size(dt, dt_bias),
                -jnp.exp(a_log), b.reshape(bsz, s, g, n),
                c.reshape(bsz, s, g, n), segment_ids, chunk=self.chunk)
            y = y + skip[:, None] * xs.astype(jnp.float32)
        scale = self.param("norm_scale", nn.initializers.ones, (d_in,),
                           jnp.float32)
        with jax.named_scope("gate_norm"):
            y = gated_group_norm(y.reshape(bsz, s, d_in), z, scale, g,
                                 self.norm_eps).astype(self.dtype)
        return projection(h, self.dtype, "out_proj", self.out_init_std)(y)


class RotaryRule(NamedTuple):
    """What a layer's rotation does beyond the plain rule (half rotation
    over the whole head at the layer's theta): ``fraction`` of each
    head's dims, its first, rotate and the rest pass; with
    ``yarn_factor`` the frequencies are YaRN's (``yarn_inv_freq``);
    ``attention_factor`` multiplies cos and sin."""
    fraction: float = 1.0
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0


def yarn_correction_range(dim: int, theta: float, original_len: int,
                          beta_fast: float, beta_slow: float
                          ) -> tuple[int, int]:
    """``(low, high)``: the rotated pairs between which YaRN's ramp
    runs. Pair ``r`` of ``dim`` rotated dims turns ``original_len · θ^(-2r
    / dim) / 2π`` times in ``original_len`` positions; ``low`` is the
    last pair that turns ``beta_fast`` times or more (rounded down),
    ``high`` the first that turns ``beta_slow`` times or fewer (rounded
    up), both inside the head."""
    def pair_turning(times: float) -> float:
        return dim * math.log(original_len / (times * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(pair_turning(beta_fast)), 0),
            min(math.ceil(pair_turning(beta_slow)), dim - 1))


def yarn_inv_freq(dim: int, theta: float, rule: RotaryRule):
    """YaRN frequencies (arXiv:2309.00071) of ``dim`` rotated dims:
    ``f_i = θ^(-2i/dim)`` kept where a pair turns often (``i <= low``),
    divided by ``yarn_factor`` where it turns rarely (``i >= high``), and
    ``f_i / factor · ramp_i + f_i · (1 - ramp_i)`` on the linear ramp
    between; float32, ``(dim / 2,)``."""
    freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    low, high = yarn_correction_range(
        dim, theta, rule.yarn_original_len, rule.yarn_beta_fast,
        rule.yarn_beta_slow)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0)
    return freq / rule.yarn_factor * ramp + freq * (1.0 - ramp)


def rotary(x, positions, theta: float, rule: RotaryRule | None = None):
    """Half-rotation rotary embedding: ``x`` (B, S, N, D), ``positions``
    (B, S); float32. Over the whole head at plain frequencies, or as
    ``rule`` says."""
    d = x.shape[-1]
    rule = rule or RotaryRule()
    rot = int(d * rule.fraction)
    if rule.yarn_factor:
        inv_freq = yarn_inv_freq(rot, theta, rule)
    else:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (B,S,R/2)
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, :, None, :]
    if rule.attention_factor != 1.0:
        cos, sin = cos * rule.attention_factor, sin * rule.attention_factor
    x = x.astype(jnp.float32)
    turned = x if rot == d else x[..., :rot]
    x1, x2 = jnp.split(turned, 2, axis=-1)
    turned = turned * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    if rot == d:
        return turned
    return jnp.concatenate([turned, x[..., rot:]], axis=-1)


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


ATTENTION_GATES = ("none", "per_head")


def gate_heads(out, u, kernel):
    """The per-head output gate: ``out`` (B, S, N, D), the kernels'
    result, times ``g = sigmoid(u W_g)`` (B, S, N), one scalar a head and
    token read from the layer's normed input ``u``; float32 end to end (a
    gate near 0 or 1 is decided by a few parts in a thousand). Returns
    ``(g ⊙ out, g)``."""
    g = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), kernel,
                               precision=jax.lax.Precision.HIGHEST))
    return out.astype(jnp.float32) * g[..., None], g


class GroupedQueryAttention(nn.Module):
    """Causal grouped-query attention over the heads it is given.
    Returns the out-projection's result, and with a ``gate`` the pair
    ``(result, mean of the gate)``: the counter ``attn_gate_mean``."""

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
    out_init_std: float = 0.0    # of attn_out; 0: the fan-in rule
    rope_rule: Any = None        # a RotaryRule; None: the plain rule
    gate: str = "none"           # one of ATTENTION_GATES

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
                t = rotary(t, positions, self.rope_theta, self.rope_rule)
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
        out_proj = projection(h, self.dtype, "attn_out", self.out_init_std)
        if self.gate == "none":
            return out_proj(out.reshape(b, s, n * d))
        kernel = self.param("gate", dense_kernel_init, (h, n), jnp.float32)
        with jax.named_scope("attn_gate"):
            out, g = gate_heads(out, x, kernel)
            out, gate_mean = out.astype(self.dtype), jnp.mean(g)
        return out_proj(out.reshape(b, s, n * d)), gate_mean


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
    rope_rule: Any = None        # this layer's RotaryRule; None: plain
    attention_gate: str = "none"
    moe_shared_dim: int = 0      # a shared expert's units held here
    routed_scaling: float = 1.0
    out_init_std: float = 0.0    # of attn_out, mlp_out, the shared down

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
                out_init_std=self.out_init_std, rope_rule=self.rope_rule,
                gate=self.attention_gate,
                name=ATTENTION_SCOPES[self.kind],
            )(normed, segment_ids, positions)
        gate_mean = None
        if self.kind != "conv" and self.attention_gate != "none":
            mixed, gate_mean = mixed
        x = x + mixed
        normed = RMSNorm(self.norm_eps, name="ffn_norm")(x)
        # A type-stable counter dict either way (zeros under a dense
        # feed-forward): return values are all that crosses nn.remat.
        counters = {key: jnp.zeros((), jnp.float32) for key in MOE_COUNTERS}
        if self.dense_ffn:
            gate = _dense(self.mlp_dim, self.dtype, "mlp_in")(normed)
            up = _dense(self.mlp_dim, self.dtype, "mlp_up")(normed)
            y = projection(x.shape[-1], self.dtype, "mlp_out",
                           self.out_init_std)(nn.silu(gate) * up)
        else:
            # The router reads what the experts read, or the stream as it
            # entered the layer (before the mixer and its norm).
            route_from = (stream,) if self.router_input == "stream" else ()
            y, counters = DroplessMoE(
                num_experts=self.num_experts, mlp_dim=self.moe_mlp_dim,
                topk=self.expert_topk, groups=self.expert_groups,
                group=self.expert_group, dtype=self.dtype,
                score=self.router_score, activation=self.expert_activation,
                shared_dim=self.moe_shared_dim,
                weight_scale=self.routed_scaling,
                out_init_std=self.out_init_std,
                name="moe",
            )(normed, *route_from)
        if gate_mean is not None:
            counters = {**counters, GATE_COUNTER: gate_mean}
        return x + y.astype(x.dtype), counters


class SoloBlock(nn.Module):
    """``x + sublayer(RMSNorm(x))`` with ONE sublayer (``SOLO_KINDS``):
    the Mamba-2 mixer, attention, or the expert feed-forward.
    ``sublayer`` holds the keyword arguments of that module."""

    kind: str
    sublayer: Any                # kwargs of the kind's module
    norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, x, segment_ids, positions):
        normed = RMSNorm(self.norm_eps, name="norm")(x)
        counters = {key: jnp.zeros((), jnp.float32) for key in MOE_COUNTERS}
        if self.kind == "mamba2_only":
            y = Mamba2Mixer(**self.sublayer, name="mamba")(
                normed, segment_ids)
        elif self.kind == "attention_only":
            y = GroupedQueryAttention(**self.sublayer, name="attn")(
                normed, segment_ids, positions)
            if self.sublayer.get("gate", "none") != "none":
                y, gate_mean = y
                counters = {**counters, GATE_COUNTER: gate_mean}
        else:
            y, counters = DroplessMoE(**self.sublayer, name="moe")(normed)
        return x + y.astype(x.dtype), counters


def layer_has_experts(kinds, num_dense_layers: int, i: int) -> bool:
    """Whether layer ``i`` of a stack of ``kinds`` carries experts."""
    if kinds[i] in SOLO_KINDS:
        return kinds[i] == "experts_only"
    return i >= num_dense_layers


def held_heads(heads: int, groups: int, group: int) -> range:
    """The heads (or B/C groups, or key/value heads) group ``group`` of
    ``groups`` holds: a contiguous run of ``heads // groups``."""
    if groups < 1 or heads % groups or not 0 <= group < groups:
        raise ValueError(
            f"cannot give group {group} of {groups} a whole share of "
            f"{heads} heads")
    n = heads // groups
    return range(group * n, (group + 1) * n)


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
    # One tensor-parallel share: this process holds run ``tensor_group``
    # of ``tensor_groups`` of every mixer's heads (and B/C groups) and of
    # a shared expert's hidden units.
    tensor_groups: int = 1
    tensor_group: int = 0
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_groups: int = 1
    mamba_state: int = 128
    mamba_chunk: int = 128
    moe_latent_dim: int = 0
    moe_shared_dim: int = 0
    routed_scaling: float = 1.0
    out_proj_init_std: float = 0.0
    sliding_num_heads: int = 0   # a window layer's query heads; 0: num_heads
    rope_rule: Any = None        # RotaryRule of the layers at rope_theta
    sliding_rope_theta: float = 0.0  # > 0: the window layers' own rule ...
    sliding_rope_rule: Any = None    # ... which is this one (None: plain)
    attention_gate: str = "none"

    def has_experts(self, i: int) -> bool:
        return layer_has_experts(self.layer_types, self.num_dense_layers, i)

    def tensor_share(self) -> dict | None:
        """Which heads of each mixer (attention's by layer kind where the
        window layers have a head count of their own) and which hidden
        units of the dense feed-forward and of the shared expert this
        process holds, for the run's opening record; None for the whole
        model."""
        if self.tensor_groups == 1:
            return None
        share = {"groups": self.tensor_groups, "group": self.tensor_group}
        held = lambda n: list(held_heads(  # noqa: E731
            n, self.tensor_groups, self.tensor_group))

        def attention(kind):
            heads = self._query_heads(kind)
            return {"heads": heads, "held": held(heads),
                    "kv_heads": self.num_kv_heads,
                    "kv_held": self._kv_heads_held(heads)}

        def units(n):
            run = held_heads(n, self.tensor_groups, self.tensor_group)
            return {"units": n, "held": [run.start, run.stop]}  # half-open

        kinds = set(self.layer_types)
        # the window layers apart where their head count is their own
        own_window = {"sliding_attention"} & kinds \
            if self.sliding_num_heads else set()
        if kinds - {"mamba2_only", "experts_only"} - own_window:
            share["attention"] = attention("full_attention")
        if own_window:
            share["attention_window"] = attention("sliding_attention")
        if "mamba2_only" in kinds:
            share["mamba2"] = {
                "heads": self.mamba_heads, "held": held(self.mamba_heads),
                "bc_groups": self.mamba_groups,
                "bc_held": held(self.mamba_groups)}
        layers = range(len(self.layer_types))
        if any(self.layer_types[i] in LAYER_KINDS
               and not self.has_experts(i) for i in layers):
            share["dense_ffn"] = units(self.mlp_dim)
        if self.moe_shared_dim and any(map(self.has_experts, layers)):
            share["shared_expert"] = units(self.moe_shared_dim)
        return share

    def _query_heads(self, kind: str) -> int:
        """Query heads of an attention layer of ``kind``, uncut."""
        if kind == "sliding_attention" and self.sliding_num_heads:
            return self.sliding_num_heads
        return self.num_heads

    def _kv_heads_held(self, heads: int) -> list:
        """The key/value heads the held of ``heads`` query heads read."""
        per_kv = heads // self.num_kv_heads
        held = held_heads(heads, self.tensor_groups, self.tensor_group)
        return sorted({q // per_kv for q in held})

    def _rotates(self, i: int) -> bool:
        return bool(self.rope_layout[i]) if self.rope_layout else True

    def _rotary(self, kind: str) -> tuple:
        """``(theta, RotaryRule or None)`` of an attention layer of
        ``kind``."""
        if kind == "sliding_attention" and self.sliding_rope_theta:
            return self.sliding_rope_theta, self.sliding_rope_rule
        return self.rope_theta, self.rope_rule

    def _attention_held(self, kind: str = "full_attention") -> tuple:
        """``(query heads, key/value heads, head size)`` of an attention
        layer of ``kind`` here."""
        heads = self._query_heads(kind)
        return (heads // self.tensor_groups,
                len(self._kv_heads_held(heads)),
                self.head_dim or self.hidden_size // self.num_heads)

    def solo_sublayer(self, kind: str, i: int) -> dict:
        """The keyword arguments of the one sublayer of layer ``i``, of a
        ``SOLO_KINDS`` kind, as ``SoloBlock`` takes them."""
        if kind == "mamba2_only":
            return dict(
                heads=self.mamba_heads // self.tensor_groups,
                head_dim=self.mamba_head_dim,
                groups=self.mamba_groups // self.tensor_groups,
                state=self.mamba_state, kernel=self.conv_kernel,
                chunk=self.mamba_chunk, norm_eps=self.norm_eps,
                out_init_std=self.out_proj_init_std, dtype=self.dtype)
        if kind == "attention_only":
            heads, kv_heads, head_dim = self._attention_held()
            return dict(
                num_heads=heads, num_kv_heads=kv_heads,
                rope_theta=self.rope_theta, norm_eps=self.norm_eps,
                dtype=self.dtype, attention_impl=self.attention_impl,
                mesh=self.mesh, head_dim=head_dim, rope=self._rotates(i),
                qk_norm=self.qk_norm, out_init_std=self.out_proj_init_std,
                rope_rule=self.rope_rule, gate=self.attention_gate)
        return dict(
            num_experts=self.num_experts, mlp_dim=self.moe_mlp_dim,
            topk=self.expert_topk, groups=self.expert_groups,
            group=self.expert_group, dtype=self.dtype,
            score=self.router_score, activation=self.expert_activation,
            latent_dim=self.moe_latent_dim,
            # a shared expert is split over the tensor groups by its units
            shared_dim=self.moe_shared_dim // self.tensor_groups,
            weight_scale=self.routed_scaling,
            out_init_std=self.out_proj_init_std)

    def _window_dispatch(self, seq_len: int):
        """The kernels and tiles the window layers' calls take on rows
        of ``seq_len``; None for a model without such layers or without
        the kernels."""
        if ("sliding_attention" not in self.layer_types
                or self.attention_impl != "pallas"):
            return None
        from distributed_tensorflow_framework_tpu.ops import flash_attention

        return flash_attention.select_dispatch(
            seq_len, seq_len, self.dtype,
            self._attention_held("sliding_attention")[2])

    def window_block_share(self, seq_len: int) -> float | None:
        """Visited ÷ causal (q-block, k-block) visits of the window
        layers' kernels on rows of ``seq_len``, at the tiles the kernels
        take (``ops/flash_attention.window_block_counts``: a static
        count, segments aside); None for a model without such layers or
        without the kernels."""
        tile = self._window_dispatch(seq_len)
        if tile is None:
            return None
        from distributed_tensorflow_framework_tpu.ops import flash_attention

        visited, causal = flash_attention.window_block_counts(
            seq_len, seq_len, tile.bwd_block_q, tile.bwd_block_k,
            self.sliding_window)
        return visited / causal

    def window_grid_share(self, seq_len: int) -> float | None:
        """Visited ÷ launched programs of the window layers' forward and
        backward kernels on rows of ``seq_len``
        (``ops/flash_attention.window_grid``: how much of the grid a
        window call builds does work; static like
        ``window_block_share``, and None where it is)."""
        tile = self._window_dispatch(seq_len)
        if tile is None:
            return None
        from distributed_tensorflow_framework_tpu.ops import flash_attention

        grid = flash_attention.window_grid(seq_len, seq_len,
                                           self.sliding_window, tile)
        return grid["visited"] / grid["launched"]

    def expert_share(self) -> dict | None:
        """Which experts this process holds and of how many groups, for
        the run's opening record; None for a stack of dense layers."""
        if not any(map(self.has_experts, range(len(self.layer_types)))):
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
        block_cls, solo_cls = Lfm2Block, SoloBlock
        if self.remat:
            policy = self.ckpt_policy
            if policy is None:
                # Keep what the attention backward kernels read and what
                # the expert layer's routing decided, so neither the
                # forward kernel nor the top-k, the chosen scores' gather
                # and the sorts are run again (module docstring).
                from distributed_tensorflow_framework_tpu.ops.flash_attention import (
                    RESIDUAL_NAMES,
                )

                policy = jax.checkpoint_policies.save_only_these_names(
                    *RESIDUAL_NAMES, *ROUTING_NAMES)
            block_cls = nn.remat(Lfm2Block, policy=policy)
            solo_cls = nn.remat(SoloBlock, policy=policy)
        totals = {key: jnp.zeros((), jnp.float32) for key in MOE_COUNTERS}
        n_moe = 0
        gates = []               # the gated attention layers' gate means
        for i, kind in enumerate(self.layer_types):
            if kind in SOLO_KINDS:
                block = solo_cls(
                    kind=kind, sublayer=self.solo_sublayer(kind, i),
                    norm_eps=self.norm_eps, name=f"layer{i}")
            else:
                heads, kv_heads, head_dim = self._attention_held(kind)
                rope_theta, rope_rule = self._rotary(kind)
                block = block_cls(
                    kind=kind, dense_ffn=i < self.num_dense_layers,
                    num_heads=heads, num_kv_heads=kv_heads,
                    mlp_dim=self.mlp_dim // self.tensor_groups,
                    moe_mlp_dim=self.moe_mlp_dim,
                    num_experts=self.num_experts,
                    expert_topk=self.expert_topk,
                    expert_groups=self.expert_groups,
                    expert_group=self.expert_group,
                    conv_kernel=self.conv_kernel, rope_theta=rope_theta,
                    norm_eps=self.norm_eps, dtype=self.dtype,
                    attention_impl=self.attention_impl, mesh=self.mesh,
                    head_dim=head_dim, sliding_window=self.sliding_window,
                    rope=self._rotates(i), qk_norm=self.qk_norm,
                    router_input=self.router_input,
                    router_score=self.router_score,
                    expert_activation=self.expert_activation,
                    rope_rule=rope_rule, attention_gate=self.attention_gate,
                    moe_shared_dim=self.moe_shared_dim // self.tensor_groups,
                    routed_scaling=self.routed_scaling,
                    out_init_std=self.out_proj_init_std,
                    name=f"layer{i}")
            x, counters = block(x, segment_ids, positions)
            if self.has_experts(i):
                totals = {key: totals[key] + counters[key]
                          for key in MOE_COUNTERS}
                n_moe += 1
            if GATE_COUNTER in counters:
                gates.append(counters[GATE_COUNTER])
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
            counters["attn_window_grid_share"] = jnp.float32(
                self.window_grid_share(input_ids.shape[1]))
        if gates:
            counters["attn_gate_mean"] = sum(gates) / len(gates)
        if "mamba2_only" in self.layer_types:
            # Document starts the scan resets at in these rows (the
            # row's first token is one).
            counters["ssm_resets"] = jnp.sum(
                document_starts(segment_ids) & (segment_ids > 0)
            ).astype(jnp.float32)
        if not counters:
            return logits
        return {"logits": logits, **counters}


def build(config, *, mesh=None, dtype=jnp.bfloat16, ckpt_policy=None):
    """``ModelConfig`` -> module, with the family's own checks."""
    kinds = tuple(config.layer_types)
    all_kinds = LAYER_KINDS + SOLO_KINDS
    if len(kinds) != config.num_layers or set(kinds) - set(all_kinds):
        raise ValueError(
            f"model.layer_types must name one of {all_kinds} for each of "
            f"model.num_layers={config.num_layers} layers, got {kinds}")
    heads = config.num_heads
    kv_heads = config.num_kv_heads or heads
    if heads % kv_heads:
        raise ValueError(f"model.num_heads={heads} is no multiple of "
                         f"model.num_kv_heads={kv_heads}")
    if config.sliding_num_heads % kv_heads:
        raise ValueError(
            f"model.sliding_num_heads={config.sliding_num_heads} (a window "
            f"layer's query heads) is no multiple of "
            f"model.num_kv_heads={kv_heads}")
    if config.attention_gate not in ATTENTION_GATES:
        raise ValueError(f"model.attention_gate must be one of "
                         f"{ATTENTION_GATES}, got {config.attention_gate!r}")
    rope_rule, sliding_rope_rule = _rotary_rules(
        config, config.head_dim or config.hidden_size // heads)
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
    has_experts = any(layer_has_experts(kinds, config.num_dense_layers, i)
                      for i in range(len(kinds)))
    if has_experts and not (config.num_experts > 0 and config.moe_mlp_dim > 0
                            and 1 <= config.expert_topk <= config.num_experts):
        raise ValueError(
            "layers past model.num_dense_layers carry experts: set "
            "model.num_experts, model.moe_mlp_dim and 1 <= "
            "model.expert_topk <= model.num_experts")
    if has_experts:
        check_expert_settings(config.router_score, config.expert_activation)
    if config.out_proj_init_std and "conv" in kinds:
        raise ValueError(
            f"model.out_proj_init_std is wired for the attention kinds and "
            f"the one-sublayer kinds {SOLO_KINDS}; a conv layer's "
            f"out-projection keeps the fan-in rule")
    _check_tensor_share(config, kinds, heads, kv_heads)
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
        expert_activation=config.expert_activation,
        tensor_groups=config.tensor_groups, tensor_group=config.tensor_group,
        mamba_heads=config.mamba_num_heads,
        mamba_head_dim=config.mamba_head_dim,
        mamba_groups=config.mamba_groups, mamba_state=config.ssm_state_size,
        mamba_chunk=config.mamba_chunk,
        moe_latent_dim=config.moe_latent_dim,
        moe_shared_dim=config.moe_shared_dim,
        routed_scaling=config.routed_scaling,
        out_proj_init_std=config.out_proj_init_std,
        sliding_num_heads=config.sliding_num_heads,
        rope_rule=rope_rule, sliding_rope_theta=config.sliding_rope_theta,
        sliding_rope_rule=sliding_rope_rule,
        attention_gate=config.attention_gate)


def _rotary_rules(config, head_dim: int) -> tuple:
    """``(rule of the layers at rope_theta, rule of the window layers at
    sliding_rope_theta)``, each None for the plain rule, refused where a
    setting cannot be a rule."""
    def rotated(fraction: float, name: str) -> None:
        dims = head_dim * fraction
        if not 0.0 < fraction <= 1.0 or dims != int(dims) or int(dims) % 2:
            raise ValueError(
                f"model.{name}={fraction} must leave an even number of a "
                f"head's {head_dim} dims to rotate (0 < fraction <= 1)")

    rotated(config.rope_fraction, "rope_fraction")
    rotated(config.sliding_rope_fraction, "sliding_rope_fraction")
    if config.rope_yarn_factor and not (
            config.rope_yarn_factor >= 1.0
            and config.rope_yarn_original_len > 0
            and config.rope_yarn_beta_fast > config.rope_yarn_beta_slow > 0):
        raise ValueError(
            "model.rope_yarn_factor > 0 (YaRN frequencies) needs a factor "
            ">= 1, model.rope_yarn_original_len > 0 and "
            "model.rope_yarn_beta_fast > model.rope_yarn_beta_slow > 0, got "
            f"{config.rope_yarn_factor}, {config.rope_yarn_original_len}, "
            f"{config.rope_yarn_beta_fast}, {config.rope_yarn_beta_slow}")
    if config.rope_attention_factor <= 0 or config.sliding_rope_theta < 0:
        raise ValueError(
            "model.rope_attention_factor must be positive and "
            "model.sliding_rope_theta zero (the window layers share the "
            "rule at model.rope_theta) or a theta of their own, got "
            f"{config.rope_attention_factor}, {config.sliding_rope_theta}")
    yarn = dict(
        yarn_factor=float(config.rope_yarn_factor),
        yarn_original_len=int(config.rope_yarn_original_len),
        yarn_beta_fast=float(config.rope_yarn_beta_fast),
        yarn_beta_slow=float(config.rope_yarn_beta_slow)) \
        if config.rope_yarn_factor else {}
    rule = RotaryRule(
        fraction=float(config.rope_fraction),
        attention_factor=float(config.rope_attention_factor), **yarn)
    sliding = RotaryRule(fraction=float(config.sliding_rope_fraction))
    plain = RotaryRule()
    return (None if rule == plain else rule,
            None if sliding == plain else sliding)


def _check_tensor_share(config, kinds, heads: int, kv_heads: int) -> None:
    """A tensor share is whole groups: whole heads for every chip, by
    layer kind, no key/value head's queries or B/C group's heads split
    unevenly, the dense feed-forward's and the shared expert's units in
    even runs."""
    groups, group = config.tensor_groups, config.tensor_group
    if "mamba2_only" in kinds:
        m_heads, m_groups = config.mamba_num_heads, config.mamba_groups
        if m_heads < 1 or m_groups < 1 or m_heads % m_groups:
            raise ValueError(
                f"a mamba2_only layer needs model.mamba_num_heads (got "
                f"{m_heads}) as a multiple of model.mamba_groups (got "
                f"{m_groups})")
    if groups == 1 and group == 0:
        return
    if groups < 1 or not 0 <= group < groups:
        raise ValueError(f"model.tensor_group={group} is not one of "
                         f"model.tensor_groups={groups}")
    if "conv" in kinds:
        raise ValueError(
            f"model.tensor_groups > 1 is wired for the attention kinds and "
            f"the one-sublayer kinds {SOLO_KINDS}; a conv layer's gated "
            f"convolution has no such split")
    # query heads of each kind of attention layer present
    by_kind = {(config.sliding_num_heads or heads)
               if kind == "sliding_attention" else heads
               for kind in kinds if kind in (*ATTENTION_SCOPES,
                                             "attention_only")}
    for n in sorted(by_kind):
        if n % groups or (kv_heads % groups and groups % kv_heads):
            raise ValueError(
                f"model.tensor_groups={groups} does not divide attention's "
                f"{n} query heads over {kv_heads} key/value heads into "
                f"whole, even shares")
    layers = range(len(kinds))
    has_experts = [layer_has_experts(kinds, config.num_dense_layers, i)
                   for i in layers]
    if any(has_experts) and config.moe_shared_dim % groups:
        raise ValueError(
            f"model.tensor_groups={groups} does not divide the shared "
            f"expert's {config.moe_shared_dim} units "
            f"(model.moe_shared_dim)")
    if config.mlp_dim % groups and any(
            kinds[i] in LAYER_KINDS and not has_experts[i] for i in layers):
        raise ValueError(
            f"model.tensor_groups={groups} does not divide the dense "
            f"feed-forward's {config.mlp_dim} units (model.mlp_dim)")
    if "mamba2_only" in kinds and config.mamba_groups % groups:
        raise ValueError(
            f"model.tensor_groups={groups} splits a B/C group of the Mamba-2 "
            f"layers (model.mamba_groups={config.mamba_groups}): its gated "
            f"norm would cross chips")
