"""Kimi Linear causal language model reference (moonshotai
Kimi-Linear-48B-A3B, ``model_type: kimi_linear``): forward, next-token
loss and gradient norm in plain float32 ``jax.numpy``, written from the
layer equations (the Kimi Linear report, arXiv:2510.26692, and the
family's published ``KimiDeltaAttention``), reading the program's
parameter tree by name and importing nothing from it.

Layer ``l``, ``x`` the stream entering it (T tokens x hidden), every norm
an RMSNorm with a learned scale and ``rms_norm_eps``, no bias anywhere,
``u = RMSNorm(x)``; the layer's mixer by ``layer_types``:

- ``kda`` (Kimi Delta Attention), ``n`` heads of ``d =
  linear_attn_config.head_dim``: ``q = silu(conv(u W_q))``, ``k =
  silu(conv(u W_k))``, ``v = silu(conv(u W_v))``, each ``conv`` depthwise
  and causal over ``short_conv_kernel_size`` taps without a bias
  (``nemotron_h.py``'s, ``c_t = sum_j taps[j] a_{t-j}``) reading zero
  before the document's start; q and k divided by
  ``sqrt(sum of squares + 1e-6)`` per head; ``g = -exp(A_log_h)
  softplus(u W_fa W_fb + dt_bias)`` per key channel, ``beta =
  sigmoid(u W_b)`` per head. Then, a token at a time, the state ``S``
  (d x d a head) zeroed at each document's start::

      S <- Diag(exp g_t) S
      S <- S + beta_t k_t (v_t - S^T k_t)^T
      o_t = S^T q_t / sqrt(d)

  NOT the program's chunked scan. ``y = RMSNorm_d(o) * w * sigmoid(u
  W_ga W_gb)`` per head, then ``W_o``.
- ``latent_attention``: ``deepseek_v3.py``'s multi-head latent attention
  with nothing rotated (``mla_use_nope``): it is called with every
  position 0, where its rotation turns by an angle of 0, the identity
  exactly (cos 1, sin 0), so ``q_h = [q_n,h ; q_r,h]``, ``k_h = [k_n,h ;
  k_r]`` with the one unrotated ``k_r`` in every head, causal inside the
  document, ``softmax(q k^T / sqrt(qk_nope_head_dim + qk_rope_head_dim))
  v``, then ``W_o``.
- feed-forward, ``m = RMSNorm(h)``: the first ``num_dense_layers``
  (``first_k_dense_replace``) layers a SwiGLU; the others
  ``deepseek_v3.py``'s expert layer: sigmoid scores over all
  ``experts_routed`` experts, the ``num_experts_per_token`` largest of
  score + selection bias chosen, the chosen scores renormalised
  (``moe_renormalize``, ``router_norm_eps`` the source's 1e-20) and scaled
  by ``routed_scaling_factor``, beside one unscaled shared SwiGLU on
  ``m``.
- a final RMSNorm and an untied head; loss: mean next-token
  cross-entropy over the labelled positions.

The share (``hparams``): ``layer_types`` and ``num_dense_layers`` are the
layers present; ``kda_heads_held`` and ``heads_held`` the heads of a
``kda`` and of a latent attention layer the tree holds (a layer computes
its held heads' part of ``W_o``'s sum; ``W_fa``, ``W_ga``, the output
norm's scale, ``W_kva`` and the latent norm are whole); the units and
experts held as in ``deepseek_v3.py``. What the other heads, units and
experts would add is left out.

Long rows: the recurrence in blocks of ``TOKEN_BLOCK`` tokens, an outer
scan over blocks under ``jax.checkpoint`` around an inner scan over the
block's tokens, so that the backward pass keeps one state a block (a
state a token would be 4.3 GB a layer at 8192 tokens); the same
recurrence, computed in blocks so that it fits. Each layer's mixer and
feed-forward, and the head with the loss, is a program of its own under
``jax.checkpoint``, differentiated from outside (``loss_and_grad_norm``),
so that alike layers share one compiled program: the four ``kda`` mixers
one, the four expert layers one. That keeps what the check compiles
within what the machine's compile cache holds beside the program's two
steps; the sums are the same.

The control (``hparams["dtype"]``, ``benchmarks/tools/check_control.py``):
the same equations with the parameters cast to that type and nothing
lifted back to float32 up to the logits, the recurrence's state
included; the loss from the logits on is float32, as the program's
(``train/losses.py`` casts its logits), so that the loss does not read
the rounding of its own scalar. It is never what ``correct`` compares
with; it is the reading a cell's limits have to refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import deepseek_v3, global_norm
from benchmarks.reference.laguna import swiglu
from benchmarks.reference.nemotron_h import causal_conv
from benchmarks.reference.smallthinker import rms_norm

L2_EPS = 1e-6
TOKEN_BLOCK = 64


def recurrence_by_token(q, k, v, g, beta, segments):
    """The gated delta rule a token at a time: ``q``, ``k``, ``g`` (B, T,
    n, d), ``v`` (B, T, n, d_v), ``beta`` (B, T, n); (B, T, n, d_v).
    Blocks of ``TOKEN_BLOCK`` tokens; padding after the row's last token
    neither decays nor writes."""
    bsz, t, n, d = q.shape
    first = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool),
         segments[:, 1:] != segments[:, :-1]], axis=1)
    pad = -t % TOKEN_BLOCK
    xs = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
          for a in (q, k, v, g, beta, first)]
    blocks = (t + pad) // TOKEN_BLOCK
    xs = [jnp.moveaxis(a, 1, 0).reshape(blocks, TOKEN_BLOCK, *a.shape[:1],
                                         *a.shape[2:]) for a in xs]

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t, first_t = at
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        state = jnp.exp(g_t)[..., None] * state
        error = v_t - jnp.einsum("bnkv,bnk->bnv", state, k_t)
        state = state + (b_t[..., None] * k_t)[..., None] * error[..., None, :]
        return state, jnp.einsum("bnkv,bnk->bnv", state, q_t) / math.sqrt(d)

    @jax.checkpoint
    def block(state, at):
        return jax.lax.scan(token, state, at)

    state = jnp.zeros((bsz, n, d, v.shape[-1]), q.dtype)
    _, out = jax.lax.scan(block, state, tuple(xs))
    out = out.reshape(blocks * TOKEN_BLOCK, *out.shape[2:])
    return jnp.moveaxis(out, 0, 1)[:, :t]


def kda(p, u, segments, h, *, recurrence=recurrence_by_token):
    bsz, t, _ = u.shape
    n, d = len(h["kda_heads_held"]), h["linear_attn_config"]["head_dim"]

    def branch(name):
        a = causal_conv(p[f"{name}_conv1d"], 0.0,
                        u @ p[f"{name}_proj"]["kernel"], segments)
        return jax.nn.silu(a).reshape(bsz, t, n, d)

    q, k, v = branch("q"), branch("k"), branch("v")
    q, k = (a * jax.lax.rsqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                              + L2_EPS) for a in (q, k))
    f = (u @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(bsz, t, n, d) + p["dt_bias"].reshape(n, d))
    beta = jax.nn.sigmoid(u @ p["b_proj"]["kernel"])
    o = recurrence(q, k, v, g, beta, segments)
    gate = (u @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]
    o = rms_norm(p["o_norm"], o, h["rms_norm_eps"]) * jax.nn.sigmoid(
        gate.reshape(bsz, t, n, d))
    return o.reshape(bsz, t, n * d) @ p["o_proj"]["kernel"]


def _deepseek_hparams(h: dict) -> dict:
    """What ``deepseek_v3.py``'s attention and expert layer read, in its
    names."""
    return {**h, "rope_interleave": False,
            "num_experts_per_tok": h["num_experts_per_token"]}


def latent_attention(p, u, segments, h):
    """``deepseek_v3.py``'s latent attention at position 0 for every
    token: its rotation by an angle of 0 is the identity."""
    return deepseek_v3.attention(p, u, segments, jnp.zeros_like(segments),
                                 _deepseek_hparams(h))


def expert_layer(p, m, h):
    return deepseek_v3.expert_layer(p, m, _deepseek_hparams(h))


def mixer(p, x, segments, *, kind, h):
    u = rms_norm(p["mixer_norm"]["scale"], x, h["rms_norm_eps"])
    if kind == "kda":
        return x + kda(p["kda"], u, segments, h)
    assert kind == "latent_attention", kind
    return x + latent_attention(p["mla"], u, segments, h)


def feed_forward(p, x, *, dense, h):
    m = rms_norm(p["ffn_norm"]["scale"], x, h["rms_norm_eps"])
    if dense:
        assert p["mlp_up"]["kernel"].shape[-1] == h["dense_units_held"]
        return x + swiglu(p["mlp_in"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_out"]["kernel"], m)
    return x + expert_layer(p["moe"], m, h)


def head_loss(p, x, targets, *, h):
    """The final norm, the untied head and the mean next-token
    cross-entropy over the labelled positions, float32 from the logits
    on, as the program's loss takes them."""
    x = rms_norm(p["final_norm"]["scale"], x, h["rms_norm_eps"])
    logp = jax.nn.log_softmax((x @ p["lm_head"].T).astype(jnp.float32),
                              axis=-1)
    labelled = (targets >= 0).astype(logp.dtype)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * labelled) / jnp.maximum(jnp.sum(labelled), 1.0)


KEEP = ("layer_types", "num_dense_layers", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
        "rope_theta", "num_experts_per_token", "routed_scaling_factor",
        "router_norm_eps", "experts_routed", "experts_held", "heads_held",
        "kda_heads_held", "dense_units_held", "shared_units_held")
MIXER_PARAMS = {"kda": "kda", "latent_attention": "mla"}


def _static(h: dict) -> tuple:
    """What the equations read of ``h``, hashable."""
    static = {k: (tuple(h[k]) if isinstance(h[k], list) else h[k])
              for k in KEEP}
    static["linear_attn_config"] = (
        ("head_dim", h["linear_attn_config"]["head_dim"]),)
    return tuple(sorted(static.items()))


@functools.lru_cache(maxsize=32)
def _part(name: str, option, static: tuple):
    """One sublayer (``mixer`` of a kind, ``feed_forward`` dense or not)
    or the head, as a program of its own under ``jax.checkpoint``: the
    layers that are alike run one compiled program between them, so the
    check compiles three sublayers and the head, not five layers."""
    h = dict(static)
    h["linear_attn_config"] = dict(h["linear_attn_config"])
    part = {"mixer": functools.partial(mixer, kind=option),
            "feed_forward": functools.partial(feed_forward, dense=option),
            "head_loss": head_loss}[name]
    return jax.jit(jax.checkpoint(functools.partial(part, h=h)))


def loss(params, batch, h):
    static = _static(h)
    ids = batch["input_ids"]
    segments = batch.get("segment_ids", jnp.ones_like(ids))
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(h["layer_types"]):
        p = params[f"layer{i}"]
        mine = {k: p[k] for k in ("mixer_norm", MIXER_PARAMS[kind])}
        x = _part("mixer", kind, static)(mine, x, segments)
        x = _part("feed_forward", i < h["num_dense_layers"], static)(
            {k: v for k, v in p.items() if k not in mine}, x)
    return _part("head_loss", None, static)(
        {k: params[k] for k in ("final_norm", "lm_head")}, x,
        batch["targets"])


def loss_and_grad_norm(params, batch, hparams):
    """Autodiff outside any one program: each part's forward and backward
    compile once, whatever number of layers calls it."""
    dtype = jnp.dtype(hparams.get("dtype", "float32"))
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    value, grads = jax.value_and_grad(loss)(params, batch, hparams)
    return value, jax.jit(global_norm)(grads)
