"""DeepSeek-V3-family causal language model reference (kakaocorp
kanana-2-30b-a3b, ``model_type: deepseek_v3``, ``q_lora_rank`` null):
forward, next-token loss and gradient norm in plain float32
``jax.numpy``, written from the layer equations as ``transformers``'
``modeling_deepseek_v3.py`` computes them, reading the program's
parameter tree by name and importing nothing from it.

Layer ``l``, ``x`` the stream entering it (T tokens x hidden), every norm
an RMSNorm with a learned scale and ``rms_norm_eps``, no bias anywhere:

- multi-head latent attention, ``u = RMSNorm(x)``: ``q = u W_q`` as
  heads of ``qk_nope_head_dim + qk_rope_head_dim``, split into ``q_n``
  and ``q_r``; ``[c, k_r] = u W_kva`` with ``c`` of ``kv_lora_rank`` and
  ONE ``k_r`` of ``qk_rope_head_dim`` a token; ``[k_n, v] = RMSNorm(c)
  W_kvb`` as heads of ``qk_nope_head_dim + v_head_dim``. ``q_r`` and
  ``k_r`` rotate by the position inside the packed document: with
  ``rope_interleave`` the source first de-interleaves them (dims ``2i``
  go first, dims ``2i + 1`` after) and then applies the half rotation,
  so the pair ``(2i, 2i + 1)`` turns by ``p theta^(-2i / rope_dim)``.
  ``q_h = [q_n,h ; rot(q_r,h)]``, ``k_h = [k_n,h ; rot(k_r)]`` (the same
  rotated key in every head). A query at ``i`` sees a key at ``j`` iff
  ``j <= i`` and the same document; ``o_h = softmax(q_h k_h^T /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)) v_h``; ``h = x + concat_h(o_h)
  W_o``.
- feed-forward, ``m = RMSNorm(h)``: the first ``num_dense_layers``
  (``first_k_dense_replace``) layers ``x' = h + W_d (silu(W_g' m) * W_u
  m)``; the others ``s = sigmoid(m W_r)`` over all routed experts, the
  token's experts the ``num_experts_per_tok`` largest of ``s + b`` (the
  selection bias, in the choice only; ``n_group`` is 1, so the group
  limit chooses nothing), ``w_e = routed_scaling_factor s_e / (sum_chosen
  s + 1e-20)`` (``norm_topk_prob``; ``router_norm_eps`` is the source's
  1e-20), and ``x' = h + sum_chosen w_e W2_e (silu(W1_e m) * W3_e m) +
  V_d (silu(V_g m) * V_u m)``: the ``n_shared_experts`` shared experts are
  one SwiGLU of their summed width on ``m``, unscaled and without a gate.
  Nothing is dropped: every held expert runs on every token here and the
  weight is zero where it was not chosen.
- a final RMSNorm and an untied head; loss: mean next-token
  cross-entropy over the labelled positions.

The share (``hparams``): ``layer_types`` and ``num_dense_layers`` are the
layers present; ``heads_held`` names the heads the tree holds (a layer
computes its held heads' part of ``W_o``'s sum; ``W_kva`` and the latent
norm are whole); ``dense_units_held`` and ``shared_units_held`` say how
many hidden units of the dense feed-forward and of the shared experts the
tree holds (a gated unit is elementwise in them, so the shares' parts add
up to the whole); ``experts_held`` the experts whose weights the tree
holds (row ``i`` of ``moe/w1`` is expert ``experts_held[i]``): the router
scores all ``experts_routed`` and only held experts add to the result.
What the other heads, units and experts would add is left out. The
vocabulary is the rows the embedding and the head have.

Long rows: attention one block of queries at a time, every layer and
every expert under ``jax.checkpoint``, so that a row of 16,384 fits
beside the trainer's state.

The control (``hparams["dtype"]``, ``benchmarks/tools/check_control.py``):
the same equations with the parameters cast to that type and nothing
lifted back to float32, router, norms, rotary products and loss included:
what the reference reads one precision below the configuration's. It is
never what ``correct`` compares with; it is the reading a cell's limits
have to refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import global_norm
from benchmarks.reference.laguna import swiglu
from benchmarks.reference.smallthinker import (
    positions_of, rms_norm, rotate_half)

QUERY_BLOCK = 512


def rope(x, positions, h):
    """``x`` (B, T, heads, rope_dim) rotated as the source does it:
    de-interleaved where ``rope_interleave`` says so, then the half
    rotation at ``rope_theta``."""
    d = x.shape[-1]
    if h["rope_interleave"]:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv_freq = float(h["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[:, :, None, :]
    return (x * jnp.cos(angle) + rotate_half(x) * jnp.sin(angle)).astype(
        x.dtype)


def attention(p, u, segments, positions, h):
    b, s, _ = u.shape
    n = len(h["heads_held"])
    d_n, d_r, d_v = (h["qk_nope_head_dim"], h["qk_rope_head_dim"],
                     h["v_head_dim"])
    rank = h["kv_lora_rank"]
    q = (u @ p["q_proj"]["kernel"]).reshape(b, s, n, d_n + d_r)
    q_n, q_r = q[..., :d_n], q[..., d_n:]
    latent = u @ p["kv_a_proj"]["kernel"]
    c, k_r = latent[..., :rank], latent[..., rank:]
    c = rms_norm(p["kv_a_norm"]["scale"], c, h["rms_norm_eps"])
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(b, s, n, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    q_r = rope(q_r, positions, h)
    k_r = rope(k_r[:, :, None, :], positions, h)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (b, s, n, d_r))],
                        axis=-1)
    block = min(QUERY_BLOCK, s)
    key_at = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(segments, start, block, axis=1)
        query_at = start + jnp.arange(block)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) / math.sqrt(d_n + d_r)
        allowed = (query_at[:, None] >= key_at[None, :])[None, None] & (
            sb[:, None, :, None] == segments[:, None, None, :])
        scores = jnp.where(allowed, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("bnqk,bknd->bqnd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, n * d_v)
    return out @ p["o_proj"]["kernel"]


def route(p, tokens, h):
    """``(chosen (T, K), weights (T, K))``: sigmoid scores over all routed
    experts, the K largest of score + selection bias, the scores
    renormalised over them and scaled."""
    scores = jax.nn.sigmoid(tokens @ p["gate"])             # (T, routed)
    assert scores.shape[-1] == h["experts_routed"]
    _, chosen = jax.lax.top_k(scores + p["expert_bias"],
                              h["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, h["routed_scaling_factor"] * picked / (
        picked.sum(axis=-1, keepdims=True) + h["router_norm_eps"])


def expert_layer(p, m, h):
    """``sum_chosen w_e expert_e(m)`` over the held experts (a dense
    loop, each held expert on every token, weighted by its router weight,
    zero where the token did not choose it) plus the shared experts."""
    b, s, hidden = m.shape
    tokens = m.reshape(b * s, hidden)
    chosen, weights = route(p, tokens, h)
    held = jnp.asarray(h["experts_held"], jnp.int32)

    @jax.checkpoint
    def one_expert(total, xs):
        expert_id, w_gate, w_up, w_down = xs
        coef = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
        return total + coef[:, None] * swiglu(w_gate, w_up, w_down,
                                              tokens), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                            (held, p["w1"], p["w3"], p["w2"]))
    shared = p["shared"]
    assert shared["up"]["kernel"].shape[-1] == h["shared_units_held"]
    total = total + swiglu(shared["gate"]["kernel"], shared["up"]["kernel"],
                           shared["down"]["kernel"], tokens)
    return total.reshape(b, s, hidden)


def block(p, x, segments, positions, *, dense, h):
    eps = h["rms_norm_eps"]
    u = rms_norm(p["mixer_norm"]["scale"], x, eps)
    x = x + attention(p["mla"], u, segments, positions, h)
    m = rms_norm(p["ffn_norm"]["scale"], x, eps)
    if dense:
        assert p["mlp_up"]["kernel"].shape[-1] == h["dense_units_held"]
        return x + swiglu(p["mlp_in"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_out"]["kernel"], m)
    return x + expert_layer(p["moe"], m, h)


def logits(params, batch, h):
    ids = batch["input_ids"]
    segments = batch.get("segment_ids", jnp.ones_like(ids))
    positions = batch.get("positions", positions_of(segments))
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(h["layer_types"]):
        assert kind == "latent_attention"
        layer = jax.checkpoint(functools.partial(
            block, dense=i < h["num_dense_layers"], h=h))
        x = layer(params[f"layer{i}"], x, segments, positions)
    x = rms_norm(params["final_norm"]["scale"], x, h["rms_norm_eps"])
    return x @ params["lm_head"].T


def loss(params, batch, h):
    targets = batch["targets"]
    logp = jax.nn.log_softmax(logits(params, batch, h), axis=-1)
    labelled = (targets >= 0).astype(logp.dtype)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * labelled) / jnp.maximum(jnp.sum(labelled), 1.0)


KEEP = ("layer_types", "num_dense_layers", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "rms_norm_eps",
        "rope_theta", "rope_interleave", "num_experts_per_tok",
        "routed_scaling_factor", "router_norm_eps", "experts_routed",
        "experts_held", "heads_held", "dense_units_held",
        "shared_units_held")


@functools.lru_cache(maxsize=8)
def _compiled(static_items: tuple):
    h = dict(static_items)

    @jax.jit
    def run(params, batch):
        value, grads = jax.value_and_grad(loss)(params, batch, h)
        return value, global_norm(grads)

    return run


def loss_and_grad_norm(params, batch, hparams):
    dtype = jnp.dtype(hparams.get("dtype", "float32"))
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    static = {k: (tuple(hparams[k]) if isinstance(hparams[k], list)
                  else hparams[k]) for k in KEEP}
    return _compiled(tuple(sorted(static.items())))(params, batch)
