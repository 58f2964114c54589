"""LFM2-MoE causal language model reference (LiquidAI LFM2-8B-A1B,
``model_type: lfm2_moe``): forward, next-token loss and gradient norm in
plain float32 ``jax.numpy``, written from the layer equations, reading
the program's parameter tree by name and importing nothing from it.

Block, pre-norm: ``h = x + mixer(RMSNorm(x))``, ``y = h + ffn(RMSNorm(h))``,
RMSNorm epsilon ``norm_eps``; a final RMSNorm and a head tied to the token
embedding; loss: mean next-token cross-entropy over the labelled positions.

``conv`` mixer: ``[B, C, u] = split(x W_in)``; ``v = B * u``;
``c_t = sum_j w_j * v_{t-j}`` (depthwise, causal, ``conv_L_cache`` taps);
``out = (C * c) W_out``. ``full_attention`` mixer: q over the query heads,
k and v over the key/value heads, no bias; RMSNorm over each head on q and
k; rotary positions (half rotation) on the whole head; causal
``softmax(q k^T / sqrt(d)) v``, each key/value head serving its group of
query heads; ``W_o``. Dense ffn: ``W2(silu(W1 x) * W3 x)``; expert ``e``
the same at the expert width. Router: ``s = sigmoid(x W_g)``; the
``num_experts_per_tok`` experts are the largest of ``s + b``; weights
``s_e / (sum of the chosen s + router_norm_eps)``; the layer adds
``sum_chosen w_e expert_e(x)``. Nothing is dropped: every expert runs on
every token here and the weight is zero where it was not chosen.

The share (``hparams``): ``layer_types`` and ``num_dense_layers`` are the
layers present; ``experts_held`` the experts whose weights the tree holds
(row ``i`` of ``moe/w1`` is expert ``experts_held[i]``): the router scores
all ``experts_routed`` and only held experts add to the result; the
vocabulary is the rows the embedding has. Packed rows (``segment_ids``, 0 on
padding): attention stays inside a document, ``positions`` restart at each
document, a convolution tap that would reach another document reads zero.

Long rows: attention one block of queries at a time, every layer and every
expert under ``jax.checkpoint``, so that a row of 8192 fits beside the
trainer's state.

The control (``hparams["dtype"]``, ``benchmarks/tools/check_control.py``):
the same equations with the parameters cast to that type and nothing
lifted back to float32, router, norms, rotary products and loss
included: what the reference reads one precision below the
configuration's. It is never what ``correct`` compares with; it is the
reading a cell's limits have to refuse.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import global_norm

QUERY_BLOCK = 512


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def positions_of(segments):
    idx = jnp.arange(segments.shape[1], dtype=jnp.int32)[None, :]
    new_doc = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool),
         segments[:, 1:] != segments[:, :-1]], axis=1)
    return idx - jax.lax.cummax(jnp.where(new_doc, idx, 0), axis=1)


def short_conv(p, x, segments):
    gates = x @ p["in_proj"]["kernel"]
    b_gate, c_gate, u = jnp.split(gates, 3, axis=-1)
    v = b_gate * u
    taps = p["conv_kernel"]                              # (L, C)
    idx = jnp.arange(x.shape[1])[None, :]
    conv = jnp.zeros_like(v)
    for j in range(taps.shape[0]):
        source = jnp.roll(v, j, axis=1)                  # v_{t-j}
        same_doc = (idx >= j) & (jnp.roll(segments, j, axis=1) == segments)
        conv = conv + jnp.where(same_doc[..., None], source, 0.0) * taps[j]
    return (c_gate * conv) @ p["out_proj"]["kernel"]


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x, positions, theta):
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[:, :, None, :]
    return (x * jnp.cos(angle) + rotate_half(x) * jnp.sin(angle)).astype(
        x.dtype)


def attention(p, x, segments, positions, h):
    b, s, _ = x.shape
    n, nkv, d = (h["num_attention_heads"], h["num_key_value_heads"],
                 h["head_dim"])
    q = (x @ p["query"]["kernel"]).reshape(b, s, n, d)
    k = (x @ p["key"]["kernel"]).reshape(b, s, nkv, d)
    v = (x @ p["value"]["kernel"]).reshape(b, s, nkv, d)
    q = rope(rms_norm(p["q_norm"]["scale"], q, h["norm_eps"]), positions,
             h["rope_theta"])
    k = rope(rms_norm(p["k_norm"]["scale"], k, h["norm_eps"]), positions,
             h["rope_theta"])
    k = jnp.repeat(k, n // nkv, axis=2)      # query head i reads i // group
    v = jnp.repeat(v, n // nkv, axis=2)
    block = min(QUERY_BLOCK, s)
    key_at = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(segments, start, block, axis=1)
        query_at = start + jnp.arange(block)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) / math.sqrt(d)
        allowed = (query_at[:, None] >= key_at[None, :])[None, None] & (
            sb[:, None, :, None] == segments[:, None, None, :])
        scores = jnp.where(allowed, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("bnqk,bknd->bqnd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, n * d)
    return out @ p["attn_out"]["kernel"]


def swiglu(w1, w3, w2, x):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def experts(p, x, h):
    """``sum_chosen w_e expert_e(x)`` over the held experts: a dense loop,
    each held expert on every token, weighted by its router weight (zero
    where the token did not choose it)."""
    b, s, hidden = x.shape
    tokens = x.reshape(b * s, hidden)
    scores = jax.nn.sigmoid(tokens @ p["gate"])           # (T, routed)
    assert scores.shape[-1] == h["experts_routed"]
    _, chosen = jax.lax.top_k(scores + p["expert_bias"],
                              h["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True)
                        + h["router_norm_eps"])
    held = jnp.asarray(h["experts_held"], jnp.int32)

    @jax.checkpoint
    def one_expert(total, xs):
        expert_id, w1, w3, w2 = xs
        coef = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
        return total + coef[:, None] * swiglu(w1, w3, w2, tokens), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                            (held, p["w1"], p["w3"], p["w2"]))
    return total.reshape(b, s, hidden)


def block(p, x, segments, positions, *, kind, dense, h):
    eps = h["norm_eps"]
    normed = rms_norm(p["mixer_norm"]["scale"], x, eps)
    if kind == "conv":
        x = x + short_conv(p["short_conv"], normed, segments)
    else:
        x = x + attention(p["attn"], normed, segments, positions, h)
    normed = rms_norm(p["ffn_norm"]["scale"], x, eps)
    if dense:
        return x + swiglu(p["mlp_in"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_out"]["kernel"], normed)
    return x + experts(p["moe"], normed, h)


def logits(params, batch, h):
    ids = batch["input_ids"]
    segments = batch.get("segment_ids", jnp.ones_like(ids))
    positions = batch.get("positions", positions_of(segments))
    table = params["embed"]["embedding"]
    x = table[ids]
    for i, kind in enumerate(h["layer_types"]):
        layer = jax.checkpoint(functools.partial(
            block, kind=kind, dense=i < h["num_dense_layers"], h=h))
        x = layer(params[f"layer{i}"], x, segments, positions)
    x = rms_norm(params["final_norm"]["scale"], x, h["norm_eps"])
    return x @ table.T


def loss(params, batch, h):
    targets = batch["targets"]
    logp = jax.nn.log_softmax(logits(params, batch, h), axis=-1)
    labelled = (targets >= 0).astype(logp.dtype)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * labelled) / jnp.maximum(jnp.sum(labelled), 1.0)


def _static(hparams: dict) -> dict:
    keep = ("layer_types", "num_dense_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "norm_eps", "rope_theta",
            "num_experts_per_tok", "experts_routed", "experts_held",
            "router_norm_eps")
    return {k: (tuple(hparams[k]) if isinstance(hparams[k], list)
                else hparams[k]) for k in keep}


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
    return _compiled(tuple(sorted(_static(hparams).items())))(params, batch)
