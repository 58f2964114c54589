"""SmallThinker causal language model reference (PowerInfer
SmallThinker-21BA3B-Instruct, ``model_name: smallthinker_21b_instruct``):
forward, next-token loss and gradient norm in plain float32 ``jax.numpy``,
written from the layer equations, reading the program's parameter tree by
name and importing nothing from it.

Layer ``l``, ``x`` the stream entering it (T tokens x hidden):

- router, BEFORE attention: ``logits = x W_r`` (hidden -> experts routed,
  no bias), on the stream as it enters the layer; ``chosen`` = the
  ``moe_num_active_primary_experts`` largest logits;
  ``w = softmax(logits[chosen])`` (softmax over all experts renormalised
  over the chosen is the same numbers).
- attention: ``a = RMSNorm(x)``; q over the query heads, k and v over the
  key/value heads of ``head_dim`` dims, no bias, no q/k norm; where
  ``rope_layout[l] = 1`` half-rotation rotary on the whole head of q and
  k, positions restarting at each packed document; where it is 0,
  nothing. A query at ``i`` sees a key at ``j`` iff ``j <= i``, same
  document and, where ``sliding_window_layout[l] = 1``,
  ``i - j < sliding_window_size``. ``o = softmax(q k^T / sqrt(d)) v``,
  each key/value head serving its group of query heads;
  ``h = x + o W_o``.
- experts: ``m = RMSNorm(h)``; ``y = sum_chosen w_e W_down,e (relu(m
  W_gate,e) * (m W_up,e))``; ``x' = h + y``. No shared expert, nothing
  dropped: every held expert runs on every token here and the weight is
  zero where it was not chosen.
- a final RMSNorm and an untied head; loss: mean next-token cross-entropy
  over the labelled positions.

The share (``hparams``): ``layer_types`` are the layers present
(``full_attention`` | ``sliding_attention``) with their ``rope_layout``;
``experts_held`` the experts whose weights the tree holds (row ``i`` of
``moe/w1`` is expert ``experts_held[i]``): the router scores all
``experts_routed`` and only held experts add to the result; the
vocabulary is the rows the embedding and the head have.

Long rows: attention one block of queries at a time, every layer and
every expert under ``jax.checkpoint``, so that a row of 16,384 fits beside
the trainer's state.

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
# The attention module's name in the program's tree, by layer kind.
ATTENTION_NAMES = {"full_attention": "attn", "sliding_attention": "attn_window"}


def rms_norm(scale, x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def positions_of(segments):
    idx = jnp.arange(segments.shape[1], dtype=jnp.int32)[None, :]
    new_doc = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool),
         segments[:, 1:] != segments[:, :-1]], axis=1)
    return idx - jax.lax.cummax(jnp.where(new_doc, idx, 0), axis=1)


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


def attention(p, x, segments, positions, h, *, window, rotates):
    b, s, _ = x.shape
    n, nkv, d = (h["num_attention_heads"], h["num_key_value_heads"],
                 h["head_dim"])
    q = (x @ p["query"]["kernel"]).reshape(b, s, n, d)
    k = (x @ p["key"]["kernel"]).reshape(b, s, nkv, d)
    v = (x @ p["value"]["kernel"]).reshape(b, s, nkv, d)
    if rotates:
        q = rope(q, positions, h["rope_theta"])
        k = rope(k, positions, h["rope_theta"])
    k = jnp.repeat(k, n // nkv, axis=2)      # query head i reads i // group
    v = jnp.repeat(v, n // nkv, axis=2)
    block = min(QUERY_BLOCK, s)
    key_at = jnp.arange(s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(segments, start, block, axis=1)
        query_at = start + jnp.arange(block)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) / math.sqrt(d)
        distance = query_at[:, None] - key_at[None, :]
        seen = distance >= 0
        if window is not None:
            seen = seen & (distance < window)
        allowed = seen[None, None] & (
            sb[:, None, :, None] == segments[:, None, None, :])
        scores = jnp.where(allowed, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("bnqk,bknd->bqnd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, s, block))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, n * d)
    return out @ p["attn_out"]["kernel"]


def reglu(w_gate, w_up, w_down, x):
    return (jax.nn.relu(x @ w_gate) * (x @ w_up)) @ w_down


def route(p, stream, h):
    """``(chosen (T, K), weights (T, K))`` from the stream entering the
    layer: the top-K logits and the softmax over them."""
    tokens = stream.reshape(-1, stream.shape[-1])
    logits = tokens @ p["gate"]                           # (T, routed)
    assert logits.shape[-1] == h["experts_routed"]
    picked, chosen = jax.lax.top_k(
        logits, h["moe_num_active_primary_experts"])
    return chosen, jax.nn.softmax(picked, axis=-1)


def experts(p, x, chosen, weights, h):
    """``sum_chosen w_e expert_e(x)`` over the held experts: a dense loop,
    each held expert on every token, weighted by its router weight (zero
    where the token did not choose it)."""
    b, s, hidden = x.shape
    tokens = x.reshape(b * s, hidden)
    held = jnp.asarray(h["experts_held"], jnp.int32)

    @jax.checkpoint
    def one_expert(total, xs):
        expert_id, w_gate, w_up, w_down = xs
        coef = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
        return total + coef[:, None] * reglu(w_gate, w_up, w_down,
                                             tokens), None

    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(tokens),
                            (held, p["w1"], p["w3"], p["w2"]))
    return total.reshape(b, s, hidden)


def block(p, x, segments, positions, *, kind, rotates, h):
    eps = h["rms_norm_eps"]
    chosen, weights = route(p["moe"], x, h)               # before attention
    window = h["sliding_window_size"] if kind == "sliding_attention" else None
    normed = rms_norm(p["mixer_norm"]["scale"], x, eps)
    x = x + attention(p[ATTENTION_NAMES[kind]], normed, segments, positions,
                      h, window=window, rotates=rotates)
    normed = rms_norm(p["ffn_norm"]["scale"], x, eps)
    return x + experts(p["moe"], normed, chosen, weights, h)


def logits(params, batch, h):
    ids = batch["input_ids"]
    segments = batch.get("segment_ids", jnp.ones_like(ids))
    positions = batch.get("positions", positions_of(segments))
    x = params["embed"]["embedding"][ids]
    for i, kind in enumerate(h["layer_types"]):
        layer = jax.checkpoint(functools.partial(
            block, kind=kind, rotates=bool(h["rope_layout"][i]), h=h))
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


def _static(hparams: dict) -> dict:
    keep = ("layer_types", "rope_layout", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "sliding_window_size", "moe_num_active_primary_experts",
            "experts_routed", "experts_held")
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
