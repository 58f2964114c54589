"""Laguna causal language model reference (poolside Laguna-S-2.1,
``model_type: laguna``): forward, next-token loss and gradient norm in
plain float32 ``jax.numpy``, written from the layer equations, reading
the program's parameter tree by name and importing nothing from it.

Layer ``l``, ``x`` the stream entering it (T tokens x hidden), every norm
an RMSNorm with a learned scale, no bias anywhere:

- attention: ``u = RMSNorm(x)``; ``q = u W_q`` as ``n_l`` heads of
  ``head_dim`` (``n_l`` by ``layer_types[l]``: a global layer's or a
  window layer's count), ``k = u W_k`` and ``v = u W_v`` as the key/value
  heads, key/value head ``j`` serving query heads ``j n_l / m ... (j + 1)
  n_l / m - 1``; no q/k norm. Positions restart at each packed document.
  The rotation is ``rope_parameters[layer_types[l]]``: the FIRST
  ``partial_rotary_factor`` of each head of q and k rotates (half
  rotation inside those dims, the rest pass untouched) at frequencies
  ``f_i = theta^(-2i/dim)`` and, where ``rope_type`` is ``yarn``::

      d(r)   = dim ln(original_max_position_embeddings / (2 pi r))
               / (2 ln theta)
      low    = max(floor(d(beta_fast)), 0)
      high   = min(ceil(d(beta_slow)), dim - 1)
      ramp_i = clip((i - low) / (high - low), 0, 1)
      inv_freq_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)

  with ``cos`` and ``sin`` of ``pos * inv_freq`` each multiplied by
  ``attention_factor``. A query at ``i`` sees a key at ``j`` iff ``j <=
  i``, same document and, in a window layer, ``i - j < sliding_window``;
  ``o = softmax(q k^T / sqrt(head_dim)) v``; the gate ``g = sigmoid(u
  W_g)``, one scalar a head and token; ``h = x + ((g * o) W_o)`` with
  ``g`` broadcast over the head's dims.
- feed-forward, ``m = RMSNorm(h)``: the first ``num_dense_layers``
  layers ``x' = h + W_d (silu(W_g' m) * W_u m)``; the others ``logits = m
  W_r`` over all routed experts, ``p = softmax(logits)``, the token's
  experts its ``num_experts_per_tok`` largest ``p``, ``w_e =
  moe_routed_scaling_factor p_e / sum_chosen p``, and ``x' = h +
  sum_chosen w_e W2_e (silu(W1_e m) * W3_e m) + V_d (silu(V_g m) * V_u
  m)``: the shared expert reads ``m``, unscaled and without a gate of its
  own. Nothing is dropped: every held expert runs on every token here
  and the weight is zero where it was not chosen.
- a final RMSNorm and an untied head; loss: mean next-token
  cross-entropy over the labelled positions.

The share (``hparams``): ``layer_types`` and ``num_dense_layers`` are the
layers present; ``heads_held`` names the query heads the tree holds of a
``full_attention`` and of a ``sliding_attention`` layer and the
``key_value`` heads they read (an attention layer computes its held
heads' part of the out-projection's sum); ``dense_units_held`` and
``shared_units_held`` say how many hidden units of the dense
feed-forward and of the shared expert the tree holds (a gated unit is
elementwise in them, so the shares' parts add up to the whole);
``experts_held`` the experts whose weights the tree holds (row ``i`` of
``moe/w1`` is expert ``experts_held[i]``): the router scores all
``experts_routed`` and only held experts add to the result. What the
other heads, units and experts would add is left out. The vocabulary is
the rows the embedding and the head have.

Long rows: attention one block of queries at a time, every layer and
every expert under ``jax.checkpoint``, so that a row of 16,384 fits
beside the trainer's state.

The control (``hparams["dtype"]``, ``benchmarks/tools/check_control.py``):
the same equations with the parameters cast to that type and nothing
lifted back to float32, router, gate, norms, rotary products and loss
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
from benchmarks.reference.smallthinker import (
    positions_of, rms_norm, rotate_half)

QUERY_BLOCK = 512
# The attention module's name in the program's tree, by layer kind.
ATTENTION_NAMES = {"full_attention": "attn", "sliding_attention": "attn_window"}


def yarn_range(rule: dict, dim: int) -> tuple[int, int]:
    """``(low, high)`` of the docstring for ``dim`` rotated dims."""
    def d(rotations):
        return dim * math.log(rule["original_max_position_embeddings"]
                              / (2 * math.pi * rotations)) \
            / (2 * math.log(rule["rope_theta"]))

    return (max(math.floor(d(rule["beta_fast"])), 0),
            min(math.ceil(d(rule["beta_slow"])), dim - 1))


def inv_frequencies(rule: dict, dim: int):
    """``(inv_freq (dim / 2,), factor on cos and sin)`` of one
    ``rope_parameters`` entry over ``dim`` rotated dims."""
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    freq = float(rule["rope_theta"]) ** (-i / dim)
    if rule["rope_type"] != "yarn":
        return freq, 1.0
    low, high = yarn_range(rule, dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (freq / rule["factor"] * ramp + freq * (1.0 - ramp),
            rule["attention_factor"])


def rope(x, positions, rule: dict):
    """``x`` (B, T, heads, head_dim): its first ``partial_rotary_factor``
    dims rotated, the rest as they are."""
    dim = int(x.shape[-1] * rule["partial_rotary_factor"])
    inv_freq, factor = inv_frequencies(rule, dim)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    angle = jnp.concatenate([angle, angle], axis=-1)[:, :, None, :]
    turned, passed = x[..., :dim], x[..., dim:]
    turned = turned * (jnp.cos(angle) * factor) \
        + rotate_half(turned) * (jnp.sin(angle) * factor)
    return jnp.concatenate([turned.astype(x.dtype), passed], axis=-1)


def attention(p, u, segments, positions, h, *, kind):
    b, s, _ = u.shape
    d = h["head_dim"]
    n, nkv = len(h["heads_held"][kind]), len(h["heads_held"]["key_value"])
    q = (u @ p["query"]["kernel"]).reshape(b, s, n, d)
    k = (u @ p["key"]["kernel"]).reshape(b, s, nkv, d)
    v = (u @ p["value"]["kernel"]).reshape(b, s, nkv, d)
    rule = h["rope_parameters"][kind]
    q, k = rope(q, positions, rule), rope(k, positions, rule)
    k = jnp.repeat(k, n // nkv, axis=2)      # query head i reads i // group
    v = jnp.repeat(v, n // nkv, axis=2)
    window = h["sliding_window"] if kind == "sliding_attention" else None
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
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, n, d)
    gate = jax.nn.sigmoid(u @ p["gate"])                  # (B, T, n)
    assert gate.shape[-1] == n
    return (out * gate[..., None]).reshape(b, s, n * d) \
        @ p["attn_out"]["kernel"]


def swiglu(w_gate, w_up, w_down, x):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def route(p, tokens, h):
    """``(chosen (T, K), weights (T, K))``: softmax over all routed
    experts, the K largest, renormalised over them and scaled."""
    probs = jax.nn.softmax(tokens @ p["gate"], axis=-1)    # (T, routed)
    assert probs.shape[-1] == h["experts_routed"]
    picked, chosen = jax.lax.top_k(probs, h["num_experts_per_tok"])
    return chosen, h["moe_routed_scaling_factor"] * picked \
        / picked.sum(axis=-1, keepdims=True)


def expert_layer(p, m, h):
    """``sum_chosen w_e expert_e(m)`` over the held experts (a dense
    loop, each held expert on every token, weighted by its router weight,
    zero where the token did not choose it) plus the shared expert."""
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


def block(p, x, segments, positions, *, kind, dense, h):
    eps = h["rms_norm_eps"]
    u = rms_norm(p["mixer_norm"]["scale"], x, eps)
    x = x + attention(p[ATTENTION_NAMES[kind]], u, segments, positions, h,
                      kind=kind)
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
        layer = jax.checkpoint(functools.partial(
            block, kind=kind, dense=i < h["num_dense_layers"], h=h))
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


def _hashable(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in value.items()))
    return tuple(value) if isinstance(value, list) else value


def _plain(value):
    """``_hashable``'s inverse for the nested groups the equations read
    by key."""
    if isinstance(value, tuple) and value and all(
            isinstance(v, tuple) and len(v) == 2 and isinstance(v[0], str)
            for v in value):
        return {k: _plain(v) for k, v in value}
    return value


def _static(hparams: dict) -> tuple:
    keep = ("layer_types", "num_dense_layers", "head_dim", "rms_norm_eps",
            "rope_parameters", "sliding_window", "num_experts_per_tok",
            "moe_routed_scaling_factor", "experts_routed", "experts_held",
            "heads_held", "dense_units_held", "shared_units_held")
    return tuple(sorted((k, _hashable(hparams[k])) for k in keep))


@functools.lru_cache(maxsize=8)
def _compiled(static_items: tuple):
    h = {k: _plain(v) for k, v in static_items}

    @jax.jit
    def run(params, batch):
        value, grads = jax.value_and_grad(loss)(params, batch, h)
        return value, global_norm(grads)

    return run


def loss_and_grad_norm(params, batch, hparams):
    dtype = jnp.dtype(hparams.get("dtype", "float32"))
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    return _compiled(_static(hparams))(params, batch)
