"""Nemotron-H causal language model reference (NVIDIA
Nemotron-3-Super-120B-A12B, ``model_type: nemotron_h``): forward,
next-token loss and gradient norm in plain float32 ``jax.numpy``, written
from the layer equations, reading the program's parameter tree by name
and importing nothing from it.

Every layer is ``x + sublayer(RMSNorm(x))`` with ONE sublayer, named by
its letter in ``hybrid_override_pattern``; ``u`` is the normed stream, no
projection has a bias, T tokens a row:

- ``M`` (Mamba-2): ``[z | xBC | dt] = u W_in`` of widths ``d_in``,
  ``d_in + 2 G N`` and ``H`` (``d_in = H P``: H heads of P channels, G
  B/C groups of N state dims); ``xBC <- silu(conv(xBC) + b)``, a
  depthwise causal convolution of ``conv_kernel`` taps (``c_t = sum_j
  taps[j] xBC_{t-j}``) that reads zero across a document's start;
  ``[x | B | C] = xBC``, head ``h`` reading group ``h // (H / G)``;
  ``Delta = softplus(dt + dt_bias)``, ``a = -exp(A_log)``, one scalar a
  head. The recurrence ``S_t = exp(Delta_t a) S_{t-1} + Delta_t x_t (x)
  B_t`` with ``S = 0`` at a document's start, ``y_t = S_t C_t``, is
  computed here in its whole-row form, one head at a time::

      y_t = sum_{s <= t, same document} (C_t . B_s)
            exp(a sum_{s < r <= t} Delta_r) Delta_s x_s   +   D x_t

  a ``(T x T)`` masked product and NOT the program's chunked scan
  (``recurrence_by_token`` writes the recurrence out as a loop over
  tokens; tests/test_nemotron_h.py ties the two). Then ``y <-
  RMSNorm_group(y * silu(z)) * w``, the mean square over each group's
  ``d_in / G`` channels, and ``W_out``.
- ``*`` (attention): q over the query heads, k and v over the key/value
  heads of ``head_dim`` dims, no q/k norm and NO positional encoding;
  causal softmax attention inside the document, each key/value head
  serving its group of query heads; ``W_o``.
- ``E`` (LatentMoE): scores ``s = sigmoid(u W_g)`` over all routed
  experts; the ``num_experts_per_tok`` experts of a token are the largest
  of ``s + b`` (the bias enters the choice only); weights
  ``routed_scaling_factor * s_e / (sum_chosen s + 1e-6)``; the latent
  ``l = u W_a`` (hidden -> ``moe_latent_size``); ``r = sum_chosen w_e
  W2_e relu(W1_e l)^2``; the sublayer is ``r W_b + V2 relu(V1 u)^2``: the
  shared expert reads the normed stream itself and is not scaled. Nothing
  is dropped: every held expert runs on every token and the weight is
  zero where it was not chosen.
- embedding in, a final RMSNorm and an untied head out; loss: mean
  next-token cross-entropy over the labelled positions.

The share (``hparams``): ``pattern`` names the layers present;
``experts_held`` the experts whose weights the tree holds (row ``i`` of
``moe/w1`` is expert ``experts_held[i]``): the router scores all
``experts_routed`` and only held experts add to the result, while latent
projections, router and shared expert are whole; ``heads_held`` says how
many heads of each mixer the tree holds (``mamba``, ``bc_groups``,
``attention``, ``key_value``: each list names them): a mixer computes its
held heads' part of the out-projection's sum, and what the other heads
would add is left out. The vocabulary is the rows the embedding and the
head have.

Long rows: attention one block of queries at a time, the Mamba-2 product
one head at a time (268 MB of float32 a head at 8192 tokens), every layer
and every expert under ``jax.checkpoint``.

The control (``hparams["dtype"]``, ``benchmarks/tools/check_control.py``):
the same equations with the parameters cast to that type and nothing
lifted back to float32. It is never what ``correct`` compares with; it is
the reading a cell's limits have to refuse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import global_norm
from benchmarks.reference.smallthinker import (
    attention, positions_of, rms_norm)

ROUTER_NORM_EPS = 1e-6


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def causal_conv(taps, bias, v, segments):
    """``c_t = sum_j taps[j] v_{t-j} + bias``; a tap that would read
    before the row or another document reads zero."""
    idx = jnp.arange(v.shape[1])[None, :]
    out = jnp.zeros_like(v)
    for j in range(taps.shape[0]):
        source = jnp.roll(v, j, axis=1)                  # v_{t-j}
        same_doc = (idx >= j) & (jnp.roll(segments, j, axis=1) == segments)
        out = out + jnp.where(same_doc[..., None], source, 0.0) * taps[j]
    return out + bias


def recurrence_whole_row(x, delta, a, b, c, segments):
    """One head: ``x`` (B, T, P), ``delta`` (B, T), ``a`` a scalar, ``b``
    and ``c`` (B, T, N) of the head's group, ``segments`` (B, T). The
    ``(T x T)`` form of the docstring; (B, T, P)."""
    t = x.shape[1]
    total = jnp.cumsum(delta * a, axis=1)                # a sum_{r<=t} Delta_r
    at = jnp.arange(t)
    seen = (at[:, None] >= at[None, :])[None] & (
        segments[:, :, None] == segments[:, None, :])    # (B, t, s)
    decay = jnp.exp(jnp.where(seen, total[:, :, None] - total[:, None, :],
                              -jnp.inf))
    pairs = jnp.einsum("btn,bsn->bts", c, b) * decay
    return jnp.einsum("bts,bsp->btp", pairs, delta[..., None] * x)


def recurrence_by_token(x, delta, a, b, c, segments):
    """The same head, by the definition: a loop over the tokens carrying
    the state ``S`` (B, P, N), zeroed at each document's start."""
    first = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool),
         segments[:, 1:] != segments[:, :-1]], axis=1)

    def step(state, at_t):
        x_t, d_t, b_t, c_t, first_t = at_t
        state = jnp.where(first_t[:, None, None], 0.0, state)
        state = jnp.exp(d_t * a)[:, None, None] * state \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("bpn,bn->bp", state, c_t)

    state = jnp.zeros((x.shape[0], x.shape[2], b.shape[2]), x.dtype)
    _, ys = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c, first)))
    return jnp.moveaxis(ys, 0, 1)


def mamba2(p, u, segments, h, *, recurrence=recurrence_whole_row):
    bsz, t, _ = u.shape
    heads, groups = (len(h["heads_held"]["mamba"]),
                     len(h["heads_held"]["bc_groups"]))
    width, state = h["mamba_head_dim"], h["ssm_state_size"]
    d_in, d_bc = heads * width, groups * state
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * d_bc], axis=-1)
    assert dt.shape[-1] == heads
    xbc = jax.nn.silu(causal_conv(p["conv_kernel"], p["conv_bias"], xbc,
                                  segments))
    x, b, c = jnp.split(xbc, [d_in, d_in + d_bc], axis=-1)
    x = x.reshape(bsz, t, heads, width)
    b = b.reshape(bsz, t, groups, state)
    c = c.reshape(bsz, t, groups, state)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    per_group = heads // groups

    @jax.checkpoint
    def one_head(i):
        group = i // per_group
        take = lambda v, j: jnp.take(v, j, axis=2)       # noqa: E731
        return recurrence(take(x, i), take(delta, i), a[i], take(b, group),
                          take(c, group), segments)

    y = jnp.moveaxis(jax.lax.map(one_head, jnp.arange(heads)), 0, 2)
    y = y + p["D"][:, None] * x
    y = y.reshape(bsz, t, d_in) * jax.nn.silu(z)
    y = y.reshape(bsz, t, groups, d_in // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + h["layer_norm_epsilon"])
    return (y.reshape(bsz, t, d_in) * p["norm_scale"]) \
        @ p["out_proj"]["kernel"]


def latent_moe(p, u, h):
    bsz, t, hidden = u.shape
    tokens = u.reshape(bsz * t, hidden)
    scores = jax.nn.sigmoid(tokens @ p["gate"])          # (T, routed)
    assert scores.shape[-1] == h["experts_routed"]
    _, chosen = jax.lax.top_k(scores + p["expert_bias"],
                              h["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = h["routed_scaling_factor"] * picked / (
        picked.sum(axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    latent = tokens @ p["latent_in"]["kernel"]
    assert latent.shape[-1] == h["moe_latent_size"]
    held = jnp.asarray(h["experts_held"], jnp.int32)

    @jax.checkpoint
    def one_expert(total, xs):
        expert_id, w1, w2 = xs
        coef = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
        return total + coef[:, None] * (relu2(latent @ w1) @ w2), None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                             (held, p["w1"], p["w2"]))
    shared = relu2(tokens @ p["shared"]["up"]["kernel"]) \
        @ p["shared"]["down"]["kernel"]
    return (routed @ p["latent_out"]["kernel"] + shared).reshape(
        bsz, t, hidden)


def block(p, x, segments, positions, *, letter, h):
    u = rms_norm(p["norm"]["scale"], x, h["layer_norm_epsilon"])
    if letter == "M":
        return x + mamba2(p["mamba"], u, segments, h)
    if letter == "*":
        held = {**h, "rms_norm_eps": h["layer_norm_epsilon"],
                "num_attention_heads": len(h["heads_held"]["attention"]),
                "num_key_value_heads": len(h["heads_held"]["key_value"])}
        return x + attention(p["attn"], u, segments, positions, held,
                             window=None, rotates=False)
    assert letter == "E", letter
    return x + latent_moe(p["moe"], u, h)


def logits(params, batch, h):
    ids = batch["input_ids"]
    segments = batch.get("segment_ids", jnp.ones_like(ids))
    positions = batch.get("positions", positions_of(segments))
    x = params["embed"]["embedding"][ids]
    for i, letter in enumerate(h["pattern"]):
        layer = jax.checkpoint(functools.partial(block, letter=letter, h=h))
        x = layer(params[f"layer{i}"], x, segments, positions)
    x = rms_norm(params["final_norm"]["scale"], x, h["layer_norm_epsilon"])
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


def _static(hparams: dict) -> tuple:
    keep = ("pattern", "head_dim", "layer_norm_epsilon", "mamba_head_dim",
            "ssm_state_size", "num_experts_per_tok", "routed_scaling_factor",
            "moe_latent_size", "experts_routed", "experts_held",
            "heads_held")
    return tuple(sorted((k, _hashable(hparams[k])) for k in keep))


@functools.lru_cache(maxsize=8)
def _compiled(static_items: tuple):
    h = dict(static_items)
    h["heads_held"] = {k: list(v) for k, v in h["heads_held"]}

    @jax.jit
    def run(params, batch):
        value, grads = jax.value_and_grad(loss)(params, batch, h)
        return value, global_norm(grads)

    return run


def loss_and_grad_norm(params, batch, hparams):
    dtype = jnp.dtype(hparams.get("dtype", "float32"))
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    return _compiled(_static(hparams))(params, batch)
