"""BERT masked-LM reference (Devlin et al., arXiv:1810.04805).

Post-LayerNorm encoder: token + learned position embeddings, LayerNorm;
N x (multi-head self-attention, add & norm, GELU MLP, add & norm); MLM
head: dense, GELU, LayerNorm, projection tied to the token table, bias.
Loss: mean cross-entropy over the masked positions.

Departures from the paper, each because the system under test makes it
(``benchmarks/configs/bert_base.json`` lists them): no token-type table;
tanh-approximated GELU; LayerNorm epsilon 1e-6; no dropout here at all
(the check runs the system with ``dropout_rate: 0``, since a reference
cannot share a dropout mask); in packed rows attention stays inside a
document and positions restart at each document, as packing requires.
The system computes activations in bfloat16; this is float32 throughout,
and the tolerance beside the check is what that costs.

Long rows: attention is computed one block of queries at a time and each
layer sits under ``jax.checkpoint``, so that two rows of 8192 fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import global_norm

LN_EPS = 1e-6
QUERY_BLOCK = 1024


def layer_norm(p, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def dense(p, x):
    return x @ p["kernel"] + p["bias"]


def qkv(attn, x):
    if "qkv" in attn:  # one (H, 3, H) kernel: the same three projections
        y = jnp.einsum("bsh,hco->bsco", x, attn["qkv"]["kernel"]) \
            + attn["qkv"]["bias"]
        return y[:, :, 0], y[:, :, 1], y[:, :, 2]
    return (dense(attn["query"], x), dense(attn["key"], x),
            dense(attn["value"], x))


def attention(q, k, v, key_ok, segments, num_heads):
    """q, k, v: (B, S, H). A query sees a key when the key is real and in
    the same document. One block of queries at a time."""
    b, s, h = q.shape
    d = h // num_heads
    split = lambda t: t.reshape(b, s, num_heads, d)  # noqa: E731
    q, k, v = split(q), split(k), split(v)
    block = min(QUERY_BLOCK, s)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(segments, start, block, axis=1)
        scores = jnp.einsum("bqnd,bknd->bnqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        allowed = key_ok[:, None, None, :] & (
            sb[:, None, :, None] == segments[:, None, None, :])
        scores = jnp.where(allowed, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bnqk,bknd->bqnd", probs, v)

    # Checkpointed per block: the backward pass forms one block's scores
    # again instead of keeping all of them (6 GB at two rows of 8192).
    out = jax.lax.map(jax.checkpoint(one_block),
                      jnp.arange(0, s, block))          # (nb, B, blk, n, d)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h)


def encoder_layer(p, x, key_ok, segments, num_heads):
    q, k, v = qkv(p["attn"], x)
    ctx = attention(q, k, v, key_ok, segments, num_heads)
    x = layer_norm(p["ln1"], x + dense(p["attn"]["attn_out"], ctx))
    y = dense(p["mlp_out"], gelu(dense(p["mlp_in"], x)))
    return layer_norm(p["ln2"], x + y)


def positions(segments):
    """Position of each token inside its own document."""
    idx = jnp.arange(segments.shape[1], dtype=jnp.int32)[None, :]
    new_doc = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool),
         segments[:, 1:] != segments[:, :-1]], axis=1)
    return idx - jax.lax.cummax(jnp.where(new_doc, idx, 0), axis=1)


def logits(params, batch, num_heads):
    ids = batch["input_ids"]
    key_ok = batch.get("attention_mask", jnp.ones_like(ids)).astype(bool)
    segments = batch.get("segment_ids", jnp.ones_like(ids))
    emb = params["embed_block"]
    table = emb["embed"]["embedding"]
    x = table[ids] + emb["pos_embedding"][positions(segments)]
    x = layer_norm(emb["embed_ln"], x)
    layer = jax.checkpoint(
        functools.partial(encoder_layer, num_heads=num_heads))
    n_layers = sum(1 for k in params if k.startswith("layer"))
    for i in range(n_layers):
        x = layer(params[f"layer{i}"], x, key_ok, segments)
    head = params["head"]
    t = layer_norm(head["mlm_ln"], gelu(dense(head["mlm_transform"], x)))
    return t @ table.T + head["mlm_bias"]


def loss(params, batch, num_heads):
    targets = batch["targets"]
    masked = (targets >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits(params, batch, num_heads), axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * masked) / jnp.maximum(jnp.sum(masked), 1.0)


@functools.partial(jax.jit, static_argnames=("num_heads",))
def _loss_and_grad_norm(params, batch, num_heads):
    value, grads = jax.value_and_grad(loss)(params, batch, num_heads)
    return value, global_norm(grads)


def loss_and_grad_norm(params, batch, hparams):
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    return _loss_and_grad_norm(params, batch,
                               num_heads=int(hparams["num_heads"]))
