"""The selective state-space recurrence of a Mamba-2 layer as a chunked
scan (the state-space-duality form, arXiv:2405.21060 §6), in XLA einsums.

Per head ``h`` (``P`` channels, one scalar decay) reading B/C group
``g = h // (H/G)`` (``N`` state dims), per token ``t`` of a document::

    S_t = exp(Δ_t a) · S_{t-1} + Δ_t · x_t ⊗ B_t        S = 0 at its start
    y_t = S_t C_t

The row is cut into chunks of ``chunk`` tokens (padded up to a whole
number of them). Inside a chunk the recurrence is the masked product
``(C Bᵀ ⊙ decay) (Δ x)`` over the pairs ``s <= t`` of one document;
each chunk's own contribution to the state at its end is one more
product; the states entering the chunks follow from those by a
``(chunks x chunks)`` decay matrix, and reach the tokens of the document
that crosses the chunk's start. No step of the program walks tokens one
by one, and JAX differentiates the einsums as they stand (the backward
pass is the same five products transposed).

Documents (``segment_ids``, equal ids in one contiguous run each, as
packed rows have them): a pair in two documents is masked, a chunk's
state keeps only the document at its end, and the state entering a chunk
reaches only tokens of the document the previous chunk ended in.

Decay sums, their exponentials, the ``(chunks x chunks)`` recurrence and
the states are float32; the products' operands take ``x``'s dtype and
accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def _masked_exp(log_decay, mask):
    """``exp(log_decay)`` where ``mask``, else 0, with no overflow behind
    the mask (forward or backward)."""
    return jnp.exp(jnp.where(mask, log_decay, -jnp.inf))


def chunked_ssm_scan(x, dt, a, b, c, segment_ids=None, *, chunk: int = 128):
    """``y`` (B, S, H, P) float32 of the recurrence above.

    ``x`` (B, S, H, P); ``dt`` (B, S, H) float32, positive (after its
    softplus); ``a`` (H,) float32, negative; ``b``, ``c`` (B, S, G, N)
    with ``H % G == 0``; ``segment_ids`` (B, S) or None for one document
    a row."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    hg = h // g
    if segment_ids is None:
        segment_ids = jnp.ones((bsz, s), jnp.int32)
    pad = -s % chunk
    if pad:
        # Padding steps neither decay (Δ = 0) nor add, in a document of
        # their own after every real token.
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                              constant_values=-1)
    nc, l = (s + pad) // chunk, chunk
    dtype = x.dtype
    seg = segment_ids.reshape(bsz, nc, l)
    dt = dt.astype(jnp.float32).reshape(bsz, nc, l, g, hg)
    # (B, nc, G, Hg, l): the chunk's positions last, whole lanes of them.
    dt = jnp.moveaxis(dt, 2, -1)
    cum = jnp.cumsum(dt * a.astype(jnp.float32).reshape(g, hg)[..., None],
                     axis=-1)
    xd = (x.astype(jnp.float32).reshape(bsz, nc, l, g, hg, p)
          * jnp.moveaxis(dt, -1, 2)[..., None]).astype(dtype)   # Δ x
    b = b.reshape(bsz, nc, l, g, n)
    c = c.reshape(bsz, nc, l, g, n)

    # Inside a chunk: pairs s <= t of one document.
    pair = (seg[:, :, :, None] == seg[:, :, None, :]) & jnp.tril(
        jnp.ones((l, l), bool))
    decay = _masked_exp(cum[..., :, None] - cum[..., None, :],
                        pair[:, :, None, None])                 # (…, t, s)
    cb = jnp.einsum("bktgn,bksgn->bkgts", c, b,
                    preferred_element_type=jnp.float32)
    scores = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bkghts,bksghp->bktghp", scores, xd,
                   preferred_element_type=jnp.float32)

    if nc > 1:
        # Each chunk's own part of the state at its end: the document
        # that ends the chunk, decayed to there.
        to_end = _masked_exp(cum[..., -1:] - cum,
                             (seg == seg[:, :, -1:])[:, :, None, None])
        xd_end = (xd.astype(jnp.float32)
                  * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype)
        states = jnp.einsum("bksgn,bksghp->bkghpn", b, xd_end,
                            preferred_element_type=jnp.float32)
        # The state entering chunk z: every earlier chunk's part, decayed
        # over the chunks between, while the document lasts.
        total = jnp.cumsum(cum[..., -1], axis=1)                # (B,nc,G,Hg)
        before = jnp.pad(total, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :-1]
        last = seg[:, :, -1]
        last_before = jnp.pad(last, ((0, 0), (1, 0)),
                              constant_values=-2)[:, :-1]
        reaches = (last_before[:, :, None] == last[:, None, :]) & jnp.tril(
            jnp.ones((nc, nc), bool), -1)                       # (B, z, k)
        carry = _masked_exp(before[:, :, None] - total[:, None, :],
                            reaches[..., None, None])           # (B,z,k,G,Hg)
        entering = jnp.einsum("bzkgh,bkghpn->bzghpn", carry, states,
                              precision=_HIGHEST)
        # ... reaches the tokens of the document the last chunk ended in.
        into = _masked_exp(cum, (seg == last_before[:, :, None])
                           [:, :, None, None])                  # (B,nc,G,Hg,l)
        y = y + jnp.einsum(
            "bktgn,bkghpn->bktghp", c, entering.astype(dtype),
            preferred_element_type=jnp.float32) * jnp.moveaxis(
                into, -1, 2)[..., None]
    return y.reshape(bsz, nc * l, h, p)[:, :s]
