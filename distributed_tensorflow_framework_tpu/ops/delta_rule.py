"""The gated delta rule with a decay per key channel (Kimi Delta
Attention, arXiv:2510.26692) as a chunked scan, in XLA products.

Per head, keys and queries of ``K`` channels and values of ``V``, per
token ``t`` of a document::

    S_t = (I - β_t k_t k_tᵀ) Diag(α_t) S_{t-1} + β_t k_t v_tᵀ     S = 0 at its start
    o_t = scale · S_tᵀ q_t

with ``α_t = exp(g_t)`` channel by channel (``g <= 0``) and ``β_t`` one
scalar a head and token: ``Diag(α_t) S_{t-1}`` decays the state, and the
write replaces what it held along ``k_t`` by ``v_t``, at the rate ``β_t``.

**Chunks.** The row is cut into chunks of ``chunk`` tokens (padded up to a
whole number of them). Inside a chunk, ``Γ_i = Σ_{t <= i} g_t`` per
channel and ``S_0`` the state entering it, the state after token ``i``
is ``Diag(exp Γ_i) S_0 + Σ_{j <= i} Diag(exp(Γ_i - Γ_j)) k_j w_jᵀ``, where
the rows ``w_j`` (the delta-corrected values) solve the unit
lower-triangular system (the UT/WY form)::

    (I + A) W = β ⊙ V - β ⊙ (K ⊙ exp Γ) S_0,   A_ij = β_i Σ_c k_ic k_jc exp(Γ_ic - Γ_jc), j < i

so ``W = U - W_k S_0`` with ``[U | W_k]`` one triangular solve per chunk,
all chunks at once. Then, with ``P_ij = Σ_c q_ic k_jc exp(Γ_ic - Γ_jc)``
for ``j <= i``::

    O   = scale · (P W + (Q ⊙ exp Γ) S_0)
    S_C = Diag(exp Γ_C) S_0 + (K ⊙ exp(Γ_C - Γ))ᵀ W

and a ``lax.scan`` over the chunks carries ``S_0`` through these and
``W``: four products a chunk. No step walks tokens one by one.

**Every exponential takes an argument <= 0.** ``g <= 0`` makes ``Γ``
non-increasing along the chunk, so ``exp Γ_i``, ``exp(Γ_C - Γ_j)`` and
``exp(Γ_i - Γ_j)`` for ``j <= i`` are at most 1. The pair terms ``A`` and
``P`` are never formed as ``exp(Γ_i) · exp(-Γ_j)``, which overflows once a
chunk decays by more than about 88 nats (a decay of ``e^-16`` a token
does that in six tokens). The chunk is cut into sub-chunks of ``sub``
tokens instead:

- a pair inside one sub-chunk takes ``exp(Γ_i - Γ_j)`` itself, per pair
  and channel, the pairs with ``j > i`` masked before the exponential;
- a pair across sub-chunks, ``j`` before the sub-chunk of ``i``, goes
  through the last token ``r`` before that sub-chunk:
  ``exp(Γ_i - Γ_j) = exp(Γ_i - Γ_r) · exp(Γ_r - Γ_j)`` with ``j <= r < i``,
  both arguments <= 0: the left side scaled once a token, the right side
  once a sub-chunk, and the sum over channels is one product.

A value under float32's range underflows to 0, where the exact one is
smaller still.

**Documents** (``segment_ids``, equal ids in one contiguous run each, as
packed rows have them): a pair in two documents is masked; the state
entering a chunk reaches only tokens of the document the previous chunk
ended in; the state leaving a chunk holds only the document that ends it
(so the state is 0 at each document's start). **Padding** up to a whole
chunk neither decays (``g = 0``) nor writes (``β = 0``), in a document of
its own after every real token.

**Precision.** Decay sums, their exponentials, ``A``, ``P``, the
triangular solve and the states are float32; the operands of the products
(``q``, ``k``, ``W``, the state) take ``q``'s dtype and accumulate in
float32. The output is float32.

**Backward** by JAX's autodiff of the products, the solve and the scan,
as ops/ssm_scan.py. What it keeps at 8192 tokens, 8 heads of 128, 64-token
chunks, one row: the scan's entering states (128 chunks x 8 x 128 x 128
float32, 67 MB), ``[U | W_k]`` (67 MB), the keys scaled to each
sub-chunk's reference (bf16, 50 MB), ``A`` and ``P`` (34 MB together):
about 220 MB a layer and row. The one large intermediate, the per-pair
decays inside sub-chunks (``sub x K`` floats a token and head: 537 MB a
row at ``sub = 16``), is recomputed in the backward pass
(``jax.checkpoint`` around the pair products), not kept. A custom VJP
that recomputed chunk states would save the 67 MB of states and no more,
and under ``model.remat`` one layer's residuals live at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens a chunk and a sub-chunk of the scan (the module's docstring).
CHUNK = 64
SUB_CHUNK = 16


def _masked_exp(log_decay, mask):
    """``exp(log_decay)`` where ``mask``, else 0, with no overflow behind
    the mask (forward or backward)."""
    return jnp.exp(jnp.where(mask, log_decay, -jnp.inf))


@jax.checkpoint
def _inside_sub_chunks(xs, y, gamma):
    """``Σ_c x_ic y_jc exp(Γ_ic - Γ_jc)`` for ``j <= i`` inside each
    sub-chunk, float32: each ``x`` of ``xs`` and ``y`` (…, n, sub, K),
    ``gamma`` the same; (…, n, sub, sub) each, 0 above the diagonal. The
    per-pair decays are rebuilt in the backward pass, not kept."""
    sub = y.shape[-2]
    causal = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    decay = _masked_exp(gamma[..., :, None, :] - gamma[..., None, :, :],
                        causal)                           # (…, n, i, j, K)
    yd = y[..., None, :, :] * decay
    return tuple(jnp.sum(x[..., :, None, :] * yd, axis=-1) for x in xs)


def _decayed_products(xs, y, gamma, sub: int, dtype):
    """For each ``x`` of ``xs``: ``M_ij = Σ_c x_ic y_jc exp(Γ_ic - Γ_jc)``
    for ``j <= i`` and 0 above, float32 (…, C, C); ``x``, ``y`` and
    ``gamma`` (…, C, K). Inside a sub-chunk per pair and channel, across
    sub-chunks through the reference before the later one (module
    docstring)."""
    *lead, c, k = y.shape
    n = c // sub
    split = lambda t: t.astype(jnp.float32).reshape(*lead, n, sub, k)  # noqa: E731
    g = split(gamma)
    inside = _inside_sub_chunks(tuple(map(split, xs)), split(y), g)
    eye = jnp.eye(n, dtype=jnp.float32)
    out = [jnp.einsum("...aij,ab->...aibj", m, eye).reshape(*lead, c, c)
           for m in inside]
    if n == 1:
        return out
    # The reference of sub-chunk a (1..n-1): its first token's predecessor.
    ref = g[..., :-1, -1, :]                              # (…, n-1, K)
    earlier = (jnp.arange(c)[None, :]
               < sub * jnp.arange(1, n)[:, None])[..., None]  # (n-1, C, 1)
    y_ref = (y.astype(jnp.float32)[..., None, :, :] * _masked_exp(
        ref[..., :, None, :] - gamma.astype(jnp.float32)[..., None, :, :],
        earlier)).astype(dtype)                           # (…, n-1, C, K)
    pad = [(0, 0)] * len(lead) + [(sub, 0), (0, 0)]
    for i, x in enumerate(xs):
        x_ref = (split(x)[..., 1:, :, :] * jnp.exp(
            g[..., 1:, :, :] - ref[..., :, None, :])).astype(dtype)
        across = jnp.einsum("...aik,...ajk->...aij", x_ref, y_ref,
                            preferred_element_type=jnp.float32)
        out[i] = out[i] + jnp.pad(across.reshape(*lead, c - sub, c), pad)
    return out


def chunked_delta_rule(q, k, v, g, beta, segment_ids=None, *,
                       chunk: int = CHUNK, sub: int = SUB_CHUNK,
                       scale: float | None = None):
    """``o`` (B, S, H, V) float32 of the recurrence above.

    ``q``, ``k`` (B, S, H, K); ``v`` (B, S, H, V); ``g`` (B, S, H, K)
    float32 and <= 0, the log decay of each key channel; ``beta`` (B, S,
    H) in (0, 1); ``segment_ids`` (B, S) or None for one document a row;
    ``scale`` on the output, ``K ** -0.5`` if None. ``chunk`` is a whole
    number of sub-chunks of ``sub`` tokens."""
    bsz, s, h, dk = q.shape
    dv = v.shape[-1]
    if chunk % sub:
        raise ValueError(f"a chunk of {chunk} tokens is no whole number of "
                         f"sub-chunks of {sub}")
    scale = dk ** -0.5 if scale is None else scale
    if segment_ids is None:
        segment_ids = jnp.ones((bsz, s), jnp.int32)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                              constant_values=-1)
    nc = (s + pad) // chunk
    dtype = q.dtype
    f32 = jnp.float32

    def chunks(t):               # (B, S, H, ...) -> (B, H, nc, C, ...)
        return jnp.moveaxis(t.reshape(bsz, nc, chunk, h, *t.shape[3:]), 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    gamma = jnp.cumsum(chunks(g.astype(f32)), axis=-2)     # (B,H,nc,C,K)
    beta = chunks(beta.astype(f32))                         # (B,H,nc,C)
    seg = segment_ids.reshape(bsz, 1, nc, chunk)
    same = seg[..., :, None] == seg[..., None, :]           # (B,1,nc,C,C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    qk, kk = _decayed_products((q, k), k, gamma, sub, dtype)
    p = jnp.where(same & lower, qk, 0.0) * scale
    a = jnp.where(same & jnp.tril(lower, -1), beta[..., :, None] * kk, 0.0)

    # The document the chunk ends in, and the one the previous chunk
    # ended in (none before the row's first chunk).
    last = seg[..., -1:]                                    # (B,1,nc,1)
    before = jnp.pad(last, ((0, 0), (0, 0), (1, 0), (0, 0)),
                     constant_values=-2)[:, :, :-1]
    into = _masked_exp(gamma, (seg == before)[..., None])  # exp Γ_i or 0
    rhs = jnp.concatenate(
        [beta[..., None] * v.astype(f32),
         beta[..., None] * k.astype(f32) * into], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)
    u, wk = solved[..., :dv], solved[..., dv:]
    q_in = (q.astype(f32) * into * scale).astype(dtype)
    k_out = (k.astype(f32) * _masked_exp(
        gamma[..., -1:, :] - gamma, (seg == last)[..., None])).astype(dtype)
    keep = _masked_exp(gamma[..., -1, :], last == before)  # (B,H,nc,K)

    def step(state, xs):
        u, wk, p, q_in, k_out, keep = xs
        held = state.astype(dtype)
        w = u - jnp.einsum("bhck,bhkv->bhcv", wk.astype(dtype), held,
                           preferred_element_type=f32)
        wd = w.astype(dtype)
        o = jnp.einsum("bhij,bhjv->bhiv", p.astype(dtype), wd,
                       preferred_element_type=f32) + jnp.einsum(
            "bhck,bhkv->bhcv", q_in, held, preferred_element_type=f32)
        state = keep[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_out, wd, preferred_element_type=f32)
        return state, o

    _, o = jax.lax.scan(
        step, jnp.zeros((bsz, h, dk, dv), f32),
        tuple(jnp.moveaxis(t, 2, 0) for t in (u, wk, p, q_in, k_out, keep)))
    # (nc, B, H, C, V) -> (B, S, H, V)
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4)
    return o.reshape(bsz, nc * chunk, h, dv)[:, :s]
