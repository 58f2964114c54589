"""Fused attention Pallas kernels — forward AND backward.

Computes softmax(qkᵀ/√d)·v with the S×S score matrix living only in VMEM.
Forward: one HBM read of q/k/v and one write of o (+ the per-row
logsumexp). Backward: Pallas kernels that RECOMPUTE the probability
blocks from the saved (q, k, v, o, lse) — so training peak memory is
O(S·D) end to end; no O(S²) tensor is ever materialized in HBM in either
direction. This is the flash-attention recompute pattern (PAPERS.md);
XLA alone tiles but still round-trips the score tensor for the unfused
einsum+softmax+einsum chain.

Shapes: q and k are (B, S, H, D), v is (B, S, H, D_v): the value heads
may be narrower or wider than the query/key heads (multi-head latent
attention's 192 over 128), and o, dO and dV take D_v where q, k, dQ and
dK take D; the softmax scale stays 1/√D. Five kernels:

  whole-K forward   a program holds a block of rows plus the FULL
                    opposing sequence in VMEM: no running-softmax state,
                    no init or finalise.
  streaming         K-blocked: the grid gains a sequential k-axis,
                    running (m, l, acc) state lives in VMEM scratch and
                    K/V stream through in tiles, so VMEM use is
                    O(block²) at any length. A forward, the fused
                    one-pass backward that computes each probability
                    block ONCE for all four cotangents, and the two-pass
                    pair (dq, then dk/dv/dbias) for where the fused one
                    may not run.

``select_dispatch`` picks forward, tile and backward from what a call
shows: the two sequence lengths, the bytes of its input dtype, its head
widths, whether it has a window, and whether the platform is one the
fused backward is verified on (the per-pair kernel times behind each
line are in PERF.md §6):

  s_k ≤ MAX_SEQ_VMEM   whole-K forward whose f32 score block keeps the
                       area of 128 rows × 4096 keys: 512 rows a program
                       to S=1024, 256 at 2048, 128 at 4096
  s_k > MAX_SEQ_VMEM   streaming forward on 512×1024 tiles
  backward, always     streaming tile; fused where its key tile is whole
                       lanes: under Mosaic's default scoped VMEM where
                       that holds its full-length dk/dv scratch (to
                       FUSED_BWD_MAX keys of 64 dims and 2 bytes: half
                       as many of 4 bytes, half as many of 128 dims),
                       past it without a window under a limit sized from
                       its footprint (``fused_bwd_vmem_limit``: 38 MiB
                       at 16,384 keys of 192 + 128 dims); the two-pass
                       pair for a window call past the default, for a
                       footprint over FUSED_BWD_VMEM_SHARE of the core,
                       and on a real TPU whose generation is off
                       FUSED_BWD_VERIFIED_PLATFORMS

The rule names no dtype: what float32 changes is the bytes a tile holds
(verified and timed on v5e, PERF.md §6, PR 28), and what a wider head
changes is the bytes of the fused backward's full-length scratch (PR
30). Ring attention over the ``seq`` mesh axis composes on top for
sharded sequences.

``causal`` masks from indices inside the kernels and skips (not masks)
the blocks wholly above the diagonal or, on packed rows, outside the
document; ``window`` on top of it also skips the blocks wholly behind
the window (``_block_needed``; the index maps name a needed block for a
skipped visit, so the pipeline fetches nothing for it). A skipped visit
is still a program (0.27–0.35 µs in the forward, 1.5–1.7 µs in the fused
backward on v5e, PERF.md §6), so:

  causal, no window   the streaming forward and the fused backward walk
                      a triangle: one sequential axis of the visits on
                      or below the diagonal, row blocks ascending and
                      key blocks 0 … diagonal inside each, read from a
                      scalar-prefetched visit table (``causal_visits``):
                      272 programs a head and row where the rectangle
                      had 512, at 16,384 keys on 512 × 1024 tiles. The
                      two-pass pair keeps its rectangle.
  window              the sequential axis of the streaming forward, dq
                      and dk/dv kernels is only as long as the blocks a
                      window can reach from one block of the parallel
                      axis, and counts from the first of them
                      (``_k_axis``, ``_q_axis``): 5 and 10 programs where
                      the row has 16 key and 32 row blocks, at 16,384
                      keys under a window of 4096. The fused backward
                      keeps every key block on its axis: its dk/dv
                      scratch is indexed by the absolute block.

Without ``causal`` or ``window`` a call traces the plain kernels,
equation for equation.

Every custom-VJP ``fwd`` rule names the two kernel outputs its ``bwd``
reads (``jax.ad_checkpoint.checkpoint_name``): the output as
``ATTN_OUT_NAME`` and a lane-dense (B, H, S) copy of the logsumexp as
``ATTN_LSE_NAME`` (as ``f32[B,H,S,1]`` a kept logsumexp is held padded to
128 lanes in HBM; PERF.md §6, PR 31). A ``jax.checkpoint`` whose policy
is ``save_only_these_names(*RESIDUAL_NAMES)`` then keeps them from the
first forward pass and the forward kernel is dead code in the re-run
(``model.remat`` of the decoder family, models/lfm2.py); without such a
policy the names are inert and a program compiles to what it did. Under
a multi-device ``jit`` the call is wrapped in a ``shard_map``
(``_flash_attention_sharded``), the names sit inside that equation where
no policy of the enclosing checkpoint sees them, and the layer re-runs
whole, to the same result.

The kernels run in interpret mode off-TPU, under the selection a
verified chip makes, so the CPU test mesh differentiates through the
kernels the chip runs; tests/test_attention.py pins fwd+bwd numerics
against the plain-XLA reference.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

log = logging.getLogger(__name__)

NEG_INF = float(jnp.finfo(jnp.float32).min)
# The hardware tile floor: every sequence is a multiple of it (or shorter
# than it), and no kernel tiles finer.
BLOCK_Q = 128
# Streaming tile targets, and the widest row block any kernel here takes.
# 128×128 tiles make a grid whose fixed per-program cost swamps the
# matmuls: at S=512 55k programs a step at 10 ps a pair forward and 30
# backward where 512 rows take 4.7 and 9.4 (PERF.md §6, PR 25), and
# 512×1024 is the tile every attention cell's ledger line was taken on.
BLOCK_Q_KB = 512
BLOCK_K_KB = 1024
# Where the whole-K forward ends (no silent fallback above it):
#   s_k ≤ MAX_SEQ_VMEM → each program holds the full opposing sequence
#     in VMEM at INPUT dtype (the kernels dot in input dtype, no f32
#     upcast) plus its f32 score block, whose area select_dispatch holds
#     at BLOCK_Q × MAX_SEQ_VMEM. Measured ahead of the streaming forward
#     at every bf16 length tried on v5e, 512 to 4096 (3.6–4.7 ps a pair
#     against 4.2–8.2; PERF.md §6, PR 25).
#   s_k > MAX_SEQ_VMEM → streaming forward, VMEM use O(BLOCK_Q_KB ·
#     BLOCK_K_KB) regardless of sequence length. No fallback to the
#     O(S²)-materializing XLA chain exists above the threshold — long
#     chunks stay fused (tests/test_attention.py pins 8192).
MAX_SEQ_VMEM = 4096
# Fused one-pass backward: one kernel over grid (B,H,nq,nk) produces dq
# AND dk/dv/dbias, computing each (q-block, k-block) probability block
# ONCE — the two-pass pair forms QKᵀ, the mask and the exp in both of
# its kernels — at the price of full-length (S_k, D) f32 dk/dv VMEM
# accumulators. FUSED_BWD_MAX is where they still fit Mosaic's DEFAULT
# scoped VMEM limit (16 MiB on v5e, whose core holds 128): 8 MiB of
# scratch at 8192 keys of 64 dims, with the kernel's double-buffered
# tiles beside it; a call within it is compiled with no limit of its
# own. The gate is the length for 2-byte inputs; select_dispatch holds
# wider inputs to the same bytes of keys, since the kernel's input and
# output tiles grow with them: under the default float32 compiles for
# v5e to 6144 keys and overflows at 7168, bf16 compiles at 8192 (PERF.md
# §6). Past it the fused kernel runs under a limit sized from its
# footprint (``fused_bwd_vmem_limit``; PERF.md §6).
FUSED_BWD_MAX = 8192
# The head size FUSED_BWD_MAX is stated for: 8192 keys of 64 dims are
# 8 MiB of lane-padded dk/dv scratch, and 128-wide heads take the same
# bytes at that length (under the default they compile for v5e at 8192
# keys and overflow at 10,240; PERF.md §6); the gate
# counts keys × head dims, so it stops 128-wide heads at 4096 keys.
FUSED_BWD_HEAD_DIM = 64
# TPU generations (substrings of device_kind, lowercased) with recorded
# scripts/verify_flash_kernels.py results: v5e, where chip_smoke.py
# re-runs that check on every smoke (the fused backward agrees with the
# two-pass pair and with the float32 reference from seq 128 to 8192 in
# bf16 and to 4096 in float32 — PERF.md §6, PRs 21, 25 and 28). Its
# dk/dv/dbias rest on in-order HBM flushes of revisited output blocks,
# which is silicon behaviour: a real TPU off this list keeps the
# two-pass pair (fused_bwd_enabled).
FUSED_BWD_VERIFIED_PLATFORMS = ("v5 lite", "v5e")
# VMEM a TensorCore of the verified generation holds (``TPU v5 lite``:
# ``pltpu.get_tpu_info().vmem_capacity_bytes``), for ``vmem_capacity``
# off the chip.
VERIFIED_VMEM_BYTES = 128 << 20
# The fused backward past Mosaic's default scoped VMEM: it runs where its
# footprint (``fused_bwd_vmem_bytes``) is at most this share of the
# core, under a limit of the footprint plus the margin.
FUSED_BWD_VMEM_SHARE = 0.75
FUSED_BWD_VMEM_MARGIN = 4 << 20
# What the compiler keeps for the kernel's body beyond its buffers, in
# float32 (block_q, block_k) tiles: 0.7–2.1 MiB over them at every
# shape compiled for v5e, where the tile is 2 MiB (ahead-of-time; PERF.md
# §6), so the footprint is within a quarter MiB under, or 2.3 MiB
# over, the least limit that compiles (tests/test_attention_window.py).
FUSED_BWD_TEMPS = 1


def _causal_mask(s, row0, col0, window=None):
    """Scores of one (rows, cols) block with every key later than its
    query masked and, under ``window``, every key ``window`` or more
    positions before it: the mask comes from the block's place in the
    sequence (``row0``/``col0``: its first row and column), never from
    HBM."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return jnp.where(keep, s, NEG_INF)


def _block_needed(qi, ki, block_q: int, block_k: int, qs=None, ks=None,
                  window=None):
    """Whether a causal (q-block, k-block) pair holds any pair to
    compute: not wholly above the diagonal, under ``window`` not wholly
    behind it (the block's last key within ``window`` of its first row)
    and, on packed rows, with a document in common (the two blocks'
    ranges of segment ids overlap; a conservative test that needs no
    order in the ids). A block that is not needed is skipped, not
    masked."""
    needed = ki * block_k <= qi * block_q + (block_q - 1)
    if window is not None:
        needed = needed & (ki * block_k + (block_k - 1)
                           > qi * block_q - window)
    if qs is not None:
        needed = needed & (jnp.max(ks) >= jnp.min(qs)) \
            & (jnp.min(ks) <= jnp.max(qs))
    return needed


def _diagonal_block(qi, block_q: int, block_k: int):
    """The last key block that holds a pair for row block ``qi`` of a
    causal call (a Python int or a traced index)."""
    return (qi * block_q + (block_q - 1)) // block_k


def _attn_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, *rest,
                     scale: float, segmented: bool, causal: bool = False,
                     window=None):
    # Segment-id refs only exist in the segmented variant — the common
    # unsegmented path carries no extra operands (and no VMEM traffic).
    if segmented:
        qseg_ref, kseg_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    # Dots take the INPUT dtype (bf16 in production) with f32 accumulation:
    # bf16 products are exact in the f32 MXU accumulator, so this matches
    # an upcast-then-f32-dot bitwise up to summation order while running
    # at the 2x bf16 MXU rate. Only the p/ds downcasts below round.
    q = q_ref[0, 0]                               # (BQ, D)
    k = k_ref[0, 0]                               # (S, D)
    v = v_ref[0, 0]                               # (S, D_v)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                     # (BQ, S) f32
    s = s + bias_ref[0]                           # additive mask bias, (1,S)
    if segmented:
        # Packed-sequence block-diagonal mask: token i may attend token j
        # only within the same segment (segment ids ride as f32 so the
        # custom_vjp stays all-float; equality on small ints is exact).
        qs = qseg_ref[0, 0]                       # (BQ,)
        ks = kseg_ref[0, 0]                       # (S,)
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
    if causal:
        s = _causal_mask(s, pl.program_id(2) * q.shape[0], 0, window)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / l                                         # (BQ, D_v)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    # Per-row logsumexp: the only softmax statistic the backward needs.
    lse_ref[0, 0] = (m + jnp.log(l)).astype(jnp.float32)


def _guarded(compute, causal: bool, qi, ki, q_ref, k_ref, seg_refs,
             window=None, reached=None, triangle: bool = False):
    """Run one (q-block, k-block) visit's ``compute``: always without
    ``causal`` (the trace is then the plain kernel's), under
    ``_block_needed`` with it and, on a window call's short axis, only
    where the block is one the axis' reach holds (``_visit``: a program
    past it stands for no block of the row). On a ``triangle``, whose
    grid holds no visit above the diagonal, only the document test is
    left: a visit on packed rows runs where its blocks share a document,
    one on unsegmented rows always."""
    if not causal:
        compute()
        return
    qs = ks = None
    if seg_refs:
        qs, ks = seg_refs[0][0, 0], seg_refs[1][0, 0]
    if triangle:
        if qs is None:
            compute()
        else:
            pl.when((jnp.max(ks) >= jnp.min(qs))
                    & (jnp.min(ks) <= jnp.max(qs)))(compute)
        return
    needed = _block_needed(qi, ki, q_ref.shape[2], k_ref.shape[2],
                           qs, ks, window)
    if reached is not None:
        needed = needed & reached
    pl.when(needed)(compute)


def _attn_fwd_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                        scale: float, segmented: bool, causal: bool = False,
                        window=None, reach=None, visit=None):
    """K-blocked forward: grid (B, H, nq, nk) with nk innermost/sequential
    (under ``window`` as long as the blocks a window reaches, ``reach``
    saying which key block a program stands for: ``_k_axis``), or on a
    causal call without a window the triangle's (B, H, T), ``visit``
    the ``(t, qi, ki)`` a program stands for (``_on_triangle``): a row
    block's state starts at its key block 0 and ends at its diagonal.

    Running-softmax state (m, l, acc) persists in VMEM scratch across the
    k-blocks of one q-block; K/V stream through in block_k tiles so no
    whole-sequence operand ever sits in VMEM. Finite NEG_INF arithmetic
    gives bit-compatible fully-masked-row semantics with the whole-K
    kernel (garbage o, lse ≈ NEG_INF — the ring merge weights it to 0).
    """
    if segmented:
        qseg_ref, kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    if visit is None:
        kr = pl.program_id(3)
        qi = pl.program_id(2) if causal else None
        ki, reached = _visit(reach, qi, kr)
        first = lambda: kr == 0                          # noqa: E731
        last = lambda: kr == pl.num_programs(3) - 1      # noqa: E731
    else:
        _, qi, ki = visit
        reached = None
        first = lambda: ki == 0                          # noqa: E731
        last = lambda: ki == _diagonal_block(            # noqa: E731
            qi, q_ref.shape[2], k_ref.shape[2])

    @pl.when(first())
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    def _compute():
        q = q_ref[0, 0]                               # (BQ, D) input dtype
        k = k_ref[0, 0]                               # (BK, D)
        v = v_ref[0, 0]                               # (BK, D_v)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[0]                       # (BQ, BK) f32
        if segmented:
            qs = qseg_ref[0, 0]                       # (BQ,)
            ks = kseg_ref[0, 0]                       # (BK,)
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * q.shape[0], ki * k.shape[0], window)
        m_prev = m_ref[...]                           # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    _guarded(_compute, causal, qi, ki, q_ref, k_ref,
             (qseg_ref, kseg_ref) if segmented else (), window, reached,
             triangle=visit is not None)

    @pl.when(last())
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])


def _attn_bwd_dq_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                           scale: float, segmented: bool,
                           causal: bool = False, window=None, reach=None):
    """K-blocked dQ: accumulate ds·k over streamed K/V tiles in scratch
    (the forward's grid and ``reach``)."""
    if segmented:
        qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = rest
    else:
        do_ref, lse_ref, delta_ref, dq_ref, acc_ref = rest
    kr = pl.program_id(3)
    qi = pl.program_id(2) if causal else None
    ki, reached = _visit(reach, qi, kr)

    @pl.when(kr == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    def _compute():
        q = q_ref[0, 0]                               # (BQ, D) input dtype
        k = k_ref[0, 0]                               # (BK, D)
        v = v_ref[0, 0]                               # (BK, D_v)
        do = do_ref[0, 0]                             # (BQ, D_v)
        lse = lse_ref[0, 0]                           # (BQ, 1)
        delta = delta_ref[0, 0]                       # (BQ, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[0]                       # (BQ, BK) f32
        if segmented:
            qs = qseg_ref[0, 0]
            ks = kseg_ref[0, 0]
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * q.shape[0], ki * k.shape[0], window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # (BQ, BK)
        ds = p * (dp - delta)                         # f32
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    _guarded(_compute, causal, qi, ki, q_ref, k_ref,
             (qseg_ref, kseg_ref) if segmented else (), window, reached)

    @pl.when(kr == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                            scale: float, segmented: bool,
                            causal: bool = False, window=None, reach=None):
    """K-blocked dK/dV/dbias: grid (B, H, nk, nq) with the q-axis
    innermost/sequential (under ``window`` as long as the row blocks a
    key block reaches, ``reach`` saying which one a program stands for:
    ``_q_axis``); Q/dO stream through in block_q tiles while the
    (dk, dv, dbias) accumulators for one k-block live in scratch."""
    if segmented:
        (qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, db_acc) = rest
    else:
        (do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, db_acc) = rest
    qr = pl.program_id(3)
    ki = pl.program_id(2) if causal else None
    qi, reached = _visit(reach, ki, qr)

    @pl.when(qr == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)
        db_acc[...] = jnp.zeros(db_acc.shape, db_acc.dtype)

    def _compute():
        q = q_ref[0, 0]                               # (BQ, D) input dtype
        k = k_ref[0, 0]                               # (BK, D)
        v = v_ref[0, 0]                               # (BK, D_v)
        do = do_ref[0, 0]                             # (BQ, D_v)
        lse = lse_ref[0, 0]                           # (BQ, 1)
        delta = delta_ref[0, 0]                       # (BQ, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[0]                       # (BQ, BK) f32
        if segmented:
            qs = qseg_ref[0, 0]
            ks = kseg_ref[0, 0]
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * q.shape[0], ki * k.shape[0], window)
        p = jnp.exp(s - lse)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # (BK, D_v)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # (BQ, BK)
        ds = p * (dp - delta)                         # f32
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (BK, D)
        db_acc[...] = db_acc[...] + jnp.sum(ds, axis=0, keepdims=True)

    _guarded(_compute, causal, qi, ki, q_ref, k_ref,
             (qseg_ref, kseg_ref) if segmented else (), window, reached)

    @pl.when(qr == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
        dbias_ref[0, 0] = db_acc[...]


def _attn_bwd_fused_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                              scale: float, segmented: bool,
                              causal: bool = False, window=None,
                              visit=None):
    """Fused one-pass streaming backward: grid (B, H, nq, nk), BOTH inner
    axes sequential ("arbitrary"), or on a causal call without a window
    the triangle's (B, H, T) with its one visit axis sequential
    (``visit``, as the forward's). Each (q-block, k-block) pair is
    visited once; its probability block is exp'd ONCE and feeds all four
    cotangents. dq accumulates per q-block in block scratch (finalized
    when the row block's key scan ends: the last key block, on the
    triangle the diagonal's); dk/dv/dbias accumulate in FULL-LENGTH VMEM
    scratch across the whole per-(b,h) subgrid, and each visit stores
    the current partial to the block output — grid steps execute in
    order on the core, so the final visit's flush of a key block is what
    HBM keeps. That visit is the last row block's, on the rectangle and
    on the triangle alike: the last row block reaches every key block,
    so every key block's last write holds all its partials. Earlier
    flushes are dead writes: ~(nq-1)·S_k·D·4B extra HBM-write traffic
    per (b,h) on the rectangle, orders below the exp savings; the
    triangle writes a key block only on the visits that reach it (272
    writes a head and row where the rectangle made 512, at 16,384 keys
    on 512 × 1024 tiles). Under ``causal`` on the rectangle a
    visit that ``_block_needed`` rules out adds nothing and still
    flushes (its out map names its own key block), so the last visit of
    every k-block keeps what HBM holds; on the triangle only the
    document test skips, and a visit it skips flushes what the scratch
    holds just the same."""
    if segmented:
        (qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dbias_ref,
         dq_acc, dk_full, dv_full, db_full) = rest
    else:
        (do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dbias_ref,
         dq_acc, dk_full, dv_full, db_full) = rest
    bk = k_ref.shape[2]
    if visit is None:
        qi = pl.program_id(2)
        ki = pl.program_id(3)
        opening = lambda: (qi == 0) & (ki == 0)          # noqa: E731
        last = lambda: ki == pl.num_programs(3) - 1      # noqa: E731
    else:
        t, qi, ki = visit
        opening = lambda: t == 0                         # noqa: E731
        last = lambda: ki == _diagonal_block(            # noqa: E731
            qi, q_ref.shape[2], bk)

    @pl.when(ki == 0)
    def _init_dq():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    @pl.when(opening())
    def _init_dkv():
        dk_full[...] = jnp.zeros(dk_full.shape, dk_full.dtype)
        dv_full[...] = jnp.zeros(dv_full.shape, dv_full.dtype)
        db_full[...] = jnp.zeros(db_full.shape, db_full.dtype)

    cache: list = []

    def k_rows():
        """This visit's rows of the full-length accumulators. Formed
        where the plain kernel forms it (inside ``_compute``, which is
        then traced inline); under ``causal`` ahead of the guard, so the
        flush below can use it too."""
        if not cache:
            cache.append(pl.ds(ki * bk, bk))
        return cache[0]

    if causal:
        k_rows()

    def _compute():
        q = q_ref[0, 0]                               # (BQ, D) input dtype
        k = k_ref[0, 0]                               # (BK, D)
        v = v_ref[0, 0]                               # (BK, D_v)
        do = do_ref[0, 0]                             # (BQ, D_v)
        lse = lse_ref[0, 0]                           # (BQ, 1)
        delta = delta_ref[0, 0]                       # (BQ, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias_ref[0]                       # (BQ, BK) f32
        if segmented:
            qs = qseg_ref[0, 0]
            ks = kseg_ref[0, 0]
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        if causal:
            s = _causal_mask(s, qi * q.shape[0], ki * bk, window)
        p = jnp.exp(s - lse)                          # the ONE exp per pair
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # (BQ, BK)
        ds = p * (dp - delta)                         # f32
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

        sl = k_rows()
        dv_full[sl, :] = dv_full[sl, :] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # (BK, D_v)
        dk_full[sl, :] = dk_full[sl, :] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (BK, D)
        db_full[:, sl] = db_full[:, sl] + jnp.sum(ds, axis=0, keepdims=True)

    _guarded(_compute, causal, qi, ki, q_ref, k_ref,
             (qseg_ref, kseg_ref) if segmented else (), window,
             triangle=visit is not None)

    @pl.when(last())
    def _finalize_dq():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)

    # Store the running partials every visit; the last (qi) visit wins.
    sl = k_rows()
    dk_ref[0, 0] = dk_full[sl, :].astype(dk_ref.dtype)
    dv_ref[0, 0] = dv_full[sl, :].astype(dv_ref.dtype)
    dbias_ref[0, 0] = db_full[:, sl]


def _xla_reference(q, k, v, bias):
    """Plain-XLA attention on the (B,H,S,D) layout — the numerics source of
    truth the kernels are tested against (tests/test_attention.py)."""
    d = q.shape[-1]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((3,), (3,)), ((0, 1), (0, 1))),
    ) / (d ** 0.5)                                  # (B,H,S,S)
    s = s + bias[:, None, :, :]
    p = jax.nn.softmax(s, axis=-1)
    return jax.lax.dot_general(
        p, v.astype(jnp.float32),
        (((3,), (2,)), ((0, 1), (0, 1))),
    ).astype(q.dtype)                               # (B,H,S,D)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def kernel_mode() -> str:
    """How this process compiles the kernels: ``"mosaic"`` on a TPU
    backend, ``"interpret"`` anywhere else (the CPU test mesh). Run-meta
    records carry it so an interpreted run never reads as a chip run."""
    return "interpret" if _interpret() else "mosaic"


def fused_bwd_verified(device_kind: str) -> bool:
    """Whether scripts/verify_flash_kernels.py results are recorded for
    this TPU generation (FUSED_BWD_VERIFIED_PLATFORMS)."""
    kind = device_kind.lower()
    return any(p in kind for p in FUSED_BWD_VERIFIED_PLATFORMS)


@functools.cache
def fused_bwd_enabled() -> bool:
    """Whether the fused one-pass backward may run in this process:
    everywhere but on a real TPU whose generation has no recorded
    scripts/verify_flash_kernels.py results. There the fused dk/dv/dbias
    flush ordering is unverified silicon behaviour and silently wrong
    gradients are the worst possible failure, so the two-pass pair runs
    and a warning says so, once. Off the chip the answer is the verified
    chip's: interpret mode walks the grid in order, so there is no flush
    ordering to get wrong, the CPU suite differentiates through the
    kernel the chip runs, and an ahead-of-time compile for a described
    chip is of the chip's program. The one seam for a test or script
    that needs the other backward: replace this function."""
    if jax.default_backend() != "tpu":
        return True
    kind = jax.devices()[0].device_kind
    verified = fused_bwd_verified(kind)
    if not verified:
        log.warning(
            "fused flash-attention backward disabled: TPU %r is not in "
            "FUSED_BWD_VERIFIED_PLATFORMS — run "
            "scripts/verify_flash_kernels.py on it and add the generation "
            "with the results it records", kind)
    return verified


def vmem_capacity() -> int:
    """VMEM of one TensorCore, in bytes: ``pltpu.get_tpu_info()``'s on a
    TPU, off the chip the verified chip's (as ``fused_bwd_enabled``
    answers there), so an ahead-of-time compile for it is of its
    program."""
    if jax.default_backend() != "tpu":
        return VERIFIED_VMEM_BYTES
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.get_tpu_info().vmem_capacity_bytes


def _vmem_buffer_bytes(rows: int, cols: int, itemsize: int) -> int:
    """A (rows, cols) buffer in VMEM: columns padded to whole lanes (128),
    rows to whole (8, 128) tiles of 4-byte words."""
    per_tile = 8 * 4 // itemsize
    return (-(-rows // per_tile) * per_tile * -(-cols // 128) * 128
            * itemsize)


def fused_bwd_vmem_bytes(s_k: int, head_dim: int, v_head_dim: int | None,
                         dtype, block_q: int, block_k: int) -> int:
    """What the fused backward keeps in VMEM on a ``block_q`` ×
    ``block_k`` tile, in bytes: the full-length dk, dv and dbias scratch
    and dq's block scratch (float32); every input and output block twice
    over, as the pipeline double-buffers them, dk and dv at 4 bytes (the
    float32 partials of grouped heads) and the segment ids counted
    whether or not a call has them; and FUSED_BWD_TEMPS float32
    (block_q, block_k) tiles for the compiler's own stack of the body."""
    d, d_v = head_dim, v_head_dim or head_dim
    item = jnp.dtype(dtype).itemsize
    buf = _vmem_buffer_bytes
    scratch = (buf(s_k, d, 4) + buf(s_k, d_v, 4) + buf(1, s_k, 4)
               + buf(block_q, d, 4))
    inputs = (buf(block_q, d, item) + buf(block_k, d, item)
              + buf(block_k, d_v, item) + buf(1, block_k, 4)      # bias
              + buf(1, block_q, 4) + buf(1, block_k, 4)           # ids
              + buf(block_q, d_v, item) + 2 * buf(block_q, 1, 4))  # lse, δ
    outputs = (buf(block_q, d, item) + buf(block_k, d, 4)
               + buf(block_k, d_v, 4) + buf(1, block_k, 4))
    return (scratch + 2 * (inputs + outputs)
            + FUSED_BWD_TEMPS * buf(block_q, block_k, 4))


def fused_bwd_vmem_limit(s_k: int, head_dim: int, v_head_dim: int | None,
                         dtype, block_q: int, block_k: int) -> int | None:
    """The scoped VMEM limit a fused backward past Mosaic's default is
    compiled under: its footprint (``fused_bwd_vmem_bytes``) and
    FUSED_BWD_VMEM_MARGIN, in whole MiB; None where the footprint is over
    FUSED_BWD_VMEM_SHARE of the core (``vmem_capacity``)."""
    need = fused_bwd_vmem_bytes(s_k, head_dim, v_head_dim, dtype,
                                block_q, block_k)
    if need > vmem_capacity() * FUSED_BWD_VMEM_SHARE:
        return None
    mib = 1 << 20
    return -(-(need + FUSED_BWD_VMEM_MARGIN) // mib) * mib


class FlashDispatch(NamedTuple):
    """What one attention call runs. ``family`` is the forward's:
    ``"whole_k"`` (a program holds its rows and ALL of the opposing
    sequence) or ``"stream"`` (the opposing sequence streams through in
    tiles under a sequential grid axis). ``backward`` is ``"fused"`` (the
    one-pass kernel) or ``"two_pass"`` (dq, then dk/dv/dbias); both
    stream, on the ``bwd_block_q`` × ``bwd_block_k`` tile.
    ``bwd_vmem_limit`` is the scoped VMEM the fused backward is compiled
    under where Mosaic's default would not hold its scratch, None where
    the default does."""
    family: str
    block_q: int
    block_k: int
    backward: str
    bwd_block_q: int
    bwd_block_k: int
    bwd_vmem_limit: int | None


def select_dispatch(s: int, s_k: int, dtype,
                    head_dim: int = FUSED_BWD_HEAD_DIM,
                    v_head_dim: int | None = None,
                    window: int | None = None) -> FlashDispatch:
    """The one place a (q length, k length, input dtype, head size,
    window) becomes kernels and tiles; the platform enters through
    ``fused_bwd_enabled()`` and ``vmem_capacity()``. ``v_head_dim`` is
    the value heads' width where it is not ``head_dim``'s.
    Called at the custom_vjp layer, outside the jitted wrappers, so the
    module globals it reads are never frozen into a trace cache: the
    wrappers take the result as a static argument.

    Whole-K forward tile: a program's f32 score block keeps the area the
    family proves at its upper edge, BLOCK_Q rows × MAX_SEQ_VMEM keys, so
    the rows grow as the keys shrink, up to the streaming q-tile: 512
    rows to S=1024, 256 at 2048, 128 at 4096."""
    stream_tile = (_pick_block(s, BLOCK_Q_KB), _pick_block(s_k, BLOCK_K_KB))
    if s_k > MAX_SEQ_VMEM:
        forward = ("stream", *stream_tile)
    else:
        rows = BLOCK_Q * MAX_SEQ_VMEM // s_k
        forward = ("whole_k",
                   _pick_block(s, min(max(rows, BLOCK_Q), BLOCK_Q_KB)), s_k)
    # The fused backward where it is allowed and can slice its
    # full-length dbias accumulator by whole lanes: on a key tile under
    # BLOCK_Q Mosaic refuses the kernel ("cannot statically prove that
    # index in dimension 1 is a multiple of 128"), and the two-pass pair
    # is what runs such inputs. What has to fit is the full-length dk/dv
    # scratch, keys × (key dims + value dims) × 4 B, beside tiles that
    # grow with the head sizes and the input's bytes alike. Under
    # Mosaic's default scoped VMEM it fits where keys × both widths ×
    # itemsize stay within what FUSED_BWD_MAX keys of FUSED_BWD_HEAD_DIM
    # dims each and 2 bytes come to; such a call is compiled as it always
    # was, with no limit of its own. Past that, a call without a window
    # is compiled under a limit sized from its own footprint
    # (``fused_bwd_vmem_limit``) where that fits the core. A window call
    # keeps the two-pass pair there: the fused kernel's key axis holds
    # every key block, where the pair's grids hold only the blocks a
    # window reaches (``_k_axis``).
    widths = head_dim + (head_dim if v_head_dim is None else v_head_dim)
    fused = fused_bwd_enabled() and stream_tile[1] % BLOCK_Q == 0
    limit = None
    if fused and (s_k * widths * jnp.dtype(dtype).itemsize
                  > FUSED_BWD_MAX * FUSED_BWD_HEAD_DIM * 2 * 2):
        limit = (None if window is not None else fused_bwd_vmem_limit(
            s_k, head_dim, v_head_dim, dtype, *stream_tile))
        fused = limit is not None
    return FlashDispatch(*forward, "fused" if fused else "two_pass",
                         *stream_tile, limit)


# (s, s_k, dtype name, segmented, causal, heads, kv_heads, head_dim,
# v_head_dim, window) -> FlashDispatch, one entry per distinct call
# traced in this process: what dispatch_log() reports.
_dispatch_log: dict = {}


def _dispatch(q, k, v, segmented: bool, causal: bool = False,
              window=None) -> FlashDispatch:
    s, s_k, d, d_v = q.shape[2], k.shape[2], q.shape[3], v.shape[3]
    dispatch = select_dispatch(s, s_k, q.dtype, d, d_v, window)
    _dispatch_log[(s, s_k, jnp.dtype(q.dtype).name, segmented, causal,
                   q.shape[1], k.shape[1], d, d_v, window or 0)] = dispatch
    return dispatch


def dispatch_log() -> list[dict]:
    """Every distinct (s, s_k, dtype, segmented, causal, heads, kv_heads,
    head_dim, v_head_dim, window) traced so far with the kernels and
    tiles it was given — the run-meta record's ``flash_dispatch``
    (train/loop.py), so a run says which attention kernels its shapes
    selected without a trace. ``window`` is None for a call without one,
    and with it the lengths of a window call's sequential axes,
    ``k_axis`` and ``q_axis`` (``window_grid``); ``visit_axis`` is the
    programs a head and row of a causal call without a window, T on the
    triangle its streaming forward and fused backward walk
    (``causal_grid``), None for any other call; ``bwd_vmem_limit`` is
    the scoped VMEM the fused backward was compiled under, None where it
    took Mosaic's default or the two-pass pair runs."""
    entries = []
    for (s, s_k, dtype, segmented, causal, heads, kv_heads, head_dim,
         v_head_dim, window), dispatch in sorted(_dispatch_log.items()):
        grid = window_grid(s, s_k, window, dispatch) if window else {}
        triangle = (causal_grid(s, s_k, dispatch) if causal and not window
                    else {})
        entries.append(dict(
            s=s, s_k=s_k, dtype=dtype, segmented=segmented, causal=causal,
            heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            v_head_dim=v_head_dim,
            window=window or None, k_axis=grid.get("k_axis"),
            q_axis=grid.get("q_axis"),
            visit_axis=triangle.get("visit_axis"), **dispatch._asdict()))
    return entries


# The names of the two forward-kernel outputs the backward kernels read,
# for a ``jax.checkpoint`` policy to keep
# (``save_only_these_names(*RESIDUAL_NAMES)``, models/lfm2.py): with both
# kept, the forward kernel is dead code in the re-run forward pass.
ATTN_OUT_NAME = "flash_attention_out"
ATTN_LSE_NAME = "flash_attention_lse"
RESIDUAL_NAMES = (ATTN_OUT_NAME, ATTN_LSE_NAME)


def _name_residuals(o, lse):
    """Tag the forward kernel's outputs inside a custom-VJP ``fwd`` rule,
    outside the jitted wrapper and before the residual tuple takes them:
    the values the backward reads are then the named ones (a tag on the
    caller's side of the ``custom_vjp`` names another variable and keeps
    nothing). The logsumexp is named as (B, H, S) and handed on as the
    reshape of that: kept as the kernel leaves it, ``f32[B,H,S,1]``, it
    costs a layer 128 times its values in HBM. Inert without a policy
    that asks for the names."""
    return (checkpoint_name(o, ATTN_OUT_NAME),
            checkpoint_name(lse[..., 0], ATTN_LSE_NAME)[..., None])


def _make_fused(segmented: bool, return_lse: bool,
                causal: bool = False, window=None):
    """Build the custom-VJP fused attention for one (segmented, lse)
    variant. Unsegmented signature: (q, k, v, bias) — the common path
    carries NO segment operands or VMEM traffic. Segmented adds
    (qseg, kseg): (B,1,Sq)/(B,1,Sk) FLOAT segment ids (all-float
    custom_vjp; zero cotangents). ``return_lse`` additionally returns the
    per-row logsumexp — the chunk primitive for ring attention, whose
    online merge needs lse and therefore flows a cotangent into it.
    Residuals are all O(S·D)/O(S): no score-matrix-shaped tensor is ever
    saved. ``causal`` masks every key later than its query, from indices
    inside the kernels; k and v may carry fewer heads than q (a whole
    divisor: grouped-query attention), reached through the block index
    maps and never repeated in memory. ``window`` (with ``causal``) also
    masks every key ``window`` or more positions before its query.
    """
    # Without a window the wrappers are called as they always were, so
    # their traces (and the jit caches' keys) are the parent's.
    win = {} if window is None else {"window": window}
    if segmented:
        @jax.custom_vjp
        def fused(q, k, v, bias, qseg, kseg):
            o, lse = _flash_fwd(q, k, v, bias, qseg, kseg,
                                segmented=True, interpret=_interpret(),
                                dispatch=_dispatch(q, k, v, True, causal,
                                                   window),
                                causal=causal, **win)
            return (o, lse) if return_lse else o

        def fwd(q, k, v, bias, qseg, kseg):
            o, lse = _flash_fwd(q, k, v, bias, qseg, kseg,
                                segmented=True, interpret=_interpret(),
                                dispatch=_dispatch(q, k, v, True, causal,
                                                   window),
                                causal=causal, **win)
            o, lse = _name_residuals(o, lse)
            out = (o, lse) if return_lse else o
            return out, (q, k, v, bias, qseg, kseg, o, lse)

        def bwd(res, g):
            q, k, v, bias, qseg, kseg, o, lse = res
            do, dlse = g if return_lse else (g, None)
            dq, dk, dv, dbias = _flash_bwd(
                q, k, v, bias, qseg, kseg, o, lse, do, dlse=dlse,
                segmented=True, interpret=_interpret(),
                dispatch=_dispatch(q, k, v, True, causal, window),
                causal=causal, **win)
            return (dq, dk, dv, dbias,
                    jnp.zeros_like(qseg), jnp.zeros_like(kseg))
    else:
        @jax.custom_vjp
        def fused(q, k, v, bias):
            o, lse = _flash_fwd(q, k, v, bias,
                                segmented=False, interpret=_interpret(),
                                dispatch=_dispatch(q, k, v, False, causal,
                                                   window),
                                causal=causal, **win)
            return (o, lse) if return_lse else o

        def fwd(q, k, v, bias):
            o, lse = _flash_fwd(q, k, v, bias,
                                segmented=False, interpret=_interpret(),
                                dispatch=_dispatch(q, k, v, False, causal,
                                                   window),
                                causal=causal, **win)
            o, lse = _name_residuals(o, lse)
            out = (o, lse) if return_lse else o
            return out, (q, k, v, bias, o, lse)

        def bwd(res, g):
            q, k, v, bias, o, lse = res
            do, dlse = g if return_lse else (g, None)
            dq, dk, dv, dbias = _flash_bwd(
                q, k, v, bias, o, lse, do, dlse=dlse,
                segmented=False, interpret=_interpret(),
                dispatch=_dispatch(q, k, v, False, causal, window),
                causal=causal, **win)
            return dq, dk, dv, dbias

    fused.defvjp(fwd, bwd)
    return fused


_FUSED = {(seg, lse): _make_fused(seg, lse)
          for seg in (False, True) for lse in (False, True)}
_FUSED_CAUSAL = {seg: _make_fused(seg, False, causal=True)
                 for seg in (False, True)}


@functools.cache
def _fused_window(segmented: bool, window: int):
    """The causal variant with a window, one per distinct window."""
    return _make_fused(segmented, False, causal=True, window=window)


def chunk_supported(s: int) -> bool:
    """Whether a ring chunk of per-shard length ``s`` fits the kernel's
    constraints (the same ones flash_attention_chunk's guards enforce) —
    the single source of truth for dispatch-vs-fallback decisions
    (parallel/ring.py). No upper bound: chunks above MAX_SEQ_VMEM take
    the K-blocked streaming kernels instead of falling back (module
    docstring; VERDICT r3 weak #2)."""
    return s > 0 and s % min(BLOCK_Q, s) == 0


def _seg_f32(seg):
    """(B,1,S) f32 view of integer segment ids for the fused kernels
    (float ids keep the custom_vjp all-float; equality on small ints is
    exact in f32)."""
    return seg.astype(jnp.float32)[:, None, :]


def flash_attention_chunk(q, k, v, bias, q_seg=None, kv_seg=None):
    """Per-chunk fused attention for the ring: (B,S,H,D) q/k/v (equal-length
    shards) + additive key bias (B, Sk) → (o (B,S,H,D), lse (B,S,H,1)).

    ``q_seg``/``kv_seg`` (B, Sq)/(B, Sk) optional packed-sequence segment
    ids: tokens attend only within equal ids (block-diagonal mask).
    ``o`` is normalized *within the chunk*; the caller merges chunks with
    the standard logsumexp reweighting (parallel/ring.py). Differentiable
    in all float inputs including through ``lse``.
    """
    s_q, s_k = q.shape[1], k.shape[1]
    if s_q != s_k or v.shape[1] != s_k:
        # _flash_fwd indexes K/V blocks by q's length; unequal shards
        # would silently read a K/V prefix.
        raise ValueError(
            f"flash_attention_chunk needs equal-length q/k/v shards, got "
            f"q={s_q} k={s_k} v={v.shape[1]}"
        )
    if s_q % min(BLOCK_Q, s_q):
        # The fwd grid is s // block_q: a non-multiple chunk (e.g.
        # seq/ring_shards = 192) would silently drop the tail rows.
        raise ValueError(
            f"chunk len {s_q} must be a multiple of {BLOCK_Q} (or smaller "
            f"than {BLOCK_Q}) — pick mesh.seq so the per-shard chunk "
            f"seq/ring_shards is a {BLOCK_Q}-multiple"
        )
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    bias_f = bias[:, None, :].astype(jnp.float32)
    if q_seg is None:
        o, lse = _FUSED[(False, True)](qt, kt, vt, bias_f)
    else:
        o, lse = _FUSED[(True, True)](qt, kt, vt, bias_f,
                                      _seg_f32(q_seg), _seg_f32(kv_seg))
    return o.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("segmented", "interpret", "dispatch",
                                    "causal", "window"))
def _flash_fwd(q, k, v, bias, qseg=None, kseg=None, *, segmented: bool,
               interpret: bool, dispatch: FlashDispatch,
               causal: bool = False, window=None):
    b, h, s, d = q.shape
    d_v = v.shape[3]
    kv_head = _kv_head_map(h, k.shape[1])
    s_k = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_q = dispatch.block_q
    if dispatch.family == "stream":
        return _flash_fwd_kb(q, k, v, bias, qseg, kseg,
                             segmented=segmented, interpret=interpret,
                             block_q=block_q, block_k=dispatch.block_k,
                             causal=causal, window=window)
    grid = (b, h, s // block_q)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, s_k, d),
                     lambda bi, hi, qi: (bi, kv_head(hi), 0, 0)),
        pl.BlockSpec((1, 1, s_k, d_v),
                     lambda bi, hi, qi: (bi, kv_head(hi), 0, 0)),
        pl.BlockSpec((1, 1, s_k), lambda bi, hi, qi: (bi, 0, 0)),
    ]
    operands = [q, k, v, bias]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi: (bi, 0, qi)),
            pl.BlockSpec((1, 1, s_k), lambda bi, hi, qi: (bi, 0, 0)),
        ]
        operands += [qseg, kseg]
    return pl.pallas_call(
        functools.partial(_attn_fwd_kernel, scale=scale, segmented=segmented,
                          causal=causal, window=window),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        interpret=interpret,
    )(*operands)


def _kv_head_map(heads: int, kv_heads: int):
    """Query head -> the key/value head it reads (grouped-query
    attention): the block index maps send ``heads // kv_heads`` query
    heads to one key/value block, so k and v are never repeated in
    memory. With as many key/value heads as query heads it is the
    identity itself, and the index maps trace as they always did."""
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} key/value "
                         f"heads: not a whole group size")
    group = heads // kv_heads
    return (lambda hi: hi) if group == 1 else (lambda hi: hi // group)


def _last_k_block(causal: bool, block_q: int, block_k: int, window=None):
    """``(qi, ki) -> k-block to fetch`` on a k-axis as long as the row's
    key blocks: under ``causal`` a visit above the diagonal names the
    last block the row block needs and, under ``window`` (the fused
    backward, whose scratch is indexed by the absolute key block), a
    visit behind the window the first, so the pipeline fetches nothing
    new for a visit the kernel skips."""
    if not causal:
        return lambda qi, ki: ki
    if window is None:
        return lambda qi, ki: jnp.minimum(
            ki, (qi * block_q + (block_q - 1)) // block_k)
    return lambda qi, ki: jnp.clip(
        ki, jnp.maximum(qi * block_q - (window - 1), 0) // block_k,
        (qi * block_q + (block_q - 1)) // block_k)


def _first_q_block(causal: bool, block_q: int, block_k: int):
    """``(ki, qi) -> q-block to fetch`` for the dk/dv kernel on a q-axis
    as long as the row's blocks: row blocks wholly before a key block
    are skipped, so they name the first one needed."""
    if not causal:
        return lambda ki, qi: qi
    return lambda ki, qi: jnp.maximum(qi, (ki * block_k) // block_q)


def _k_reach(qi, block_q: int, block_k: int, window: int, n_k: int):
    """``(first, last)`` of the key blocks (of ``n_k``) that hold a pair
    inside diagonal and ``window`` for row block ``qi``: every block
    between them does. ``qi`` is a Python int (the static counts) or a
    traced index (index maps and kernels)."""
    lo, hi = ((max, min) if isinstance(qi, int)
              else (jnp.maximum, jnp.minimum))
    return (lo(qi * block_q - (window - 1), 0) // block_k,
            hi((qi * block_q + (block_q - 1)) // block_k, n_k - 1))


def _q_reach(ki, block_q: int, block_k: int, window: int, n_q: int):
    """``(first, last)`` of the row blocks (of ``n_q``) that hold a pair
    inside diagonal and ``window`` for key block ``ki`` (the dk/dv
    kernel's sequential axis), as ``_k_reach``."""
    hi = min if isinstance(ki, int) else jnp.minimum
    return ((ki * block_k) // block_q,
            hi((ki * block_k + (block_k - 1) + (window - 1)) // block_q,
               n_q - 1))


def _reach_axis(reach, n_parallel: int):
    """A window call's sequential axis from ``reach`` (``_k_reach`` or
    ``_q_reach`` with the call's tiles bound): ``(length, reach,
    fetch)``. The axis is as long as the widest reach of the
    ``n_parallel`` blocks beside it; program ``r`` beside block ``p``
    stands for block ``first(p) + r`` (``_visit``) and ``fetch(p, r)``
    names the block the pipeline reads for it: that one, or ``last(p)``
    for a program past the reach, so nothing new is fetched for it."""
    length = max(last - first + 1
                 for first, last in map(reach, range(n_parallel)))

    def fetch(p, r):
        first, last = reach(p)
        return jnp.minimum(first + r, last)

    return length, reach, fetch


def _k_axis(causal: bool, block_q: int, block_k: int, window,
            n_q: int, n_k: int):
    """The sequential k-axis of the streaming forward and dq kernels:
    ``(length, reach, fetch)``. Without ``window`` every key block has a
    program (``reach`` None: program ``r`` is block ``r``) and
    ``_last_k_block`` says what it fetches; with it the axis holds only
    the blocks a window can reach (``_reach_axis``)."""
    if window is None:
        return n_k, None, _last_k_block(causal, block_q, block_k)
    return _reach_axis(
        functools.partial(_k_reach, block_q=block_q, block_k=block_k,
                          window=window, n_k=n_k), n_q)


def _q_axis(causal: bool, block_q: int, block_k: int, window,
            n_q: int, n_k: int):
    """The sequential q-axis of the dk/dv kernel, as ``_k_axis``."""
    if window is None:
        return n_q, None, _first_q_block(causal, block_q, block_k)
    return _reach_axis(
        functools.partial(_q_reach, block_q=block_q, block_k=block_k,
                          window=window, n_q=n_q), n_k)


def _visit(reach, p, r):
    """Inside a kernel: the block that program ``r`` of the sequential
    axis stands for beside parallel block ``p``, and whether ``p``
    reaches it. On a full-length axis (``reach`` None) that is ``r``
    itself and nothing to test."""
    if reach is None:
        return r, None
    first, last = reach(p)
    block = first + r
    return block, block <= last


def window_block_counts(s: int, s_k: int, block_q: int, block_k: int,
                        window: int) -> tuple[int, int]:
    """``(visited, causal)``: how many (q-block, k-block) visits of a
    causal call on these tiles hold a pair inside ``window``, and how
    many hold a pair at all (``_block_needed`` without segments, counted
    in Python: the window layers' ``attn_window_block_share``)."""
    visited = causal = 0
    for q0 in range(0, s, block_q):
        for k0 in range(0, s_k, block_k):
            if k0 <= q0 + block_q - 1:
                causal += 1
                visited += k0 + block_k - 1 > q0 - window
    return visited, causal


def window_grid(s: int, s_k: int, window: int,
                dispatch: FlashDispatch) -> dict:
    """What a window call's kernels launch a head and row under
    ``dispatch``, counted in Python like ``window_block_counts``:
    ``k_axis``, the sequential axis of the dq kernel (and of a streaming
    forward: the same tile), ``q_axis``, the dk/dv kernel's (None under
    the fused backward, which has no such kernel and keeps every key
    block on its axis), and over forward and backward together the
    programs ``launched`` and those of them ``visited`` (holding a pair
    inside the window): the window layers' ``attn_window_grid_share``."""
    block_q, block_k = dispatch.bwd_block_q, dispatch.bwd_block_k
    n_q, n_k = s // block_q, s_k // block_k
    visited = window_block_counts(s, s_k, block_q, block_k, window)[0]
    k_len = _k_axis(True, block_q, block_k, window, n_q, n_k)[0]
    q_len = _q_axis(True, block_q, block_k, window, n_q, n_k)[0]
    fused = dispatch.backward == "fused"
    backward = ((visited, n_q * n_k) if fused
                else (2 * visited, n_q * k_len + n_k * q_len))
    forward = ((visited, n_q * k_len) if dispatch.family == "stream"
               # whole-K: a program a row block, none skipped
               else (s // dispatch.block_q,) * 2)
    return dict(k_axis=n_k if fused else k_len,
                q_axis=None if fused else q_len,
                visited=forward[0] + backward[0],
                launched=forward[1] + backward[1])


@functools.cache
def causal_visits(s: int, s_k: int, block_q: int,
                  block_k: int) -> tuple[tuple, tuple]:
    """``(q_of, k_of)``: the (q-block, k-block) visits of a causal call
    without a window that hold a pair, in the order the rectangle ran
    them: row blocks ascending, key blocks ``0 … _diagonal_block(qi)``
    inside each. ``T = len(q_of)``: 272 at 16,384 keys and 72 at 8192 on
    512 × 1024 tiles, the causal count of ``window_block_counts``. The
    rows are as long as the keys (a causal call's are), so the last row
    block reaches every key block."""
    if s != s_k:
        raise ValueError(f"a causal call's rows ({s}) and keys ({s_k}) "
                         f"differ in length")
    q_of, k_of = [], []
    for qi in range(s // block_q):
        reach = _diagonal_block(qi, block_q, block_k) + 1
        q_of += [qi] * reach
        k_of += range(reach)
    return tuple(q_of), tuple(k_of)


def _triangle(causal: bool, window, s: int, s_k: int, block_q: int,
              block_k: int):
    """The visit table a call's streaming forward and fused backward walk
    (``causal_visits``): a causal call's without a window; None for any
    other call, which keeps its grid."""
    if not causal or window is not None:
        return None
    return causal_visits(s, s_k, block_q, block_k)


def _on_triangle(kernel):
    """``kernel`` on a triangle's grid (B, H, T): the visit table comes
    first, scalar-prefetched into SMEM, and program ``t`` stands for row
    block ``q_of[t]`` and key block ``k_of[t]``."""
    def run(q_of, k_of, *refs):
        t = pl.program_id(2)
        kernel(*refs, visit=(t, q_of[t], k_of[t]))
    # the Mosaic kernel keeps the kernel's own name
    run.__name__ = run.__qualname__ = kernel.func.__name__
    return run


def _layout(grid, in_specs, out_specs, scratch_shapes, visits):
    """The ``pallas_call`` keywords of a streaming kernel and the operands
    that go before its own: on a rectangle or a window call's axes the
    grid and specs as they are, and none; on a triangle (``visits``) a
    scalar-prefetch grid spec over (B, H, T) whose index maps take
    ``(bi, hi, t, q_of, k_of)``, and the visit table."""
    if visits is None:
        return dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                    scratch_shapes=scratch_shapes), ()
    from jax.experimental.pallas import tpu as pltpu

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)
    return (dict(grid_spec=spec),
            tuple(jnp.asarray(np.asarray(v, np.int32)) for v in visits))


def _triangle_row(t, q_of, k_of):
    """A triangle's index maps: the row block of program ``t``."""
    return q_of[t]


def _triangle_col(t, q_of, k_of):
    """A triangle's index maps: the key block of program ``t``."""
    return k_of[t]


def causal_grid(s: int, s_k: int, dispatch: FlashDispatch) -> dict:
    """What a causal call without a window launches a head and row under
    ``dispatch``, as ``window_grid`` counts a window call's:
    ``visit_axis``, the triangle's length T where the streaming forward
    or the fused backward walks it (None where neither runs), and over
    forward and backward together the programs ``launched`` and those of
    them ``visited`` (on or below the diagonal): the layers' without a
    window ``attn_causal_grid_share``. The two-pass pair keeps its
    rectangle, the whole-K forward a program a row block."""
    block_q, block_k = dispatch.bwd_block_q, dispatch.bwd_block_k
    visits = len(causal_visits(s, s_k, block_q, block_k)[0])
    fused = dispatch.backward == "fused"
    stream = dispatch.family == "stream"
    backward = ((visits, visits) if fused
                else (2 * visits, 2 * (s // block_q) * (s_k // block_k)))
    forward = ((visits, visits) if stream
               else (s // dispatch.block_q,) * 2)
    return dict(visit_axis=visits if fused or stream else None,
                visited=forward[0] + backward[0],
                launched=forward[1] + backward[1])


def _sum_kv_groups(dk, dv, kv_heads: int, dtype):
    """Per-query-head dk/dv partials (float32 when heads share a
    key/value head) summed over each group."""
    b, h, s_k = dk.shape[:3]
    if h == kv_heads:
        return dk, dv
    fold = lambda t: t.reshape(b, kv_heads, h // kv_heads, s_k,  # noqa: E731
                               t.shape[-1]).sum(axis=2).astype(dtype)
    return fold(dk), fold(dv)


def _vmem_scratch(*shapes_dtypes):
    """VMEM scratch specs for the K-blocked kernels (plain buffers under
    interpret mode on CPU)."""
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM(shape, dtype) for shape, dtype in shapes_dtypes]


def _pick_block(s: int, target: int) -> int:
    """Largest BLOCK_Q-multiple ≤ ``target`` that divides ``s`` (clamped
    to at least BLOCK_Q, so an env target below the hardware tile floor
    degrades to BLOCK_Q instead of dividing by zero). The dispatch
    guards already force s to be a BLOCK_Q-multiple (or < BLOCK_Q), so
    BLOCK_Q always divides and the loop terminates; non-power-of-two
    lengths like 4224 = 33·128 simply land on a smaller tile."""
    if s <= BLOCK_Q:
        return s
    b = max(BLOCK_Q, min(target - target % BLOCK_Q, s))
    while s % b:
        b -= BLOCK_Q
    return b


def _kb_params(interpret: bool, n_parallel: int = 3, vmem_limit=None,
               axes: int = 4):
    """Mosaic grid semantics for the streaming kernels: the leading
    ``n_parallel`` of the grid's ``axes`` are parallel, the rest
    sequential ("arbitrary"). The two-pass kernels accumulate only over
    their innermost axis (n_parallel=3); the fused backward reduces over
    BOTH inner axes (n_parallel=2); on a triangle's (B, H, T) grid the
    one visit axis is sequential (n_parallel=2, axes=3). ``vmem_limit``
    is the scoped VMEM limit, None for Mosaic's default. Interpret mode
    (CPU tests) takes no TPU compiler params."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * (axes - n_parallel),
        vmem_limit_bytes=vmem_limit)}


def _flash_fwd_kb(q, k, v, bias, qseg, kseg, *, segmented: bool,
                  interpret: bool, block_q: int, block_k: int,
                  causal: bool = False, window=None):
    """Streaming forward: sequential k-axis grid, or a causal call's
    triangle without a window, + VMEM-scratch running softmax (kernel
    docstring)."""
    b, h, s, d = q.shape
    s_k, d_v = k.shape[2], v.shape[3]
    scale = 1.0 / (d ** 0.5)
    visits = _triangle(causal, window, s, s_k, block_q, block_k)
    if visits is None:
        n_k, reach, col = _k_axis(causal, block_q, block_k, window,
                                  s // block_q, s_k // block_k)
        row = lambda qi, ki: qi  # noqa: E731
        grid = (b, h, s // block_q, n_k)
    else:
        reach, row, col = None, _triangle_row, _triangle_col
        grid = (b, h, len(visits[0]))
    kv_head = _kv_head_map(h, k.shape[1])
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, *g: (bi, hi, row(*g), 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, *g: (bi, kv_head(hi), col(*g), 0)),
        pl.BlockSpec((1, 1, block_k, d_v),
                     lambda bi, hi, *g: (bi, kv_head(hi), col(*g), 0)),
        pl.BlockSpec((1, 1, block_k),
                     lambda bi, hi, *g: (bi, 0, col(*g))),
    ]
    operands = [q, k, v, bias]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, *g: (bi, 0, row(*g))),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, *g: (bi, 0, col(*g))),
        ]
        operands += [qseg, kseg]
    kernel = functools.partial(_attn_fwd_kernel_kb, scale=scale,
                               segmented=segmented, causal=causal,
                               window=window, reach=reach)
    layout, tables = _layout(
        grid, in_specs,
        [
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
        ],
        _vmem_scratch(
            ((block_q, d_v), jnp.float32),
            ((block_q, 1), jnp.float32),
            ((block_q, 1), jnp.float32),
        ), visits)
    return pl.pallas_call(
        kernel if visits is None else _on_triangle(kernel),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        **layout,
        interpret=interpret,
        **_kb_params(interpret, n_parallel=len(grid) - 1, axes=len(grid)),
    )(*tables, *operands)


@functools.partial(jax.jit,
                   static_argnames=("segmented", "interpret", "dispatch",
                                    "causal", "window"))
def _flash_bwd(q, k, v, bias, *seg_then_rest, segmented: bool,
               interpret: bool, dispatch: FlashDispatch, dlse=None,
               causal: bool = False, window=None):
    if segmented:
        qseg, kseg, o, lse, do = seg_then_rest
    else:
        qseg = kseg = None
        o, lse, do = seg_then_rest
    # delta_i = Σ_d dO_i·O_i — the softmax-jacobian row correction; an
    # O(S·D) elementwise+reduce, cheap in plain XLA.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # (B,H,S,1)
    if dlse is not None:
        # lse cotangent (ring-merge path): ∂lse_i/∂s_ij = p_ij, so the
        # contribution folds into ds = p·(dp − delta + dlse) — i.e. the
        # kernels run unchanged with delta := delta − dlse.
        delta = delta - dlse.astype(jnp.float32)
    args = (q, k, v, bias, qseg, kseg, lse, do, delta)
    kw = dict(segmented=segmented, interpret=interpret,
              block_q=dispatch.bwd_block_q, block_k=dispatch.bwd_block_k,
              causal=causal, window=window)
    if dispatch.backward == "fused":
        return _flash_bwd_fused_kb(*args, **kw,
                                   vmem_limit=dispatch.bwd_vmem_limit)
    return _flash_bwd_kb(*args, **kw)


def _flash_bwd_kb(q, k, v, bias, qseg, kseg, lse, do, delta, *,
                  segmented: bool, interpret: bool, block_q: int,
                  block_k: int, causal: bool = False, window=None):
    """Two-pass streaming backward: dQ accumulates over a sequential
    k-axis, dK/dV/dbias over a sequential q-axis; no whole-sequence
    operand in VMEM (kernel docstrings)."""
    b, h, s, d = q.shape
    s_k, d_v = k.shape[2], v.shape[3]
    scale = 1.0 / (d ** 0.5)
    kv_heads = k.shape[1]
    kv_head = _kv_head_map(h, kv_heads)
    dkv_dtype = k.dtype if h == kv_heads else jnp.float32
    n_k, k_reach, k_blk = _k_axis(causal, block_q, block_k, window,
                                  s // block_q, s_k // block_k)
    n_q, q_reach, q_blk = _q_axis(causal, block_q, block_k, window,
                                  s // block_q, s_k // block_k)

    seg_operands = [qseg, kseg] if segmented else []
    dq_seg_specs = [
        pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, 0, qi)),
        pl.BlockSpec((1, 1, block_k),
                     lambda bi, hi, qi, ki: (bi, 0, k_blk(qi, ki))),
    ] if segmented else []
    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel_kb, scale=scale,
                          segmented=segmented, causal=causal, window=window,
                          reach=k_reach),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        grid=(b, h, s // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, kv_head(hi), k_blk(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, qi, ki: (bi, kv_head(hi), k_blk(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k),
                     lambda bi, hi, qi, ki: (bi, 0, k_blk(qi, ki))),
        ] + dq_seg_specs + [
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        scratch_shapes=_vmem_scratch(((block_q, d), jnp.float32)),
        interpret=interpret,
        **_kb_params(interpret),
    )(q, k, v, bias, *seg_operands, do, lse, delta)

    dkv_seg_specs = [
        pl.BlockSpec((1, 1, block_q),
                     lambda bi, hi, ki, qi: (bi, 0, q_blk(ki, qi))),
        pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki, qi: (bi, 0, ki)),
    ] if segmented else []
    dk, dv, dbias_h = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel_kb, scale=scale,
                          segmented=segmented, causal=causal, window=window,
                          reach=q_reach),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_k, d), dkv_dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d_v), dkv_dtype),
            jax.ShapeDtypeStruct((b, h, 1, s_k), jnp.float32),
        ],
        grid=(b, h, s_k // block_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, q_blk(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, kv_head(hi), ki, 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, ki, qi: (bi, kv_head(hi), ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki, qi: (bi, 0, ki)),
        ] + dkv_seg_specs + [
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, ki, qi: (bi, hi, q_blk(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, q_blk(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, q_blk(ki, qi), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda bi, hi, ki, qi: (bi, hi, 0, ki)),
        ],
        scratch_shapes=_vmem_scratch(
            ((block_k, d), jnp.float32),
            ((block_k, d_v), jnp.float32),
            ((1, block_k), jnp.float32),
        ),
        interpret=interpret,
        **_kb_params(interpret),
    )(q, k, v, bias, *seg_operands, do, lse, delta)
    dbias = jnp.sum(dbias_h, axis=1)               # (B, 1, S): Σ over heads
    dk, dv = _sum_kv_groups(dk, dv, kv_heads, k.dtype)
    return dq, dk, dv, dbias


def _flash_bwd_fused_kb(q, k, v, bias, qseg, kseg, lse, do, delta, *,
                        segmented: bool, interpret: bool, block_q: int,
                        block_k: int, causal: bool = False, window=None,
                        vmem_limit=None):
    """One-pass streaming backward (kernel docstring): one grid, one exp
    per (q-block, k-block) pair, full-length dk/dv VMEM accumulators —
    gated to what fits by ``select_dispatch``, which also says under
    what scoped VMEM limit (``vmem_limit``, None for Mosaic's
    default)."""
    b, h, s, d = q.shape
    s_k, d_v = k.shape[2], v.shape[3]
    scale = 1.0 / (d ** 0.5)
    kv_heads = k.shape[1]
    kv_head = _kv_head_map(h, kv_heads)
    dkv_dtype = k.dtype if h == kv_heads else jnp.float32
    visits = _triangle(causal, window, s, s_k, block_q, block_k)
    if visits is None:
        row = lambda qi, ki: qi  # noqa: E731
        col = _last_k_block(causal, block_q, block_k, window)
        own = lambda qi, ki: ki  # noqa: E731
        grid = (b, h, s // block_q, s_k // block_k)
    else:
        row, col, own = _triangle_row, _triangle_col, _triangle_col
        grid = (b, h, len(visits[0]))

    seg_operands = [qseg, kseg] if segmented else []
    seg_specs = [
        pl.BlockSpec((1, 1, block_q), lambda bi, hi, *g: (bi, 0, row(*g))),
        pl.BlockSpec((1, 1, block_k),
                     lambda bi, hi, *g: (bi, 0, col(*g))),
    ] if segmented else []
    kernel = functools.partial(_attn_bwd_fused_kernel_kb, scale=scale,
                               segmented=segmented, causal=causal,
                               window=window)
    layout, tables = _layout(
        grid,
        [
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, *g: (bi, kv_head(hi), col(*g), 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, *g: (bi, kv_head(hi), col(*g), 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bi, hi, *g: (bi, 0, col(*g))),
        ] + seg_specs + [
            pl.BlockSpec((1, 1, block_q, d_v),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
        ],
        [
            # dk, dv and dbias name the visit's own key block, so a block
            # is written back when a visit moves on from it
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, *g: (bi, hi, row(*g), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, *g: (bi, hi, own(*g), 0)),
            pl.BlockSpec((1, 1, block_k, d_v),
                         lambda bi, hi, *g: (bi, hi, own(*g), 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda bi, hi, *g: (bi, hi, 0, own(*g))),
        ],
        _vmem_scratch(
            ((block_q, d), jnp.float32),
            ((s_k, d), jnp.float32),
            ((s_k, d_v), jnp.float32),
            ((1, s_k), jnp.float32),
        ), visits)
    dq, dk, dv, dbias_h = pl.pallas_call(
        kernel if visits is None else _on_triangle(kernel),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d), dkv_dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d_v), dkv_dtype),
            jax.ShapeDtypeStruct((b, h, 1, s_k), jnp.float32),
        ],
        **layout,
        interpret=interpret,
        **_kb_params(interpret, n_parallel=2, vmem_limit=vmem_limit,
                     axes=len(grid)),
    )(*tables, q, k, v, bias, *seg_operands, do, lse, delta)
    dbias = jnp.sum(dbias_h, axis=1)               # (B, 1, S): Σ over heads
    dk, dv = _sum_kv_groups(dk, dv, kv_heads, k.dtype)
    return dq, dk, dv, dbias


def flash_attention(q, k, v, *, mask=None, segment_ids=None, mesh=None,
                    causal: bool = False, window: int | None = None):
    """Fused attention. q: (B, S, H, D); k: (B, S, H_kv, D); v: (B, S,
    H_kv, D_v), D_v any width (D where the heads are alike), with H_kv a
    divisor of H (grouped-query attention: each key/value head serves
    H/H_kv query heads, reached through the kernels' block index maps); mask: (B,1,1,S) bool or None;
    segment_ids: (B, S) int packed-sequence ids or None — tokens attend
    only within equal ids (block-diagonal mask computed INSIDE the kernel
    from O(S) ids, so packing never materializes an S×S mask).
    ``causal``: a query sees no later key; the mask comes from indices
    inside the kernels, and the streaming kernels skip (not mask) blocks
    wholly above the diagonal or, on packed rows, outside the document.
    ``window`` (an integer ``W``, with ``causal``): a query at ``i`` sees
    a key at ``j`` only if ``i - j < W``, on top of ``causal`` and the
    segments; blocks wholly behind the window are skipped like the ones
    above the diagonal, the edge blocks masked from indices. ``None`` is
    no window, and traces the programs it always did.

    ``mesh``: the physical mesh when the caller is global-view (``jit``)
    code over more than one device. A Mosaic kernel has no partitioning
    rule — on TPU a bare ``pallas_call`` under a multi-device ``jit``
    does not lower at all ("Mosaic kernels cannot be automatically
    partitioned") — so the call is wrapped in a ``shard_map`` over every
    mesh axis: batch split over the data axes, heads over ``model`` when
    they divide, each device running the kernel on its own shard. Inside
    an enclosing ``shard_map`` (the explicit-collective train step, a
    pipeline stage) the axes are already manual and the kernel is called
    as is. Interpret mode (CPU tests) takes the same wrap, so the CPU
    mesh compiles the structure the chips run.

    Returns (B, S, H, D_v) in q's dtype. Differentiable end to end with
    Pallas forward AND backward kernels (module docstring).
    """
    if (mesh is not None and mesh.size > 1
            and not jax.sharding.get_abstract_mesh().manual_axes):
        return _flash_attention_sharded(q, k, v, mask, segment_ids, mesh,
                                        causal, window)
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal=True and at least one key")
    b, s, hh, d = q.shape
    if s % min(BLOCK_Q, s):
        raise ValueError(f"seq len {s} must be a multiple of {BLOCK_Q}")
    # (B, S, H, D) → (B, H, S, D) for contiguous per-head blocks.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if mask is not None:
        bias = jnp.where(mask[:, 0, :, :], 0.0, NEG_INF).astype(jnp.float32)
    else:
        bias = jnp.zeros((b, 1, s), jnp.float32)
    segmented = segment_ids is not None
    if window is not None:
        fused = _fused_window(segmented, int(window))
    else:
        fused = (_FUSED_CAUSAL[segmented] if causal
                 else _FUSED[(segmented, False)])
    if segmented:
        seg = _seg_f32(segment_ids)
        out = fused(qt, kt, vt, bias, seg, seg)
    else:
        out = fused(qt, kt, vt, bias)
    return out.transpose(0, 2, 1, 3)


def _flash_attention_sharded(q, k, v, mask, segment_ids, mesh,
                             causal: bool = False, window=None):
    """``flash_attention`` per device under a ``shard_map`` over all of
    ``mesh`` (see its ``mesh`` argument)."""
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_framework_tpu.core.mesh import batch_spec

    batch_axes = tuple(a for a in batch_spec(mesh)[0] if a in mesh.shape)
    heads = ("model" if mesh.shape.get("model", 1) > 1
             and q.shape[2] % mesh.shape["model"] == 0
             and k.shape[2] % mesh.shape["model"] == 0 else None)
    qkv_spec = P(batch_axes, None, heads, None)
    optional = {
        "mask": (mask, P(batch_axes, None, None, None)),
        "segment_ids": (segment_ids, P(batch_axes, None)),
    }
    present = {name: pair for name, pair in optional.items()
               if pair[0] is not None}

    def per_device(q, k, v, *rest):
        return flash_attention(q, k, v, causal=causal, window=window,
                               **dict(zip(present, rest)))

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(qkv_spec,) * 3 + tuple(s for _, s in present.values()),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, *(a for a, _ in present.values()))
