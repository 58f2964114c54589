"""Fused attention Pallas kernels — forward AND backward.

Computes softmax(qkᵀ/√d)·v with the S×S score matrix living only in VMEM.
Forward: one HBM read of q/k/v and one write of o (+ the per-row
logsumexp) per (batch, head, q-block) program. Backward: two Pallas
kernels (dq over q-blocks; dk/dv over k-blocks) that RECOMPUTE the
probability blocks online from the saved (q, k, v, o, lse) — so training
peak memory is O(S·D) end to end; no O(S²) tensor is ever materialized in
HBM in either direction. This is the flash-attention recompute pattern
(PAPERS.md); XLA alone tiles but still round-trips the score tensor for
the unfused einsum+softmax+einsum chain.

Shapes: q, k, v are (B, S, H, D). Two kernel regimes, dispatched on
sequence length (see MAX_SEQ_VMEM): whole-K (each program holds its
block plus the full opposing sequence in VMEM — the measured-fast path
to S=4K) and K-blocked streaming (sequential k-axis grid with running
softmax state in VMEM scratch — any length, VMEM use O(block²)). Ring
attention over the ``seq`` mesh axis composes on top for sharded
sequences.

The kernels run in interpret mode off-TPU so the CPU test mesh exercises
the same code path; tests/test_attention.py pins fwd+bwd numerics against
the plain-XLA reference.
"""

from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

log = logging.getLogger(__name__)

NEG_INF = float(jnp.finfo(jnp.float32).min)
BLOCK_Q = 128
BLOCK_K = 128
# Streaming-kernel tile sizes (the s_k > MAX_SEQ_VMEM regime only). The
# 128×128 tiles the whole-K path uses are far too fine here: at S=8192
# they make a (B,H,64,64) grid of ~200k programs whose per-program
# overhead swamps the 128×64×128 matmuls (measured 3% MFU on v5e,
# PERF_NOTES.md round 4). Fatter tiles amortize the grid: 8 sequential
# k-steps instead of 64, and each dot is MXU-sized. Measured ladder at
# seq 8192 (PERF_NOTES round 4): 128/128 → 7.9k tok/s, 256/1024 → 30k,
# 512/1024 → 35.2k, 1024/1024 → 35.4k, 512/2048 → 31.9k (VMEM pressure).
# 512/1024 ships: within noise of the peak at half the q-tile VMEM.
# Env-tunable for A/Bs, same spirit as the BENCH_* knobs.
BLOCK_Q_KB = int(os.environ.get("FLASH_BLOCK_Q_KB", "512"))
BLOCK_K_KB = int(os.environ.get("FLASH_BLOCK_K_KB", "1024"))
# VMEM dispatch policy (VERDICT r3 weak #2 — no silent fallback above this):
#   s_k ≤ MAX_SEQ_VMEM → whole-K kernels: each program holds the full
#     opposing sequence in VMEM at INPUT dtype (S*D*2B*2 for bf16 K and
#     V — the round-4 kernels dot in input dtype, no f32 upcast — plus
#     the BLOCK_Q*S*4B f32 score block) — fits ~16MB with double
#     buffering, and is the variant whose perf was measured on real TPU
#     (PERF_NOTES.md).
#   s_k > MAX_SEQ_VMEM → K-blocked streaming kernels: the grid gains a
#     sequential k-axis; running (m, l, acc) softmax state lives in VMEM
#     scratch and K/V stream through in BLOCK_K_KB tiles, so VMEM use is
#     O(BLOCK_Q_KB·BLOCK_K_KB) regardless of sequence length. No
#     fallback to the O(S²)-materializing XLA chain exists above the
#     threshold — long chunks stay fused (tests/test_attention.py pins
#     8192), and the chain is not even COMPILABLE there: at seq 8192 the
#     XLA impl failed compilation outright (PERF_NOTES.md round 4).
# Env-tunable so the whole-K vs K-blocked crossover can be re-measured
# without an edit (FLASH_MAX_SEQ_VMEM=0 forces the streaming kernels
# everywhere).
MAX_SEQ_VMEM = int(os.environ.get("FLASH_MAX_SEQ_VMEM", "4096"))
# Fused one-pass streaming backward: one kernel over grid (B,H,nq,nk)
# produces dq AND dk/dv/dbias, computing each (q-block, k-block)
# probability block ONCE — the two-pass backward exps every block twice
# (dq pass + dkv pass). The round-5 PERF_NOTES bound analysis puts the
# streaming regime's cost in exactly that S² VPU transcendental work,
# at the price of full-length (S_k, D) f32 dk/dv VMEM accumulators —
# hence the MAX gate (4 MB at 8192; beyond ~2·8192 it cannot fit and
# the two-pass kernels remain the only path).
#
# Tri-state default: ``None`` (env unset) = auto — ON only on backends
# where scripts/verify_flash_kernels.py results are RECORDED: v5e, where
# chip_smoke.py re-runs that check on every smoke (on jax 0.9.0 /
# libtpu 0.0.34 the fused backward agrees with the two-pass kernels and
# with the float32 reference at seq 8192, 4096 and 2048 — PERF.md,
# PR 21; the +7.8% step A/B at seq 8192 is a 2026-08-01 number from an
# earlier tree and toolchain, PERF_NOTES round 5). On any other real TPU
# generation the fused dk/dv/dbias flush ordering is UNVERIFIED silicon
# behavior (ADVICE r5): auto keeps the two-pass backward and says so
# once. FLASH_FUSED_BWD=1/0 forces either way (env read at import time
# like the other FLASH_* knobs); tests and
# scripts/verify_flash_kernels.py assign the module global directly —
# the backward closures consult it at call time through
# fused_bwd_enabled().
_FUSED_BWD_ENV = os.environ.get("FLASH_FUSED_BWD")
FUSED_BWD: bool | None = (
    None if _FUSED_BWD_ENV is None else _FUSED_BWD_ENV not in ("", "0"))
FUSED_BWD_MAX = int(os.environ.get("FLASH_FUSED_BWD_MAX", "8192"))
# Backend substrings (matched against device_kind, lowercased) with
# recorded verify_flash_kernels.py results.
FUSED_BWD_VERIFIED_PLATFORMS = ("v5 lite", "v5e")
# The fused one-pass backward can also REPLACE the whole-K two-pass
# backward for mid-length sequences (FUSED_WHOLE_K_MIN ≤ s ≤
# MAX_SEQ_VMEM): the whole-K dq/dkv kernel pair pays the same three S²
# exp evaluations the streaming two-pass does, and the round-4 crossover
# showed the K-blocked kernels already TIE whole-K at 2048 — so the fused
# kernel's saved exp SHOULD be pure win from there up. But that band's
# win is EXTRAPOLATED from the 8192 measurement, not measured for f32.
# The bf16 arm of the §13 precision ladder
# (scripts/chip_window_queue.sh) re-ran the crossover under the
# production compute dtype: at bf16 the MXU matmuls halve, leaving the
# fused kernel's saved S² exp pass as a larger FRACTION of the backward
# — the takeover is armed by default at 2048 for bf16 inputs only. f32
# keeps the conservative park above MAX_SEQ_VMEM (where the streaming
# kernels are the only path anyway and the knob is inert) until the
# wk2048/wk4096 f32 A/B (scripts/chip_window_queue.sh item 7) lands.
# FLASH_FUSED_WHOLE_K_MIN=<n> forces one threshold for every dtype
# (tests and scripts assign the module global directly, same contract);
# unset leaves the dtype-aware default via fused_whole_k_min(). Forward
# stays whole-K either way (the streaming backward needs only
# q/k/v/bias/lse/do, all of which the whole-K forward saves).
_FUSED_WHOLE_K_MIN_ENV = os.environ.get("FLASH_FUSED_WHOLE_K_MIN")
FUSED_WHOLE_K_MIN: int | None = (
    None if _FUSED_WHOLE_K_MIN_ENV is None else int(_FUSED_WHOLE_K_MIN_ENV))
FUSED_WHOLE_K_MIN_BF16 = 2048


def fused_whole_k_min(dtype) -> int:
    """Minimum sequence length where the fused one-pass backward takes
    over from the whole-K two-pass pair, resolved per input dtype.
    An explicit FUSED_WHOLE_K_MIN (env or direct module-global
    assignment — tests/scripts do the latter) wins for every dtype;
    otherwise bf16 gets the armed 2048 default and everything else stays
    parked above MAX_SEQ_VMEM. Reads the module globals at call time so
    monkeypatching keeps working."""
    if FUSED_WHOLE_K_MIN is not None:
        return FUSED_WHOLE_K_MIN
    if jnp.dtype(dtype) == jnp.bfloat16:
        return FUSED_WHOLE_K_MIN_BF16
    return MAX_SEQ_VMEM + 1


def _attn_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, *rest,
                     scale: float, segmented: bool):
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
    v = v_ref[0, 0]                               # (S, D)
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
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / l                                         # (BQ, D)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    # Per-row logsumexp: the only softmax statistic the backward needs.
    lse_ref[0, 0] = (m + jnp.log(l)).astype(jnp.float32)


def _attn_bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, *rest,
                        scale: float, segmented: bool):
    """dQ for one q-block: recompute p from (q, k, lse), no S×S residual."""
    if segmented:
        qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref, dq_ref = rest
    else:
        do_ref, lse_ref, delta_ref, dq_ref = rest
    q = q_ref[0, 0]                               # (BQ, D) input dtype
    k = k_ref[0, 0]                               # (S, D)
    v = v_ref[0, 0]                               # (S, D)
    do = do_ref[0, 0]                             # (BQ, D)
    lse = lse_ref[0, 0]                           # (BQ, 1)
    delta = delta_ref[0, 0]                       # (BQ, 1)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bias_ref[0]                       # (BQ, S)
    if segmented:
        qs = qseg_ref[0, 0]
        ks = kseg_ref[0, 0]
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
    p = jnp.exp(s - lse)                          # recomputed probabilities
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (BQ, S)
    ds = p * (dp - delta)                         # (BQ, S) f32
    dq = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, *rest,
                         scale: float, segmented: bool):
    """dK/dV (+ per-head dbias) for one k-block: full Q/dO in VMEM."""
    if segmented:
        (qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dbias_ref) = rest
    else:
        do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dbias_ref = rest
    q = q_ref[0, 0]                               # (S, D) input dtype
    k = k_ref[0, 0]                               # (BK, D)
    v = v_ref[0, 0]                               # (BK, D)
    do = do_ref[0, 0]                             # (S, D)
    lse = lse_ref[0, 0]                           # (S, 1)
    delta = delta_ref[0, 0]                       # (S, 1)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bias_ref[0]                       # (S, BK)
    if segmented:
        qs = qseg_ref[0, 0]                       # (S,)
        ks = kseg_ref[0, 0]                       # (BK,)
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
    p = jnp.exp(s - lse)
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (BK, D)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (S, BK)
    ds = p * (dp - delta)                         # (S, BK) f32
    dk = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                     # (BK, D)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    dbias_ref[0, 0] = jnp.sum(ds, axis=0, keepdims=True)  # (1, BK)


def _attn_fwd_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                        scale: float, segmented: bool):
    """K-blocked forward: grid (B, H, nq, nk) with nk innermost/sequential.

    Running-softmax state (m, l, acc) persists in VMEM scratch across the
    k-blocks of one q-block; K/V stream through in BLOCK_K tiles so no
    whole-sequence operand ever sits in VMEM. Finite NEG_INF arithmetic
    gives bit-compatible fully-masked-row semantics with the whole-K
    kernel (garbage o, lse ≈ NEG_INF — the ring merge weights it to 0).
    """
    if segmented:
        qseg_ref, kseg_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    q = q_ref[0, 0]                               # (BQ, D) input dtype
    k = k_ref[0, 0]                               # (BK, D)
    v = v_ref[0, 0]                               # (BK, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale + bias_ref[0]                       # (BQ, BK) f32
    if segmented:
        qs = qseg_ref[0, 0]                       # (BQ,)
        ks = kseg_ref[0, 0]                       # (BK,)
        s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
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

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_ref[...])


def _attn_bwd_dq_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                           scale: float, segmented: bool):
    """K-blocked dQ: accumulate ds·k over streamed K/V tiles in scratch."""
    if segmented:
        qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref = rest
    else:
        do_ref, lse_ref, delta_ref, dq_ref, acc_ref = rest
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    q = q_ref[0, 0]                               # (BQ, D) input dtype
    k = k_ref[0, 0]                               # (BK, D)
    v = v_ref[0, 0]                               # (BK, D)
    do = do_ref[0, 0]                             # (BQ, D)
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

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _attn_bwd_dkv_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                            scale: float, segmented: bool):
    """K-blocked dK/dV/dbias: grid (B, H, nk, nq) with the q-axis
    innermost/sequential; Q/dO stream through in BLOCK_Q tiles while the
    (dk, dv, dbias) accumulators for one k-block live in scratch."""
    if segmented:
        (qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, db_acc) = rest
    else:
        (do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, db_acc) = rest
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, dk_acc.dtype)
        dv_acc[...] = jnp.zeros(dv_acc.shape, dv_acc.dtype)
        db_acc[...] = jnp.zeros(db_acc.shape, db_acc.dtype)

    q = q_ref[0, 0]                               # (BQ, D) input dtype
    k = k_ref[0, 0]                               # (BK, D)
    v = v_ref[0, 0]                               # (BK, D)
    do = do_ref[0, 0]                             # (BQ, D)
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
    p = jnp.exp(s - lse)
    dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (BK, D)
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

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
        dbias_ref[0, 0] = db_acc[...]


def _attn_bwd_fused_kernel_kb(q_ref, k_ref, v_ref, bias_ref, *rest,
                              scale: float, segmented: bool):
    """Fused one-pass streaming backward: grid (B, H, nq, nk), BOTH inner
    axes sequential ("arbitrary"). Each (q-block, k-block) pair is
    visited once; its probability block is exp'd ONCE and feeds all four
    cotangents. dq accumulates per q-block in block scratch (finalized
    when the k-scan ends); dk/dv/dbias accumulate in FULL-LENGTH VMEM
    scratch across the whole per-(b,h) subgrid, and each visit stores
    the current partial to the block output — grid steps execute in
    order on the core, so the final visit's flush (qi == nq-1) is what
    HBM keeps. Earlier flushes are dead writes: ~(nq-1)·S_k·D·4B extra
    HBM-write traffic per (b,h), orders below the exp savings
    (PERF_NOTES round-5 analysis)."""
    if segmented:
        (qseg_ref, kseg_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dbias_ref,
         dq_acc, dk_full, dv_full, db_full) = rest
    else:
        (do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dbias_ref,
         dq_acc, dk_full, dv_full, db_full) = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init_dq():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

    @pl.when((qi == 0) & (ki == 0))
    def _init_dkv():
        dk_full[...] = jnp.zeros(dk_full.shape, dk_full.dtype)
        dv_full[...] = jnp.zeros(dv_full.shape, dv_full.dtype)
        db_full[...] = jnp.zeros(db_full.shape, db_full.dtype)

    q = q_ref[0, 0]                               # (BQ, D) input dtype
    k = k_ref[0, 0]                               # (BK, D)
    v = v_ref[0, 0]                               # (BK, D)
    do = do_ref[0, 0]                             # (BQ, D)
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

    bk = k.shape[0]
    sl = pl.ds(ki * bk, bk)
    dv_full[sl, :] = dv_full[sl, :] + jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                             # (BK, D)
    dk_full[sl, :] = dk_full[sl, :] + jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                     # (BK, D)
    db_full[:, sl] = db_full[:, sl] + jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize_dq():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)

    # Store the running partials every visit; the last (qi) visit wins.
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


_fused_bwd_auto: bool | None = None  # memoized auto-resolution


def fused_bwd_enabled() -> bool:
    """Resolve the FUSED_BWD tri-state at backward-dispatch time.

    A bool in the module global (env knob, test monkeypatch, or
    scripts/verify_flash_kernels.py's direct assignment) always wins. ``None``
    = auto: ON only when the default backend is a TPU whose device_kind
    matches a FUSED_BWD_VERIFIED_PLATFORMS entry; any OTHER real TPU gets
    the two-pass backward plus a one-line warning (once) — the fused
    flush ordering is verified per-generation, and silently-wrong
    gradients are the worst possible failure mode. Non-TPU backends run
    the kernels in interpret mode where perf is moot: auto stays off,
    quietly (CPU parity for the fused path is pinned by tests that force
    the flag)."""
    global _fused_bwd_auto
    if FUSED_BWD is not None:
        return FUSED_BWD
    if _fused_bwd_auto is None:
        if jax.default_backend() != "tpu":
            _fused_bwd_auto = False
        else:
            kind = jax.devices()[0].device_kind.lower()
            _fused_bwd_auto = any(
                p in kind for p in FUSED_BWD_VERIFIED_PLATFORMS)
            if not _fused_bwd_auto:
                log.warning(
                    "fused flash-attention backward disabled: no recorded "
                    "verify_flash_kernels.py results for TPU %r — run "
                    "scripts/verify_flash_kernels.py and set "
                    "FLASH_FUSED_BWD=1 to enable", kind,
                )
    return _fused_bwd_auto


def _make_fused(segmented: bool, return_lse: bool):
    """Build the custom-VJP fused attention for one (segmented, lse)
    variant. Unsegmented signature: (q, k, v, bias) — the common path
    carries NO segment operands or VMEM traffic. Segmented adds
    (qseg, kseg): (B,1,Sq)/(B,1,Sk) FLOAT segment ids (all-float
    custom_vjp; zero cotangents). ``return_lse`` additionally returns the
    per-row logsumexp — the chunk primitive for ring attention, whose
    online merge needs lse and therefore flows a cotangent into it.
    Residuals are all O(S·D)/O(S): no score-matrix-shaped tensor is ever
    saved.
    """
    if segmented:
        @jax.custom_vjp
        def fused(q, k, v, bias, qseg, kseg):
            o, lse = _flash_fwd(q, k, v, bias, qseg, kseg,
                                segmented=True, interpret=_interpret())
            return (o, lse) if return_lse else o

        def fwd(q, k, v, bias, qseg, kseg):
            o, lse = _flash_fwd(q, k, v, bias, qseg, kseg,
                                segmented=True, interpret=_interpret())
            out = (o, lse) if return_lse else o
            return out, (q, k, v, bias, qseg, kseg, o, lse)

        def bwd(res, g):
            q, k, v, bias, qseg, kseg, o, lse = res
            do, dlse = g if return_lse else (g, None)
            use_fused = fused_bwd_enabled() and k.shape[2] <= FUSED_BWD_MAX
            dq, dk, dv, dbias = _flash_bwd(
                q, k, v, bias, qseg, kseg, o, lse, do, dlse=dlse,
                segmented=True, interpret=_interpret(),
                fused=use_fused,
                force_stream=use_fused and min(
                    q.shape[2], k.shape[2]) >= fused_whole_k_min(q.dtype))
            return (dq, dk, dv, dbias,
                    jnp.zeros_like(qseg), jnp.zeros_like(kseg))
    else:
        @jax.custom_vjp
        def fused(q, k, v, bias):
            o, lse = _flash_fwd(q, k, v, bias,
                                segmented=False, interpret=_interpret())
            return (o, lse) if return_lse else o

        def fwd(q, k, v, bias):
            o, lse = _flash_fwd(q, k, v, bias,
                                segmented=False, interpret=_interpret())
            out = (o, lse) if return_lse else o
            return out, (q, k, v, bias, o, lse)

        def bwd(res, g):
            q, k, v, bias, o, lse = res
            do, dlse = g if return_lse else (g, None)
            use_fused = fused_bwd_enabled() and k.shape[2] <= FUSED_BWD_MAX
            dq, dk, dv, dbias = _flash_bwd(
                q, k, v, bias, o, lse, do, dlse=dlse,
                segmented=False, interpret=_interpret(),
                fused=use_fused,
                force_stream=use_fused and min(
                    q.shape[2], k.shape[2]) >= fused_whole_k_min(q.dtype))
            return dq, dk, dv, dbias

    fused.defvjp(fwd, bwd)
    return fused


_FUSED = {(seg, lse): _make_fused(seg, lse)
          for seg in (False, True) for lse in (False, True)}


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


@functools.partial(jax.jit, static_argnames=("segmented", "interpret"))
def _flash_fwd(q, k, v, bias, qseg=None, kseg=None, *, segmented: bool,
               interpret: bool):
    b, h, s, d = q.shape
    s_k = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_q = min(BLOCK_Q, s)
    if s_k > MAX_SEQ_VMEM:
        return _flash_fwd_kb(q, k, v, bias, qseg, kseg,
                             segmented=segmented, interpret=interpret)
    grid = (b, h, s // block_q)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
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
        functools.partial(_attn_fwd_kernel, scale=scale, segmented=segmented),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        interpret=interpret,
    )(*operands)


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


def _kb_params(interpret: bool, n_parallel: int = 3):
    """Mosaic grid semantics for the streaming kernels: the leading
    ``n_parallel`` axes are parallel, the rest sequential ("arbitrary").
    The two-pass kernels accumulate only over their innermost axis
    (n_parallel=3); the fused backward reduces over BOTH inner axes
    (n_parallel=2). Interpret mode (CPU tests) takes no TPU compiler
    params."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_parallel
        + ("arbitrary",) * (4 - n_parallel))}


def _flash_fwd_kb(q, k, v, bias, qseg, kseg, *, segmented: bool,
                  interpret: bool):
    """Streaming forward for s_k > MAX_SEQ_VMEM: sequential k-axis grid +
    VMEM-scratch running softmax (kernel docstring)."""
    b, h, s, d = q.shape
    s_k = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_q = _pick_block(s, BLOCK_Q_KB)
    block_k = _pick_block(s_k, BLOCK_K_KB)
    grid = (b, h, s // block_q, s_k // block_k)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        pl.BlockSpec((1, 1, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)),
    ]
    operands = [q, k, v, bias]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, 0, qi)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ]
        operands += [qseg, kseg]
    return pl.pallas_call(
        functools.partial(_attn_fwd_kernel_kb, scale=scale,
                          segmented=segmented),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        scratch_shapes=_vmem_scratch(
            ((block_q, d), jnp.float32),
            ((block_q, 1), jnp.float32),
            ((block_q, 1), jnp.float32),
        ),
        interpret=interpret,
        **_kb_params(interpret),
    )(*operands)


@functools.partial(jax.jit,
                   static_argnames=("segmented", "interpret", "fused",
                                    "force_stream"))
def _flash_bwd(q, k, v, bias, *seg_then_rest, segmented: bool,
               interpret: bool, dlse=None, fused: bool = False,
               force_stream: bool = False):
    if segmented:
        qseg, kseg, o, lse, do = seg_then_rest
    else:
        qseg = kseg = None
        o, lse, do = seg_then_rest
    b, h, s, d = q.shape
    s_k = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    # delta_i = Σ_d dO_i·O_i — the softmax-jacobian row correction; an
    # O(S·D) elementwise+reduce, cheap in plain XLA.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)        # (B,H,S,1)
    if dlse is not None:
        # lse cotangent (ring-merge path): ∂lse_i/∂s_ij = p_ij, so the
        # contribution folds into ds = p·(dp − delta + dlse) — i.e. the
        # kernels run unchanged with delta := delta − dlse.
        delta = delta - dlse.astype(jnp.float32)

    seg_operands = [qseg, kseg] if segmented else []

    if max(s, s_k) > MAX_SEQ_VMEM or force_stream:
        # force_stream: mid-length sequences take the FUSED streaming
        # backward instead of the whole-K two-pass (FUSED_WHOLE_K_MIN
        # note above). The decision is made at the custom_vjp layer —
        # this function is jitted, so a module-attr read HERE would
        # freeze into the first trace's cache (the _flash_bwd_kb
        # docstring's rule; MAX_SEQ_VMEM predates it and is accepted).
        return _flash_bwd_kb(q, k, v, bias, qseg, kseg, lse, do, delta,
                             segmented=segmented, interpret=interpret,
                             fused=fused)

    block_q = min(BLOCK_Q, s)
    dq_seg_specs = [
        pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi: (bi, 0, qi)),
        pl.BlockSpec((1, 1, s_k), lambda bi, hi, qi: (bi, 0, 0)),
    ] if segmented else []
    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel, scale=scale,
                          segmented=segmented),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        grid=(b, h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s_k, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s_k), lambda bi, hi, qi: (bi, 0, 0)),
        ] + dq_seg_specs + [
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)
        ),
        interpret=interpret,
    )(q, k, v, bias, *seg_operands, do, lse, delta)

    block_k = min(BLOCK_K, s_k)
    dkv_seg_specs = [
        pl.BlockSpec((1, 1, s), lambda bi, hi, ki: (bi, 0, 0)),
        pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki: (bi, 0, ki)),
    ] if segmented else []
    dk, dv, dbias_h = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel, scale=scale,
                          segmented=segmented),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d), v.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s_k), jnp.float32),
        ],
        grid=(b, h, s_k // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki: (bi, 0, ki)),
        ] + dkv_seg_specs + [
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, 1), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, 1, block_k), lambda bi, hi, ki: (bi, hi, 0, ki)),
        ],
        interpret=interpret,
    )(q, k, v, bias, *seg_operands, do, lse, delta)
    dbias = jnp.sum(dbias_h, axis=1)               # (B, 1, S): Σ over heads
    return dq, dk, dv, dbias


def _flash_bwd_kb(q, k, v, bias, qseg, kseg, lse, do, delta, *,
                  segmented: bool, interpret: bool, fused: bool = False):
    """Streaming backward for sequences > MAX_SEQ_VMEM: dQ accumulates
    over a sequential k-axis, dK/dV/dbias over a sequential q-axis; no
    whole-sequence operand in VMEM (kernel docstrings). ``fused`` is the
    COMPLETE FLASH_FUSED_BWD ∧ s_k ≤ FUSED_BWD_MAX decision, made at the
    custom_vjp layer OUTSIDE the inner jit — both module attrs are jit-
    invisible, so reading either here would freeze it into the first
    trace's cache."""
    b, h, s, d = q.shape
    s_k = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_q = _pick_block(s, BLOCK_Q_KB)
    block_k = _pick_block(s_k, BLOCK_K_KB)

    if fused:
        return _flash_bwd_fused_kb(q, k, v, bias, qseg, kseg, lse, do,
                                   delta, segmented=segmented,
                                   interpret=interpret)

    seg_operands = [qseg, kseg] if segmented else []
    dq_seg_specs = [
        pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, 0, qi)),
        pl.BlockSpec((1, 1, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)),
    ] if segmented else []
    dq = pl.pallas_call(
        functools.partial(_attn_bwd_dq_kernel_kb, scale=scale,
                          segmented=segmented),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        grid=(b, h, s // block_q, s_k // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ] + dq_seg_specs + [
            pl.BlockSpec((1, 1, block_q, d),
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
        pl.BlockSpec((1, 1, block_q), lambda bi, hi, ki, qi: (bi, 0, qi)),
        pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki, qi: (bi, 0, ki)),
    ] if segmented else []
    dk, dv, dbias_h = pl.pallas_call(
        functools.partial(_attn_bwd_dkv_kernel_kb, scale=scale,
                          segmented=segmented),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d), v.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s_k), jnp.float32),
        ],
        grid=(b, h, s_k // block_k, s // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, ki, qi: (bi, 0, ki)),
        ] + dkv_seg_specs + [
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda bi, hi, ki, qi: (bi, hi, 0, ki)),
        ],
        scratch_shapes=_vmem_scratch(
            ((block_k, d), jnp.float32),
            ((block_k, d), jnp.float32),
            ((1, block_k), jnp.float32),
        ),
        interpret=interpret,
        **_kb_params(interpret),
    )(q, k, v, bias, *seg_operands, do, lse, delta)
    dbias = jnp.sum(dbias_h, axis=1)               # (B, 1, S): Σ over heads
    return dq, dk, dv, dbias


def _flash_bwd_fused_kb(q, k, v, bias, qseg, kseg, lse, do, delta, *,
                        segmented: bool, interpret: bool):
    """One-pass streaming backward (FLASH_FUSED_BWD; kernel docstring):
    one grid, one exp per (q-block, k-block) pair, full-length dk/dv
    VMEM accumulators — gated to s_k ≤ FUSED_BWD_MAX by the caller."""
    b, h, s, d = q.shape
    s_k = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_q = _pick_block(s, BLOCK_Q_KB)
    block_k = _pick_block(s_k, BLOCK_K_KB)

    seg_operands = [qseg, kseg] if segmented else []
    seg_specs = [
        pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, 0, qi)),
        pl.BlockSpec((1, 1, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)),
    ] if segmented else []
    dq, dk, dv, dbias_h = pl.pallas_call(
        functools.partial(_attn_bwd_fused_kernel_kb, scale=scale,
                          segmented=segmented),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, s_k, d), v.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s_k), jnp.float32),
        ],
        grid=(b, h, s // block_q, s_k // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, hi, qi, ki: (bi, 0, ki)),
        ] + seg_specs + [
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda bi, hi, qi, ki: (bi, hi, 0, ki)),
        ],
        scratch_shapes=_vmem_scratch(
            ((block_q, d), jnp.float32),
            ((s_k, d), jnp.float32),
            ((s_k, d), jnp.float32),
            ((1, s_k), jnp.float32),
        ),
        interpret=interpret,
        **_kb_params(interpret, n_parallel=2),
    )(q, k, v, bias, *seg_operands, do, lse, delta)
    dbias = jnp.sum(dbias_h, axis=1)               # (B, 1, S): Σ over heads
    return dq, dk, dv, dbias


def flash_attention(q, k, v, *, mask=None, segment_ids=None, mesh=None):
    """Fused attention. q,k,v: (B, S, H, D); mask: (B,1,1,S) bool or None;
    segment_ids: (B, S) int packed-sequence ids or None — tokens attend
    only within equal ids (block-diagonal mask computed INSIDE the kernel
    from O(S) ids, so packing never materializes an S×S mask).

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

    Returns (B, S, H, D) in q's dtype. Differentiable end to end with
    Pallas forward AND backward kernels (module docstring).
    """
    if (mesh is not None and mesh.size > 1
            and not jax.sharding.get_abstract_mesh().manual_axes):
        return _flash_attention_sharded(q, k, v, mask, segment_ids, mesh)
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
    if segment_ids is None:
        out = _FUSED[(False, False)](qt, kt, vt, bias)
    else:
        seg = _seg_f32(segment_ids)
        out = _FUSED[(True, False)](qt, kt, vt, bias, seg, seg)
    return out.transpose(0, 2, 1, 3)


def _flash_attention_sharded(q, k, v, mask, segment_ids, mesh):
    """``flash_attention`` per device under a ``shard_map`` over all of
    ``mesh`` (see its ``mesh`` argument)."""
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_framework_tpu.core.mesh import batch_spec

    batch_axes = tuple(a for a in batch_spec(mesh)[0] if a in mesh.shape)
    heads = ("model" if mesh.shape.get("model", 1) > 1
             and q.shape[2] % mesh.shape["model"] == 0 else None)
    qkv_spec = P(batch_axes, None, heads, None)
    optional = {
        "mask": (mask, P(batch_axes, None, None, None)),
        "segment_ids": (segment_ids, P(batch_axes, None)),
    }
    present = {name: pair for name, pair in optional.items()
               if pair[0] is not None}

    def per_device(q, k, v, *rest):
        return flash_attention(q, k, v, **dict(zip(present, rest)))

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(qkv_spec,) * 3 + tuple(s for _, s in present.values()),
        out_specs=qkv_spec, check_vma=False,
    )(q, k, v, *(a for a, _ in present.values()))
