"""DeepSeek-V3-family decoder (multi-head latent attention over sparse
experts), from shapes: the work done HERE, on this chip's share of the
heads, of the hidden units, of the experts and of the vocabulary.

Per real token, forward (a multiply-add is 2 operations; H hidden; a
latent attention layer holds ``n`` heads, each a query/key of ``d_qk =
qk_nope_head_dim + qk_rope_head_dim`` and a value of ``d_v``, over a
latent of ``r`` with one rotary key of ``qk_rope_head_dim``; M the dense
feed-forward's and S the shared experts' hidden units held, F the expert
width, E the experts routed with K a token; V the vocabulary rows held):

- a latent attention layer: q ``2*H*n*d_qk``, the latent and the rotary
  key ``2*H*(r + qk_rope_head_dim)``, keys and values out of the latent
  ``2*r*n*(qk_nope_head_dim + d_v)``, output ``2*n*d_v*H`` (the norm,
  the rotation and the rotary key's broadcast are elementwise: bytes,
  not operations); scores ``2*n*d_qk`` and values ``2*n*d_v`` per pair a
  query sees: inside its document and not after it, ``L(L+1)/2`` pairs
  in a document of L tokens;
- a dense feed-forward (the first ``num_dense_layers`` layers):
  ``3*2*H*M``;
- an expert layer: the router ``2*H*E`` over all E routed experts, the
  shared experts ``3*2*H*S``, and ``3*2*H*F`` per LOCAL assignment,
  ``K*held/E`` a token by expectation (the run counts the real number;
  ``moe_gemm_work`` takes it);
- head: ``2*H*V`` (untied; the embedding is a lookup).

Backward is twice the forward; recomputation (``model.remat``), padding
and the optimizer do not count.
"""

from __future__ import annotations

import numpy as np

# The grouped products are lfm2's count at this family's keys (H, F, the
# experts held; the shared experts are a dense product and no part of
# them); causal pairs inside documents are smallthinker's count.
from benchmarks.flops.lfm2 import document_lengths, moe_gemm_work  # noqa: F401
from benchmarks.flops.smallthinker import causal_pairs


def _widths(h: dict) -> tuple[int, int, int]:
    """``(heads held, query/key width, value width)`` of a latent
    attention layer."""
    return (len(h["heads_held"]),
            h["qk_nope_head_dim"] + h["qk_rope_head_dim"], h["v_head_dim"])


def dense_flops_per_token(h: dict) -> float:
    """Forward operations a real token needs outside the attention
    pairs, local expert assignments by expectation."""
    H, F, r = h["hidden_size"], h["moe_intermediate_size"], h["kv_lora_rank"]
    n, d_qk, d_v = _widths(h)
    attention = (2 * H * n * d_qk + 2 * H * (r + h["qk_rope_head_dim"])
                 + 2 * r * n * (h["qk_nope_head_dim"] + d_v)
                 + 2 * n * d_v * H)
    layers = len(h["layer_types"])
    n_dense = int(h["num_dense_layers"])
    local = h["num_experts_per_tok"] * len(h["experts_held"]) \
        / h["experts_routed"]
    moe = 2 * H * h["experts_routed"] + 3 * 2 * H * h["shared_units_held"] \
        + local * 3 * 2 * H * F
    return float(layers * attention + n_dense * 3 * 2 * H
                 * h["dense_units_held"] + (layers - n_dense) * moe
                 + 2 * H * h["vocab_size"])


def pair_flops(h: dict) -> int:
    """Forward operations of one (query, key) pair in one layer: scores
    at the query/key width, values at the value width, every held head."""
    n, d_qk, d_v = _widths(h)
    return 2 * n * (d_qk + d_v)


def train_flops(batch: dict, h: dict) -> float:
    pairs = causal_pairs(document_lengths(batch))
    tokens = float(document_lengths(batch).sum())
    return 3.0 * (dense_flops_per_token(h) * tokens
                  + len(h["layer_types"]) * pair_flops(h) * pairs)


def attention_kernel_work(batch: dict, h: dict, rows_per_chip: int) -> dict:
    """What one chip's attention kernels must do in one step.
    Operations: forward ``QKᵀ`` at ``d_qk`` and ``P·V`` at ``d_v``, 2 a
    multiply-add; backward ``QKᵀ`` again, ``dS·K`` and ``dSᵀ·Q`` at
    ``d_qk`` and ``dO·Vᵀ`` and ``Pᵀ·dO`` at ``d_v``; per causal pair and
    held head. Bytes (bf16, every operand and result crossing HBM once):
    forward reads q, k (``d_qk``) and v (``d_v``) and writes o (``d_v``)
    and the logsumexp (4 B a row and head); backward reads q, k, v, o
    and dO and the logsumexp and writes dq, dk and dv."""
    rows, s = np.asarray(batch["input_ids"]).shape
    pairs = causal_pairs(document_lengths(batch)) * rows_per_chip / rows
    layers = len(h["layer_types"])
    n, d_qk, d_v = _widths(h)
    tokens = rows_per_chip * s
    q_like = tokens * n * d_qk * 2                 # q, k, dq, dk each
    v_like = tokens * n * d_v * 2                  # v, o, do, dv each
    lse = tokens * n * 4
    return {
        "forward_flops": layers * n * (2 * d_qk + 2 * d_v) * pairs,
        "backward_flops": layers * n * (6 * d_qk + 4 * d_v) * pairs,
        "forward_bytes": layers * (2 * q_like + 2 * v_like + lse),
        "backward_bytes": layers * (4 * q_like + 4 * v_like + lse),
    }
