"""LFM2-MoE decoder, from shapes: the work done HERE, on this chip's
share of the experts and of the vocabulary.

Per real token, forward (a multiply-add is 2 operations; H hidden, M the
dense width, F the expert width, n query heads and m key/value heads of
d, V the vocabulary rows held):

- ``conv`` mixer: ``W_in`` ``2*H*3H`` and ``W_out`` ``2*H*H`` (the 3-tap
  convolution and the two gates are elementwise: bytes, not operations);
- ``full_attention`` mixer: q ``2*H*n*d``, k and v ``2*2*H*m*d``, output
  ``2*n*d*H``; scores and values over the keys a causal query sees
  inside its document: ``4*n*d`` per pair, ``L(L+1)/2`` pairs a document;
- dense ffn: ``3*2*H*M``; expert ffn: the router ``2*H*E`` over all E
  routed experts, and ``3*2*H*F`` per LOCAL assignment, ``K*held/E`` a
  token by expectation (the run counts the real number;
  ``moe_gemm_work`` takes it);
- head: ``2*H*V``.

Backward is twice the forward; recomputation (``model.remat``), padding
and the optimizer do not count.
"""

from __future__ import annotations

import numpy as np


def document_lengths(batch: dict) -> np.ndarray:
    seg = np.asarray(batch["segment_ids"])
    out = []
    for row in seg:
        _, counts = np.unique(row[row > 0], return_counts=True)
        out.extend(counts.tolist())
    return np.asarray(out, np.int64)


def causal_pairs(lengths: np.ndarray) -> float:
    n = lengths.astype(np.float64)
    return float(np.sum(n * (n + 1) / 2))


def _layers(h: dict) -> tuple:
    kinds = list(h["layer_types"])
    n_attn = kinds.count("full_attention")
    n_moe = len(kinds) - int(h["num_dense_layers"])
    return kinds, n_attn, n_moe


def dense_flops_per_token(h: dict) -> float:
    """Forward operations a real token needs outside the attention
    pairs, local expert assignments by expectation."""
    H, M, F = h["hidden_size"], h["intermediate_size"], \
        h["moe_intermediate_size"]
    n, m, d = (h["num_attention_heads"], h["num_key_value_heads"],
               h["head_dim"])
    kinds, n_attn, n_moe = _layers(h)
    conv = 2 * H * 3 * H + 2 * H * H
    attn = 2 * H * n * d + 2 * 2 * H * m * d + 2 * n * d * H
    local = h["num_experts_per_tok"] * len(h["experts_held"]) \
        / h["experts_routed"]
    moe = 2 * H * h["experts_routed"] + local * 3 * 2 * H * F
    dense = 3 * 2 * H * M
    return float((len(kinds) - n_attn) * conv + n_attn * attn
                 + int(h["num_dense_layers"]) * dense + n_moe * moe
                 + 2 * H * h["vocab_size"])


def train_flops(batch: dict, h: dict) -> float:
    lengths = document_lengths(batch)
    _, n_attn, _ = _layers(h)
    pairs = 4 * h["num_attention_heads"] * h["head_dim"] * n_attn \
        * causal_pairs(lengths)
    return 3.0 * (dense_flops_per_token(h) * float(lengths.sum()) + pairs)


def attention_kernel_work(batch: dict, h: dict, rows_per_chip: int) -> dict:
    """What one chip's attention kernels must do in one step, all
    attention layers: operations over the causal pairs inside documents
    (forward 4, backward 10 per pair and head dimension, as
    ``flops/bert.py`` counts them); bytes with every operand and result
    crossing HBM once, keys and values once per KEY/VALUE head."""
    lengths = document_lengths(batch)
    rows, s = np.asarray(batch["input_ids"]).shape
    pairs = causal_pairs(lengths) * rows_per_chip / rows
    n, m, d = (h["num_attention_heads"], h["num_key_value_heads"],
               h["head_dim"])
    _, n_attn, _ = _layers(h)
    q_like = rows_per_chip * s * n * d * 2       # q, o, do, dq: bf16
    kv_like = rows_per_chip * s * m * d * 2      # k, v, dk, dv
    lse = rows_per_chip * n * s * 4
    return {
        "forward_flops": n_attn * 4 * n * d * pairs,
        "backward_flops": n_attn * 10 * n * d * pairs,
        "forward_bytes": n_attn * (2 * q_like + 2 * kv_like + lse),
        "backward_bytes": n_attn * (4 * q_like + 4 * kv_like + lse),
    }


def moe_gemm_work(local_assignments: float, h: dict, *,
                  recomputed_forward: bool = False) -> dict:
    """The grouped expert products of ONE expert layer in one step, fed
    the number of assignments the chip computed (rows of the sorted
    buffer that belong to a held expert).

    Operations: three products a row forward (``W1``, ``W3``: ``2*H*F``
    each; ``W2``: ``2*F*H``), and twice that backward (each product's
    input gradient and weight gradient). Bytes, bf16: forward reads the
    rows twice, writes and reads the two hidden arrays, writes the
    result, and reads the three weight stacks once; backward moves the
    same arrays' gradients as well and reads the weights and writes
    their gradients once each. ``recomputed_forward`` counts the forward
    products twice: for a kernel's roofline share where the timed kernels
    include the forward pass that ``model.remat`` runs again (never for
    the model's FLOP/s utilization)."""
    H, F = h["hidden_size"], h["moe_intermediate_size"]
    a = float(local_assignments)
    weights = 3 * len(h["experts_held"]) * H * F * 2
    forward_rows = a * 2 * (2 * H + 3 * F + H)
    passes = 2 if recomputed_forward else 1
    return {
        "forward_flops": passes * a * 3 * 2 * H * F,
        "backward_flops": 2 * a * 3 * 2 * H * F,
        "forward_bytes": passes * (forward_rows + weights),
        "backward_bytes": 2 * forward_rows + 2 * weights,
    }
