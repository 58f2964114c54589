"""BERT encoder + MLM head, from shapes.

Per real token and layer, forward: QKV ``2*H*3H``, attention output
``2*H*H``, MLP ``2*2*H*M``. Head: transform ``2*H*H``, tied projection
``2*H*V`` (over every real position: the system projects all of them).
Attention: a token in a document of ``L`` tokens needs ``QK^T`` and
``PV`` against ``L`` keys, ``4*L*H`` per layer, so a batch needs
``4*H*sum(L_d^2)``: block-diagonal for packed rows, ``S^2`` per row for
whole documents. Padding positions need nothing.
"""

from __future__ import annotations

import numpy as np


def document_lengths(batch: dict) -> np.ndarray:
    """Lengths of all documents in a host batch, from ``segment_ids``
    (packed) or ``attention_mask`` (one document per row)."""
    if "segment_ids" in batch:
        seg = np.asarray(batch["segment_ids"])
        out = []
        for row in seg:
            ids, counts = np.unique(row[row > 0], return_counts=True)
            out.extend(counts.tolist())
        return np.asarray(out, np.int64)
    mask = np.asarray(batch.get(
        "attention_mask", np.ones_like(batch["input_ids"])))
    return mask.sum(axis=1).astype(np.int64)


def dense_flops_per_token(h: dict) -> float:
    H, M, V, N = (h["hidden_size"], h["intermediate_size"], h["vocab_size"],
                  h["num_hidden_layers"])
    per_layer = 2 * H * 3 * H + 2 * H * H + 2 * 2 * H * M
    head = 2 * H * H + 2 * H * V
    return float(N * per_layer + head)


def attention_flops_forward(lengths: np.ndarray, h: dict) -> float:
    """QK^T and PV over all layers, forward."""
    return float(4 * h["hidden_size"] * h["num_hidden_layers"]
                 * np.sum(lengths.astype(np.float64) ** 2))


def train_flops(batch: dict, h: dict) -> float:
    lengths = document_lengths(batch)
    forward = dense_flops_per_token(h) * float(lengths.sum()) \
        + attention_flops_forward(lengths, h)
    return 3.0 * forward


def attention_kernel_work(batch: dict, h: dict, rows_per_chip: int) -> dict:
    """What one chip's attention kernels must do in one step, all layers.

    Operations: forward ``QK^T`` and ``PV`` (4 per key-query pair and
    head dimension); backward the five products a flash backward cannot
    avoid: ``QK^T`` again (the probabilities are not kept), ``dP = dO
    V^T``, ``dV = P^T dO``, ``dK = dS^T Q``, ``dQ = dS K`` (10). A
    two-pass backward that forms ``QK^T`` and ``dP`` twice does 14; the
    extra 4 are its own, not the algorithm's.

    Bytes: every operand and result crosses HBM once: forward reads
    q, k, v and writes o (bf16) and the log-sum-exp (f32); backward reads
    q, k, v, o, do and the log-sum-exp and writes dq, dk, dv.
    """
    lengths = document_lengths(batch)
    rows = len(np.asarray(batch["input_ids"]))
    share = rows_per_chip / rows
    pairs = float(np.sum(lengths.astype(np.float64) ** 2)) * share
    s = np.asarray(batch["input_ids"]).shape[1]
    H, N = h["hidden_size"], h["num_hidden_layers"]
    heads = h["num_attention_heads"]
    tensor = rows_per_chip * s * H * 2          # one (B, S, H) bf16 array
    lse = rows_per_chip * heads * s * 4
    return {
        "forward_flops": N * 4 * H * pairs,
        "backward_flops": N * 10 * H * pairs,
        "forward_bytes": N * (4 * tensor + lse),
        "backward_bytes": N * (8 * tensor + lse),
    }
