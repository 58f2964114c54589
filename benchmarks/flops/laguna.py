"""Laguna decoder, from shapes: the work done HERE, on this chip's share
of the heads, of the hidden units, of the experts and of the vocabulary.

Per real token, forward (a multiply-add is 2 operations; H hidden; an
attention layer of kind ``c`` holds ``n_c`` query heads over ``m``
key/value heads of ``d``: ``heads_held``; M the dense feed-forward's and
S the shared expert's hidden units held, F the expert width, E the
experts routed with K a token; V the vocabulary rows held):

- an attention layer: q ``2*H*n_c*d``, k and v ``2*2*H*m*d``, the gate
  ``2*H*n_c``, output ``2*n_c*d*H`` (the gate's product with the
  kernel's output and the rotation are elementwise: bytes, not
  operations); scores and values ``4*n_c*d`` per pair a query sees:
  inside its document, not after it and, in a ``sliding_attention``
  layer, fewer than ``sliding_window`` positions before it. A document of
  L tokens holds ``L(L+1)/2`` causal pairs and ``L(L+1)/2 -
  (L-W)(L-W+1)/2`` inside a window of W < L;
- a dense feed-forward (the first ``num_dense_layers`` layers):
  ``3*2*H*M``;
- an expert layer: the router ``2*H*E`` over all E routed experts, the
  shared expert ``3*2*H*S``, and ``3*2*H*F`` per LOCAL assignment,
  ``K*held/E`` a token by expectation (the run counts the real number;
  ``moe_gemm_work`` takes it);
- head: ``2*H*V`` (untied; the embedding is a lookup).

Backward is twice the forward; recomputation (``model.remat``), padding
and the optimizer do not count.
"""

from __future__ import annotations

import numpy as np

# The grouped products are lfm2's count at this family's keys (H, F, the
# experts held; the shared expert is a dense product and no part of it),
# the window layers' part of the kernels' work is read as smallthinker's.
from benchmarks.flops.lfm2 import document_lengths, moe_gemm_work  # noqa: F401
from benchmarks.flops.smallthinker import causal_pairs, window_part  # noqa: F401

GLOBAL_KIND, WINDOW_KIND = "full_attention", "sliding_attention"


def _heads(h: dict, kind: str) -> tuple[int, int, int]:
    """``(query heads, key/value heads, head size)`` held of a layer of
    ``kind``."""
    return (len(h["heads_held"][kind]), len(h["heads_held"]["key_value"]),
            h["head_dim"])


def _layer_counts(h: dict) -> dict:
    kinds = list(h["layer_types"])
    return {GLOBAL_KIND: kinds.count(GLOBAL_KIND),
            WINDOW_KIND: kinds.count(WINDOW_KIND)}


def dense_flops_per_token(h: dict) -> float:
    """Forward operations a real token needs outside the attention
    pairs, local expert assignments by expectation."""
    H, F = h["hidden_size"], h["moe_intermediate_size"]
    total = 0.0
    for kind, layers in _layer_counts(h).items():
        n, m, d = _heads(h, kind)
        total += layers * (2 * H * n * d + 2 * 2 * H * m * d + 2 * H * n
                           + 2 * n * d * H)
    n_dense = int(h["num_dense_layers"])
    n_moe = len(h["layer_types"]) - n_dense
    local = h["num_experts_per_tok"] * len(h["experts_held"]) \
        / h["experts_routed"]
    moe = 2 * H * h["experts_routed"] + 3 * 2 * H * h["shared_units_held"] \
        + local * 3 * 2 * H * F
    return float(total + n_dense * 3 * 2 * H * h["dense_units_held"]
                 + n_moe * moe + 2 * H * h["vocab_size"])


def _pairs_by_kind(batch: dict, h: dict) -> dict:
    lengths = document_lengths(batch)
    return {GLOBAL_KIND: causal_pairs(lengths),
            WINDOW_KIND: causal_pairs(lengths, int(h["sliding_window"]))}


def train_flops(batch: dict, h: dict) -> float:
    pairs = _pairs_by_kind(batch, h)
    in_pairs = sum(
        layers * 4 * _heads(h, kind)[0] * h["head_dim"] * pairs[kind]
        for kind, layers in _layer_counts(h).items())
    tokens = float(document_lengths(batch).sum())
    return 3.0 * (dense_flops_per_token(h) * tokens + in_pairs)


def _kernel_work(pairs: float, layers: int, rows: int, s: int,
                 heads: tuple) -> dict:
    """Operations (forward 4, backward 10 per pair and head dimension, as
    ``flops/bert.py`` counts them) and bytes (every operand and result
    crossing HBM once, keys and values once per KEY/VALUE head, bf16) of
    ``layers`` attention calls of ``heads`` on ``rows`` rows of ``s``."""
    n, m, d = heads
    q_like = rows * s * n * d * 2                # q, o, do, dq
    kv_like = rows * s * m * d * 2               # k, v, dk, dv
    lse = rows * n * s * 4
    return {
        "forward_flops": layers * 4 * n * d * pairs,
        "backward_flops": layers * 10 * n * d * pairs,
        "forward_bytes": layers * (2 * q_like + 2 * kv_like + lse),
        "backward_bytes": layers * (4 * q_like + 4 * kv_like + lse),
    }


def attention_kernel_work(batch: dict, h: dict, rows_per_chip: int) -> dict:
    """What one chip's attention kernels must do in one step: both kinds
    of layer together under the four names every family gives (the global
    layers' heads over the causal pairs inside documents, the window
    layers' heads over those inside the window as well), and the window
    layers' part of it once more under ``window_<name>``: pairs inside
    window, diagonal and document only, whatever computes them
    (``window_part`` reads it)."""
    rows, s = np.asarray(batch["input_ids"]).shape
    layers, pairs = _layer_counts(h), _pairs_by_kind(batch, h)
    scale = rows_per_chip / rows
    work = {kind: _kernel_work(pairs[kind] * scale, layers[kind],
                               rows_per_chip, s, _heads(h, kind))
            for kind in layers}
    whole, window = work[GLOBAL_KIND], work[WINDOW_KIND]
    return {**{k: whole[k] + window[k] for k in whole},
            **{f"window_{k}": v for k, v in window.items()}}
