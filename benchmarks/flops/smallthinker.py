"""SmallThinker decoder, from shapes: the work done HERE, on this chip's
share of the experts and of the vocabulary.

Per real token, forward (a multiply-add is 2 operations; H hidden, F the
expert width, n query heads and m key/value heads of d, V the vocabulary
rows held, E the experts routed, K a token):

- an attention layer of either kind: q ``2*H*n*d``, k and v
  ``2*2*H*m*d``, output ``2*n*d*H``; scores and values ``4*n*d`` per
  pair a query sees: inside its document, not after it and, in a
  ``sliding_attention`` layer, fewer than ``sliding_window_size``
  positions before it. A document of L tokens holds ``L(L+1)/2`` causal
  pairs and ``L(L+1)/2 - (L-W)(L-W+1)/2`` inside a window of W < L;
- every layer's experts: the router ``2*H*E`` over all E routed experts,
  and ``3*2*H*F`` per LOCAL assignment, ``K*held/E`` a token by
  expectation (the run counts the real number; ``moe_gemm_work`` takes
  it);
- head: ``2*H*V`` (untied; the embedding is a lookup).

Backward is twice the forward; recomputation (``model.remat``), padding
and the optimizer do not count.
"""

from __future__ import annotations

import numpy as np

from benchmarks.flops.lfm2 import document_lengths

WINDOW_KIND = "sliding_attention"


def causal_pairs(lengths: np.ndarray, window: int | None = None) -> float:
    """Pairs (i, j) of one document each with ``j <= i`` and, under
    ``window``, ``i - j < window``."""
    n = lengths.astype(np.float64)
    pairs = n * (n + 1) / 2
    if window is not None:
        beyond = np.maximum(n - window, 0.0)
        pairs = pairs - beyond * (beyond + 1) / 2
    return float(np.sum(pairs))


def _layer_counts(h: dict) -> tuple[int, int]:
    kinds = list(h["layer_types"])
    n_window = kinds.count(WINDOW_KIND)
    return len(kinds) - n_window, n_window


def dense_flops_per_token(h: dict) -> float:
    """Forward operations a real token needs outside the attention
    pairs, local expert assignments by expectation."""
    H, F = h["hidden_size"], h["moe_ffn_hidden_size"]
    n, m, d = (h["num_attention_heads"], h["num_key_value_heads"],
               h["head_dim"])
    layers = len(h["layer_types"])
    attn = 2 * H * n * d + 2 * 2 * H * m * d + 2 * n * d * H
    local = h["moe_num_active_primary_experts"] * len(h["experts_held"]) \
        / h["experts_routed"]
    moe = 2 * H * h["experts_routed"] + local * 3 * 2 * H * F
    return float(layers * (attn + moe) + 2 * H * h["vocab_size"])


def _pairs_by_kind(batch: dict, h: dict) -> tuple[float, float]:
    lengths = document_lengths(batch)
    return (causal_pairs(lengths),
            causal_pairs(lengths, int(h["sliding_window_size"])))


def train_flops(batch: dict, h: dict) -> float:
    n_global, n_window = _layer_counts(h)
    in_global, in_window = _pairs_by_kind(batch, h)
    pairs = 4 * h["num_attention_heads"] * h["head_dim"] * (
        n_global * in_global + n_window * in_window)
    tokens = float(document_lengths(batch).sum())
    return 3.0 * (dense_flops_per_token(h) * tokens + pairs)


def _kernel_work(pairs: float, layers: int, rows: int, s: int, h: dict
                 ) -> dict:
    """Operations (forward 4, backward 10 per pair and head dimension, as
    ``flops/bert.py`` counts them) and bytes (every operand and result
    crossing HBM once, keys and values once per KEY/VALUE head, bf16) of
    ``layers`` attention calls on ``rows`` rows of ``s``."""
    n, m, d = (h["num_attention_heads"], h["num_key_value_heads"],
               h["head_dim"])
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
    layers over the causal pairs inside documents, the window layers over
    those inside the window as well), and the window layers' part of it
    once more under ``window_<name>``: pairs inside window, diagonal and
    document only, whatever computes them (``window_part`` reads it)."""
    rows, s = np.asarray(batch["input_ids"]).shape
    n_global, n_window = _layer_counts(h)
    in_global, in_window = _pairs_by_kind(batch, h)
    scale = rows_per_chip / rows
    whole = _kernel_work(in_global * scale, n_global, rows_per_chip, s, h)
    window = _kernel_work(in_window * scale, n_window, rows_per_chip, s, h)
    return {**{k: whole[k] + window[k] for k in whole},
            **{f"window_{k}": v for k, v in window.items()}}


def window_part(work: dict, *, recomputed_forward: bool = False) -> dict:
    """The window layers' kernels alone, from ``attention_kernel_work``'s
    result (or a mean of several). ``recomputed_forward`` counts the
    forward twice: for the kernels' roofline share where the timed
    kernels include the forward pass that ``model.remat`` runs again."""
    passes = 2 if recomputed_forward else 1
    return {
        "forward_flops": passes * work["window_forward_flops"],
        "backward_flops": work["window_backward_flops"],
        "forward_bytes": passes * work["window_forward_bytes"],
        "backward_bytes": work["window_backward_bytes"],
    }


def moe_gemm_work(local_assignments: float, h: dict, *,
                  recomputed_forward: bool = False) -> dict:
    """The grouped expert products of ONE expert layer in one step, fed
    the number of assignments the chip computed (rows of the sorted
    buffer that belong to a held expert); counted as
    ``flops/lfm2.moe_gemm_work`` counts them: three products a row
    forward (gate, up: ``2*H*F`` each; down: ``2*F*H``), twice that
    backward; bytes in bf16, the three weight stacks once a pass."""
    H, F = h["hidden_size"], h["moe_ffn_hidden_size"]
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
