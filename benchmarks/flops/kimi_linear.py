"""Kimi Linear decoder (Kimi Delta Attention and latent attention layers
over sparse experts), from shapes: the work done HERE, on this chip's
share of the heads, of the hidden units, of the experts and of the
vocabulary.

Per real token, forward (a multiply-add is 2 operations; H hidden; a
``kda`` layer holds ``n`` heads of ``d`` channels for q, k and v; a latent
attention layer as ``flops/deepseek_v3.py`` counts it; M the dense
feed-forward's and S the shared expert's hidden units held, F the expert
width, E the experts routed with K a token; V the vocabulary rows held):

- a ``kda`` layer: ``W_q``, ``W_k``, ``W_v`` ``3*2*H*n*d``; the decay's
  ``W_fa`` ``2*H*d`` and ``W_fb`` ``2*d*n*d``; ``W_b`` ``2*H*n``; the
  output gate's ``W_ga`` ``2*H*d`` and ``W_gb`` ``2*d*n*d``; ``W_o``
  ``2*n*d*H`` (the convolutions, the L2 and RMS norms, the softplus and
  the sigmoids are elementwise: bytes, not operations); the recurrence as
  the chunked algorithm computes it, chunks of c tokens, per head
  (``scan_flops_per_token``): the decayed pair products of the chunk,
  ``q_i . k_j`` for ``j <= i`` and ``k_i . k_j`` for ``j < i`` at ``d``
  (``2*c*d`` a token), the unit lower-triangular solve for the ``d``
  columns of the values and the ``d`` of the keys (``(c-1)*2d``), and the
  four products a chunk with the entering state (``W_k S``, ``(Q ⊙ γ)
  S``, ``(K ⊙ γ')ᵀ W`` at ``2*d*d`` each and ``P W`` over the causal half
  at ``(c+1)*d``);
- a latent attention layer and its causal pairs: ``flops/deepseek_v3.py``;
- a dense feed-forward (the first ``num_dense_layers`` layers):
  ``3*2*H*M``;
- an expert layer: the router ``2*H*E``, the shared expert ``3*2*H*S``,
  and ``3*2*H*F`` per LOCAL assignment, ``K*held/E`` a token by
  expectation (the run counts the real number; ``moe_gemm_work`` takes
  it);
- head: ``2*H*V`` (untied; the embedding is a lookup).

Backward is twice the forward; recomputation (``model.remat``), padding
and the optimizer do not count.
"""

from __future__ import annotations

from benchmarks.flops import deepseek_v3
# The chunk is the program's own (no configuration sets it): the count is
# of the algorithm as it runs.
from distributed_tensorflow_framework_tpu.ops.delta_rule import CHUNK
# The grouped products are lfm2's count at this family's keys (H, F, the
# experts held; the shared expert is a dense product and no part of them).
from benchmarks.flops.lfm2 import (  # noqa: F401
    causal_pairs, document_lengths, moe_gemm_work)


def _kda(h: dict) -> tuple[int, int]:
    """``(heads held, head channels)`` of a ``kda`` layer."""
    return len(h["kda_heads_held"]), h["linear_attn_config"]["head_dim"]


def _latent(h: dict) -> dict:
    """``h`` as ``flops/deepseek_v3.py`` reads it: the latent attention
    layers only."""
    return {**h, "layer_types": [k for k in h["layer_types"]
                                 if k == "latent_attention"]}


def scan_flops_per_token(h: dict) -> float:
    """Forward operations of one ``kda`` layer's chunked recurrence for
    one token, over the heads held."""
    n, d = _kda(h)
    c = CHUNK
    return float(n * (2 * c * d + (c - 1) * 2 * d + 6 * d * d
                      + (c + 1) * d))


def dense_flops_per_token(h: dict) -> float:
    """Forward operations a real token needs outside the latent
    attention pairs, local expert assignments by expectation."""
    H, F = h["hidden_size"], h["moe_intermediate_size"]
    n, d = _kda(h)
    kinds = list(h["layer_types"])
    n_kda = kinds.count("kda")
    kda = (3 * 2 * H * n * d + 2 * H * d + 2 * d * n * d + 2 * H * n
           + 2 * H * d + 2 * d * n * d + 2 * n * d * H
           + scan_flops_per_token(h))
    m = len(h["heads_held"])
    nope, rope, d_v, r = (h["qk_nope_head_dim"], h["qk_rope_head_dim"],
                          h["v_head_dim"], h["kv_lora_rank"])
    latent = (2 * H * m * (nope + rope) + 2 * H * (r + rope)
              + 2 * r * m * (nope + d_v) + 2 * m * d_v * H)
    n_dense = int(h["num_dense_layers"])
    local = h["num_experts_per_token"] * len(h["experts_held"]) \
        / h["experts_routed"]
    moe = 2 * H * h["experts_routed"] + 3 * 2 * H * h["shared_units_held"] \
        + local * 3 * 2 * H * F
    return float(n_kda * kda + (len(kinds) - n_kda) * latent
                 + n_dense * 3 * 2 * H * h["dense_units_held"]
                 + (len(kinds) - n_dense) * moe + 2 * H * h["vocab_size"])


def train_flops(batch: dict, h: dict) -> float:
    lengths = document_lengths(batch)
    lat = _latent(h)
    pairs = len(lat["layer_types"]) * deepseek_v3.pair_flops(lat) \
        * causal_pairs(lengths)
    return 3.0 * (dense_flops_per_token(h) * float(lengths.sum()) + pairs)


def attention_kernel_work(batch: dict, h: dict, rows_per_chip: int) -> dict:
    """What one chip's attention kernels must do in one step: the latent
    attention layers', counted as ``flops/deepseek_v3.py`` counts them."""
    return deepseek_v3.attention_kernel_work(batch, _latent(h),
                                             rows_per_chip)


def kda_scan_work(tokens: float, h: dict, *,
                  recomputed_forward: bool = False) -> dict:
    """The chunked recurrence of ALL ``kda`` layers in one step on
    ``tokens`` positions (the rows as run, padding included: the scan
    runs on every position), whatever implements it.

    Operations: ``scan_flops_per_token`` forward, twice that backward.
    Bytes, a token and head: forward reads ``q`` and ``k`` (d each) and
    ``v`` (d) in bf16, the decay ``g`` (d) and ``β`` (1) in float32, and
    writes ``o`` (d) in float32; nothing in between crosses HBM (the
    pair products, the solve and the states stay on the chip in the
    least time); backward moves the same arrays and their gradients.
    ``recomputed_forward`` counts the forward twice: for the roofline
    share where the timed operations include the forward pass that
    ``model.remat`` runs again."""
    n, d = _kda(h)
    layers = list(h["layer_types"]).count("kda")
    forward_flops = layers * tokens * scan_flops_per_token(h)
    forward_bytes = layers * tokens * n * (3 * 2 * d + 4 * d + 4 + 4 * d)
    passes = 2 if recomputed_forward else 1
    return {
        "forward_flops": passes * forward_flops,
        "backward_flops": 2 * forward_flops,
        "forward_bytes": passes * forward_bytes,
        "backward_bytes": 2 * forward_bytes,
    }
