"""Nemotron-H decoder, from shapes: the work done HERE, on this chip's
share of the heads, of the experts and of the vocabulary.

Per real token, forward (a multiply-add is 2 operations; H hidden; a
Mamba-2 layer holds h heads of P channels, d = h*P, and g B/C groups of
N state dims; an attention layer n query heads over m key/value heads of
D; L the latent, F the expert width, S the shared expert's units held, E the experts
routed with K a token; V the vocabulary rows held):

- ``M``: ``W_in`` ``2*H*(2d + 2gN + h)`` and ``W_out`` ``2*d*H``; the
  recurrence as a chunked scan with chunks of c tokens (``scan_flops``):
  a group's ``C B^T`` over the causal half of a chunk ``2*N*(c+1)/2``, a
  head's masked product with ``Delta x`` ``2*P*(c+1)/2``, its part of
  the chunk's state ``2*P*N`` and the entering state's part of its
  output ``2*P*N``. The convolution, the gate and the norms are
  elementwise: bytes, not operations;
- ``*``: q ``2*H*n*D``, k and v ``2*2*H*m*D``, output ``2*n*D*H``;
  scores and values ``4*n*D`` per causal pair inside the document;
- ``E``: the router ``2*H*E``, latent in and out ``2*2*H*L``, the shared
  expert ``2*2*H*S`` over the S of its hidden units held here
  (``shared_units_held``), and ``2*2*L*F`` per LOCAL assignment, ``K*held/E``
  a token by expectation (the run counts the real number;
  ``moe_gemm_work`` takes it);
- head: ``2*H*V`` (untied; the embedding is a lookup).

Backward is twice the forward; recomputation (``model.remat``), padding
and the optimizer do not count.
"""

from __future__ import annotations

import numpy as np

from benchmarks.flops.lfm2 import causal_pairs, document_lengths


def _held(h: dict) -> dict:
    """Heads and groups held, by mixer."""
    return {k: len(v) for k, v in h["heads_held"].items()}


def _counts(h: dict) -> dict:
    return {letter: h["pattern"].count(letter) for letter in "ME*"}


def scan_flops_per_token(h: dict) -> float:
    """Forward operations of one Mamba-2 layer's chunked recurrence for
    one token, over the heads and groups held."""
    held = _held(h)
    c, p, n = h["chunk_size"], h["mamba_head_dim"], h["ssm_state_size"]
    half = (c + 1) / 2
    return float(held["bc_groups"] * 2 * n * half
                 + held["mamba"] * (2 * p * half + 2 * 2 * p * n))


def dense_flops_per_token(h: dict) -> float:
    """Forward operations a real token needs outside the attention
    pairs, local expert assignments by expectation."""
    H = h["hidden_size"]
    held, layers = _held(h), _counts(h)
    d = held["mamba"] * h["mamba_head_dim"]
    gn = held["bc_groups"] * h["ssm_state_size"]
    mamba = 2 * H * (2 * d + 2 * gn + held["mamba"]) + 2 * d * H \
        + scan_flops_per_token(h)
    n, m, D = held["attention"], held["key_value"], h["head_dim"]
    attn = 2 * H * n * D + 2 * 2 * H * m * D + 2 * n * D * H
    L, F = h["moe_latent_size"], h["moe_intermediate_size"]
    local = h["num_experts_per_tok"] * len(h["experts_held"]) \
        / h["experts_routed"]
    moe = 2 * H * h["experts_routed"] + 2 * 2 * H * L \
        + 2 * 2 * H * h["shared_units_held"] \
        + local * 2 * 2 * L * F
    return float(layers["M"] * mamba + layers["*"] * attn
                 + layers["E"] * moe + 2 * H * h["vocab_size"])


def train_flops(batch: dict, h: dict) -> float:
    lengths = document_lengths(batch)
    pairs = 4 * _held(h)["attention"] * h["head_dim"] * _counts(h)["*"] \
        * causal_pairs(lengths)
    return 3.0 * (dense_flops_per_token(h) * float(lengths.sum()) + pairs)


def attention_kernel_work(batch: dict, h: dict, rows_per_chip: int) -> dict:
    """What one chip's attention kernels must do in one step, all
    attention layers, over the heads held: counted as
    ``flops/lfm2.attention_kernel_work`` counts them."""
    lengths = document_lengths(batch)
    rows, s = np.asarray(batch["input_ids"]).shape
    pairs = causal_pairs(lengths) * rows_per_chip / rows
    held, layers = _held(h), _counts(h)["*"]
    n, m, d = held["attention"], held["key_value"], h["head_dim"]
    q_like = rows_per_chip * s * n * d * 2       # q, o, do, dq: bf16
    kv_like = rows_per_chip * s * m * d * 2      # k, v, dk, dv
    lse = rows_per_chip * n * s * 4
    return {
        "forward_flops": layers * 4 * n * d * pairs,
        "backward_flops": layers * 10 * n * d * pairs,
        "forward_bytes": layers * (2 * q_like + 2 * kv_like + lse),
        "backward_bytes": layers * (4 * q_like + 4 * kv_like + lse),
    }


def ssm_scan_work(tokens: float, h: dict, *,
                  recomputed_forward: bool = False) -> dict:
    """The chunked recurrence of ALL Mamba-2 layers in one step on
    ``tokens`` positions (the rows as run, padding included: the scan
    runs on every position), whatever implements it.

    Operations: ``scan_flops_per_token`` forward, twice that backward.
    Bytes: forward reads ``x`` (d channels), ``B`` and ``C`` (g*N each)
    in bf16 and ``Delta`` (h) in float32, and writes ``y`` (d) in
    float32; backward moves the same arrays and their gradients.
    ``recomputed_forward`` counts the forward twice: for the roofline
    share where the timed operations include the forward pass that
    ``model.remat`` runs again."""
    held, layers = _held(h), _counts(h)["M"]
    d = held["mamba"] * h["mamba_head_dim"]
    gn = held["bc_groups"] * h["ssm_state_size"]
    forward_flops = layers * tokens * scan_flops_per_token(h)
    forward_bytes = layers * tokens * (
        2 * (d + 2 * gn) + 4 * held["mamba"] + 4 * d)
    passes = 2 if recomputed_forward else 1
    return {
        "forward_flops": passes * forward_flops,
        "backward_flops": 2 * forward_flops,
        "forward_bytes": passes * forward_bytes,
        "backward_bytes": 2 * forward_bytes,
    }


def moe_gemm_work(local_assignments: float, h: dict, *,
                  recomputed_forward: bool = False) -> dict:
    """The grouped expert products of ONE expert layer in one step, fed
    the number of assignments the chip computed: two products a row in
    the latent forward (``W1``: ``2*L*F``, ``W2``: ``2*F*L``) and twice
    that backward. Bytes, bf16: forward reads the rows, writes and reads
    the hidden array, writes the result, and reads the two weight stacks
    once; backward moves the same arrays' gradients as well and reads
    the weights and writes their gradients once each."""
    L, F = h["moe_latent_size"], h["moe_intermediate_size"]
    a = float(local_assignments)
    weights = 2 * len(h["experts_held"]) * L * F * 2
    forward_rows = a * 2 * (L + 2 * F + L)
    passes = 2 if recomputed_forward else 1
    return {
        "forward_flops": passes * a * 2 * 2 * L * F,
        "backward_flops": 2 * a * 2 * 2 * L * F,
        "forward_bytes": passes * (forward_rows + weights),
        "backward_bytes": 2 * forward_rows + 2 * weights,
    }
