"""BERT-base for masked-LM pretraining (BASELINE.json config 5).

Transformer encoder exercising the MXU (attention + MLP matmuls) and the
Adam all-reduce path. Post-LayerNorm BERT topology: token+position
embeddings → N×(MHA → add&norm → MLP → add&norm) → tied-embedding MLM head.

Parallelism hooks:
  * Parameter names are chosen to match ``parallel/sharding.py``'s TP
    rules: ``query/key/value`` (column-parallel), ``attn_out``
    (row-parallel), ``mlp_in``/``mlp_out``, ``embed/embedding`` — setting
    mesh axis ``model>1`` shards the transformer megatron-style with no
    model changes.
  * ``attention_impl``: "xla" (jnp einsum attention, XLA-fused),
    "pallas" (ops/flash_attention.py fused online-softmax kernel),
    "ring" (parallel/ring.py sequence-parallel ring attention over the
    ``seq`` mesh axis, for long-context).

Param count pinned by test: 109.5M (BERT-base, tied MLM head).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_framework_tpu.models.layers import dense_kernel_init


def dot_product_attention(q, k, v, *, mask=None, segment_ids=None,
                          dtype=jnp.float32):
    """Reference XLA attention. q,k,v: (B, S, H, D); mask: (B, 1, 1, S) or
    any shape broadcastable to (B, H, Sq, Sk); segment_ids: (B, S) packed-
    sequence ids (attend only within equal ids) or None."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    # Mask in f32: f32-min rounds to -inf in bf16, and a fully-masked row
    # (a padding query under packing) would then softmax to NaN
    # (max=-inf → -inf-(-inf)); in f32 the min is finite so the row
    # degrades to a harmless uniform distribution instead.
    scores = scores.astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    if segment_ids is not None:
        seg_mask = (segment_ids[:, None, :, None]
                    == segment_ids[:, None, None, :])
        scores = jnp.where(seg_mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class MultiHeadAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"
    # The physical mesh: required for attention_impl="ring", and what
    # lets "pallas" split its kernel across devices under a global-view
    # jit (ops/flash_attention.flash_attention).
    mesh: Any = None
    # One (H, 3·H) projection GEMM instead of three (H, H) — fewer,
    # fatter MXU calls on a step whose measured limit is GEMM
    # fragmentation, not a roofline (PERF_NOTES.md BERT analysis).
    # Column-block-exact: the fused output's q/k/v slices equal the
    # separate projections (parity-tested by weight transplant in
    # tests/test_models.py). The kernel is laid out (H, 3, H) so the TP
    # rule shards the LAST axis: every model-axis shard holds its own
    # q/k/v column slice and the split below stays shard-local — a flat
    # (H, 3H) layout would put whole projections on single shards and
    # force per-layer resharding under TP.
    fused_qkv: bool = False

    @nn.compact
    def __call__(self, x, mask=None, segment_ids=None):
        b, s, h = x.shape
        head_dim = h // self.num_heads
        dense = lambda name: nn.Dense(  # noqa: E731
            h, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=dense_kernel_init, name=name,
        )
        if self.fused_qkv:
            # DenseGeneral flattens the kernel to (H, 3*H) before calling
            # kernel_init, so fan_in is H — identical init statistics to
            # the three separate projections.
            qkv = nn.DenseGeneral(
                features=(3, h), dtype=self.dtype, param_dtype=jnp.float32,
                kernel_init=dense_kernel_init, name="qkv",
            )(x)                                   # (B, S, 3, H)
            q, k, v = (qkv[..., i, :].reshape(b, s, self.num_heads, head_dim)
                       for i in range(3))
        else:
            q = dense("query")(x).reshape(b, s, self.num_heads, head_dim)
            k = dense("key")(x).reshape(b, s, self.num_heads, head_dim)
            v = dense("value")(x).reshape(b, s, self.num_heads, head_dim)

        if self.attention_impl == "pallas":
            from distributed_tensorflow_framework_tpu.ops.flash_attention import (
                flash_attention,
            )

            out = flash_attention(q, k, v, mask=mask,
                                  segment_ids=segment_ids, mesh=self.mesh)
        elif self.attention_impl == "ring":
            from distributed_tensorflow_framework_tpu.parallel.ring import (
                ring_attention_sharded,
            )

            out = ring_attention_sharded(q, k, v, mesh=self.mesh, mask=mask,
                                         segment_ids=segment_ids)
        else:
            out = dot_product_attention(q, k, v, mask=mask,
                                        segment_ids=segment_ids,
                                        dtype=self.dtype)
        out = out.reshape(b, s, h)
        return nn.Dense(h, dtype=self.dtype, param_dtype=jnp.float32,
                        kernel_init=dense_kernel_init, name="attn_out")(out)


class EncoderLayer(nn.Module):
    num_heads: int
    mlp_dim: int
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"
    mesh: Any = None
    fused_qkv: bool = False
    # MoE FFN (models/moe.py): 0 = dense MLP; >0 = expert-parallel MoE.
    num_experts: int = 0
    expert_topk: int = 2
    capacity_factor: float = 1.25
    moe_dispatch: str = "sorted"
    moe_zloss_weight: float = 0.0

    @nn.compact
    def __call__(self, x, mask=None, train: bool = True, segment_ids=None):
        # NOTE: ``train`` is positional-able (not keyword-only) so nn.remat
        # can mark it static by argnum (BertForMLM.remat).
        attn = MultiHeadAttention(
            self.num_heads, dtype=self.dtype,
            attention_impl=self.attention_impl, mesh=self.mesh,
            fused_qkv=self.fused_qkv, name="attn",
        )(x, mask, segment_ids)
        attn = nn.Dropout(self.dropout_rate, deterministic=not train)(attn)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x + attn)
        # Aux outputs are a type-stable dict either way (zeros for the
        # dense FFN) so callers — including nn.remat'd instances, whose
        # return values are the ONLY thing that survives the checkpoint
        # boundary — never branch on the layer flavor.
        aux = {k: jnp.zeros((), jnp.float32)
               for k in ("aux_loss", "zloss", "drop_frac")}
        if self.num_experts > 0:
            from distributed_tensorflow_framework_tpu.models.moe import MoEMlp

            y, aux = MoEMlp(
                num_experts=self.num_experts, mlp_dim=self.mlp_dim,
                topk=self.expert_topk, capacity_factor=self.capacity_factor,
                dispatch_impl=self.moe_dispatch,
                zloss_weight=self.moe_zloss_weight,
                dtype=self.dtype, name="moe",
            )(x)
        else:
            y = nn.Dense(self.mlp_dim, dtype=self.dtype, param_dtype=jnp.float32,
                         kernel_init=dense_kernel_init, name="mlp_in")(x)
            y = nn.gelu(y, approximate=True)
            y = nn.Dense(x.shape[-1], dtype=self.dtype, param_dtype=jnp.float32,
                         kernel_init=dense_kernel_init, name="mlp_out")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return nn.LayerNorm(dtype=jnp.float32, name="ln2")(x + y), aux


class BertEmbed(nn.Module):
    """Token + position embedding front. Returns the activations AND the
    raw embedding table so the caller can tie the MLM projection to it."""

    vocab_size: int
    hidden_size: int
    max_seq_len: int
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, position_ids=None, *, train: bool = True):
        s = input_ids.shape[1]
        embed = nn.Embed(self.vocab_size, self.hidden_size,
                         param_dtype=jnp.float32, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(0.02),
                         name="embed")
        x = embed(input_ids)
        pos = self.param(
            "pos_embedding", nn.initializers.normal(0.02),
            (self.max_seq_len, self.hidden_size), jnp.float32,
        )
        if position_ids is None:
            x = x + pos[None, :s, :].astype(self.dtype)
        else:
            # Packed rows: per-document positions (reset at each segment
            # boundary) so packed training sees the same position
            # distribution as unpacked training/eval.
            x = x + jnp.take(pos, position_ids, axis=0).astype(self.dtype)
        x = nn.LayerNorm(dtype=jnp.float32, name="embed_ln")(x)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        return x.astype(self.dtype), embed.embedding


class MLMHead(nn.Module):
    """MLM head: transform → gelu → LN → tied-embedding projection + bias.
    The embedding table is passed in (tying is the caller's wiring)."""

    vocab_size: int
    hidden_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, embedding):
        x = nn.Dense(self.hidden_size, dtype=self.dtype,
                     param_dtype=jnp.float32, kernel_init=dense_kernel_init,
                     name="mlm_transform")(x)
        x = nn.gelu(x, approximate=True)
        x = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(x)
        # Vocab projection in the compute dtype (bf16 on TPU): this is the
        # model's largest matmul (H×V) — running it f32 would double its
        # MXU cost. The f32 promotion happens at the bias add; the loss
        # does its softmax in f32 regardless.
        logits = x.astype(self.dtype) @ embedding.astype(self.dtype).T
        bias = self.param("mlm_bias", nn.initializers.zeros,
                          (self.vocab_size,), jnp.float32)
        return logits.astype(jnp.float32) + bias


class LabelledWindows(NamedTuple):
    """What a training step hands :class:`BertForMLM` so that its head
    runs on the labelled positions only (:func:`head_in_windows`)."""

    targets: jax.Array   # (B, S): the label where one is, -1 elsewhere
    width: int           # positions of a row the head takes at a time
    sums: Callable       # (logits (B, P, V), targets (B, P)) -> (loss_sum,
    #                      others): the scalar to differentiate, and a
    #                      tree of float32 sums that are only reported


class HeadSums(NamedTuple):
    """What :func:`head_in_windows` returns, and :class:`BertForMLM` in
    place of logits: ``LabelledWindows.sums`` added up over the windows,
    and how many of them were computed (float32; 1.0 at best)."""

    loss_sum: jax.Array
    others: Any
    windows: jax.Array


# A window is this much wider than the labelled positions a row is
# expected to hold, in whole lane tiles.
HEAD_WINDOW_HEADROOM = 1.25
HEAD_WINDOW_STEP = 128


def head_window(seq_len: int, mask_prob: float) -> int:
    """Positions of a row the MLM head takes at a time in training: the
    smallest multiple of 128 that holds 1.25x the labelled positions
    ``mask_prob`` promises, at most the row. A row's count is
    Binomial(S, mask_prob), so at 0.15 the window is +6.6 s.d. at S=512
    (128) and +9.6 at 8192 (1536); a row over it costs a further window
    (:func:`head_in_windows`), never a position."""
    want = HEAD_WINDOW_HEADROOM * mask_prob * seq_len
    steps = max(math.ceil(want / HEAD_WINDOW_STEP - 1e-9), 1)
    return min(steps * HEAD_WINDOW_STEP, seq_len)


def _labelled_first(targets, rows: int):
    """Per row of ``targets`` (B, S): its positions with the labelled ones
    (``>= 0``) first, both runs in order, and the targets there; filled
    to ``rows`` with position 0 under the label -1. Integers of (B, S)
    only; one sort (the keys are distinct, so it need not be stable)."""
    s = targets.shape[1]
    at = jnp.arange(s, dtype=jnp.int32)
    keys = jnp.sort(jnp.where(targets >= 0, at, at + s), axis=1,
                    stable=False)
    pos = jnp.where(keys >= s, keys - s, keys)
    there = jnp.take_along_axis(targets, pos, axis=1)
    fill = ((0, 0), (0, rows - s))
    return jnp.pad(pos, fill), jnp.pad(there, fill, constant_values=-1)


def _take_rows(hidden, pos):
    """``hidden[b, pos[b, p]]``: (B, S, H), (B, P) -> (B, P, H), as the
    product with a one-hot (B, P, S). Exact (one term of each sum is not
    a zero), B stays a batch dimension so a batch sharded over chips
    stays where it is, and the backward is the transposed product: on a
    TPU both run on the MXU in microseconds where XLA's row gather and
    its sorted scatter-add take a millisecond."""
    with jax.named_scope("take_rows"):
        pick = pos[..., None] == jnp.arange(hidden.shape[1], dtype=pos.dtype)
        return jnp.matmul(pick.astype(hidden.dtype), hidden,
                          precision=jax.lax.Precision.HIGHEST)


def _plan(targets, width: int):
    """Which positions each window takes: ``(pos, there, used)`` with
    ``pos`` and ``there`` (windows, B, width), the positions labelled
    first and the targets at them, and ``used`` the number of windows
    that hold a label of some row, at least the first."""
    b, s = targets.shape
    n = -(-s // width)
    with jax.named_scope("head"):
        pos, there = _labelled_first(targets, n * width)
        pos = pos.reshape(b, n, width).swapaxes(0, 1)
        there = there.reshape(b, n, width).swapaxes(0, 1)
        most = (targets >= 0).sum(axis=1).max()
        return pos, there, jnp.maximum(-(-most // width), 1)


def _one(window: Callable, plan, operands, hidden, w):
    """``window`` on window ``w`` of the plan: ``(loss_sum, others)``."""
    pos, there, _ = plan
    with jax.named_scope("head"):
        rows = _take_rows(hidden, pos[w])
    return window(operands, rows, there[w])


def _further(plan, first, step):
    """``first`` plus ``step(w)`` for each further window in use."""
    return jax.lax.while_loop(
        lambda at: at[0] < plan[2],
        lambda at: (at[0] + 1, jax.tree.map(jnp.add, at[1], step(at[0]))),
        (jnp.int32(1), first))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def head_in_windows(window: Callable, width: int, operands, hidden, targets):
    """``window(operands, rows, targets) -> (loss_sum, others)`` over the
    labelled positions of ``hidden`` (B, S, H), ``width`` positions of
    every row at a time.

    Each row's positions are taken labelled first. The first window of
    them is straight-line code, computed unconditionally, and holds every
    label of a row with at most ``width``; further windows run only while
    some row has labels left, in a ``lax.while_loop``, and add their
    sums: exact for any count, at the price of one window per ``width``
    labels of the fullest row.

    JAX differentiates no ``while_loop``, so the backward pass is written
    out: the first window's is what ``jax.vjp`` makes of it (the same
    program as autodiff of the whole-row head, on a quarter of the rows,
    so a compiler that shards the batch places the same collectives), and
    a further window is differentiated inside the backward pass's own
    loop, its forward pass re-run there, so no further window's logits
    outlive it. ``others`` is reported, not differentiated.

    Returns :class:`HeadSums`."""
    plan = _plan(targets, width)
    one = functools.partial(_one, window, plan, operands, hidden)
    return HeadSums(*_further(plan, one(0), one),
                    plan[2].astype(jnp.float32))


def _window_vjp(window: Callable, plan, operands, hidden, w):
    """``jax.vjp`` of window ``w``'s loss sum in ``operands`` and
    ``hidden``: ``(loss_sum, back, others)``. JAX writes ``jvp(...)``
    around the first scope it meets under ``jax.vjp``: this one, so that
    the operations inside keep the ``head`` they carry in every other
    pass (a trace folds to it)."""
    def named(ops, rows_of):
        with jax.named_scope("window"):
            return _one(window, plan, ops, rows_of, w)

    return jax.vjp(named, operands, hidden, has_aux=True)


def _head_in_windows_fwd(window, width, operands, hidden, targets):
    plan = _plan(targets, width)
    loss_sum, back, others = _window_vjp(window, plan, operands, hidden, 0)
    sums = _further(plan, (loss_sum, others),
                    functools.partial(_one, window, plan, operands, hidden))
    return (HeadSums(*sums, plan[2].astype(jnp.float32)),
            (back, plan, operands, hidden))


def _head_in_windows_bwd(window, width, res, g):
    back, plan, operands, hidden = res

    def grads(w):
        # Behind a barrier, so that a compiler that shards the batch
        # reduces a further window's parameter gradients where they are
        # made, inside the loop: XLA otherwise moves that all-reduce
        # behind the loop, where every step pays for it, the loop run or
        # not (94 MB of float32 at BERT-base's vocabulary).
        return jax.lax.optimization_barrier(
            _window_vjp(window, plan, operands, hidden, w)[1](g.loss_sum))

    return (*_further(plan, back(g.loss_sum), grads), None)


head_in_windows.defvjp(_head_in_windows_fwd, _head_in_windows_bwd)


# ---------------------------------------------------------------------------
# Autoregressive decode path (serve/decode.py, docs/SERVING.md
# "Autoregressive decode").
#
# The decode engine needs two forwards the training module cannot express:
# a CAUSAL prefill over the prompt that also exports every layer's K/V, and
# a per-token step whose keys/values come from a paged cache instead of the
# layer input. Both are pure jnp functions over the trained BertForMLM
# parameter tree (same names: embed_block/layer{i}/head), with the KV
# residency abstracted behind an ``attend`` callback so the engine owns
# paging while the model owns the math. Everything runs in f32: decode
# parity is pinned BITWISE between batched and unbatched streams, and a
# replicated f32 walk is the cheapest way to make that hold by
# construction.
# ---------------------------------------------------------------------------


def decode_support_reason(model_config) -> str | None:
    """Why this model config cannot take the autoregressive decode path
    (None = supported). The pure-jnp decode forward walks the dense BERT
    parameter tree by name; trees it does not know must be refused by
    name rather than failing as a KeyError mid-stream."""
    from distributed_tensorflow_framework_tpu.models import _is_lfm2_name

    name = model_config.name.lower()
    if _is_lfm2_name(name):
        return (f"model {model_config.name!r} (the lfm2 decoder family) "
                f"trains only: serving it needs a per-layer cache of "
                f"several kinds (keys/values for its attention layers, a "
                f"window of them for its sliding layers, a latent and one "
                f"rotated key a token for its latent attention layers, the last "
                f"conv_kernel-1 gated inputs for its short convolutions, "
                f"the recurrent state of its Mamba-2 layers) "
                f"that serve/decode.py does not have")
    if name not in ("bert", "bert_base", "bert-base"):
        return (f"model {model_config.name!r} has no causal decode head "
                f"(decode supports the dense bert family)")
    if getattr(model_config, "num_experts", 0):
        return "MoE encoder layers are not supported by the decode path"
    if getattr(model_config, "pipeline_stages", 1) > 1:
        return "pipelined checkpoints are not servable (see serve/export.py)"
    return None


def _decode_ln(p, x):
    """f32 LayerNorm matching nn.LayerNorm(epsilon=1e-6) semantics."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]


def _decode_dense(p, x):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"]


def _decode_qkv(attn_params, x):
    """q/k/v projections for one layer, handling both parameter layouts
    (separate query/key/value vs the fused (H, 3, H) qkv kernel)."""
    if "qkv" in attn_params:
        w = attn_params["qkv"]["kernel"].astype(jnp.float32)  # (H, 3, H)
        b = attn_params["qkv"]["bias"].astype(jnp.float32)    # (3, H)
        qkv = jnp.einsum("...h,hco->...co", x, w) + b
        return qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    return (_decode_dense(attn_params["query"], x),
            _decode_dense(attn_params["key"], x),
            _decode_dense(attn_params["value"], x))


def bert_decode_layers(params, ids, positions, attend):
    """The shared causal walk: embed -> N x (attn -> add&norm -> MLP ->
    add&norm), f32. ``ids``/``positions``: (B, T) int32. ``attend(layer,
    q, k, v) -> context`` with q/k/v/context all (B, T, H) f32 — prefill
    passes an in-register causal attention, the per-token decode step a
    paged-pool write+gather. Returns the final hidden states (B, T, H)."""
    emb = params["embed_block"]
    table = emb["embed"]["embedding"].astype(jnp.float32)
    x = jnp.take(table, ids, axis=0)
    x = x + jnp.take(emb["pos_embedding"].astype(jnp.float32),
                     positions, axis=0)
    x = _decode_ln(emb["embed_ln"], x)
    n_layers = sum(1 for k in params if str(k).startswith("layer"))
    for i in range(n_layers):
        lp = params[f"layer{i}"]
        q, k, v = _decode_qkv(lp["attn"], x)
        ctx = attend(i, q, k, v)
        x = _decode_ln(lp["ln1"], x + _decode_dense(lp["attn"]["attn_out"],
                                                    ctx))
        y = nn.gelu(_decode_dense(lp["mlp_in"], x), approximate=True)
        x = _decode_ln(lp["ln2"], x + _decode_dense(lp["mlp_out"], y))
    return x


def bert_decode_head_params(params):
    """Derive serving-layout head params: adds ``mlm_projection``, the
    tied embedding table pre-transposed to (H, V). Transposing inside
    the jitted step makes XLA CPU materialize the 4-byte-per-vocab-entry
    transpose on EVERY call — at serving batch sizes that one op dwarfs
    the whole forward pass (B=1 prefill especially). Paying it once per
    weight (re)load keeps the per-call matmul in the same (B,H)@(H,V)
    kernel for every row bucket, which is also what keeps logits
    bitwise-identical across batch sizes."""
    table = params["embed_block"]["embed"]["embedding"]
    head = dict(params["head"])
    head["mlm_projection"] = jnp.asarray(
        np.ascontiguousarray(np.asarray(table).T))
    out = dict(params)
    out["head"] = head
    return out


def bert_decode_logits(params, hidden):
    """MLM head over decode hidden states: transform -> gelu -> LN ->
    tied-embedding projection + bias, all f32. hidden: (..., H).
    Prefers the pre-transposed ``mlm_projection`` planted by
    :func:`bert_decode_head_params`; falls back to transposing the tied
    table in-graph (slow on CPU, see above) so direct callers without
    the derived leaf still work."""
    head = params["head"]
    t = nn.gelu(_decode_dense(head["mlm_transform"], hidden),
                approximate=True)
    t = _decode_ln(head["mlm_ln"], t)
    proj = head.get("mlm_projection")
    if proj is None:
        proj = params["embed_block"]["embed"]["embedding"].T
    logits = t @ proj.astype(jnp.float32)
    return logits + head["mlm_bias"].astype(jnp.float32)


def causal_prefill_attention(q, k, v, length, num_heads):
    """In-register causal attention for the prefill pass. q/k/v:
    (B, S, H) f32; ``length`` (B,) masks keys past each row's prompt.
    Query row i attends keys j <= i (and j < length)."""
    b, s, h = q.shape
    d = h // num_heads
    qh = q.reshape(b, s, num_heads, d)
    kh = k.reshape(b, s, num_heads, d)
    vh = v.reshape(b, s, num_heads, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / jnp.sqrt(
        jnp.float32(d))
    idx = jnp.arange(s, dtype=jnp.int32)
    causal = idx[None, :] <= idx[:, None]                      # (Sq, Sk)
    valid = idx[None, None, None, :] < length[:, None, None, None]
    mask = causal[None, None, :, :] & valid
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
    return out.reshape(b, s, h)


def paged_decode_attention(q, k_keys, v_keys, positions, num_heads):
    """One-token attention over gathered paged KV. q: (B, H) for the
    current token; k_keys/v_keys: (B, S_kv, H) gathered from the page
    pool (padding included); keys at j <= positions[b] are live, the
    rest — page-table padding and not-yet-written slots — are masked."""
    b, h = q.shape
    s_kv = k_keys.shape[1]
    d = h // num_heads
    qh = q.reshape(b, num_heads, d)
    kh = k_keys.reshape(b, s_kv, num_heads, d)
    vh = v_keys.reshape(b, s_kv, num_heads, d)
    scores = jnp.einsum("bhd,bkhd->bhk", qh, kh) / jnp.sqrt(jnp.float32(d))
    live = (jnp.arange(s_kv, dtype=jnp.int32)[None, :]
            <= positions[:, None])
    scores = jnp.where(live[:, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", probs, vh)
    return out.reshape(b, h)


class BertForMLM(nn.Module):
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_seq_len: int = 512
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"
    mesh: Any = None
    fused_qkv: bool = False
    # MoE: with num_experts>0, every `moe_every`-th layer (from the top of
    # each group) uses an expert-parallel FFN; returns a dict with the
    # load-balancing aux loss alongside the logits.
    num_experts: int = 0
    moe_every: int = 2
    expert_topk: int = 2
    capacity_factor: float = 1.25
    moe_dispatch: str = "sorted"
    moe_zloss_weight: float = 0.0
    # Rematerialize each encoder layer in the backward pass
    # (jax.checkpoint): activations are recomputed per layer instead of
    # stored, cutting activation memory from O(layers) to O(1) layers at
    # ~30% extra forward FLOPs — the fit lever for long-context/big-model
    # configs (ModelConfig.remat). Numerically exact (same ops replayed;
    # parity-tested in tests/test_remat.py).
    remat: bool = False
    # Selective-remat override (precision.remat_policy): a
    # jax.checkpoint_policies callable applied to the per-layer checkpoint
    # when set — e.g. dots_saveable keeps the GEMM outputs and replays
    # only the cheap elementwise tail. None = save-nothing-but-inputs
    # (jax.checkpoint's default), the max-savings/max-recompute point.
    ckpt_policy: Any = None

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, segment_ids=None,
                 *, train: bool = True,
                 labelled: LabelledWindows | None = None):
        """Logits at every position (B, S, V); or, given ``labelled`` (the
        ``mlm`` training step does), in their place the :class:`HeadSums`
        of :func:`head_in_windows`: the head and the sums it feeds run on
        the labelled positions only."""
        position_ids = None
        if segment_ids is not None:
            # Positions restart at every segment boundary: each packed
            # document sees pos_embedding[0..len) exactly as it would
            # unpacked (index i minus the running start-of-segment index).
            idx = jnp.arange(segment_ids.shape[1], dtype=jnp.int32)
            change = jnp.concatenate([
                jnp.ones_like(segment_ids[:, :1], bool),
                segment_ids[:, 1:] != segment_ids[:, :-1],
            ], axis=1)
            starts = jax.lax.cummax(
                jnp.where(change, idx[None, :], 0), axis=1)
            position_ids = idx[None, :] - starts
        x, emb_table = BertEmbed(
            self.vocab_size, self.hidden_size, self.max_seq_len,
            self.dropout_rate, self.dtype, name="embed_block",
        )(input_ids, position_ids, train=train)

        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)
        aux_total = jnp.zeros((), jnp.float32)
        zloss_total = jnp.zeros((), jnp.float32)
        drop_total = jnp.zeros((), jnp.float32)
        n_moe = 0
        # argnums of EncoderLayer.__call__: 0=self, 1=x, 2=mask, 3=train —
        # train branches Python-side (Dropout determinism) so it must stay
        # static under the checkpoint transform.
        if self.remat:
            remat_kwargs: dict[str, Any] = {"static_argnums": (3,)}
            if self.ckpt_policy is not None:
                remat_kwargs["policy"] = self.ckpt_policy
            layer_cls = nn.remat(EncoderLayer, **remat_kwargs)
        else:
            layer_cls = EncoderLayer
        for i in range(self.num_layers):
            use_moe = (
                self.num_experts > 0
                and i % max(self.moe_every, 1) == max(self.moe_every, 1) - 1
            )
            x, aux = layer_cls(
                self.num_heads, self.mlp_dim, self.dropout_rate,
                dtype=self.dtype, attention_impl=self.attention_impl,
                mesh=self.mesh, fused_qkv=self.fused_qkv,
                num_experts=self.num_experts if use_moe else 0,
                expert_topk=self.expert_topk,
                capacity_factor=self.capacity_factor,
                moe_dispatch=self.moe_dispatch,
                moe_zloss_weight=self.moe_zloss_weight,
                name=f"layer{i}",
            )(x, mask, train, segment_ids)
            if use_moe:
                aux_total = aux_total + aux["aux_loss"]
                zloss_total = zloss_total + aux["zloss"]
                drop_total = drop_total + aux["drop_frac"]
                n_moe += 1

        head = MLMHead(self.vocab_size, self.hidden_size, self.dtype,
                       name="head")
        if labelled is None:
            logits = head(x, emb_table)
        else:
            # The windows run under lax control flow, where a bound module
            # cannot be called: the same head, applied as a function of
            # its parameters.
            free = head.clone(parent=None)

            def window(operands, rows, targets):
                params, table = operands
                out = free.apply({"params": params}, rows, table)
                with jax.named_scope("head"):
                    return labelled.sums(out, targets)

            # The rows are taken in the head's compute dtype: its first
            # projection casts them anyway, and ``x`` leaves the last
            # LayerNorm in float32.
            logits = head_in_windows(
                window, labelled.width, (head.variables["params"], emb_table),
                x.astype(self.dtype), labelled.targets)
        if self.num_experts > 0:
            out = {
                "logits": logits,
                "moe_aux_loss": aux_total / max(n_moe, 1),
                "moe_drop_frac": drop_total / max(n_moe, 1),
            }
            if self.moe_zloss_weight:
                # Only when armed — matches the metric's conditional
                # presence in the step output (train/step.py).
                out["moe_zloss"] = zloss_total / max(n_moe, 1)
            return out
        return logits
