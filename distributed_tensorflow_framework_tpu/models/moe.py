"""Mixture-of-Experts feed-forward layers.

The reference framework has no MoE (SURVEY.md §2 parallelism inventory —
expert parallel: NO); this extends the capability surface the TPU-native
way. Two layers with two semantics live here:

``MoEMlp`` (GShard/Switch-style, the BERT family's): experts live on a
dedicated ``expert`` mesh axis, tokens are routed by a learned softmax
top-k gate into static per-group CAPACITY, and the dispatch/combine
einsums against expert-sharded weights make XLA emit ``all_to_all``
collectives over ICI — the idiomatic pjit MoE (no hand-written routing
RPCs). Tokens over capacity are dropped.

``DroplessMoE`` (the decoder family's, models/lfm2.py): sigmoid scores
with a selection bias, normalised top-k weights, NO capacity and no
dropped token — assignments are sorted by expert and the experts run as
one grouped matrix product (``jax.lax.ragged_dot``) over the experts
this process HOLDS. It is told its share of an expert-parallel
deployment (``num_experts`` over ``groups``, this is ``group``), routes
over all experts and computes its own experts' part of the result.

Design points of the capacity layer:
  * **Two dispatchers, one semantics** (parity pinned in tests/test_moe.py):
    the default **sorted** dispatch ranks assignments inside their expert
    with one argsort and gathers/scatters through O(B·E·C) index tables —
    linear in tokens, scales to hundreds of experts; the **dense** dispatch
    (one-hot (B,S,E,C) dispatch/combine einsums) is kept as the reference.
    Both use a static per-group capacity — shapes are static so everything
    jits; tokens over capacity are dropped (standard GShard semantics) and
    their combine weight is zero, which keeps the layer differentiable.
  * **Grouping**: the batch dim is the dispatch group — capacity is
    ``ceil(topk * seq / num_experts * capacity_factor)`` per example.
  * **Load-balancing aux loss** (Switch Transformer): E * Σ_e me·ce where
    me = mean gate prob, ce = fraction of tokens whose first choice is e.
    Perfectly balanced routing gives 1.0.
  * Gating math runs in float32 regardless of compute dtype.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_framework_tpu.models.layers import dense_kernel_init

expert_kernel_init = nn.initializers.variance_scaling(
    1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1
)


def topk_dispatch(
    gate_logits: jax.Array,  # (B, S, E) float32
    topk: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with per-group (= per-batch-row) capacity.

    Returns ``(dispatch, combine, aux_loss)`` where dispatch/combine are
    (B, S, E, C) one-hot/weighted one-hot tensors and aux_loss is the
    scalar load-balancing loss.

    Scale limits (dense dispatch): the one-hot dispatch/combine tensors
    are O(B·S·E·C) with C ≈ topk·S/E·cf. Fine for small mixtures
    (E ≤ 64, topk ≤ 2); at hundreds of experts use
    ``topk_dispatch_sorted`` (the MoEMlp default), which produces the
    same routing through O(B·E·C) index tables.
    """
    b, s, e = gate_logits.shape
    if not 1 <= topk <= e:
        raise ValueError(
            f"topk={topk} must be in [1, num_experts={e}] — above e, argmax "
            f"over the exhausted gate would silently re-dispatch to expert 0"
        )
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    choices, first_mask = _topk_choices(probs, topk)  # the SHARED decision

    dispatch = jnp.zeros((b, s, e, capacity), jnp.float32)
    gate_weights = jnp.zeros((b, s, e), jnp.float32)
    # Tokens already claimed per (group, expert) by earlier choices.
    claimed = jnp.zeros((b, e), jnp.float32)
    for k in range(topk):
        mask = jax.nn.one_hot(choices[:, k], e, dtype=jnp.float32)  # (B,S,E)
        # Position of each token within its chosen expert's buffer.
        pos = jnp.cumsum(mask, axis=1) - 1.0 + claimed[:, None, :]
        mask = mask * (pos < capacity)
        claimed = claimed + mask.sum(axis=1)
        gate_weights = gate_weights + probs * mask
        pos_in = (pos * mask).sum(axis=-1)  # (B, S)
        cap_oh = jax.nn.one_hot(pos_in.astype(jnp.int32), capacity,
                                dtype=jnp.float32)
        cap_oh = cap_oh * mask.sum(axis=-1, keepdims=True)
        dispatch = dispatch + mask[..., None] * cap_oh[..., None, :]

    if topk == 1:
        # Switch-style: scale by the RAW top-1 prob. Normalizing would make
        # the weight identically 1, killing the router's task-loss gradient
        # (it would then learn only from the aux loss).
        combine = dispatch * gate_weights[..., None]
    else:
        # GShard top-k: normalize selected gate probs to sum to 1 per token.
        denom = gate_weights.sum(axis=-1, keepdims=True)
        gate_weights = gate_weights / jnp.maximum(denom, 1e-9)
        combine = dispatch * gate_weights[..., None]

    me = probs.mean(axis=(0, 1))          # (E,) mean gate prob
    ce = first_mask.mean(axis=(0, 1))     # (E,) first-choice fraction
    aux_loss = e * jnp.sum(me * ce)
    return dispatch, combine, aux_loss


def _topk_choices(probs: jax.Array, topk: int
                  ) -> tuple[jax.Array, jax.Array]:
    """The shared routing decision: iterated argmax-with-masking (NOT
    jnp.top_k — tie-breaking must match between the dense and sorted
    dispatchers for their parity contract). Returns (choices (B,K,S),
    first_mask (B,S,E))."""
    e = probs.shape[-1]
    choices = []
    remaining = probs
    first_mask = None
    for _ in range(topk):
        choice = jnp.argmax(remaining, axis=-1)          # (B, S)
        if first_mask is None:
            first_mask = jax.nn.one_hot(choice, e, dtype=jnp.float32)
        choices.append(choice)
        remaining = remaining * (1.0 - jax.nn.one_hot(choice, e,
                                                      dtype=jnp.float32))
    return jnp.stack(choices, axis=1), first_mask


def topk_dispatch_sorted(
    gate_logits: jax.Array,  # (B, S, E) float32
    topk: int,
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort-based top-k routing — same semantics as ``topk_dispatch``
    (same choices, same first-come-first-served positions, same drops,
    same combine weights; pinned in tests/test_moe.py) WITHOUT the
    O(B·S·E·C) one-hot tensors that cap the dense path's scale
    (VERDICT r3 missing #5).

    Mechanics: the B·K·S assignments are ranked within their expert by a
    single integer sort key ``expert·A + (k-major index)`` — reproducing
    the dense path's round-then-position claim order — and scattered into
    an O(B·E·C) token table (a C+1-wide dump column absorbs over-capacity
    assignments). Everything is O(B·S·E) gating math, one O(A log A)
    argsort, and O(B·E·C) tables: linear in tokens, never quadratic in
    capacity.

    Returns ``(token_table (B,E,C) i32, table_valid (B,E,C) f32,
    expert_a (B,K,S) i32, pos_a (B,K,S) i32 — clamped to [0, C),
    combine_w (B,K,S) f32 — 0 for dropped, aux_loss scalar)``.
    """
    b, s, e = gate_logits.shape
    if not 1 <= topk <= e:
        raise ValueError(
            f"topk={topk} must be in [1, num_experts={e}] — above e, argmax "
            f"over the exhausted gate would silently re-dispatch to expert 0"
        )
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert_a, first_mask = _topk_choices(probs, topk)    # (B,K,S)

    w_a = jnp.take_along_axis(
        jnp.broadcast_to(probs[:, None], (b, topk, s, e)),
        expert_a[..., None], axis=-1,
    )[..., 0]                                            # (B,K,S)

    a = topk * s  # assignments per batch row, k-major s-minor
    expert_f = expert_a.reshape(b, a)
    token_f = jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32), (b, topk, s)).reshape(b, a)
    # Rank assignments within their expert in (round, position) order —
    # the dense path's claim order — via one sort on a composite key.
    key = expert_f * a + jnp.arange(a, dtype=expert_f.dtype)[None, :]
    order = jnp.argsort(key, axis=-1)
    se_ = jnp.take_along_axis(expert_f, order, axis=-1)
    st_ = jnp.take_along_axis(token_f, order, axis=-1)
    counts = jax.nn.one_hot(expert_f, e, dtype=jnp.int32).sum(axis=1)
    starts = jnp.cumsum(counts, axis=-1) - counts        # (B, E) exclusive
    pos_sorted = (jnp.arange(a, dtype=jnp.int32)[None, :]
                  - jnp.take_along_axis(starts, se_, axis=-1))
    valid_sorted = pos_sorted < capacity
    dest = jnp.where(valid_sorted, pos_sorted, capacity)  # dump column C

    bidx = jnp.arange(b)[:, None]
    token_table = jnp.zeros((b, e, capacity + 1), jnp.int32)
    token_table = token_table.at[bidx, se_, dest].set(st_)[:, :, :capacity]
    table_valid = jnp.zeros((b, e, capacity + 1), jnp.float32)
    table_valid = table_valid.at[bidx, se_, dest].set(
        valid_sorted.astype(jnp.float32))[:, :, :capacity]

    # Unsort position/validity back to assignment (k-major) order for the
    # combine-side gather.
    inv = jnp.argsort(order, axis=-1)
    pos_a = jnp.take_along_axis(pos_sorted, inv, axis=-1).reshape(b, topk, s)
    valid_a = jnp.take_along_axis(
        valid_sorted, inv, axis=-1).reshape(b, topk, s).astype(jnp.float32)
    pos_a = jnp.clip(pos_a, 0, capacity - 1)

    w_placed = w_a * valid_a
    if topk == 1:
        combine_w = w_placed  # Switch-style raw prob (see topk_dispatch)
    else:
        denom = w_placed.sum(axis=1, keepdims=True)
        combine_w = w_placed / jnp.maximum(denom, 1e-9)

    me = probs.mean(axis=(0, 1))
    ce = first_mask.mean(axis=(0, 1))
    aux_loss = e * jnp.sum(me * ce)
    return token_table, table_valid, expert_a, pos_a, combine_w, aux_loss


class MoEMlp(nn.Module):
    """Expert-parallel MLP block replacing the dense transformer FFN.

    Expert weights ``wi`` (E, H, F) / ``wo`` (E, F, H) are sharded
    ``P("expert", ...)`` by parallel/sharding.py's MoE rules (plus megatron
    column/row splits over ``model`` when TP is on); the dispatch einsum
    below then lowers to an XLA all_to_all between the data and expert
    shards.
    """

    num_experts: int
    mlp_dim: int
    topk: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    # "sorted" (default): index/gather dispatch, O(B·E·C) tables — scales
    # in experts and capacity. "dense": the original O(B·S·E·C) one-hot
    # einsum dispatch — kept as the parity reference (tests/test_moe.py)
    # and for shapes where XLA fuses the one-hots well. Sharding note:
    # the combine gather's expert dim is data-dependently indexed, which
    # the SPMD partitioner can't partition (b/433785288) — the explicit
    # pre-gather constraint below turns that into a clean all-gather over
    # ``expert`` instead of an involuntary full remat; both dispatchers
    # now partition dp+ep+tp warning-free (verified in the dryrun gate).
    dispatch_impl: str = "sorted"
    # Router z-loss weight RELATIVE to the balance aux (see
    # core/config.py ModelConfig.moe_zloss_weight for the weighting
    # contract). 0 = off, bit-identical to the pre-knob module.
    zloss_weight: float = 0.0

    @nn.compact
    def __call__(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        if self.dispatch_impl not in ("sorted", "dense"):
            # A typo here would silently run the O(B·S·E·C) dense path —
            # the exact cost the sorted default exists to avoid.
            raise ValueError(
                f"moe dispatch_impl must be 'sorted' or 'dense', got "
                f"{self.dispatch_impl!r}"
            )
        b, s, h = x.shape
        e = self.num_experts
        capacity = max(
            self.topk,
            int(math.ceil(self.topk * s / e * self.capacity_factor)),
        )
        gate_logits = nn.Dense(
            e, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            kernel_init=dense_kernel_init, name="gate",
        )(x.astype(jnp.float32))
        zloss = jnp.zeros((), jnp.float32)
        if self.zloss_weight:
            # ST-MoE router z-loss: mean over tokens of logsumexp(logits)².
            # Bounds router-logit magnitude so early reduction-order noise
            # cannot push the softmax into a winner-take-all collapse
            # (PERF_NOTES round-5 forensics); gradient is well-defined and
            # small near uniform logits.
            z = jax.scipy.special.logsumexp(gate_logits, axis=-1)  # (B,S)
            zloss = jnp.mean(jnp.square(z))

        wi = self.param("wi", expert_kernel_init, (e, h, self.mlp_dim),
                        jnp.float32)
        wo = self.param("wo", expert_kernel_init, (e, self.mlp_dim, h),
                        jnp.float32)

        # Expert-tensor sharding hint: keep every (B, E, C, *) tensor —
        # and, via propagation, its AD cotangent — sharded batch-over-data
        # and experts-over-expert. Without it the SPMD partitioner batch-
        # shards some backward intermediates over the WHOLE mesh and then
        # "involuntarily fully rematerializes" (replicates) them to reach
        # the expert-sharded weights. No-op without a mesh context (plain
        # tests, init, the shard_map twin) — see sharding.constrain_activation.
        from distributed_tensorflow_framework_tpu.parallel.sharding import (
            constrain_activation,
        )

        # B stays on the data-like axes (the batch enters sharded over
        # ("data","fsdp","expert") — core/mesh.batch_spec); E moves to the
        # ``expert`` axis. The batch-dim expert→data reshard is exactly
        # the dispatch/return all_to_all. The hidden dim (xe/oe) is
        # replicated; he's mlp dim keeps the megatron "model" split that
        # column-parallel wi produces and row-parallel wo consumes.
        expert_hint = lambda t: constrain_activation(  # noqa: E731
            t, ("data", "fsdp"), "expert", None, None)
        expert_hint_mlp = lambda t: constrain_activation(  # noqa: E731
            t, ("data", "fsdp"), "expert", None, "model")

        if self.dispatch_impl == "sorted":
            (token_table, table_valid, expert_a, pos_a, combine_w,
             aux_loss) = topk_dispatch_sorted(gate_logits, self.topk,
                                              capacity)
            drop_frac = 1.0 - table_valid.sum() / (b * s * self.topk)
            # Dispatch: gather each expert's claimed tokens from x —
            # (B,E,C,H), the all_to_all site under dp+ep sharding (tokens
            # move from data shards to expert shards), with no
            # (B,S,E,C) intermediary.
            xg = jnp.take_along_axis(
                x[:, None].astype(self.dtype),
                token_table[..., None], axis=2)           # (B,E,C,H)
            xe = xg * table_valid[..., None].astype(self.dtype)
        else:
            dispatch, combine, aux_loss = topk_dispatch(
                gate_logits, self.topk, capacity
            )
            # Router overflow diagnostic: fraction of the B·S·topk
            # assignments dropped by the static capacity — persistently
            # high drop means the gate is imbalanced or cf is too tight.
            drop_frac = 1.0 - dispatch.sum() / (b * s * self.topk)
            # (B,S,E,C) × (B,S,H) → (B,E,C,H): the all_to_all site.
            xe = jnp.einsum("bsec,bsh->bech", dispatch.astype(self.dtype),
                            x.astype(self.dtype))

        xe = expert_hint(xe)
        he = nn.gelu(
            jnp.einsum("bech,ehf->becf", xe, wi.astype(self.dtype)),
            approximate=True,
        )
        he = expert_hint_mlp(he)
        oe = expert_hint(
            jnp.einsum("becf,efh->bech", he, wo.astype(self.dtype)))

        if self.dispatch_impl == "sorted":
            # Combine: gather each token's expert outputs back and weight
            # them — the return all_to_all, again with no (B,S,E,C).
            # The gather's expert dim is indexed by DATA-DEPENDENT
            # expert_a, which the SPMD partitioner cannot partition over
            # the ``expert`` axis — left alone it falls back to
            # "involuntary full rematerialization" of the (B,E,C,H)
            # cotangent over the whole mesh (b/433785288, VERDICT r4).
            # Constraining oe to batch-sharded/expert-REPLICATED right
            # before the gather makes the movement an explicit all-gather
            # over ``expert`` (the return hop of the a2a pair), the
            # gather itself shard-local in B, and the backward a clean
            # slice back to expert shards at the expert_hint site.
            oe = constrain_activation(oe, ("data", "fsdp"), None, None, None)
            og = oe[jnp.arange(b)[:, None, None], expert_a, pos_a]
            out = (og * combine_w[..., None].astype(self.dtype)).sum(axis=1)
        else:
            out = jnp.einsum("bsec,bech->bsh", combine.astype(self.dtype),
                             oe)
        # Metrics ride the return value as EXPLICIT aux outputs (not sown
        # intermediates): return values thread through jax.checkpoint —
        # ``model.remat=true`` keeps moe_drop_frac/moe_zloss observable,
        # where sown intermediates are silently dropped in replayed
        # segments. ``aux_loss`` is the loss-side term (balance aux PLUS
        # the weighted z term — the contract core/config.py documents);
        # zloss/drop_frac are diagnostics.
        return out, {
            "aux_loss": aux_loss + self.zloss_weight * zloss,
            "zloss": zloss,
            "drop_frac": drop_frac,
        }


# ---------------------------------------------------------------------------
# Dropless layer (the decoder family's).
# ---------------------------------------------------------------------------

ROUTER_NORM_EPS = 1e-6  # added to the chosen scores' sum before dividing
# Initial std of the selection bias: non-zero, so that leaving the bias
# out of the choice changes it. No balancing update moves it afterwards.
EXPERT_BIAS_INIT_STD = 0.005


def held_experts(num_experts: int, groups: int, group: int) -> range:
    """The experts group ``group`` of ``groups`` holds: a contiguous run
    of ``num_experts // groups``."""
    if groups < 1 or num_experts % groups or not 0 <= group < groups:
        raise ValueError(
            f"cannot give group {group} of {groups} a whole share of "
            f"{num_experts} experts")
    n = num_experts // groups
    return range(group * n, (group + 1) * n)


def route_sigmoid_topk(gate_logits: jax.Array, bias: jax.Array, topk: int
                       ) -> tuple[jax.Array, jax.Array]:
    """Bias-routed top-k over sigmoid scores, in float32.

    ``s = sigmoid(logits)``; the ``topk`` experts of a token are the
    largest of ``s + bias`` (the bias enters the CHOICE only, and carries
    no gradient); their weights are ``s_e / (sum of the chosen s + 1e-6)``.
    Returns ``(experts (T, K) int32, weights (T, K) float32)``."""
    scores = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), topk)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (chosen.sum(axis=-1, keepdims=True) + ROUTER_NORM_EPS)
    return experts.astype(jnp.int32), weights


@jax.custom_vjp
def _permute(x, order, inverse):
    """``x[order]`` for a permutation ``order`` with inverse ``inverse``:
    the cotangent is gathered back (``g[inverse]``) instead of
    scatter-added, which is what makes dispatch and combine both plain
    row gathers, forward and backward."""
    return jnp.take(x, order, axis=0)


def _permute_fwd(x, order, inverse):
    return jnp.take(x, order, axis=0), (order, inverse)


def _permute_bwd(res, g):
    order, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def sort_by_expert(experts: jax.Array, first: int, held: int):
    """Order the ``T·K`` assignments by held expert, the ones to experts
    this process does not hold last.

    Returns ``(order, inverse, group_sizes (held,), valid (T·K,) bool)``:
    ``order[r]`` is the assignment (token-major, ``t·K + k``) that sits at
    sorted row ``r``, ``inverse`` its inverse permutation,
    ``group_sizes[e]`` the rows of held expert ``first + e`` and ``valid``
    marks the sorted rows that belong to a held expert (a prefix)."""
    flat = experts.reshape(-1)
    local = (flat >= first) & (flat < first + held)
    key = jnp.where(local, flat - first, held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    valid = jnp.arange(flat.shape[0], dtype=jnp.int32) < group_sizes.sum()
    return order, inverse, group_sizes, valid


class DroplessMoE(nn.Module):
    """Expert feed-forward without capacity or dropped tokens.

    ``num_experts`` is the router's width; this process holds experts
    ``held_experts(num_experts, groups, group)`` and adds their part of
    ``sum_chosen w_e · expert_e(x)``. With one group that is the whole
    layer; with several, the parts of all groups add up to it (the share
    test, tests/test_lfm2.py) — on a mesh they would meet through the
    expert exchange, which this layer does not have: on one chip it runs
    without it and nothing imitates the other groups.

    Mechanics: every assignment gets a row of a ``T·K``-row buffer (so
    no routing can overflow it), rows sorted by held expert, the
    assignments to other groups' experts last; each expert is a SwiGLU
    of width ``mlp_dim`` and all held experts run as three grouped
    matrix products (``jax.lax.ragged_dot``: on TPU one Mosaic
    grouped-matmul kernel each, rows beyond the groups untouched). Rows that belong to no held
    expert are zeroed on both sides of the products.

    Returns ``(out (B, S, H), counters)``; the counters are explicit
    outputs so they survive ``nn.remat``: ``local_assignments`` (rows
    computed here), ``load_max_mean`` (fullest held expert ÷ mean),
    ``dropped`` (local assignments without a row: 0 by construction) and
    ``local_share`` (local ÷ all ``T·K`` assignments)."""

    num_experts: int
    mlp_dim: int
    topk: int
    groups: int = 1
    group: int = 0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> tuple[jax.Array, dict]:
        b, s, h = x.shape
        t, k = b * s, self.topk
        mine = held_experts(self.num_experts, self.groups, self.group)
        held = len(mine)
        tokens = x.reshape(t, h)
        with jax.named_scope("router"):
            # float32 end to end: the scores decide a discrete choice.
            gate = self.param("gate", dense_kernel_init,
                              (h, self.num_experts), jnp.float32)
            bias = self.param(
                "expert_bias", nn.initializers.normal(EXPERT_BIAS_INIT_STD),
                (self.num_experts,), jnp.float32)
            logits = jnp.dot(tokens.astype(jnp.float32), gate,
                             precision=jax.lax.Precision.HIGHEST)
            experts, weights = route_sigmoid_topk(logits, bias, k)
            # For whoever applies the model with mutable=["intermediates"]
            # (tests, the router-agreement count of PERF.md); else a no-op.
            self.sow("intermediates", "experts", experts)
        w1 = self.param("w1", expert_kernel_init, (held, h, self.mlp_dim),
                        jnp.float32)
        w3 = self.param("w3", expert_kernel_init, (held, h, self.mlp_dim),
                        jnp.float32)
        w2 = self.param("w2", expert_kernel_init, (held, self.mlp_dim, h),
                        jnp.float32)
        with jax.named_scope("dispatch"):
            order, inverse, group_sizes, valid = sort_by_expert(
                experts, mine.start, held)
            per_token = jnp.broadcast_to(
                tokens.astype(self.dtype)[:, None], (t, k, h)).reshape(t * k, h)
            xs = jnp.where(valid[:, None],
                           _permute(per_token, order, inverse), 0)
        with jax.named_scope("experts"):
            grouped = lambda lhs, w: jax.lax.ragged_dot(  # noqa: E731
                lhs, w.astype(self.dtype), group_sizes)
            hidden = nn.silu(grouped(xs, w1)) * grouped(xs, w3)
            ys = grouped(hidden, w2)
        with jax.named_scope("combine"):
            ys = jnp.where(valid[:, None], ys, 0)
            back = _permute(ys, inverse, order).reshape(t, k, h)
            out = jnp.einsum("tkh,tk->th", back.astype(jnp.float32),
                             weights).astype(self.dtype)
        local = ((experts >= mine.start) & (experts < mine.stop)).sum()
        placed = group_sizes.sum()
        sizes = group_sizes.astype(jnp.float32)
        counters = {
            "local_assignments": placed.astype(jnp.float32),
            "load_max_mean": sizes.max() / jnp.maximum(sizes.mean(), 1.0),
            "dropped": (local - placed).astype(jnp.float32),
            "local_share": local.astype(jnp.float32) / (t * k),
        }
        return out.reshape(b, s, h), counters
