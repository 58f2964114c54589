"""Pipeline parallelism — microbatched stages over ``pipe``, 3 schedules.

The reference has no pipeline parallelism (SURVEY.md §2 parallelism
inventory — PP: NO); this extends the capability surface the TPU way: the
transformer layer stack is *stacked* (leading dim = num_layers) and that
dim is sharded over the ``pipe`` mesh axis, so each device owns a
contiguous stage of layers. A nested shard_map (the same
inside-jit pattern as parallel/ring.py) runs a static schedule
(parallel/schedule.py picks it from ``model.pipeline_schedule``):

``gpipe`` (default) — circular fill-drain:

    t:      0    1    2    ...                (M + S - 1 steps total)
    stage0  mb0  mb1  mb2
    stage1       mb0  mb1  ...
    stage2            mb0  ...

Each step every stage applies its layers to its current activation, then
``ppermute`` rotates activations one stage forward — neighbor ICI traffic
that XLA overlaps with the next step's compute. The backward comes from
autodiff: transposing the scan+ppermute yields the mirror-image drain
schedule for free. The reverse scan keeps every forward slot's residuals
live until the mirrored backward slot: activation residency O(M + S)
stage-sets per device.

NOTE on validating grads: eager ``jnp.concatenate`` over leaves sharded
``P("pipe", ...)`` on a mesh with replicated data axes mis-reshards on
this jax version and returns values scaled by the data-axis size — so
``jax.flatten_util.ravel_pytree`` on the grad tree is NOT a valid parity
probe. Compare per-leaf (``np.asarray`` each leaf) instead; the tests do.

``1f1b`` — the forward pass is the same circular schedule, but the
backward is HAND-BUILT (autodiff cannot express it: a 1F1B slot runs the
forward of one microbatch and the backward of a *different* microbatch).
``_pipeline_apply_1f1b`` wraps the stack in a jax.custom_vjp whose bwd
unrolls the combined recompute+backward slot table: per slot, one
forward (re)compute hop down the ring (``ppermute`` +1) feeding a
depth-``2S-1`` rolling store of stage-input boundary activations, and
one backward hop up the ring (``ppermute`` -1) where each stage runs a
per-microbatch VJP against its local layer params from its stored
boundary input. Per-layer residuals exist only transiently inside that
slot's VJP → activation residency O(S), independent of M — 1f1b is the
MEMORY schedule (same analytic bubble as gpipe; it buys more
microbatches at a fixed activation budget, at one extra forward of
recompute in the backward pass).

``interleaved`` — v virtual stages per device, round-robin chunk
assignment (global chunk q = c·S + s lives on device s): the circular
schedule runs over v·M chunk-slots of 1/v-sized work, cutting the
fill/drain bubble to (S-1)/(v·M + S-1) — the THROUGHPUT schedule.
Backward from autodiff like gpipe.

The batch stays sharded over the data axes (replicated across ``pipe``);
microbatching happens on the per-shard batch inside the shard_map, so PP
composes with DP/FSDP for free (pinned by tests/test_pipeline.py's
{fsdp:2, pipe:4} parity case).

v1 scope: the pipelined stack itself is sharded ONLY over ``pipe`` —
combining TP / sequence (ring) / expert parallelism *inside* the pipelined
layers needs hand-placed collectives in manual mode and is rejected at
StepBuilder level; dense (embed/head) params still get FSDP/TP from the
jit path as usual.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_framework_tpu.parallel import collectives as coll
from distributed_tensorflow_framework_tpu.parallel import schedule as sched

# Param-tree key for the stacked layer stack — parallel/sharding.py keys its
# P("pipe", None, ...) rule off this prefix.
STACK_KEY = "pipeline_layers"


def _stage_apply(layer: nn.Module, stage_params: Any, x: jax.Array,
                 mask: jax.Array | None, rng: jax.Array | None,
                 layer0: jax.Array, *, train: bool,
                 ckpt_policy: Any = None) -> jax.Array:
    """Apply this stage's local layers (leading dim = layers-per-stage)
    sequentially. ``layer0`` is the stage's first global layer index, used
    to give every (microbatch, layer) a distinct dropout stream.
    ``ckpt_policy`` (precision.remat_policy) checkpoints each layer apply
    with the given jax.checkpoint_policies callable — the selective-remat
    lever for the pipelined stack, whose stage body otherwise manages its
    own activation lifetime."""
    n_local = jax.tree.leaves(stage_params)[0].shape[0]

    def one_layer(p, h, rngs):
        out, _aux = layer.apply({"params": p}, h, mask, train=train,
                                rngs=rngs)
        return out

    if ckpt_policy is not None:
        one_layer = jax.checkpoint(one_layer, policy=ckpt_policy)

    def body(h, xs):
        p, i = xs
        rngs = None
        if train and rng is not None:
            rngs = {"dropout": jax.random.fold_in(rng, layer0 + i)}
        return one_layer(p, h, rngs), None

    x, _ = lax.scan(body, x, (stage_params, jnp.arange(n_local)))
    return x


def _check_microbatch(b_loc: int, m: int) -> None:
    if b_loc % m:
        raise ValueError(
            f"per-shard batch {b_loc} not divisible by "
            f"num_microbatches={m}"
        )


def _circular_fwd_fn(layer, s_stages: int, m: int, num_layers: int,
                     train: bool, axis_name: str, ckpt_policy: Any = None):
    """Per-shard forward of the circular fill-drain schedule — the gpipe
    forward AND the 1f1b primal forward (they are the same pass; the
    schedules differ only in how the backward is produced)."""
    layers_per_stage = num_layers // s_stages

    def fn(p_local, x_loc, mask_loc, rng_in):
        idx = lax.axis_index(axis_name)
        b_loc = x_loc.shape[0]
        _check_microbatch(b_loc, m)
        xm = x_loc.reshape((m, b_loc // m) + x_loc.shape[1:])
        maskm = None
        if mask_loc is not None:
            maskm = mask_loc.reshape((m, b_loc // m) + mask_loc.shape[1:])
        layer0 = idx * layers_per_stage

        def body(buf, t):
            # Rotate: stage p's activation moves to stage p+1 (stage 0
            # receives S-1's garbage, overwritten by the injection below).
            buf = lax.ppermute(
                buf, axis_name, [(i, (i + 1) % s_stages) for i in range(s_stages)]
            )
            inject = lax.dynamic_index_in_dim(
                xm, jnp.clip(t, 0, m - 1), 0, keepdims=False
            )
            buf = jnp.where((idx == 0) & (t < m), inject, buf)
            # The microbatch currently in this stage is t - idx.
            mb_id = jnp.clip(t - idx, 0, m - 1)
            mb_mask = None
            if maskm is not None:
                mb_mask = lax.dynamic_index_in_dim(maskm, mb_id, 0,
                                                   keepdims=False)
            mb_rng = None
            if rng_in is not None:
                mb_rng = jax.random.fold_in(rng_in, mb_id * num_layers)
            buf = _stage_apply(layer, p_local, buf, mb_mask, mb_rng, layer0,
                               train=train, ckpt_policy=ckpt_policy)
            return buf, buf

        buf0 = jnp.zeros_like(xm[0])
        _, emitted = lax.scan(body, buf0, jnp.arange(m + s_stages - 1))
        # The last stage emits microbatch t-(S-1) at step t, so its slice
        # emitted[S-1:] is exactly [mb0..mbM-1]; other stages' slices are
        # pipeline garbage, dropped by the [-1] selection outside (the
        # stacked out-spec makes that a one-hop broadcast from the last
        # stage, not a ring-wide all-reduce of zeros).
        outs = emitted[s_stages - 1:].reshape(x_loc.shape)
        return outs[None]

    return fn


def _interleaved_fwd_fn(layer, s_stages: int, m: int, v: int,
                        num_layers: int, train: bool, axis_name: str,
                        ckpt_policy: Any = None):
    """Per-shard forward of the interleaved schedule: v·M + S - 1 slots;
    at stage-local clock t' = t - s, chunk c = (t' % (S·v)) // S of
    microbatch (t' // (S·v))·S + t' % S. Microbatches advance through the
    virtual chunks in groups of S (needs M % S == 0); the ring hop is the
    same +1 ppermute as gpipe — global chunk q on device q mod S hands to
    chunk q+1 on device (q+1) mod S exactly one slot later."""
    chunk_layers = num_layers // (s_stages * v)
    t_total = v * m + s_stages - 1

    def fn(p_local, x_loc, mask_loc, rng_in):
        idx = lax.axis_index(axis_name)
        b_loc = x_loc.shape[0]
        _check_microbatch(b_loc, m)
        xm = x_loc.reshape((m, b_loc // m) + x_loc.shape[1:])
        maskm = None
        if mask_loc is not None:
            maskm = mask_loc.reshape((m, b_loc // m) + mask_loc.shape[1:])
        # Local stack rows are the device's v round-robin chunks in c
        # order (pipeline_apply pre-permuted the stacked dim).
        p_chunks = jax.tree.map(
            lambda leaf: leaf.reshape((v, chunk_layers) + leaf.shape[1:]),
            p_local,
        )

        def body(buf, t):
            buf = lax.ppermute(
                buf, axis_name, [(i, (i + 1) % s_stages) for i in range(s_stages)]
            )
            tp = t - idx  # stage-local clock; negative/overflow = idle
            tpc = jnp.clip(tp, 0, v * m - 1)
            g = tpc // (s_stages * v)
            r = tpc % (s_stages * v)
            c = r // s_stages
            j = r % s_stages
            mb_id = g * s_stages + j
            inject = lax.dynamic_index_in_dim(xm, mb_id, 0, keepdims=False)
            buf = jnp.where((idx == 0) & (c == 0) & (tp < v * m), inject, buf)
            mb_mask = None
            if maskm is not None:
                mb_mask = lax.dynamic_index_in_dim(maskm, mb_id, 0,
                                                   keepdims=False)
            mb_rng = None
            if rng_in is not None:
                mb_rng = jax.random.fold_in(rng_in, mb_id * num_layers)
            # Global first layer of this chunk — keeps the per-(mb, layer)
            # dropout streams identical to gpipe and the reference.
            layer0 = (c * s_stages + idx) * chunk_layers
            p_c = jax.tree.map(
                lambda leaf: lax.dynamic_index_in_dim(leaf, c, 0,
                                                      keepdims=False),
                p_chunks,
            )
            buf = _stage_apply(layer, p_c, buf, mb_mask, mb_rng, layer0,
                               train=train, ckpt_policy=ckpt_policy)
            return buf, buf

        buf0 = jnp.zeros_like(xm[0])
        _, emitted = lax.scan(body, buf0, jnp.arange(t_total))
        # Microbatch g·S+j finishes its last chunk (v-1 on device S-1) at
        # global slot g·S·v + (v-1)·S + j + (S-1); the slots are ascending
        # in microbatch order, so one static gather reassembles the batch.
        out_slots = jnp.asarray([
            g * s_stages * v + (v - 1) * s_stages + j + s_stages - 1
            for g in range(m // s_stages) for j in range(s_stages)
        ])
        outs = emitted[out_slots].reshape(x_loc.shape)
        return outs[None]

    return fn


def _interleave_perm(num_layers: int, s_stages: int, v: int) -> np.ndarray:
    """Row permutation putting device s's round-robin chunks (global
    chunk q = c·S + s, c ascending) into its contiguous pipe-shard."""
    chunk_layers = num_layers // (s_stages * v)
    perm = [
        layer
        for s in range(s_stages)
        for c in range(v)
        for layer in range((c * s_stages + s) * chunk_layers,
                           (c * s_stages + s + 1) * chunk_layers)
    ]
    return np.asarray(perm, np.int32)


def _nondiff_cotangent(x):
    """float0 cotangent for non-differentiable primal inputs (bool
    attention masks, PRNG keys) — the custom_vjp contract for
    non-inexact dtypes."""
    if x is None:
        return None
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _pipeline_apply_1f1b(layer, stacked_params, x, mask, rng, *, mesh,
                         num_stages, num_microbatches, num_layers, train,
                         axis_name, in_specs, out_spec, x_spec, stack_spec,
                         ckpt_policy=None):
    """The 1f1b executor: primal forward is the circular schedule; the
    hand-built backward unrolls parallel/schedule.py's combined
    recompute+backward slot table (see module docstring)."""
    s_stages, m = num_stages, num_microbatches
    layers_per_stage = num_layers // s_stages
    fwd_mapped = jax.shard_map(
        _circular_fwd_fn(layer, s_stages, m, num_layers, train, axis_name,
                         ckpt_policy),
        mesh=mesh, in_specs=in_specs, out_specs=out_spec, check_vma=False,
    )

    from distributed_tensorflow_framework_tpu.core.mesh import batch_spec

    data_axes = batch_spec(mesh)[0]

    def bwd_fn(p_local, x_loc, mask_loc, rng_in, dy_loc):
        idx = lax.axis_index(axis_name)
        b_loc = x_loc.shape[0]
        _check_microbatch(b_loc, m)
        xm = x_loc.reshape((m, b_loc // m) + x_loc.shape[1:])
        dym = dy_loc.reshape(xm.shape)
        maskm = None
        if mask_loc is not None:
            maskm = mask_loc.reshape((m, b_loc // m) + mask_loc.shape[1:])
        layer0 = idx * layers_per_stage

        def stage_f(p, xin, mb_id):
            mb_mask = None
            if maskm is not None:
                mb_mask = lax.dynamic_index_in_dim(maskm, mb_id, 0,
                                                   keepdims=False)
            mb_rng = None
            if rng_in is not None:
                # Same per-(microbatch, layer) streams as the forward pass
                # — the recompute replays identical dropout masks.
                mb_rng = jax.random.fold_in(rng_in, mb_id * num_layers)
            return _stage_apply(layer, p, xin, mb_mask, mb_rng, layer0,
                                train=train, ckpt_policy=ckpt_policy)

        fwd_perm = [(i, (i + 1) % s_stages) for i in range(s_stages)]
        bwd_perm = [(i, (i - 1) % s_stages) for i in range(s_stages)]
        # Rolling store of stage-INPUT boundary activations: microbatch mb
        # enters stage s's forward at slot mb+s and its backward fires at
        # slot mb+2(S-1)-s — a span of at most 2S-1 slots, so depth 2S-1
        # suffices for every stage. This store (plus the one transient VJP
        # below) IS the 1f1b memory story: O(S) live microbatch states vs
        # the gpipe scan's O(M+S) saved residual sets.
        depth = 2 * s_stages - 1
        store = jnp.zeros((depth,) + xm.shape[1:], xm.dtype)
        fbuf = jnp.zeros_like(xm[0])
        gbuf = jnp.zeros_like(xm[0])
        dp_sum = jax.tree.map(jnp.zeros_like, p_local)
        dxm = jnp.zeros_like(xm)
        for slot in sched.slot_table("1f1b", s_stages, m):
            t = slot.t
            if slot.fwd:  # forward (re)compute phase
                fbuf = lax.ppermute(fbuf, axis_name, fwd_perm)
                inject = lax.dynamic_index_in_dim(
                    xm, jnp.clip(t, 0, m - 1), 0, keepdims=False
                )
                fbuf = jnp.where((idx == 0) & (t < m), inject, fbuf)
                mb_f = jnp.clip(t - idx, 0, m - 1)
                store = lax.dynamic_update_index_in_dim(
                    store, fbuf, t % depth, 0
                )
                fbuf = stage_f(p_local, fbuf, mb_f)
            if slot.bwd:  # backward phase
                gbuf = lax.ppermute(gbuf, axis_name, bwd_perm)
                mb_b = t - 2 * (s_stages - 1) + idx
                active_b = (mb_b >= 0) & (mb_b < m)
                mb_b_c = jnp.clip(mb_b, 0, m - 1)
                ginj = lax.dynamic_index_in_dim(dym, mb_b_c, 0,
                                                keepdims=False)
                gbuf = jnp.where(
                    (idx == s_stages - 1) & (t - (s_stages - 1) < m),
                    ginj, gbuf,
                )
                # This stage forwarded mb_b at slot t - (2(S-1) - 2·idx);
                # fetch its saved boundary input and run the
                # per-microbatch VJP against the local layer params.
                t_f = t - (2 * (s_stages - 1) - 2 * idx)
                xin = lax.dynamic_index_in_dim(store, t_f % depth, 0,
                                               keepdims=False)
                _, pb = jax.vjp(
                    lambda p, xin_: stage_f(p, xin_, mb_b_c), p_local, xin
                )
                dp, dxin = pb(gbuf)
                dp_sum = jax.tree.map(
                    lambda a, b: a + jnp.where(active_b, b, 0.0),
                    dp_sum, dp,
                )
                dxin = jnp.where(active_b, dxin, jnp.zeros_like(dxin))
                dxm = dxm.at[mb_b_c].add(
                    jnp.where(idx == 0, dxin, jnp.zeros_like(dxin))
                )
                gbuf = dxin
        # The stacked params entered replicated over the data axes, so
        # their true cotangent is the sum of the per-data-shard grads;
        # dx is only real on stage 0 (others masked to zero) — the psum
        # over pipe is a one-hop broadcast of stage 0's value.
        dp_sum = lax.psum(dp_sum, data_axes)
        dx = lax.psum(dxm.reshape(x_loc.shape), axis_name)
        return dp_sum, dx

    dx_out_spec = P(data_axes, *([None] * (x.ndim - 1)))
    bwd_mapped = jax.shard_map(
        bwd_fn, mesh=mesh,
        in_specs=in_specs + (x_spec,),
        out_specs=(stack_spec, dx_out_spec),
        check_vma=False,
    )

    @jax.custom_vjp
    def run(p, x_, mask_, rng_):
        return fwd_mapped(p, x_, mask_, rng_)[-1]

    def run_fwd(p, x_, mask_, rng_):
        return run(p, x_, mask_, rng_), (p, x_, mask_, rng_)

    def run_bwd(res, dy):
        p, x_, mask_, rng_ = res
        dp, dx = bwd_mapped(p, x_, mask_, rng_, dy)
        return (dp, dx, _nondiff_cotangent(mask_), _nondiff_cotangent(rng_))

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, x, mask, rng)


def pipeline_apply(
    layer: nn.Module,
    stacked_params: Any,
    x: jax.Array,
    mask: jax.Array | None,
    rng: jax.Array | None,
    *,
    mesh,
    num_stages: int,
    num_microbatches: int,
    train: bool,
    schedule: str = "gpipe",
    virtual_stages: int = 1,
    axis_name: str = "pipe",
    ckpt_policy: Any = None,
) -> jax.Array:
    """Run the stacked layer params over ``x`` with the configured
    schedule (gpipe | 1f1b | interleaved — see module docstring).

    ``stacked_params`` leaves have leading dim num_layers (sharded over
    ``pipe``); ``x`` is (B, S, H) sharded over the data axes. Returns the
    activations after the full stack, same sharding as ``x``.
    """
    s_stages, m = num_stages, num_microbatches
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if num_layers % s_stages:
        raise ValueError(
            f"num_layers={num_layers} not divisible by pipeline stages {s_stages}"
        )
    v = sched.resolve_virtual(schedule, s_stages, m, virtual_stages,
                              num_layers)

    from distributed_tensorflow_framework_tpu.core.mesh import batch_spec

    data_axes = batch_spec(mesh)[0]  # the canonical batch-sharding axes
    x_spec = P(data_axes, *([None] * (x.ndim - 1)))
    stack_spec = jax.tree.map(
        lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))), stacked_params
    )
    mask_spec = None
    if mask is not None:
        mask_spec = P(data_axes, *([None] * (mask.ndim - 1)))
    rng_spec = None if rng is None else P()
    out_spec = P(axis_name, data_axes, *([None] * (x.ndim - 1)))
    in_specs = (stack_spec, x_spec, mask_spec, rng_spec)

    if schedule == "1f1b":
        return _pipeline_apply_1f1b(
            layer, stacked_params, x, mask, rng, mesh=mesh,
            num_stages=s_stages, num_microbatches=m, num_layers=num_layers,
            train=train, axis_name=axis_name, in_specs=in_specs,
            out_spec=out_spec, x_spec=x_spec, stack_spec=stack_spec,
            ckpt_policy=ckpt_policy,
        )
    if schedule == "interleaved":
        # Reorder the stacked dim so each device's contiguous pipe-shard
        # holds its v round-robin chunks (autodiff scatters the grads
        # back through the gather; the reshuffle is a per-step
        # collective-permute of the small layer params).
        perm = _interleave_perm(num_layers, s_stages, v)
        stacked_params = jax.tree.map(
            lambda leaf: jnp.take(leaf, jnp.asarray(perm), axis=0),
            stacked_params,
        )
        fn = _interleaved_fwd_fn(layer, s_stages, m, v, num_layers, train,
                                 axis_name, ckpt_policy)
    else:
        fn = _circular_fwd_fn(layer, s_stages, m, num_layers, train,
                              axis_name, ckpt_policy)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_spec, check_vma=False)
    # Stacked out over pipe: every stage emits its slot trace; only the
    # last stage's row is the real output (selected outside shard_map so
    # the transpose routes the cotangent to stage S-1 alone).
    return mapped(stacked_params, x, mask, rng)[-1]


class PipelinedBert:
    """BERT-for-MLM with the encoder stack pipelined over ``pipe``.

    Flax-compatible ``init``/``apply`` surface (duck-typed for
    train/step.py's StepBuilder) without being an nn.Module: the stacked
    layer params are built with a vmapped per-layer init and managed as a
    plain pytree under params["pipeline_layers"], which is what the
    sharding rules key on. ``schedule``/``virtual_stages`` select the
    stage schedule (parallel/schedule.py); the parameter tree is
    schedule-independent, so checkpoints are interchangeable across
    schedules.
    """

    def __init__(self, *, vocab_size: int, hidden_size: int, num_layers: int,
                 num_heads: int, mlp_dim: int, max_seq_len: int,
                 dropout_rate: float, dtype: Any, mesh,
                 num_stages: int, num_microbatches: int,
                 attention_impl: str = "xla", fused_qkv: bool = False,
                 schedule: str = "gpipe", virtual_stages: int = 0,
                 ckpt_policy: Any = None):
        if mesh is None:
            raise ValueError("PipelinedBert needs the physical mesh")
        if num_layers % num_stages:
            raise ValueError(
                f"num_layers={num_layers} must divide into "
                f"pipeline_stages={num_stages}"
            )
        if attention_impl == "ring":
            raise ValueError(
                "attention_impl='ring' nests a shard_map inside the pipeline "
                "shard_map — unsupported; use 'xla' or 'pallas' with PP"
            )
        from distributed_tensorflow_framework_tpu.models.bert import (
            BertEmbed,
            EncoderLayer,
            MLMHead,
        )

        self.num_layers = num_layers
        self.num_stages = num_stages
        self.num_microbatches = num_microbatches or num_stages
        self.schedule = schedule
        # Fails loudly at model build on a bad (schedule, S, M, v, L).
        self.virtual_stages = sched.resolve_virtual(
            schedule, num_stages, self.num_microbatches, virtual_stages,
            num_layers,
        )
        self.mesh = mesh
        # Selective-remat policy for the per-layer stage applies
        # (precision.remat_policy; see _stage_apply).
        self.ckpt_policy = ckpt_policy
        self.embed = BertEmbed(vocab_size, hidden_size, max_seq_len,
                               dropout_rate, dtype)
        self.layer = EncoderLayer(num_heads, mlp_dim, dropout_rate,
                                  dtype=dtype, attention_impl=attention_impl,
                                  fused_qkv=fused_qkv)
        self.head = MLMHead(vocab_size, hidden_size, dtype)

    # ---------------------------------------------------- flax-like API --
    def init(self, rngs: dict, input_ids, attention_mask=None, *,
             train: bool = False) -> dict:
        del attention_mask, train
        params_rng = rngs["params"]
        k_embed, k_layers, k_head = jax.random.split(params_rng, 3)
        e_vars = self.embed.init({"params": k_embed}, input_ids, train=False)
        x, emb_table = self.embed.apply(e_vars, input_ids, train=False)

        keys = jax.random.split(k_layers, self.num_layers)
        stacked = jax.vmap(
            lambda k: self.layer.init({"params": k}, x, None,
                                      train=False)["params"]
        )(keys)

        h_vars = self.head.init({"params": k_head}, x, emb_table)
        return {"params": {
            "embed_block": e_vars["params"],
            STACK_KEY: stacked,
            "head": h_vars["params"],
        }}

    def apply(self, variables: dict, input_ids, attention_mask=None, *,
              train: bool = True, mutable=False, rngs: dict | None = None):
        p = variables["params"]
        embed_rngs = None
        rng = None
        if rngs is not None and train:
            rng = rngs.get("dropout")
            if rng is not None:
                embed_rngs = {"dropout": jax.random.fold_in(rng, 0x5A5A)}
        x, emb_table = self.embed.apply({"params": p["embed_block"]},
                                        input_ids, train=train,
                                        rngs=embed_rngs)
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)
        x = pipeline_apply(
            self.layer, p[STACK_KEY], x, mask, rng,
            mesh=self.mesh, num_stages=self.num_stages,
            num_microbatches=self.num_microbatches, train=train,
            schedule=self.schedule, virtual_stages=self.virtual_stages,
            ckpt_policy=self.ckpt_policy,
        )
        logits = self.head.apply({"params": p["head"]}, x, emb_table)
        if mutable:
            return logits, {}
        return logits

    # Reference (non-pipelined) forward with the same params — used by the
    # numerics tests to pin the schedules' correctness.
    def apply_reference(self, variables: dict, input_ids,
                        attention_mask=None, *, train: bool = False):
        p = variables["params"]
        x, emb_table = self.embed.apply({"params": p["embed_block"]},
                                        input_ids, train=train)
        mask = None
        if attention_mask is not None:
            mask = attention_mask[:, None, None, :].astype(bool)
        for i in range(self.num_layers):
            layer_p = jax.tree.map(lambda leaf: leaf[i], p[STACK_KEY])
            x, _ = self.layer.apply({"params": layer_p}, x, mask, train=train)
        return self.head.apply({"params": p["head"]}, x, emb_table)
