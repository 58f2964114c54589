"""Train/eval step construction — the framework's hot loop.

Replaces SURVEY.md §3.1's per-step pipeline (read vars from PS over grpc →
local fwd/bwd → NCCL grad aggregation → chief applies update → sync token)
with ONE compiled SPMD program in two selectable flavors:

  * ``spmd_mode="jit"``: the batch is a global array sharded over the data
    axes; the loss is a mean over the global batch, so XLA emits the
    cross-replica-sum for the gradients automatically. BN statistics are
    global (cross-replica) by construction.
  * ``spmd_mode="shard_map"``: per-replica code with explicit
    `pmean(grads)` — structurally the closest analogue of the reference's
    SyncReplicasOptimizer+NCCL pipeline, and the mode in which per-replica
    BN (the reference's exact semantics) is expressible.

Both modes produce bitwise-identical parameter trajectories for BN-free
models (tested in tests/test_train_lenet.py::test_jit_and_shard_map_agree).
"""

from __future__ import annotations

import inspect
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_framework_tpu.core.config import ExperimentConfig
from distributed_tensorflow_framework_tpu.core import prng
from distributed_tensorflow_framework_tpu.core.mesh import batch_spec
from distributed_tensorflow_framework_tpu.models import get_model
from distributed_tensorflow_framework_tpu.models.bert import (
    LabelledWindows, head_window)
from distributed_tensorflow_framework_tpu.parallel import sharding as shd
from distributed_tensorflow_framework_tpu.parallel import collectives as coll
from distributed_tensorflow_framework_tpu.parallel import zero
from distributed_tensorflow_framework_tpu.train import losses
from distributed_tensorflow_framework_tpu.train.optimizers import make_optimizer
from distributed_tensorflow_framework_tpu.train.state import TrainState

DATA_AXES = ("data", "fsdp")


def _fsdp_dim(shape, fsdp_n: int) -> int:
    """Dim index the explicit-fsdp path shards over, or -1 for replicated
    leaves (no divisible dim, scalars). Delegates to the ONE tie-break
    rule in parallel/sharding.pick_fsdp_dim so the explicit layout can
    never diverge from the jit-spec one."""
    return shd.pick_fsdp_dim(tuple(shape), fsdp_n)


def task_for_model(name: str) -> str:
    from distributed_tensorflow_framework_tpu.models import (
        builtin_task, custom_model_task)

    custom = custom_model_task(name)
    if custom is not None:
        return custom
    return builtin_task(name)


def model_inputs(task: str, batch: Any) -> tuple:
    if task == "mlm":
        if "segment_ids" in batch:
            # Packed sequences (data.pack_factor>1): block-diagonal
            # attention over the per-row segment ids.
            return (batch["input_ids"], batch["attention_mask"],
                    batch["segment_ids"])
        if "attention_mask" in batch:
            return (batch["input_ids"], batch["attention_mask"])
        return (batch["input_ids"],)
    if task == "causal_lm":
        # Packed rows carry their documents' ids and the positions that
        # restart at each; one document per row needs neither.
        return (batch["input_ids"], batch.get("segment_ids"),
                batch.get("positions"))
    return (batch["image"],)


class StepBuilder:
    """Builds the compiled init / train_step / eval_step for a workload."""

    def __init__(self, config: ExperimentConfig, mesh: Mesh):
        self.config = config
        self.mesh = mesh
        self.task = task_for_model(config.model.name)
        self.shard_map_mode = config.train.spmd_mode == "shard_map"
        # Collective wire format: parallel.collective_dtype, with the
        # deprecated train.grad_allreduce_dtype honored for configs built
        # without load_config's shim.
        self._collective_dtype = (config.parallel.collective_dtype
                                  or config.train.grad_allreduce_dtype)
        self._collective_block = config.parallel.collective_block_size
        if self._collective_dtype and not self.shard_map_mode:
            raise ValueError(
                "parallel.collective_dtype (and the deprecated "
                "train.grad_allreduce_dtype) only applies to the explicit "
                "collective path — set train.spmd_mode='shard_map' (under "
                "'jit' XLA owns the gradient reduction wire format)"
            )
        if config.train.grad_allreduce_accum not in ("float32", "wire"):
            raise ValueError(
                "train.grad_allreduce_accum must be 'float32' or 'wire', "
                f"got {config.train.grad_allreduce_accum!r}"
            )
        # Error-feedback residual rides the TrainState only for the int8
        # block-scaled collectives (parallel/collectives.py, parallel/zero.py).
        self._use_residual = (self.shard_map_mode
                              and self._collective_dtype == "int8"
                              and config.parallel.error_feedback)
        # ZeRO weight-update sharding (parallel/zero.py). "jit" is the
        # passive spec variant (the deprecated optimizer.shard_opt_state,
        # honored here for configs built without load_config's shim);
        # "shard_map" is the explicit bucketed reduce-scatter path.
        zs = config.optimizer.zero_sharding
        if config.optimizer.shard_opt_state and zs == "off":
            zs = "jit"
        self._zero = zs == "shard_map"
        self._zero_n = (mesh.shape.get("data", 1)
                        * mesh.shape.get("fsdp", 1))
        self._zero_plan = None
        if self._zero:
            if not self.shard_map_mode:
                raise ValueError(
                    "optimizer.zero_sharding='shard_map' is the explicit "
                    "bucketed reduce-scatter path and needs "
                    "train.spmd_mode='shard_map'; under spmd_mode='jit' "
                    "use optimizer.zero_sharding='jit' (XLA owns the "
                    "update-shard/all-gather pattern there)"
                )
            if self._zero_n <= 1:
                raise ValueError(
                    "optimizer.zero_sharding='shard_map' shards the weight "
                    "update over the data×fsdp replicas — this mesh has "
                    f"{self._zero_n}, so it would be a silent no-op"
                )
            if config.optimizer.name == "lars":
                raise ValueError(
                    "optimizer.name='lars' needs full per-layer "
                    "param/update norms, but zero_sharding='shard_map' "
                    "updates flattened parameter SHARDS — use "
                    "zero_sharding='jit' for lars"
                )
            if config.optimizer.grad_clip_norm > 0:
                raise ValueError(
                    "optimizer.grad_clip_norm>0 computes the global grad "
                    "norm inside the optimizer, which under "
                    "zero_sharding='shard_map' sees only gradient SHARDS "
                    "— use zero_sharding='jit' for clipped training"
                )
        # Fused donated optimizer update (precision.fused_update): the
        # optax apply moves into the bucketed reverse-layer walk
        # (parallel/zero.fused_update_walk) so each param shard is
        # read-modified-written once while hot. The walk IS the ZeRO
        # bucketed path, so it inherits zero_sharding='shard_map' and its
        # lars/grad-clip exclusions (validated above).
        precision = getattr(config, "precision", None)
        self._fused = bool(precision is not None and precision.fused_update)
        if self._fused and not self._zero:
            raise ValueError(
                "precision.fused_update=true fuses the optax apply into "
                "the ZeRO bucketed reverse-layer walk and therefore "
                "requires optimizer.zero_sharding='shard_map'"
            )
        self._fused_txs = None  # built lazily, one tx per plan bucket
        # shard_map + mesh.fsdp>1 runs EXPLICIT fsdp: params/opt state/EMA
        # sharded over fsdp, a hand-placed (optionally quantized)
        # all_gather around the fwd/bwd, grads sliced back to shards for
        # the update. With fsdp==1 the path is pure replicated DP as
        # before. Under ZeRO the fsdp axis instead folds into the shard
        # count (params stay replicated — no forward-pass gathers).
        self._explicit_fsdp = (self.shard_map_mode
                               and mesh.shape.get("fsdp", 1) > 1
                               and not self._zero)
        if self._explicit_fsdp:
            if config.optimizer.name == "lars":
                raise ValueError(
                    "optimizer.name='lars' needs full per-layer param/update "
                    "norms, but explicit fsdp (spmd_mode='shard_map' with "
                    "mesh.fsdp>1) updates parameter SHARDS — use "
                    "spmd_mode='jit' for lars+fsdp"
                )
            if config.optimizer.grad_clip_norm > 0:
                raise ValueError(
                    "optimizer.grad_clip_norm>0 computes the global grad "
                    "norm inside the optimizer, which under explicit fsdp "
                    "(spmd_mode='shard_map' with mesh.fsdp>1) sees only "
                    "gradient SHARDS — use spmd_mode='jit' for clipped "
                    "fsdp training"
                )
        if (self.task in ("mlm", "causal_lm")
                and getattr(config.data, "vocab_size", None) is not None
                and config.data.vocab_size > config.model.vocab_size):
            # Token ids at/above the embedding size clamp silently under
            # jit and the CE loss on out-of-range TARGETS goes NaN on the
            # first step — measured: a drive with model.vocab_size=512
            # over the recipe's 30522-token synthetic stream was
            # loss=nan at step 1 with nothing pointing at the cause.
            raise ValueError(
                f"data.vocab_size={config.data.vocab_size} exceeds "
                f"model.vocab_size={config.model.vocab_size}: the stream "
                f"can emit token ids the embedding/MLM head cannot "
                f"represent (silent clamp + NaN loss). Shrink "
                f"data.vocab_size or grow model.vocab_size."
            )
        if self.shard_map_mode and mesh.shape.get("expert", 1) > 1:
            raise ValueError(
                "spmd_mode='shard_map' is the pure-DP reference-parity path; "
                "expert parallelism (mesh.expert>1) requires spmd_mode='jit'"
            )
        self._zero_jit = zs == "jit"
        if self._zero_jit:
            if self.shard_map_mode:
                raise ValueError(
                    "optimizer.zero_sharding='jit' (and the deprecated "
                    "optimizer.shard_opt_state) needs spmd_mode='jit' — "
                    "XLA owns the update-shard/all-gather pattern there; "
                    "the explicit path is optimizer.zero_sharding="
                    "'shard_map'"
                )
            if mesh.shape.get("fsdp", 1) <= 1:
                raise ValueError(
                    "optimizer.zero_sharding='jit' (and the deprecated "
                    "optimizer.shard_opt_state) shards over the fsdp mesh "
                    "axis — set mesh.fsdp > 1 (it would be a silent no-op "
                    "on this mesh)"
                )
        pipe = mesh.shape.get("pipe", 1)
        stages = config.model.pipeline_stages
        self._pipe_virtual = 1
        if pipe > 1 or stages > 1 or config.model.pipeline_microbatches > 0:
            if stages <= 1:
                raise ValueError(
                    "pipeline_microbatches / mesh.pipe>1 require "
                    "model.pipeline_stages>1"
                )
            if "bert" not in config.model.name.lower():
                raise ValueError(
                    "pipeline parallelism is only wired for the transformer "
                    "(bert) models (parallel/pipeline.py)"
                )
            if stages != pipe:
                raise ValueError(
                    f"model.pipeline_stages={stages} must equal the mesh's "
                    f"pipe axis size {pipe}"
                )
            if self.shard_map_mode:
                raise ValueError(
                    "pipeline parallelism runs under spmd_mode='jit' (the "
                    "stage schedule is its own nested shard_map)"
                )
            if (
                mesh.shape.get("model", 1) > 1
                or mesh.shape.get("seq", 1) > 1
                or mesh.shape.get("expert", 1) > 1
                or config.model.num_experts > 0
            ):
                raise ValueError(
                    "v1 pipeline scope: pipe composes with data/fsdp only — "
                    "TP/seq/expert parallelism inside the pipelined stack "
                    "needs manual-mode collectives in the stage body"
                )
            # Schedule validation at StepBuilder level (fails before any
            # compile on a bad (schedule, S, M, v, L) tuple); the resolved
            # tuple also drives the per-step analytic bubble metric.
            from distributed_tensorflow_framework_tpu.parallel import (
                schedule as pipe_sched,
            )

            micro = config.model.pipeline_microbatches or stages
            self._pipe_virtual = pipe_sched.resolve_virtual(
                config.model.pipeline_schedule, stages, micro,
                config.model.pipeline_virtual_stages,
                config.model.num_layers,
            )
        # BN axis name: only meaningful under shard_map (under jit, stats
        # are global automatically; see models/layers.py docstring).
        bn_axis = None
        if self.shard_map_mode and config.model.bn_cross_replica:
            bn_axis = DATA_AXES
        self.model = get_model(config.model, bn_axis_name=bn_axis, mesh=mesh,
                               precision=precision)
        self.tx, self.schedule = make_optimizer(
            config.optimizer, config.train.total_steps
        )
        self._state_specs = None
        self._fsdp_dims = None  # params-shaped tree of shard dims (fsdp)
        self._schedule_wrapper = None
        # Set by state_specs once param shapes are known (ZeRO only): the
        # ref tree the weight-decay mask is computed from, since the tx
        # there runs on flattened shards with path/rank erased.
        self._decay_mask_ref = None

    def set_schedule_wrapper(self, wrapper) -> None:
        """Rebuild tx/schedule with ``wrapper`` applied (the post-rollback
        LR re-warmup, train/schedules.with_rewarmup; None restores the
        plain schedule). The opt-state pytree stays valid — optax keeps
        only a schedule-agnostic step counter — but the caller must
        rebuild its compiled train step afterwards (the old jit captured
        the old chain)."""
        self._schedule_wrapper = wrapper
        self.tx, self.schedule = make_optimizer(
            self.config.optimizer, self.config.train.total_steps,
            schedule_wrapper=wrapper,
            decay_mask_ref=self._decay_mask_ref,
        )
        # Per-bucket fused txs captured the old schedule — rebuild lazily.
        self._fused_txs = None

    # ------------------------------------------------------------- init --
    def _ensure_zero_plan(self, params: Any) -> "zero.ZeroPlan":
        """Build (once) the shard/bucket plan. Only shapes and tree paths
        are read, so tracers and ShapeDtypeStructs both work — the plan
        computed inside ``eval_shape`` is identical to the live one."""
        if self._zero_plan is None:
            self._zero_plan = zero.build_plan(
                params, self._zero_n, self.config.optimizer.zero_bucket_mb)
        return self._zero_plan

    def _ensure_fused_txs(self, params: Any) -> tuple:
        """One optax chain per ZeRO bucket (precision.fused_update), each
        carrying its bucket's positional subset of the weight-decay mask
        — the shard leaves the bucket update runs on have rank and path
        erased, so the mask must be precomputed from the real param tree
        (only paths/ranks are read: tracers and structs both work)."""
        if self._fused_txs is None:
            from distributed_tensorflow_framework_tpu.train.optimizers import (
                decay_mask_tree,
            )

            plan = self._ensure_zero_plan(params)
            mask_leaves = jax.tree.leaves(decay_mask_tree(params))
            self._fused_txs = tuple(
                make_optimizer(
                    self.config.optimizer, self.config.train.total_steps,
                    schedule_wrapper=self._schedule_wrapper,
                    decay_mask=tuple(
                        mask_leaves[lc.index] for lc in bucket),
                )[0]
                for bucket in plan.buckets
            )
        return self._fused_txs

    def _create_state(self, seed_arr: jax.Array, batch: Any) -> TrainState:
        root = jax.random.key(seed_arr[0])
        init_rng = prng.for_role(root, prng.ROLE_INIT)
        dropout_root = prng.for_role(root, prng.ROLE_DROPOUT)
        inputs = model_inputs(self.task, batch)
        variables = self.model.init(
            {"params": init_rng, "dropout": dropout_root}, *inputs, train=False
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        residual = None
        if self._use_residual:
            # One f32 row per data-parallel replica, globally
            # (n_dp, *param.shape) sharded over DATA_AXES — each replica's
            # local slice is its own uncompensated quantization error.
            n_dp = (self.mesh.shape.get("data", 1)
                    * self.mesh.shape.get("fsdp", 1))
            residual = jax.tree.map(
                lambda p: jnp.zeros((n_dp,) + p.shape, jnp.float32), params
            )
        opt_params = None
        opt_state = None
        if self._zero:
            # Slots are born at the stacked (n, chunk) layout — row i is
            # replica i's shard of the flattened leaf (parallel/zero.py).
            plan = self._ensure_zero_plan(params)
            opt_params = zero.stacked_shards(params, plan)
            if self._fused:
                # Fused update: one optax state per reduce-scatter bucket
                # (same slot bytes, grouped by the walk's issue order).
                txs = self._ensure_fused_txs(params)
                s_leaves = jax.tree.leaves(opt_params)
                opt_state = tuple(
                    tx_b.init(tuple(s_leaves[lc.index] for lc in bucket))
                    for tx_b, bucket in zip(txs, plan.buckets)
                )
                opt_params = None
        return TrainState.create(
            params=params, batch_stats=batch_stats, tx=self.tx,
            rng=dropout_root, ema=self.config.optimizer.ema_decay > 0,
            collective_residual=residual, opt_params=opt_params,
            opt_state=opt_state,
        )

    def state_specs(self, sample_batch: Any) -> Any:
        if self._state_specs is None:
            seed = jnp.zeros((1,), jnp.uint32)
            shapes = jax.eval_shape(self._create_state, seed, sample_batch)
            if self._zero:
                # Rebuild tx with the weight-decay mask PRECOMPUTED from
                # the real param tree: the shard-domain update sees
                # flattened 1-D leaves, so the rank/path-based mask
                # callable would misclassify every leaf. Mask values do
                # not change opt-state structure or init values (masked
                # optax wrappers are stateless), so the eval_shape above
                # — taken with the callable-mask tx — stays valid.
                self._decay_mask_ref = shapes.params
                self.tx, self.schedule = make_optimizer(
                    self.config.optimizer, self.config.train.total_steps,
                    schedule_wrapper=self._schedule_wrapper,
                    decay_mask_ref=self._decay_mask_ref,
                )
            if self.shard_map_mode:
                # Pure DP (reference semantics) replicates everything.
                # Explicit fsdp (mesh.fsdp>1) shards params / optimizer
                # slots / EMA over the fsdp axis by shape; the EF residual
                # shards its replica row over the combined data axes.
                specs = jax.tree.map(lambda _: P(), shapes)
                if self._explicit_fsdp:
                    if jax.tree.leaves(shapes.batch_stats):
                        raise ValueError(
                            "explicit fsdp (spmd_mode='shard_map' with "
                            "mesh.fsdp>1) does not support BN models: "
                            "running stats would be updated from gathered "
                            "params on every replica — use spmd_mode='jit' "
                            "or a BN-free model"
                        )
                    fsdp_n = self.mesh.shape["fsdp"]

                    def leaf_spec(s):
                        d = _fsdp_dim(s.shape, fsdp_n)
                        if d < 0:
                            return P()
                        parts = [None] * len(s.shape)
                        parts[d] = "fsdp"
                        return P(*parts)

                    self._fsdp_dims = jax.tree.map(
                        lambda s: _fsdp_dim(s.shape, fsdp_n), shapes.params)
                    specs = specs.replace(
                        params=jax.tree.map(leaf_spec, shapes.params),
                        opt_state=jax.tree.map(leaf_spec, shapes.opt_state),
                        ema_params=jax.tree.map(leaf_spec,
                                                shapes.ema_params),
                    )
                if self._zero:
                    # Stacked (n, chunk) slots shard their row dim over
                    # the combined data axes — per-device slot HBM ~1/n.
                    # Scalars (optax step counters) stay replicated.
                    specs = specs.replace(opt_state=jax.tree.map(
                        lambda s: (P(DATA_AXES)
                                   if getattr(s, "ndim", 0) >= 2 else P()),
                        shapes.opt_state))
                if self._use_residual:
                    specs = specs.replace(collective_residual=jax.tree.map(
                        lambda _: P(DATA_AXES), shapes.collective_residual))
                self._state_specs = specs
            elif self._zero_jit:
                # ZeRO-1 (cross-replica weight-update sharding): params /
                # BN stats / EMA replicated like pure DP, optimizer slots
                # sharded over fsdp. XLA partitions the weight update and
                # all-gathers the new params (SURVEY.md §7 hard part 5).
                base = shd.infer_param_specs(shapes, self.mesh, fsdp=False)
                opt = shd.infer_param_specs(shapes.opt_state, self.mesh,
                                            fsdp=True)
                self._state_specs = base.replace(opt_state=opt)
            else:
                self._state_specs = shd.infer_param_specs(shapes, self.mesh)
        return self._state_specs

    def init_state(self, seed: int, sample_batch: Any) -> TrainState:
        """Create the sharded TrainState directly on the mesh (params are
        materialized device-side with their final shardings — no host
        round-trip)."""
        specs = self.state_specs(sample_batch)
        out_sh = shd.specs_to_shardings(specs, self.mesh)
        create = jax.jit(self._create_state, out_shardings=out_sh)
        seed_arr = jnp.asarray([seed], jnp.uint32)
        return create(seed_arr, sample_batch)

    # ------------------------------------------------------- train step --
    def _has_bn(self, state: TrainState) -> bool:
        return bool(jax.tree.leaves(state.batch_stats))

    def _loss_and_updates(self, state: TrainState, batch: Any):
        """Shared fwd/bwd body (identical in both SPMD modes), with
        optional gradient accumulation over microbatches."""
        accum = self.config.train.grad_accum_steps
        if accum <= 1:
            return self._microbatch_grads(state, batch)
        return self._accumulated_grads(state, batch, accum)

    def mlm_head_window(self, seq_len: int) -> int:
        """Positions of a row the MLM head and its loss run on at a time
        in the training step, from what the step can observe; ``seq_len``
        is the whole row, today's program. That is what a ``causal_lm``
        gets (nearly every position is labelled), a mesh that shards the
        sequence (a gather along a sharded S would cross chips: no cell
        runs one, so nobody has measured it) and a model whose forward
        takes no ``labelled`` (the pipelined stack, a registered model).
        Otherwise ``data.mask_prob`` sizes it (models/bert.head_window)."""
        call = getattr(type(self.model), "__call__", None)
        if (self.task != "mlm" or self.mesh.shape.get("seq", 1) > 1
                or call is None
                or "labelled" not in inspect.signature(call).parameters):
            return seq_len
        return head_window(seq_len, self.config.data.mask_prob)

    def _microbatch_grads(self, state: TrainState, batch: Any):
        step_rng = prng.fold_in_step(state.rng, state.step)
        has_bn = self._has_bn(state)
        inputs = model_inputs(self.task, batch)
        head_on = {}
        if self.task == "mlm":
            seq_len = batch["targets"].shape[1]
            width = self.mlm_head_window(seq_len)
            if width < seq_len:
                head_on["labelled"] = LabelledWindows(
                    batch["targets"], width, losses.mlm_sums)

        def loss_fn(params):
            variables = {"params": params}
            if has_bn:
                variables["batch_stats"] = state.batch_stats
            out = self.model.apply(
                variables,
                *inputs,
                train=True,
                mutable=["batch_stats"] if has_bn else False,
                rngs={"dropout": step_rng},
                **head_on,
            )
            if has_bn:
                logits, new_model_state = out
            else:
                logits, new_model_state = out, {}
            if self.task == "causal_lm":
                counters = {}
                if isinstance(logits, dict):  # expert layers: + counters
                    counters = {k: v for k, v in logits.items()
                                if k != "logits"}
                    logits = logits["logits"]
                loss, metrics = losses.causal_lm_loss(logits,
                                                      batch["targets"])
                # Router counters (models/moe.DroplessMoE) ride the same
                # fetch as the loss: no sync of their own.
                metrics.update(counters)
            elif self.task == "mlm":
                moe_aux = moe_drop = moe_zloss = None
                if isinstance(logits, dict):  # MoE model: logits + aux dict
                    moe_aux = logits.get("moe_aux_loss")
                    # Router diagnostics arrive as EXPLICIT model outputs
                    # (models/moe.py) — return values thread through
                    # jax.checkpoint, so these stay observable under
                    # model.remat where sown intermediates would vanish.
                    moe_drop = logits.get("moe_drop_frac")
                    # z-loss is emitted only when the knob is armed, so
                    # moe_aux_loss — balance aux PLUS the weighted z term
                    # (the loss-side contract) — can be disambiguated when
                    # reading a collapse signature (docs/DISTRIBUTED.md).
                    moe_zloss = logits.get("moe_zloss")
                    logits = logits["logits"]
                if head_on:
                    # The head ran on the labelled positions only and
                    # hands back their sums; the window counter rides the
                    # same fetch as the loss, like the routers' counters.
                    loss, metrics = losses.mlm_loss_of_sums(
                        logits.loss_sum, logits.others, batch["targets"])
                    metrics["mlm_head_windows"] = logits.windows
                else:
                    loss, metrics = losses.mlm_loss(logits,
                                                    batch["targets"])
                if moe_aux is not None:
                    loss = loss + self.config.train.moe_aux_weight * moe_aux
                    metrics["moe_aux_loss"] = moe_aux
                    metrics["total_loss"] = loss
                if moe_drop is not None:
                    # Mean over the model's MoE layers. Under grad
                    # accumulation this rides the shared masked-token
                    # metric weighting (slightly skewed vs a plain
                    # per-microbatch mean) — fine for a diagnostic.
                    metrics["moe_drop_frac"] = moe_drop
                if moe_zloss is not None:
                    metrics["moe_zloss"] = moe_zloss
            else:
                aux_logits = None
                if isinstance(logits, dict):  # Inception aux head
                    aux_logits = logits.get("aux_logits")
                    logits = logits["logits"]
                loss, metrics = losses.classification_loss(
                    logits,
                    batch["label"],
                    label_smoothing=self.config.train.label_smoothing,
                )
                if aux_logits is not None:
                    aux_loss, _ = losses.classification_loss(
                        aux_logits,
                        batch["label"],
                        label_smoothing=self.config.train.label_smoothing,
                    )
                    # Canonical Inception-v3 auxiliary weighting.
                    loss = loss + 0.4 * aux_loss
                    metrics["aux_loss"] = aux_loss
                    metrics["total_loss"] = loss
            return loss, (metrics, new_model_state)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, (metrics, new_model_state)), grads = grad_fn(state.params)
        return grads, metrics, new_model_state

    def _microbatch_weight(self, mb: Any) -> jax.Array:
        """Each microbatch's share of the full-batch loss denominator.

        Classification losses are means over examples (equal microbatches →
        equal weights); MLM normalizes by the masked-token count, which
        varies per microbatch under dynamic masking — weighting by it makes
        the accumulated gradient exactly the full-batch gradient."""
        if self.task in ("mlm", "causal_lm"):
            return losses.mlm_mask(mb["targets"]).sum()
        return jnp.float32(1.0)

    def _accumulated_grads(self, state: TrainState, batch: Any, accum: int):
        """Split the batch into `accum` microbatches, scan fwd/bwd
        accumulating the denominator-weighted gradient sum — numerically
        the full-batch gradient at 1/accum the activation memory. BN
        running stats thread through the scan sequentially; the dropout
        rng differs per microbatch (step folded with the microbatch
        index). The MoE aux loss becomes a weighted mean of per-microbatch
        aux losses (routing capacity is per-microbatch under accumulation,
        so this is the quantity its gradient actually regularizes)."""

        def split(path, x):
            if x.shape[0] % accum:
                raise ValueError(
                    f"grad_accum_steps={accum} does not divide batch leaf "
                    f"{shd._path_str(path)} of size {x.shape[0]}"
                )
            return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

        micro = jax.tree_util.tree_map_with_path(split, batch)
        first = jax.tree.map(lambda x: x[0], micro)
        st0 = state.replace(step=state.step * accum)
        g_shape, m_shape, _ = jax.eval_shape(self._microbatch_grads, st0, first)
        zeros = lambda tree: jax.tree.map(  # noqa: E731
            lambda s: jnp.zeros(s.shape, s.dtype), tree
        )

        def body(carry, xs):
            stats, grads_sum, metrics_sum, w_sum = carry
            i, mb = xs
            st = state.replace(batch_stats=stats, step=state.step * accum + i)
            g, m, ms = self._microbatch_grads(st, mb)
            w = self._microbatch_weight(mb)
            return (
                ms.get("batch_stats", stats),
                jax.tree.map(lambda a, b: a + w * b, grads_sum, g),
                jax.tree.map(lambda a, b: a + w * b, metrics_sum, m),
                w_sum + w,
            ), None

        carry0 = (state.batch_stats, zeros(g_shape), zeros(m_shape),
                  jnp.float32(0.0))
        (stats, grads, metrics, w_sum), _ = jax.lax.scan(
            body, carry0, (jnp.arange(accum), micro)
        )
        inv = 1.0 / jnp.maximum(w_sum, 1e-9)
        grads = jax.tree.map(lambda g: g * inv, grads)
        metrics = jax.tree.map(lambda m: m * inv, metrics)
        new_model_state = {"batch_stats": stats} if self._has_bn(state) else {}
        return grads, metrics, new_model_state

    def _apply_updates(self, state, grads, metrics, new_model_state):
        # named_scope → op_name metadata on every optimizer HLO op, the
        # handle core/trace_analysis.py uses to attribute trace time to
        # the optimizer-update category.
        with jax.named_scope("optimizer_update"):
            updates, new_opt_state = self.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = coll.global_norm(grads)
        return self._finalize_state(state, new_params, new_opt_state,
                                    metrics, new_model_state)

    def _finalize_state(self, state, new_params, new_opt_state, metrics,
                        new_model_state):
        """Shared post-update tail: lr/bubble metrics, EMA, state.replace.
        Split from _apply_updates so the ZeRO path — whose update runs on
        shards and produces new_params/new_opt_state its own way — reuses
        the exact same trailing semantics."""
        metrics = dict(metrics)
        metrics["learning_rate"] = self.schedule(state.step)
        stages = self.config.model.pipeline_stages
        if stages > 1:
            # Analytic schedule bubble — fill/drain slots over total slots
            # (parallel/schedule.py, single source of truth per schedule;
            # gpipe keeps its original (S-1)/(M+S-1)). Static for a static
            # schedule — logged per step so PP runs carry their fill-drain
            # overhead in the metric stream (VERDICT r4 #6).
            from distributed_tensorflow_framework_tpu.parallel import (
                schedule as pipe_sched,
            )

            micro = self.config.model.pipeline_microbatches or stages
            metrics["pipe_bubble_frac"] = jnp.float32(pipe_sched.bubble_frac(
                self.config.model.pipeline_schedule, stages, micro,
                self._pipe_virtual))
        ema_decay = self.config.optimizer.ema_decay
        if ema_decay > 0:
            # tf.train.ExponentialMovingAverage(num_updates=step) schedule:
            # early steps track params closely, late steps converge to decay.
            t = state.step.astype(jnp.float32)
            d = jnp.minimum(ema_decay, (1.0 + t) / (10.0 + t))
            new_ema = jax.tree.map(
                lambda e, p: e * d + p.astype(e.dtype) * (1.0 - d),
                state.ema_params, new_params,
            )
        else:
            new_ema = state.ema_params
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_model_state.get("batch_stats", state.batch_stats),
            ema_params=new_ema,
        )
        return new_state, metrics

    def _train_step_jit(self, state: TrainState, batch: Any):
        # Mesh context (trace-time only) arms best-effort activation
        # sharding hints inside the models (shd.constrain_activation);
        # the shard_map twin deliberately never enters one.
        with self.mesh:
            grads, metrics, new_model_state = self._loss_and_updates(
                state, batch)
            # Loss is a global-batch mean → grads already carry the
            # cross-replica-sum; no explicit collective needed.
            return self._apply_updates(state, grads, metrics,
                                       new_model_state)

    def _zero_train_step_replica(self, state: TrainState, batch: Any):
        """Per-replica ZeRO step (optimizer.zero_sharding='shard_map').

        Replaces the monolithic all-reduce with: bucketed mean
        reduce-scatter of the grads (reverse layer order — each bucket's
        collective overlaps the backward of the layers issued after it,
        parallel/zero.py) → per-replica optax update on this replica's
        1/n of the flattened weights → bucketed all-gather of the UPDATE
        values → every replica applies the identical update to its full
        f32 master params. Params/EMA/BN stay replicated (pure-DP
        forward); only the slots and the update are sharded.
        """
        wire = self._collective_dtype or None
        block = self._collective_block
        plan = self._ensure_zero_plan(state.params)
        grads, metrics, new_model_state = self._loss_and_updates(
            state, batch)
        residual = None
        if self._use_residual:
            # Local (1, *shape) row of the global (n, *shape) residual —
            # this replica's carried int8 quantization error.
            residual = jax.tree.map(
                lambda r: r[0], state.collective_residual)
        if self._fused:
            # Fused donated update (precision.fused_update): per bucket,
            # RS → shard update → AG → apply, instead of three whole-tree
            # passes. Same collectives per bucket; params RMW'd once hot.
            txs = self._ensure_fused_txs(state.params)
            row = coll.linear_axis_index(DATA_AXES)
            new_params, new_opt, new_res, sq_sum = zero.fused_update_walk(
                plan, txs, grads, state.params, state.opt_state, DATA_AXES,
                wire_dtype=wire, block_size=block, residual=residual,
                row=row)
            metrics = coll.pmean(metrics, DATA_AXES)
            if self._has_bn(state):
                new_model_state = dict(new_model_state)
                new_model_state["batch_stats"] = coll.pmean(
                    new_model_state["batch_stats"], DATA_AXES)
            metrics = dict(metrics)
            # Same quantity shard_global_norm logs, from the walk's local
            # squared sums (coll.psum keeps the tally ledger identical).
            metrics["grad_norm"] = jnp.sqrt(
                coll.psum(sq_sum, DATA_AXES))
            new_state, metrics = self._finalize_state(
                state, new_params, new_opt, metrics, new_model_state)
            if new_res is not None:
                new_state = new_state.replace(
                    collective_residual=jax.tree.map(
                        lambda r: r[None], new_res))
            return new_state, metrics
        shard_grads, new_res = zero.bucketed_reduce_scatter(
            plan, grads, DATA_AXES, wire_dtype=wire, block_size=block,
            residual=residual)
        row = coll.linear_axis_index(DATA_AXES)
        param_shards = zero.local_shards(state.params, plan, row)
        opt_local = zero.squeeze_slots(state.opt_state)
        with jax.named_scope("optimizer_update"):
            updates, new_opt_local = self.tx.update(
                shard_grads, opt_local, param_shards)
        full_updates = zero.bucketed_all_gather(
            plan, updates, DATA_AXES, wire_dtype=wire, block_size=block)
        new_params = optax.apply_updates(state.params, full_updates)
        metrics = coll.pmean(metrics, DATA_AXES)
        if self._has_bn(state):
            new_model_state = dict(new_model_state)
            new_model_state["batch_stats"] = coll.pmean(
                new_model_state["batch_stats"], DATA_AXES)
        metrics = dict(metrics)
        # Norm of the full MEAN gradient, from its disjoint shards — the
        # same quantity the unsharded path logs.
        metrics["grad_norm"] = zero.shard_global_norm(shard_grads, DATA_AXES)
        new_state, metrics = self._finalize_state(
            state, new_params, zero.unsqueeze_slots(new_opt_local),
            metrics, new_model_state)
        if new_res is not None:
            new_state = new_state.replace(collective_residual=jax.tree.map(
                lambda r: r[None], new_res))
        return new_state, metrics

    def _train_step_replica(self, state: TrainState, batch: Any):
        if self._zero:
            return self._zero_train_step_replica(state, batch)
        wire = self._collective_dtype
        block = self._collective_block
        if self._explicit_fsdp:
            # Unshard params for fwd/bwd: the hand-placed (optionally
            # quantized) all_gather over fsdp — the explicit twin of the
            # jit path's XLA-inserted fsdp gather.
            def gather(p, dim):
                if dim < 0:
                    return p
                return coll.all_gather(p, "fsdp", axis=dim, tiled=True,
                                       wire_dtype=wire or None,
                                       block_size=block)

            full_params = jax.tree.map(gather, state.params, self._fsdp_dims)
            grads, metrics, new_model_state = self._loss_and_updates(
                state.replace(params=full_params), batch)
        else:
            grads, metrics, new_model_state = self._loss_and_updates(
                state, batch)
        # Explicit sync-DP: mean grads across replicas — the NCCL all-reduce
        # site of the reference (SURVEY.md §2 row 3). Optionally compressed
        # to a narrower wire dtype (parallel.collective_dtype): bfloat16
        # casts; int8 runs the block-scaled reduce, with the per-replica
        # quantization error carried in state.collective_residual when
        # error feedback is on.
        new_residual = None
        if self._use_residual:
            residual = jax.tree.map(lambda r: r[0], state.collective_residual)
            grads, new_res = coll.allreduce_gradients_ef(
                grads, residual, DATA_AXES, block_size=block)
            new_residual = jax.tree.map(lambda r: r[None], new_res)
        else:
            grads = coll.allreduce_gradients(
                grads, DATA_AXES,
                compute_dtype=jnp.dtype(wire) if wire else None,
                accumulate_f32=(
                    self.config.train.grad_allreduce_accum == "float32"),
                block_size=block,
            )
        full_grad_norm = None
        if self._explicit_fsdp:
            # The update runs on shards; grad_norm must come from the FULL
            # mean gradients, so take it before slicing.
            full_grad_norm = coll.global_norm(grads)
            fsdp_n = coll.axis_size("fsdp")
            idx = coll.axis_index("fsdp")

            def shard(g, dim):
                if dim < 0:
                    return g
                size = g.shape[dim] // fsdp_n
                return jax.lax.dynamic_slice_in_dim(
                    g, idx * size, size, axis=dim)

            grads = jax.tree.map(shard, grads, self._fsdp_dims)
        metrics = coll.pmean(metrics, DATA_AXES)
        if self._has_bn(state):
            # Running stats were updated from per/cross-replica batch stats;
            # average them so replicas stay consistent.
            new_model_state = dict(new_model_state)
            new_model_state["batch_stats"] = coll.pmean(
                new_model_state["batch_stats"], DATA_AXES
            )
        new_state, metrics = self._apply_updates(state, grads, metrics,
                                                 new_model_state)
        if full_grad_norm is not None:
            metrics["grad_norm"] = full_grad_norm
        if new_residual is not None:
            new_state = new_state.replace(collective_residual=new_residual)
        return new_state, metrics

    def make_train_step(self, sample_batch: Any) -> Callable:
        specs = self.state_specs(sample_batch)
        state_sh = shd.specs_to_shardings(specs, self.mesh)
        batch_sh = jax.tree.map(
            lambda _: NamedSharding(self.mesh, batch_spec(self.mesh)), sample_batch
        )
        if not self.shard_map_mode:
            return jax.jit(
                self._train_step_jit,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            )

        state_P = specs
        batch_P = jax.tree.map(lambda _: batch_spec(self.mesh), sample_batch)
        # check_vma=False: with vma tracking on, jax's autodiff inserts the
        # cross-replica psum for replicated params itself and our explicit
        # pmean would double-count. The explicit-collective mode exists to
        # mirror the reference's SyncReplicasOptimizer pipeline, so we keep
        # the collectives visible and own them.
        mapped = jax.shard_map(
            self._train_step_replica,
            mesh=self.mesh,
            in_specs=(state_P, batch_P),
            out_specs=(state_P, P()),
            check_vma=False,
        )
        return jax.jit(
            mapped,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )

    # -------------------------------------------------------- eval step --
    def _eval_step(self, state: TrainState, batch: Any):
        """Weighted metric SUMS for one eval batch.

        Returns ``{*_sum, weight_sum}``; the eval loop accumulates and
        divides, making a full pass over a padded finite eval stream the
        EXACT metric over the real examples (SURVEY.md §3.4). Batches
        without a ``weight`` key (infinite synthetic streams) weight every
        example 1.0, which reproduces the plain batched mean.
        """
        has_bn = self._has_bn(state)
        use_ema = (
            self.config.optimizer.ema_decay > 0
            and self.config.train.eval_use_ema
            and jax.tree.leaves(state.ema_params)
        )
        variables = {"params": state.ema_params if use_ema else state.params}
        if has_bn:
            variables["batch_stats"] = state.batch_stats
        inputs = model_inputs(self.task, batch)
        with self.mesh:  # arm activation sharding hints (see train step)
            logits = self.model.apply(variables, *inputs, train=False)
        if isinstance(logits, dict):  # MoE aux loss / Inception aux head
            logits = logits["logits"]
        if self.task in ("mlm", "causal_lm"):
            weight = batch.get(
                "weight", jnp.ones(batch["targets"].shape[0], jnp.float32)
            )
            return losses.mlm_metrics_sums(logits, batch["targets"], weight)
        weight = batch.get(
            "weight", jnp.ones(batch["label"].shape[0], jnp.float32)
        )
        return losses.classification_metrics_sums(logits, batch["label"], weight)

    def make_eval_step(self, sample_batch: Any) -> Callable:
        specs = self.state_specs(sample_batch)
        state_sh = shd.specs_to_shardings(specs, self.mesh)
        batch_sh = jax.tree.map(
            lambda _: NamedSharding(self.mesh, batch_spec(self.mesh)), sample_batch
        )
        return jax.jit(
            self._eval_step, in_shardings=(state_sh, batch_sh), out_shardings=None
        )
