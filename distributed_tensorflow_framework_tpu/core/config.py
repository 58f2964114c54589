"""Typed configuration system.

Replaces the reference's ``tf.app.flags`` global FLAGS (SURVEY.md §2 row 11:
cluster topology, model, dataset paths, hparams all as process-global flags)
with typed dataclasses loaded from YAML plus ``key=value`` CLI overrides.

Unlike the reference there are no cluster-topology flags (``--ps_hosts``,
``--worker_hosts``, ``--job_name``, ``--task_index``): the SPMD runtime
discovers the slice topology from JAX, and the only topology knob the user
holds is the logical mesh shape (`MeshConfig`).
"""

from __future__ import annotations

import dataclasses
import logging
import pathlib
import re
from dataclasses import dataclass, field
from typing import Any

import yaml

log = logging.getLogger(__name__)


def _fields(cls) -> dict[str, dataclasses.Field]:
    return {f.name: f for f in dataclasses.fields(cls)}


def _build(cls, data: dict[str, Any]):
    """Construct a (possibly nested) config dataclass from a plain dict."""
    if data is None:
        data = {}
    kwargs = {}
    fields = _fields(cls)
    unknown = set(data) - set(fields)
    if unknown:
        raise ValueError(
            f"Unknown key(s) {sorted(unknown)} for {cls.__name__}; "
            f"valid keys: {sorted(fields)}"
        )
    types = getattr(cls, "__field_types__", {})
    for name, f in fields.items():
        if name not in data:
            continue
        value = data[name]
        target = _dataclass_in(types.get(name, f.type))
        if target is not None and isinstance(value, dict):
            value = _build(target, value)
        kwargs[name] = value
    return cls(**kwargs)


def _dataclass_in(tp) -> type | None:
    """Return the dataclass inside ``tp`` (handles Optional[...] unions)."""
    import typing

    if dataclasses.is_dataclass(tp):
        return tp
    for arg in typing.get_args(tp):
        if dataclasses.is_dataclass(arg):
            return arg
    return None


def _annotate_types(cls):
    """Resolve concrete field types once (handles string annotations)."""
    import typing

    cls.__field_types__ = typing.get_type_hints(cls)
    return cls


def config_dataclass(cls):
    return _annotate_types(dataclass(cls))


@config_dataclass
class MeshConfig:
    """Logical device mesh. Axis sizes of 1 collapse that axis.

    ``data`` is the data-parallel axis (the reference's worker-replica count,
    SURVEY.md §2 row 3); ``fsdp`` shards params/optimizer state ZeRO-style;
    ``expert`` is expert parallelism (MoE experts sharded, all_to_all
    dispatch — the batch is also sharded over it, so it doubles as extra
    data parallelism for the dense params); ``pipe`` is pipeline parallelism
    (layer stages, microbatched); ``model`` is tensor parallelism; ``seq``
    is sequence/context parallelism for ring attention. -1 for ``data``
    means "all remaining devices".
    """

    data: int = -1
    fsdp: int = 1
    expert: int = 1
    pipe: int = 1
    model: int = 1
    seq: int = 1

    def axis_sizes(self) -> dict[str, int]:
        return {"data": self.data, "fsdp": self.fsdp, "expert": self.expert,
                "pipe": self.pipe, "model": self.model, "seq": self.seq}


@config_dataclass
class OptimizerConfig:
    name: str = "sgd_momentum"  # sgd_momentum | adam | adamw | lars | rmsprop
    learning_rate: float = 0.1
    warmup_steps: int = 0
    schedule: str = "constant"  # constant | cosine | staircase | linear
    # staircase: multiply lr by `decay_factor` at each boundary (in steps).
    boundaries: list[int] = field(default_factory=list)
    decay_factor: float = 0.1
    momentum: float = 0.9
    nesterov: bool = False
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    # RMSProp second-moment decay (the reference's Inception recipe family
    # is RMSProp decay=0.9, momentum=0.9, eps=1.0 — set eps accordingly
    # when using name=rmsprop for recipe fidelity).
    rms_decay: float = 0.9
    weight_decay: float = 0.0
    grad_clip_norm: float = 0.0  # 0 disables
    # Exponential moving average of params (0 disables). Uses the
    # tf.train.ExponentialMovingAverage warmup schedule
    # min(decay, (1+step)/(10+step)); eval reads the averaged params
    # unless train.eval_use_ema is false.
    ema_decay: float = 0.0
    # ZeRO-1/2 cross-replica weight-update sharding (PAPERS.md "Automatic
    # Cross-Replica Sharding of Weight Update"). Params stay REPLICATED
    # (pure-DP reference semantics); the optimizer state and the weight
    # update itself are sharded 1/n over the data(+fsdp) replicas:
    #   "off"       — replicated optimizer state, monolithic all-reduce.
    #   "jit"       — passive jit-spec sharding of the slot tensors over
    #                 the fsdp axis; XLA inserts the collectives. Requires
    #                 mesh.fsdp > 1 and train.spmd_mode="jit".
    #   "shard_map" — explicit ZeRO path (parallel/zero.py): bucketed
    #                 reduce-scatter of grads in reverse-layer order
    #                 (overlaps backward compute), per-replica optax
    #                 update on 1/n of the flattened weights, updates
    #                 all-gathered (wire format via
    #                 parallel.collective_dtype). Requires
    #                 train.spmd_mode="shard_map".
    zero_sharding: str = "off"  # off | jit | shard_map
    # Bucket size for the shard_map reduce-scatter, in MiB of f32
    # gradient. Smaller buckets → more collectives hidden behind backward
    # (overlap_frac_est = (B-1)/B) but more per-collective latency.
    zero_bucket_mb: float = 4.0
    # DEPRECATED — use zero_sharding="jit". Folded in by load_config with
    # a warning (conflicting settings of both are rejected).
    shard_opt_state: bool = False


@config_dataclass
class ModelConfig:
    name: str = "lenet5"  # lenet5 | resnet50 | inception_v3 | bert
    num_classes: int = 10
    # BatchNorm statistic scope: "global" computes stats over the full
    # (sharded) batch — XLA inserts the cross-replica reduction; "per_replica"
    # matches the reference's per-GPU BN via shard_map (SURVEY.md §7 hard
    # part 2).
    bn_cross_replica: bool = True
    dtype: str = "bfloat16"     # compute dtype; params stay float32
    # ResNet ImageNet-stem only: space-to-depth input transform — replaces
    # the 7×7/s2 conv with an exactly-equivalent 4×4/s1 conv on a
    # (H/2,W/2,12) regrouped input. Avoids the MXU-wasting 3-channel conv
    # and the full-res activation's HBM round-trip (the step is
    # HBM-BW-bound; see PERF_NOTES.md). Changes stem param shape, so
    # checkpoints are not interchangeable with the conv7 stem.
    space_to_depth_stem: bool = False
    # BERT-family knobs.
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_seq_len: int = 512
    dropout_rate: float = 0.1
    # Attention implementation: "xla" (dot-product, XLA-fused) or
    # "pallas" (fused flash-attention kernel, ops/flash_attention.py) or
    # "ring" (sequence-parallel ring attention over the seq mesh axis).
    attention_impl: str = "xla"
    # Fuse the q/k/v projections into one (H, 3H) GEMM (bert models):
    # fewer, fatter MXU calls on a GEMM-fragmentation-bound step;
    # column-block-exact vs the separate projections (parity-tested).
    # Changes the parameter tree (qkv/kernel replaces query|key|value), so
    # checkpoints are not interchangeable across this flag.
    fused_qkv: bool = False
    # Mixture-of-Experts (models/moe.py): 0 = dense FFN everywhere; >0 =
    # every `moe_every`-th encoder layer uses an expert-parallel MoE FFN
    # routed top-`expert_topk` with per-group capacity `capacity_factor`.
    num_experts: int = 0
    moe_every: int = 2
    expert_topk: int = 2
    capacity_factor: float = 1.25
    # "sorted" (argsort+gather dispatch, O(B·E·C) tables — the scalable
    # default) or "dense" (one-hot einsum dispatch, the parity reference).
    moe_dispatch: str = "sorted"
    # Router z-loss (ST-MoE): penalizes mean(logsumexp(router logits)^2),
    # shrinking logit magnitudes so routing stays near-uniform early —
    # the measured round-5 failure mode is a seed-dependent router-
    # collapse basin (docs/DISTRIBUTED.md "Operating note"). RELATIVE
    # weight: the trainer multiplies the whole MoE aux output (balance
    # aux + moe_zloss_weight * zloss) by train.moe_aux_weight, so with
    # the 0.01 default, moe_zloss_weight=0.1 lands on ST-MoE's canonical
    # 1e-3 absolute z weight. 0 disables (default — bit-identical to
    # pre-knob behavior).
    moe_zloss_weight: float = 0.0
    # Pipeline parallelism (parallel/pipeline.py): >1 splits the encoder
    # stack into this many stages over the `pipe` mesh axis (must equal the
    # mesh's pipe size) with microbatched GPipe scheduling.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 0  # 0 → defaults to pipeline_stages
    # Stage schedule (parallel/schedule.py):
    #   "gpipe"       — circular fill-drain, backward from autodiff.
    #                   Bubble (S-1)/(M+S-1); activation residency O(M+S).
    #   "1f1b"        — hand-built one-forward-one-backward backward with
    #                   per-stage recompute: same analytic bubble as
    #                   gpipe, activation residency O(S) — the MEMORY
    #                   schedule (buys more microbatches at a fixed
    #                   activation budget, ~one extra forward of
    #                   recompute in the backward pass).
    #   "interleaved" — v virtual stages per device, round-robin layer
    #                   assignment: bubble (S-1)/(v·M+S-1) — the
    #                   THROUGHPUT schedule. Needs microbatches % stages
    #                   == 0 and num_layers % (stages·v) == 0.
    # Default "gpipe": zero behavior change for existing runs; the param
    # tree is schedule-independent, so checkpoints are interchangeable
    # across schedules.
    pipeline_schedule: str = "gpipe"
    # Virtual stages per device for pipeline_schedule="interleaved".
    # 0 → defaults to num_layers // pipeline_stages (one layer per
    # virtual chunk — the maximal bubble cut). Must be 0/1 for the other
    # schedules.
    pipeline_virtual_stages: int = 0
    # Rematerialize transformer encoder layers in the backward pass
    # (jax.checkpoint via nn.remat): trades ~30% more FLOPs for O(layers)
    # less activation memory — the lever for long-context / big-model
    # fits. Supported for the bert models (numerics parity tested); other
    # model families reject it rather than silently ignore it. A decoder
    # layer (models/lfm2.py) keeps, beside its input, its attention
    # kernels' output and logsumexp (O(S·D) a layer) and what its expert
    # layer's routing decided (the float32 logits, the chosen experts
    # and their scores, the sort by expert): the re-run forward pass
    # recomputes everything but the Mosaic forward, the router's
    # product, the top-k, the scores' gather and the sorts;
    # precision.remat_policy: save_nothing is the full re-run.
    remat: bool = False
    # What the remat blocks may keep from the forward pass:
    #   "full"       — save nothing; replay the whole block (max memory
    #                  savings, full recompute cost — measured -13% img/s
    #                  on the HBM-bound ResNet-50 step, PERF_NOTES.md).
    #   "conv_saved" — save conv outputs (jax.ad_checkpoint name tag in
    #                  layers.ConvBN), replay only the BN/ReLU/residual
    #                  tail — near-zero recompute flops for roughly half
    #                  the activation bytes. ResNet only.
    remat_policy: str = "full"
    # Decoder family (models/lfm2.py; names "lfm2*", "smallthinker*",
    # "nemotron*", "laguna*" and "kanana*").
    # Knobs it shares with the BERT family keep their names: vocab_size,
    # hidden_size, num_layers, num_heads, mlp_dim (the dense SwiGLU
    # width), num_experts (the router's width), expert_topk,
    # attention_impl, remat.
    # One mixer kind per layer: "conv" (gated short convolution),
    # "full_attention" (causal grouped-query attention),
    # "sliding_attention" (the same inside a window of sliding_window
    # keys, over sliding_num_heads query heads where that is set) or
    # "latent_attention" (multi-head latent attention, the mla_*
    # settings), each followed by a feed-forward; or a layer of ONE
    # sublayer,
    # x + sublayer(RMSNorm(x)): "mamba2_only" (the Mamba-2 mixer),
    # "attention_only" (causal grouped-query attention) or "experts_only"
    # (the expert feed-forward). Its length must be num_layers.
    layer_types: list[str] = field(default_factory=list)
    # Leading layers with a dense feed-forward; the rest carry experts.
    num_dense_layers: int = 0
    num_kv_heads: int = 0       # 0 = as many as num_heads
    moe_mlp_dim: int = 0        # width of one expert's SwiGLU
    # taps of the short convolution (conv_L_cache), or of a Mamba-2
    # layer's convolution over xBC
    conv_kernel: int = 3
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    # The share of an expert-parallel deployment this process computes:
    # num_experts are divided over expert_groups device groups in
    # contiguous runs, and this is group expert_group. The layer routes
    # over all num_experts and adds its own experts' part of the result;
    # nothing stands in for the other groups. 1 group = the whole layer.
    expert_groups: int = 1
    expert_group: int = 0
    # What tells the family's models apart; every default is LFM2's.
    head_dim: int = 0           # 0 = hidden_size // num_heads
    # Keys a query of a "sliding_attention" layer sees, its own included
    # (i - j < sliding_window).
    sliding_window: int = 0
    # Per layer, 1 where an attention layer rotates its queries and keys
    # (rotary positions) and 0 where it uses no positions at all; empty =
    # every attention layer rotates.
    rope_layout: list[int] = field(default_factory=list)
    qk_norm: bool = True        # RMSNorm over each head of q and of k
    # Query heads of a "sliding_attention" layer, over the same
    # num_kv_heads; 0 = num_heads, like every other attention layer.
    sliding_num_heads: int = 0
    # The rotary rule (half rotation at rope_theta over the whole head by
    # default). rope_fraction: the share of each head's dims, its first,
    # that rotates (the rest pass unrotated). rope_yarn_factor > 0: YaRN
    # frequencies (arXiv:2309.00071): those that turn fewer than
    # rope_yarn_beta_slow times in rope_yarn_original_len positions are
    # divided by the factor, those that turn more than
    # rope_yarn_beta_fast times are kept, a linear ramp over the rotated
    # dims between. rope_attention_factor multiplies cos and sin.
    rope_fraction: float = 1.0
    rope_yarn_factor: float = 0.0
    rope_yarn_original_len: int = 0
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    # Which dims rotate together: "half" (dim i with i + rotated / 2) or
    # "interleaved" (dims 2i and 2i + 1; rope_interleave), pair i at
    # frequency theta^(-2i / rotated) either way.
    rope_pairs: str = "half"
    # A rule of their own for the "sliding_attention" layers: plain
    # frequencies at sliding_rope_theta over the first
    # sliding_rope_fraction of each head. 0 = they share the rule above.
    sliding_rope_theta: float = 0.0
    sliding_rope_fraction: float = 1.0
    # "per_head": every attention layer multiplies each query head's
    # output by sigmoid(u W_g), one scalar a head and token read from the
    # layer's normed input u in float32, before the out-projection.
    attention_gate: str = "none"
    tie_embeddings: bool = True  # false: an output matrix of its own
    # Std of the token embedding's normal init. A router that reads the
    # un-normed stream (router_input: stream) sees what the stream holds
    # at init: under 0.02 that is attention's output, whose part common to
    # every token passes each attention layer whole while the tokens' own
    # parts average away, and the routing collapses onto a few experts
    # (PERF.md §6, PR 30); unit-variance embeddings keep the tokens' own
    # content on top.
    embed_init_std: float = 0.02
    # What the router reads: "ffn_norm" (the normed tensor the experts
    # read) or "stream" (the residual stream as it enters the layer,
    # before the mixer and its norm).
    router_input: str = "ffn_norm"
    # "sigmoid_bias": sigmoid scores, top-k of score + selection bias,
    # weights normalised over the chosen; "softmax_topk": top-k of the
    # logits, softmax over the chosen, no bias.
    router_score: str = "sigmoid_bias"
    # silu (SwiGLU) | relu (ReGLU): gated, W2(act(W1 x) * W3 x);
    # relu2: not gated, W2 relu(W1 x)^2
    expert_activation: str = "silu"
    # One tensor-parallel share, taken wherever a layer has something to
    # split: tensor_groups chips divide it in contiguous runs and this is
    # chip tensor_group. An attention layer of any kind holds its query
    # heads / tensor_groups (num_heads, or sliding_num_heads in a window
    # layer) with the key/value heads they read, a Mamba-2 layer
    # mamba_num_heads / tensor_groups heads with mamba_groups /
    # tensor_groups B/C groups, a dense feed-forward mlp_dim /
    # tensor_groups and a shared expert moe_shared_dim / tensor_groups of
    # their hidden units (a unit, gated or not, is elementwise in them);
    # each adds its heads' (units') part of the out-projection's sum and
    # nothing stands in for the others. Shares are whole groups or the
    # model is refused; so is a "conv" layer, whose gated convolution has
    # no such split.
    tensor_groups: int = 1
    tensor_group: int = 0
    # Mamba-2 layers ("mamba2_only"): heads of mamba_head_dim channels,
    # mamba_groups B/C groups of ssm_state_size state dims (n_groups),
    # the recurrence computed in chunks of mamba_chunk tokens.
    mamba_num_heads: int = 0
    mamba_head_dim: int = 64
    mamba_groups: int = 1
    ssm_state_size: int = 128
    mamba_chunk: int = 128
    # Experts in a latent: they read x W_a (hidden -> moe_latent_dim) and
    # their weighted sum goes back through W_b; the router still reads
    # the stream. 0: the experts read the stream itself.
    moe_latent_dim: int = 0
    # Width of a shared expert beside the routed ones, on what they read,
    # in every layer that has experts; 0: none. It is a unit of the
    # experts' own form: gated, V_d(act(V_g x) * V_u x), under a gated
    # expert_activation, and V2 relu(V1 x)^2 under relu2.
    moe_shared_dim: int = 0
    routed_scaling: float = 1.0   # on the routed experts' weights
    # Std of the normal init of every residual branch's output projection
    # (a Mamba-2 layer's out_proj, attention's attn_out, a dense
    # feed-forward's mlp_out, an expert layer's latent_out and its shared
    # expert's down; not a "conv" layer's, which is refused): the scaled
    # output init of the GPT-2 / Megatron-LM recipes,
    # 0.02 / sqrt(2 x layers). 0: the fan-in rule of every other
    # projection. With unit-variance embeddings it keeps each token's own
    # content on top of the stream at a random init, so a router behind a
    # norm spreads its tokens evenly (PERF.md section 6, PR 32).
    out_proj_init_std: float = 0.0
    # A "latent_attention" layer (multi-head latent attention, no query
    # latent): keys and values come out of a latent of mla_kv_rank dims
    # under its own RMSNorm, each of num_heads query/key heads is
    # mla_nope_dim dims without positions and mla_rope_dim rotated ones
    # (one rotated key a token, shared by every head), each value head
    # mla_v_dim dims. 0: unset, and a latent layer is refused.
    mla_kv_rank: int = 0
    mla_nope_dim: int = 0
    mla_rope_dim: int = 0
    mla_v_dim: int = 0


@config_dataclass
class DataConfig:
    name: str = "synthetic_images"  # mnist | cifar10 | imagenet | text_mlm | synthetic_*
    data_dir: str = ""
    # Global batch size across all replicas (the reference exposed per-worker
    # batch; global is the SPMD-native unit — per-host share is derived).
    global_batch_size: int = 64
    image_size: int = 28
    channels: int = 1
    # Label range of the records; must not exceed the model head
    # (load_config cross-checks, and every reader path validates per
    # batch). load_config defaults this to 1000 for name="imagenet".
    num_classes: int = 10
    # Dtype images are fed to the device in. "bfloat16" halves infeed HBM
    # traffic — the ResNet-50 train step is HBM-bandwidth-bound on v5e
    # (~95% of peak BW at bs 256/chip; see bench.py), so this is a real
    # throughput lever. Augmentation math stays float32 host-side.
    image_dtype: str = "float32"
    shuffle_buffer: int = 10_000
    prefetch: int = 2
    # Run the host pipeline pull + device transfer on a producer thread so
    # decode/augment work overlaps device steps (data/infeed.py). The
    # batch/snapshot pairing and order are identical to the synchronous
    # prefetcher; disable when debugging host-side pipeline errors (they
    # surface with a cleaner stack synchronously). NOTE: not implicated
    # in the XLA:CPU rendezvous freezes on oversubscribed virtual-device
    # hosts — an 8-device MoE run froze with async_infeed=false too; see
    # core/platform.py for that failure class and the bounded-terminate +
    # checkpoint-restart mitigation.
    async_infeed: bool = True
    seed: int = 0
    # text / MLM
    seq_len: int = 128
    mask_prob: float = 0.15
    vocab_size: int = 30522  # must match ModelConfig.vocab_size
    # Sequence packing (MLM train path): each batch consumes pack_factor
    # raw record batches and lays the documents end-to-end with per-row
    # segment ids (block-diagonal attention, data/packing.pack_documents)
    # — more useful tokens per step when documents are shorter than
    # seq_len. 1 = off. Train-only; eval streams stay unpacked.
    pack_factor: int = 1
    # native C++ record reader (ops/native) when available
    use_native_reader: bool = False
    # How each host slices the shared epoch permutation (data/shard.py).
    # "block": host h takes the h-th contiguous host-batch rows of every
    # global batch — the consumed prefix is host-count-INVARIANT, so a
    # resumed data state survives an N→M elastic refit with no sample
    # replayed or dropped (docs/RESILIENCE.md "Exactly-once data").
    # "stride": the legacy perm[h::P] layout — kept for bit-exact
    # continuation of old runs; NOT repartitionable across a host-count
    # change. Single-process runs are identical under both.
    shard_mode: str = "block"
    # Restore-time data-state gate (data/shard.check_restore_data): when
    # True, a restored iterator state that fails its manifest sha256 or
    # hits a host-count change it cannot repartition raises DataShardError.
    # False downgrades both to warnings and resumes anyway (samples may
    # replay or drop) — the escape hatch for salvaging a run.
    resume_strict: bool = True


@config_dataclass
class CheckpointConfig:
    directory: str = ""
    save_interval_steps: int = 1000
    max_to_keep: int = 3
    # Commit checkpoints (orbax write + manifest hash + fsync) on a
    # background saver thread (ckpt/async_saver.py): the step loop pays
    # only a device→host snapshot of the train state. False = fully
    # synchronous save on the training thread — required for multi-host
    # sharded state (the snapshot path assumes fully-addressable arrays)
    # and useful when debugging save failures (clean stacks). Either way
    # the manifest commit record and crash semantics are identical.
    async_save: bool = True
    restore: bool = True  # auto-restore latest on startup (MonitoredTrainingSession contract)
    # Re-hash every file against the step's integrity manifest before
    # restoring (ckpt/manifest.py); corrupt/torn steps are quarantined with
    # automatic fallback to the newest verified older step. Disabling skips
    # the hashing (huge checkpoints on trusted storage) but still requires
    # the manifest commit record, so torn SAVES are caught either way.
    verify_restore: bool = True
    # Restore a SPECIFIC saved step instead of the latest (-1 = latest) —
    # the Saver's restore-any-checkpoint capability, e.g. to branch an
    # experiment off an earlier snapshot. Fails loudly if the step was
    # never saved (or was GC'd by max_to_keep).
    restore_step: int = -1
    # Allow restoring a checkpoint saved under a DIFFERENT mesh topology:
    # partition specs are re-derived against the current mesh and the
    # state is resharded on load (ckpt/reshard.py, docs/RESILIENCE.md
    # "losing a slice"). Off by default so an accidental mesh.* change
    # fails fast with MeshTopologyError instead of silently rescattering
    # a production run; the elastic supervisor turns it on when it
    # shrinks/grows the mesh (scripts/train_resilient.py, rc 84).
    allow_reshard: bool = False


@config_dataclass
class TrainConfig:
    total_steps: int = 100
    log_interval: int = 10
    # Backpressure on async step dispatch: at most this many steps may be
    # in flight on the device queue; the host then syncs on the OLDEST
    # pending step (a scalar device_get) before
    # dispatching the next. Without a bound the host runs ahead by a full
    # log_interval (observed: 250 queued multi-device programs, 35 s
    # metric drains, and amplified XLA:CPU collective-rendezvous freezes
    # on oversubscribed virtual-device hosts). Default 8: measured safe
    # over 7000 MoE-mesh steps, while depth 64 froze the dp+pp CPU mesh
    # at its first cross-data all-reduce 3/3 times (64 queued pipelined
    # programs starve the 1-thread XLA:CPU pool's rendezvous — round-5
    # RESULTS.md). Deep queues buy nothing on real TPU either (the
    # device runs one program at a time; ~2 in flight already hides
    # host latency). 0 = unbounded.
    dispatch_ahead: int = 8
    eval_interval: int = 0        # 0 disables mid-training eval
    # Batches per MID-TRAINING eval firing, and the fallback length for
    # infinite (synthetic) eval streams. The final eval and --eval-only
    # always walk the FULL validation set when the stream is finite
    # (exact-eval contract); 0 disables the final eval entirely.
    eval_steps: int = 10
    seed: int = 42
    # "jit" = pjit-style automatic partitioning; "shard_map" = explicit
    # per-replica code with hand-placed collectives (the closer analogue of
    # the reference's SyncReplicasOptimizer + NCCL pipeline).
    spmd_mode: str = "jit"
    # DEPRECATED — use parallel.collective_dtype, which covers the fsdp
    # gather/scatter wires too. load_config maps this onto it with a
    # warning and rejects conflicting settings of both.
    grad_allreduce_dtype: str = ""
    # Accumulation for the compressed all-reduce: "float32" (default)
    # reduce-scatters in f32 (exact adds, 6/8 of f32 bytes, one
    # n-independent rounding — the accuracy-safe choice for n≫8 DCN);
    # "wire" reduces in the wire dtype itself (4/8 of f32 bytes, log2(n)
    # narrow adds). See parallel/collectives.allreduce_gradients.
    grad_allreduce_accum: str = "float32"
    nan_guard: bool = True
    label_smoothing: float = 0.0
    eval_use_ema: bool = True  # only meaningful with optimizer.ema_decay>0
    # Weight of the MoE load-balancing aux loss (Switch Transformer uses 0.01).
    moe_aux_weight: float = 0.01
    # Gradient accumulation: split each global batch into this many
    # microbatches, scan fwd/bwd accumulating grads, apply once. The
    # accumulated gradient equals the full-batch gradient exactly. BN
    # caveat: running stats are EMA-updated once per *microbatch* (k
    # updates per optimizer step from microbatch statistics), so the
    # effective BN momentum is momentum**k and BN-model trajectories
    # differ slightly from the accum=1 step — only BN-free models get
    # bitwise full-batch parity (tests/test_grad_accum.py).
    grad_accum_steps: int = 1
    # XPlane trace capture over steps [profile_start, profile_stop);
    # 0/0 disables (SURVEY.md §5 tracing).
    profile_start: int = 0
    profile_stop: int = 0
    # Goodput ledger (core/goodput.py): cumulative KIND_GOODPUT snapshots
    # at most this often (checked at metric-fetch steps; the final rollup
    # always fires). 0 emits at every fetch.
    goodput_interval_s: float = 30.0
    # HBM sampling (core/memstats.py): periodic KIND_MEMORY
    # device.memory_stats() samples, same cadence contract.
    memory_interval_s: float = 60.0
    # Also capture compiled.memory_analysis() of the train step (one
    # extra lowering+compile when profiling isn't already doing one —
    # that cost is why it defaults off; the profile-window path captures
    # it for free).
    memory_analysis: bool = False


@config_dataclass
class ResilienceConfig:
    """In-process recovery ladder (train/anomaly.py, docs/RESILIENCE.md).

    The ladder runs at metric-fetch steps (train.log_interval cadence —
    metrics are already on host there, so detection costs no extra device
    syncs): classify the step, and on an anomaly restore the last good
    in-memory snapshot, skip the offending data, and resume. Only after
    ``max_rollbacks`` consecutive failed recoveries does the process
    escalate to the supervisor with ``ANOMALY_ESCALATION_RC``.
    """

    # Master switch for detection + in-memory rollback. Off, anomalies go
    # straight to the PR 2 path: NaNGuardHook abort → supervisor relaunch.
    rollback: bool = True
    # Device→host state snapshot cadence/retention for the rollback ring.
    # Snapshots are taken at CLEAN metric-fetch steps, so the effective
    # cadence is max(snapshot_interval_steps, train.log_interval).
    snapshot_interval_steps: int = 100
    snapshot_depth: int = 2
    # Consecutive rollbacks (no clean fetch between them) before the
    # ladder declares the anomaly persistent and escalates.
    max_rollbacks: int = 3
    # Loss-spike detector: flag when the loss sits more than this many
    # EWMA standard deviations above its running mean (0 disables). The
    # EWMA needs min_observations clean fetches before it can fire.
    loss_spike_zscore: float = 10.0
    loss_ewma_beta: float = 0.95
    min_observations: int = 5
    # Hard grad-norm ceiling (0 disables): a finite but exploding
    # grad_norm metric is anomalous even before the loss moves.
    grad_norm_max: float = 0.0
    # After a rollback, linearly re-warm the learning rate over this many
    # steps (0 disables). Costs one train-step recompile per rollback —
    # still far cheaper than the relaunch+restore+recompile it replaces.
    lr_rewarmup_steps: int = 0
    # Infeed watchdog (data/infeed.py): deadline on each next(batch) pull
    # in seconds (0 disables). On InfeedStallError the loop retries with
    # exponential backoff up to infeed_retries times before escalating.
    infeed_deadline_s: float = 0.0
    infeed_retries: int = 3
    infeed_backoff_s: float = 0.5


@config_dataclass
class ClusterConfig:
    """Gang supervision knobs for the multi-process runtime
    (core/cluster.py, scripts/train_cluster.py, docs/RESILIENCE.md
    "Gang supervision"). All of these matter only when
    jax.process_count() > 1; single-process runs ignore them.
    """

    # After a gang (re)launch, a worker that produces no heartbeat within
    # this window while at least one peer has → dropped from the gang and
    # the mesh is refit to the survivors (gang-level rc-84, no attempt
    # consumed). 0 disables the rejoin watchdog: the supervisor waits
    # forever (or until the heartbeat-staleness watchdog fires).
    rejoin_timeout_s: float = 0.0
    # Coordinator-led exit barrier: at the end of training every worker
    # blocks until the chief's manifest commit record for the final step
    # is durable, polling every exit_barrier_poll_s, raising
    # ExitBarrierTimeoutError past exit_barrier_timeout_s.
    exit_barrier_timeout_s: float = 120.0
    exit_barrier_poll_s: float = 0.5
    # Per-worker heartbeat cadence (heartbeat-p<i>.json) — the supervisor's
    # staleness watchdog budget must exceed this.
    heartbeat_interval_s: float = 10.0


@config_dataclass
class ParallelConfig:
    """Collective wire-format knobs (parallel/collectives.py,
    docs/PERFORMANCE.md "Quantized collectives")."""

    # Wire dtype for the explicit collectives (shard_map mode only):
    #   ""         — full-precision wires (bit-identical to pre-knob runs);
    #   "bfloat16" — narrow the gradient all-reduce and fsdp gathers to
    #                bf16 (f32 accumulation per train.grad_allreduce_accum);
    #   "int8"     — EQuARX block-scaled int8 (per-block max-abs scales,
    #                f32 accumulation of dequantized partials, ~3.9× fewer
    #                wire bytes than f32) with a per-leaf error-feedback
    #                residual carried in the training state.
    # Subsumes the deprecated train.grad_allreduce_dtype, which mapped the
    # same compression onto the gradient all-reduce only.
    collective_dtype: str = ""
    # Elements per quantization block for collective_dtype="int8". One f32
    # scale rides the wire per block (~1.6% overhead at 256). Smaller
    # blocks track magnitude variation more tightly at more overhead.
    collective_block_size: int = 256
    # Carry the int8 compression error forward in a per-leaf residual
    # (TrainState.collective_residual) and re-inject it into the next
    # step's gradients — compensated, not accumulated. Disable only for
    # A/B measurement of the raw quantization error.
    error_feedback: bool = True


@config_dataclass
class PrecisionConfig:
    """Memory-traffic reduction pack (docs/PERFORMANCE.md "Flipping the
    bound"): three composable levers against the HBM roofline, each
    verifiable on the CPU mesh via the graftcheck trace/HLO audits."""

    # Activation/compute dtype policy threaded through the model zoo:
    #   ""     — defer to model.dtype (bit-identical to pre-knob runs);
    #   "f32"  — force f32 compute everywhere (the A/B control arm);
    #   "bf16" — bf16 compute casts at module boundaries with f32 master
    #            params, f32 logits/loss head preserved (the
    #            jaxpr-f32-upcast pass audits that only the justified
    #            head widens back up).
    activation_dtype: str = ""
    # Forward-matmul operand quantization for the dense/conv paths
    # (models/layers.py): "" = matmuls run at the activation dtype;
    # "int8" = block-scaled int8 operands (the parallel/quantization.py
    # EQuARX codecs, DEFAULT_BLOCK_SIZE elements per f32 scale) with s32
    # MXU accumulation and per-block f32 rescale. Classifier/logits
    # heads stay full-precision. On CPU this is bit-exact emulation of
    # the TPU int8 MXU path; error is bounded per element by the same
    # maxabs/254 contract the collective codecs pin.
    matmul_dtype: str = ""
    # Fuse the optax apply into the backward's bucketed reverse-layer
    # walk (parallel/zero.py fused_update_walk): each param shard is
    # read-modified-written once while hot instead of a separate
    # whole-tree optimizer pass re-reading every parameter. Requires
    # optimizer.zero_sharding="shard_map" (the walk IS the bucketed
    # reduce-scatter / shard-update / update-all-gather path); composes
    # with parallel.collective_dtype (int8 + error feedback) and
    # train.grad_accum_steps. Optimizer slots are stored per bucket
    # (tuple of per-bucket optax states) — same bytes, different
    # grouping; toggling across a resume is rejected like zero_sharding.
    fused_update: bool = False
    # Selective rematerialization policy mapped onto
    # jax.checkpoint_policies for the remat-capable models and the
    # pipeline stages:
    #   "none"          — defer to model.remat/model.remat_policy (a
    #                     decoder layer then keeps its attention
    #                     kernels' output and logsumexp and its expert
    #                     layer's routing, no more);
    #   "dots_saveable" — save matmul outputs, replay the cheap
    #                     elementwise tail (recompute ≈ free, roughly
    #                     half the activation bytes);
    #   "save_nothing"  — save only block inputs, replay everything,
    #                     the attention kernels' forward and the
    #                     experts' routing included (max memory
    #                     savings, max recompute — the
    #                     long-context fit lever).
    # Needs model.remat=true (pipeline stages excepted) and conflicts
    # with resnet's model.remat_policy="conv_saved" spelling.
    remat_policy: str = "none"


@config_dataclass
class ServeConfig:
    """Standing batched-inference engine (serve/, docs/SERVING.md).

    The serving mesh is DATA-PARALLEL ONLY by design: a serving replica is
    the deployment unit and params are replicated across it (multi-stage
    pipelined serving is the 1F1B slot-table follow-up, ROADMAP item 3).
    """

    # Frozen artifact directory written by cli/export.py (serve/export.py).
    artifact_dir: str = ""
    # HTTP front end (serve/server.py). port=0 binds an ephemeral port
    # (tests / local probing); cli/serve.py writes the resolved endpoint
    # to <log_dir>/endpoint.json either way.
    host: str = "127.0.0.1"
    port: int = 8000
    # Devices in the serving mesh (-1 = all visible). Unlike mesh.data
    # this may be SMALLER than the visible device count — serving takes
    # the first `data` devices, so a training-mesh checkpoint restores
    # onto a 1-device engine on an 8-device host.
    data: int = 1
    # Dynamic batching admission: close a batch at max_batch_size rows,
    # or max_wait_ms after the FIRST queued request arrived — the
    # latency/fill tradeoff dial.
    max_batch_size: int = 8
    max_wait_ms: float = 5.0
    # Padding buckets for variable-length (MLM) requests: ascending seq
    # lengths a batch is padded up to ([] = one bucket at the model's
    # max_seq_len). Together with the power-of-two row buckets this
    # bounds XLA recompiles to len(seq_buckets) x len(row buckets).
    seq_buckets: list[int] = field(default_factory=list)
    # Admission bound on queued requests: beyond this depth submit()
    # fails fast (HTTP 503) instead of growing latency without bound.
    queue_capacity: int = 1024
    # Export-side: freeze the EMA params when the checkpoint carries them
    # (matches the trainer's eval_use_ema eval contract).
    use_ema: bool = True
    # Gate for restoring a TRAINING-mesh checkpoint onto the serving
    # mesh. Off, a topology mismatch raises the typed MeshTopologyError
    # naming this knob — the same deliberate gate as
    # checkpoint.allow_reshard, scoped to the serve path.
    allow_reshard: bool = False
    # Graceful SIGTERM drain budget (mirrors the supervisor's preemption
    # contract, core/supervision.py): stop admitting, finish every
    # in-flight request within this budget, flush telemetry, exit 0.
    drain_timeout_s: float = 30.0
    # Cadence of the KIND_SERVE_QUEUE / KIND_SERVE_LATENCY gauge events.
    report_interval_s: float = 10.0
    # Telemetry logdir ("" = <artifact_dir>/serve_logs).
    log_dir: str = ""

    # ---- Fleet router (serve/fleet.py, cli/fleet.py) ----
    # Replica engines the router fronts (each a cli/serve.py subprocess).
    fleet_replicas: int = 3
    # End-to-end deadline for one proxied /predict, spanning every retry.
    fleet_deadline_s: float = 30.0
    # Per-attempt cap (the hedge window): an attempt that has not
    # answered within this budget is abandoned and the request re-issued
    # on a DIFFERENT replica while deadline budget remains.
    fleet_attempt_timeout_s: float = 10.0
    # Bounded retry count after the first attempt; each retry lands on a
    # different replica (POST /predict is idempotent — POST /reload and
    # anything else is proxied at most once).
    fleet_retries: int = 2
    # Backoff between retry attempts (doubles per attempt).
    fleet_retry_backoff_ms: float = 25.0
    # Consecutive proxy/probe failures before a replica is ejected into
    # the circuit-breaker probing state.
    fleet_eject_failures: int = 3
    # A replica whose last good /healthz is older than this is ejected
    # (stale health = not routable, even if the TCP port still accepts).
    fleet_healthz_stale_s: float = 10.0
    # Background prober cadence: healthz polls of admitted replicas,
    # probe/readmit of ejected ones, restart of dead ones.
    fleet_probe_interval_s: float = 0.5
    # Retry-After seconds returned with a 503 when every admitted
    # replica is saturated (shed, never queue unboundedly).
    fleet_shed_retry_after_s: float = 1.0
    # Per-replica restart budget (supervision backoff applies between
    # attempts; the crash-loop breaker can stop earlier).
    fleet_max_restarts: int = 8

    # ---- Autoscaler (serve/autoscale.py, driven from the prober tick) ----
    # Master switch for the closed control loop. Off (default), the fleet
    # stays at the fixed fleet_replicas count — exactly the PR 14 behavior.
    fleet_autoscale: bool = False
    # Hard bounds on live (non-retired, non-given-up) replicas. The
    # autoscaler never drains below min or spawns above max, no matter
    # what the pressure signal says.
    fleet_min_replicas: int = 1
    fleet_max_replicas: int = 8
    # Hysteresis band on fleet pressure (0..1-ish utilization: queued +
    # in-flight + chaos-injected synthetic load over admitted capacity).
    # Scale up at/above the up threshold, down at/below the down
    # threshold; the gap between them is what keeps the loop from
    # flapping on a noisy signal. A shed since the last decision forces
    # pressure to at least the up threshold (shedding IS saturation).
    fleet_scale_up_threshold: float = 0.75
    fleet_scale_down_threshold: float = 0.25
    # Minimum seconds between scaling actions (either direction), so one
    # spike produces a measured ramp instead of a thundering spawn herd.
    fleet_scale_cooldown_s: float = 30.0

    # ---- Multi-tenant QoS at the router (X-DTF-Tenant header) ----
    # Priority class assumed when a request carries no tenant header.
    # Known classes, best-first: "high", "default", "batch".
    tenant_default_class: str = "default"
    # Queue slots per replica reserved per priority step: a class that is
    # p steps below "high" may only claim a replica whose load is under
    # queue_capacity - p * reserve. Under exact-capacity load this sheds
    # batch strictly before default before high. 0 = classless routing.
    tenant_priority_reserve: int = 1
    # Per-tenant token-bucket quota: sustained requests/second and burst
    # capacity. Breach = HTTP 429 with Retry-After at the router, before
    # a replica slot is ever claimed. rps 0.0 = quotas off (default).
    tenant_quota_rps: float = 0.0
    # Bucket depth; 0 = ceil(tenant_quota_rps), minimum 1.
    tenant_quota_burst: int = 0


@config_dataclass
class DecodeConfig:
    """Autoregressive decode engine (serve/decode.py, docs/SERVING.md
    "Autoregressive decode"): prefill/decode split with a paged KV cache
    and continuous batching over the serving mesh."""

    # Master switch: cli/serve.py stands a DecodeEngine next to the
    # single-shot engine (POST /generate) only when enabled AND the
    # artifact's task supports decode (mlm/bert family).
    enabled: bool = False
    # "continuous" admits/retires streams at EVERY token (freed slots
    # refill from the queue mid-flight); "static" joins only at batch
    # boundaries — the whole batch must finish before the next group is
    # admitted. Static exists as the A/B control arm: mixed-length
    # streams idle its slots, which is exactly what continuous fixes.
    scheduler: str = "continuous"
    # Tokens per KV page. Pages are the cache's allocation unit: a
    # stream holds ceil(tokens / page_size) pages and grows one page at
    # a time as decode crosses each boundary.
    page_size: int = 16
    # Physical pages in the pool (page 0 is a reserved scratch page, so
    # num_pages - 1 are allocatable). Total resident-token capacity per
    # replica = (num_pages - 1) * page_size.
    num_pages: int = 64
    # Concurrent streams in the in-flight decode batch. The row ladder
    # is the power-of-two ladder over dp multiples up to this cap, the
    # same discipline as serve.max_batch_size.
    max_streams: int = 8
    # Ceiling on prompt + generated tokens per stream. 0 = the model's
    # max_seq_len (position-embedding capacity bounds it either way).
    max_len: int = 0
    # Server-side cap on requested new tokens per stream.
    max_new_tokens: int = 64
    # Page-table width buckets (pages per stream a table is padded up
    # to, ascending). [] = power-of-two ladder up to ceil(max_len /
    # page_size). Together with the row ladder this bounds decode-step
    # recompiles to |page_buckets| x |row ladder|.
    page_buckets: list = field(default_factory=list)
    # Prompt-length padding buckets for the prefill forward (ascending).
    # [] = one bucket at max_len. Prefill compiles are bounded to
    # |prompt_buckets| x |page_buckets| (prefill always runs one row).
    prompt_buckets: list = field(default_factory=list)
    # KV page storage dtype: "float32" (exact) or "int8" (EQuARX-style
    # block-scaled pages via parallel/quantization.py — ~4x more
    # resident streams per replica, per-token logits pinned within a
    # quantization bound of the f32 path rather than bitwise).
    kv_dtype: str = "float32"
    # Streaming granularity: a stream's tokens are buffered scheduler-
    # side and delivered every this-many decode steps (the FIRST token
    # and the finish summary always flush immediately, so TTFT is
    # unaffected). 1 = deliver every token as it lands. Raising it
    # trades up to (interval - 1) steps of in-stream latency for far
    # fewer consumer wakeups — on hosts where clients, handlers and the
    # scheduler share cores, per-token wakeups steal enough CPU from
    # the step loop to show up in tokens/s.
    stream_interval: int = 1


@config_dataclass
class TraceConfig:
    """Distributed tracing + flight recorder (core/tracing.py,
    docs/OBSERVABILITY.md "Tracing and flight recorder")."""

    # Master switch for span emission (KIND_SPAN events) and the
    # per-process flight recorder. Off, propagation headers/env are
    # still accepted and forwarded but no spans are recorded.
    enabled: bool = True
    # Flight-recorder ring capacity: the last N telemetry events (spans
    # included) kept in memory per process for the flightrec-<pid>.json
    # dump. Sized so a fault's causal neighborhood survives a few
    # hundred ms of peak serve-path event rate.
    ring_size: int = 512
    # Directory for flight-recorder dumps ("" = the DTF_TRACE_DIR env
    # var, falling back to the process's telemetry log directory).
    dump_dir: str = ""


@config_dataclass
class AutotuneConfig:
    """Goodput-driven autotuner (scripts/autotune.py, tools/autotune,
    docs/PERFORMANCE.md "Autotuning")."""

    # Roofline pruning tolerance: a candidate whose PREDICTED rate is
    # more than this fraction below the incumbent's on the binding
    # resource is skipped without spending a run (the prediction is
    # logged + journaled either way). 0 disables the tolerance (any
    # predicted loss prunes); keep it wide enough to absorb model error.
    prune_margin: float = 0.05
    # Cap on RUN (not pruned/resumed) trials per window; 0 = unbounded.
    max_trials: int = 0
    # Trial journal path (dtf-autotune-journal/1 JSONL). "" =
    # <out_dir>/autotune_journal.jsonl. The journal is the resume
    # contract: settled trials never re-run after a killed window.
    journal_path: str = ""
    # Where best_<workload>.yaml + leaderboard.json land.
    out_dir: str = "configs"
    # Regression tolerance written into the leaderboard entry: bench.py
    # flags a headline run this fraction below the pinned incumbent.
    regression_margin: float = 0.05


@config_dataclass
class ExperimentConfig:
    name: str = "experiment"
    mesh: MeshConfig = field(default_factory=MeshConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    eval_data: DataConfig | None = None
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    decode: DecodeConfig = field(default_factory=DecodeConfig)
    trace: TraceConfig = field(default_factory=TraceConfig)
    autotune: AutotuneConfig = field(default_factory=AutotuneConfig)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def _set_by_path(data: dict, dotted: str, value: Any) -> None:
    keys = dotted.split(".")
    node = data
    for k in keys[:-1]:
        node = node.setdefault(k, {})
        if not isinstance(node, dict):
            raise ValueError(f"Override path {dotted!r} collides with non-dict")
    node[keys[-1]] = value


_SCI_NOTATION = re.compile(r"^[+-]?(\d+\.?\d*|\.\d+)[eE][+-]?\d+$")


def _parse_scalar(text: str) -> Any:
    value = yaml.safe_load(text)
    # YAML 1.1 reads "1e-3" (no decimal point) as a *string*; CLI overrides
    # mean numbers when they look like numbers. Coerce ONLY the
    # scientific-notation shapes YAML misses — a bare float() would also
    # swallow intended strings like "nan", "inf" or "1_000".
    if isinstance(value, str) and _SCI_NOTATION.match(value):
        return float(value)
    return value


def load_config(
    path: str | pathlib.Path | None = None,
    overrides: list[str] | None = None,
    base: dict[str, Any] | None = None,
) -> ExperimentConfig:
    """Load an ExperimentConfig from YAML with ``a.b.c=value`` overrides."""
    data: dict[str, Any] = dict(base or {})
    if path is not None:
        with open(path) as fh:
            loaded = yaml.safe_load(fh) or {}
        if not isinstance(loaded, dict):
            raise ValueError(f"Config file {path} must contain a mapping")
        data.update(loaded)
    for item in overrides or []:
        if "=" not in item:
            raise ValueError(f"Override {item!r} must look like key.path=value")
        key, _, raw = item.partition("=")
        _set_by_path(data, key.strip(), _parse_scalar(raw.strip()))
    # ImageNet's label space is 1000 classes; the DataConfig-wide default
    # of 10 predates the label-range guards and would abort real ImageNet
    # data on the first record past label 10. Applied on the raw dict so
    # an explicit num_classes always wins.
    for section in ("data", "eval_data"):
        sec = data.get(section)
        if (isinstance(sec, dict) and sec.get("name") == "imagenet"
                and "num_classes" not in sec):
            sec["num_classes"] = 1000
    cfg = _build(ExperimentConfig, data)
    # Deprecation shim: train.grad_allreduce_dtype predates the quantized
    # collective layer and named only the gradient all-reduce wire; it maps
    # onto parallel.collective_dtype (which also covers the fsdp
    # gather/scatter wires). Conflicting settings of both are rejected
    # rather than silently picking one.
    if cfg.train.grad_allreduce_dtype:
        if (cfg.parallel.collective_dtype
                and cfg.parallel.collective_dtype
                != cfg.train.grad_allreduce_dtype):
            raise ValueError(
                f"train.grad_allreduce_dtype="
                f"{cfg.train.grad_allreduce_dtype!r} conflicts with "
                f"parallel.collective_dtype="
                f"{cfg.parallel.collective_dtype!r}; set only "
                f"parallel.collective_dtype (the old knob is deprecated)"
            )
        if not cfg.parallel.collective_dtype:
            log.warning(
                "train.grad_allreduce_dtype is deprecated — mapping it to "
                "parallel.collective_dtype=%r (docs/MIGRATING.md)",
                cfg.train.grad_allreduce_dtype,
            )
            cfg.parallel.collective_dtype = cfg.train.grad_allreduce_dtype
    # Deprecation shim: optimizer.shard_opt_state predates the explicit
    # ZeRO path and named only the passive jit-spec variant; it maps onto
    # optimizer.zero_sharding="jit". Conflicting settings of both are
    # rejected rather than silently picking one (same contract as the
    # grad_allreduce_dtype shim above).
    if cfg.optimizer.shard_opt_state:
        if cfg.optimizer.zero_sharding not in ("off", "jit"):
            raise ValueError(
                "optimizer.shard_opt_state=true conflicts with "
                f"optimizer.zero_sharding={cfg.optimizer.zero_sharding!r}; "
                "set only optimizer.zero_sharding (the old knob is "
                "deprecated)"
            )
        if cfg.optimizer.zero_sharding == "off":
            log.warning(
                "optimizer.shard_opt_state is deprecated — mapping it to "
                "optimizer.zero_sharding='jit' (docs/MIGRATING.md)",
            )
            cfg.optimizer.zero_sharding = "jit"
    if cfg.optimizer.zero_sharding not in ("off", "jit", "shard_map"):
        raise ValueError(
            "optimizer.zero_sharding must be 'off', 'jit' or 'shard_map', "
            f"got {cfg.optimizer.zero_sharding!r}"
        )
    if cfg.optimizer.zero_bucket_mb <= 0:
        raise ValueError(
            "optimizer.zero_bucket_mb must be > 0, got "
            f"{cfg.optimizer.zero_bucket_mb}"
        )
    if cfg.parallel.collective_dtype not in ("", "bfloat16", "int8"):
        raise ValueError(
            "parallel.collective_dtype must be '', 'bfloat16' or 'int8', "
            f"got {cfg.parallel.collective_dtype!r}"
        )
    if cfg.parallel.collective_block_size < 1:
        raise ValueError(
            "parallel.collective_block_size must be >= 1, got "
            f"{cfg.parallel.collective_block_size}"
        )
    if cfg.precision.activation_dtype not in ("", "f32", "bf16"):
        raise ValueError(
            "precision.activation_dtype must be '', 'f32' or 'bf16', got "
            f"{cfg.precision.activation_dtype!r}"
        )
    if cfg.precision.matmul_dtype not in ("", "int8"):
        raise ValueError(
            "precision.matmul_dtype must be '' or 'int8', got "
            f"{cfg.precision.matmul_dtype!r}"
        )
    if cfg.precision.remat_policy not in ("none", "dots_saveable",
                                          "save_nothing"):
        raise ValueError(
            "precision.remat_policy must be 'none', 'dots_saveable' or "
            f"'save_nothing', got {cfg.precision.remat_policy!r}"
        )
    if (cfg.precision.fused_update
            and cfg.optimizer.zero_sharding != "shard_map"):
        raise ValueError(
            "precision.fused_update=true fuses the optax apply into the "
            "ZeRO bucketed reverse-layer walk and therefore requires "
            "optimizer.zero_sharding='shard_map', got "
            f"{cfg.optimizer.zero_sharding!r}"
        )
    if cfg.model.pipeline_schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(
            "model.pipeline_schedule must be 'gpipe', '1f1b' or "
            f"'interleaved', got {cfg.model.pipeline_schedule!r}"
        )
    res = cfg.resilience
    if res.snapshot_depth < 1:
        raise ValueError(
            f"resilience.snapshot_depth must be >= 1, got {res.snapshot_depth}"
        )
    if res.max_rollbacks < 1:
        raise ValueError(
            f"resilience.max_rollbacks must be >= 1, got {res.max_rollbacks}"
        )
    if not 0.0 < res.loss_ewma_beta < 1.0:
        raise ValueError(
            "resilience.loss_ewma_beta must be in (0, 1), got "
            f"{res.loss_ewma_beta}"
        )
    clu = cfg.cluster
    if clu.rejoin_timeout_s < 0:
        raise ValueError(
            f"cluster.rejoin_timeout_s must be >= 0, got "
            f"{clu.rejoin_timeout_s}"
        )
    if clu.exit_barrier_timeout_s <= 0:
        raise ValueError(
            "cluster.exit_barrier_timeout_s must be > 0, got "
            f"{clu.exit_barrier_timeout_s}"
        )
    if clu.exit_barrier_poll_s <= 0:
        raise ValueError(
            f"cluster.exit_barrier_poll_s must be > 0, got "
            f"{clu.exit_barrier_poll_s}"
        )
    if clu.heartbeat_interval_s <= 0:
        raise ValueError(
            "cluster.heartbeat_interval_s must be > 0, got "
            f"{clu.heartbeat_interval_s}"
        )
    if cfg.trace.ring_size < 1:
        raise ValueError(
            f"trace.ring_size must be >= 1, got {cfg.trace.ring_size}"
        )
    srv = cfg.serve
    if srv.max_batch_size < 1:
        raise ValueError(
            f"serve.max_batch_size must be >= 1, got {srv.max_batch_size}"
        )
    if srv.max_wait_ms < 0:
        raise ValueError(
            f"serve.max_wait_ms must be >= 0, got {srv.max_wait_ms}"
        )
    if srv.queue_capacity < 1:
        raise ValueError(
            f"serve.queue_capacity must be >= 1, got {srv.queue_capacity}"
        )
    if srv.fleet_min_replicas < 1:
        raise ValueError(
            "serve.fleet_min_replicas must be >= 1, got "
            f"{srv.fleet_min_replicas}"
        )
    if srv.fleet_max_replicas < srv.fleet_min_replicas:
        raise ValueError(
            f"serve.fleet_max_replicas={srv.fleet_max_replicas} must be >= "
            f"serve.fleet_min_replicas={srv.fleet_min_replicas}"
        )
    if not (0.0 < srv.fleet_scale_down_threshold
            < srv.fleet_scale_up_threshold):
        raise ValueError(
            "serve autoscaler hysteresis requires 0 < "
            f"fleet_scale_down_threshold={srv.fleet_scale_down_threshold} < "
            f"fleet_scale_up_threshold={srv.fleet_scale_up_threshold} — a "
            f"degenerate or inverted band makes the control loop flap"
        )
    if srv.fleet_scale_cooldown_s < 0:
        raise ValueError(
            "serve.fleet_scale_cooldown_s must be >= 0, got "
            f"{srv.fleet_scale_cooldown_s}"
        )
    if srv.tenant_priority_reserve < 0:
        raise ValueError(
            "serve.tenant_priority_reserve must be >= 0, got "
            f"{srv.tenant_priority_reserve}"
        )
    if srv.tenant_priority_reserve and (
            2 * srv.tenant_priority_reserve >= srv.queue_capacity):
        raise ValueError(
            f"serve.tenant_priority_reserve={srv.tenant_priority_reserve} "
            f"leaves no claimable capacity for the lowest priority class "
            f"(2*reserve >= queue_capacity={srv.queue_capacity}) — batch "
            f"traffic would shed even on an idle fleet"
        )
    if srv.tenant_quota_rps < 0:
        raise ValueError(
            f"serve.tenant_quota_rps must be >= 0, got "
            f"{srv.tenant_quota_rps}"
        )
    if srv.tenant_quota_burst < 0:
        raise ValueError(
            f"serve.tenant_quota_burst must be >= 0, got "
            f"{srv.tenant_quota_burst}"
        )
    if srv.seq_buckets:
        if (any(int(b) < 1 for b in srv.seq_buckets)
                or list(srv.seq_buckets) != sorted(set(srv.seq_buckets))):
            raise ValueError(
                "serve.seq_buckets must be strictly ascending positive "
                f"sequence lengths, got {srv.seq_buckets} — each request "
                f"is padded up to the smallest bucket that fits it"
            )
        if srv.seq_buckets[-1] > cfg.model.max_seq_len:
            raise ValueError(
                f"serve.seq_buckets max {srv.seq_buckets[-1]} exceeds "
                f"model.max_seq_len={cfg.model.max_seq_len} — the model "
                f"cannot embed positions past its trained length"
            )
    dec = cfg.decode
    if dec.scheduler not in ("continuous", "static"):
        raise ValueError(
            f"decode.scheduler must be 'continuous' or 'static', got "
            f"{dec.scheduler!r}"
        )
    if dec.kv_dtype not in ("float32", "int8"):
        raise ValueError(
            f"decode.kv_dtype must be 'float32' or 'int8', got "
            f"{dec.kv_dtype!r}"
        )
    if dec.page_size < 1:
        raise ValueError(
            f"decode.page_size must be >= 1, got {dec.page_size}"
        )
    if dec.stream_interval < 1:
        raise ValueError(
            f"decode.stream_interval must be >= 1, got "
            f"{dec.stream_interval}"
        )
    if dec.num_pages < 2:
        raise ValueError(
            f"decode.num_pages must be >= 2 (page 0 is the reserved "
            f"scratch page), got {dec.num_pages}"
        )
    if dec.max_streams < 1:
        raise ValueError(
            f"decode.max_streams must be >= 1, got {dec.max_streams}"
        )
    if dec.max_new_tokens < 1:
        raise ValueError(
            f"decode.max_new_tokens must be >= 1, got {dec.max_new_tokens}"
        )
    if dec.max_len < 0:
        raise ValueError(
            f"decode.max_len must be >= 0 (0 = model.max_seq_len), got "
            f"{dec.max_len}"
        )
    if dec.max_len > cfg.model.max_seq_len:
        raise ValueError(
            f"decode.max_len={dec.max_len} exceeds model.max_seq_len="
            f"{cfg.model.max_seq_len} — the model cannot embed positions "
            f"past its trained length"
        )
    for knob, buckets in (("decode.page_buckets", dec.page_buckets),
                          ("decode.prompt_buckets", dec.prompt_buckets)):
        if buckets and (
                any(int(b) < 1 for b in buckets)
                or list(buckets) != sorted(set(buckets))):
            raise ValueError(
                f"{knob} must be strictly ascending positive values, got "
                f"{buckets}"
            )
    # Head-vs-labels cross-check for the built-in classification datasets:
    # a label outside the head's range turns the loss metric into NaN
    # through the integer-label CE gather (fill semantics) while grads
    # stay finite — the NaN guard kills the run without naming the cause.
    # Only data > model is fatal (a wider head than the label range is
    # wasteful but valid); eval_data feeds the same head.
    for role, dc in (("data", cfg.data), ("eval_data", cfg.eval_data)):
        if dc is None:
            continue
        if dc.shard_mode not in ("block", "stride"):
            raise ValueError(
                f"{role}.shard_mode must be 'block' or 'stride', got "
                f"{dc.shard_mode!r}"
            )
        if (dc.name in ("mnist", "cifar10", "imagenet", "synthetic_images")
                and dc.num_classes > cfg.model.num_classes):
            raise ValueError(
                f"{role}.num_classes={dc.num_classes} > "
                f"model.num_classes={cfg.model.num_classes} for "
                f"classification dataset {dc.name!r} — out-of-range labels "
                f"poison the loss metric with NaN; widen the model head or "
                f"fix {role}.num_classes"
            )
    tune = cfg.autotune
    if not (0.0 <= tune.prune_margin < 1.0):
        raise ValueError(
            f"autotune.prune_margin must be in [0, 1), got "
            f"{tune.prune_margin} — it is the fraction of predicted loss "
            f"the pruner tolerates before skipping a candidate"
        )
    if tune.max_trials < 0:
        raise ValueError(
            f"autotune.max_trials must be >= 0 (0 = unbounded), got "
            f"{tune.max_trials}"
        )
    if not (0.0 <= tune.regression_margin < 1.0):
        raise ValueError(
            f"autotune.regression_margin must be in [0, 1), got "
            f"{tune.regression_margin}"
        )
    return cfg
