"""Model zoo: the reference's model families, rebuilt as Flax modules.

SURVEY.md §2 rows 6–8 + BASELINE.json configs: LeNet-5 (MNIST smoke test),
ResNet-50 (CIFAR-10 and ImageNet variants, fused/cross-replica BN),
Inception-v3 (the reference's async-PS workload, here sync replicas), and
BERT-base MLM (the new-build transformer workload).

``get_model(config)`` is the registry — the analogue of the reference's
model-name flag dispatch. The reference is a framework TEMPLATE whose
extension point is "user plugs in a model build function" (SURVEY.md §1
L4); ``register_model`` is that extension point here: a user package
registers a builder under a name and every runtime feature (Trainer,
sharding rules, checkpointing, eval) works unchanged.
"""

from __future__ import annotations

from typing import Any, Callable

from distributed_tensorflow_framework_tpu.core.config import ModelConfig

# name → (builder(config, bn_axis_name=..., mesh=...) -> module, task).
_CUSTOM_MODELS: dict[str, tuple[Callable[..., Any], str]] = {}


def _is_builtin_model_name(name: str) -> bool:
    """Name-pattern twin of get_model's built-in dispatch below — keep the
    two in sync when adding a model family. The whole resnet-N pattern is
    reserved (including depths that don't exist yet)."""
    import re

    return (
        name in ("lenet", "lenet5", "lenet-5",
                 "bert", "bert_base", "bert-base",
                 "inception_v3", "inception-v3", "inceptionv3")
        or _is_lfm2_name(name)
        or re.fullmatch(r"resnet-?(\d+)(_cifar|-cifar)?", name) is not None
    )


def _is_lfm2_name(name: str) -> bool:
    """The causal decoder family of models/lfm2.py: ``lfm2``,
    ``lfm2_moe``, ``lfm2-8b-a1b``, ``smallthinker``,
    ``smallthinker_moe``, ``nemotron_h``, ``laguna``, ``kanana``,
    ``kimi_linear`` — the whole ``lfm2``, ``smallthinker``, ``nemotron``,
    ``laguna``, ``kanana`` and ``kimi`` prefixes are reserved (the models
    differ by ModelConfig settings, not by class).
    One test for
    get_model, the task and the decode refusal."""
    return name.lower().startswith(
        ("lfm2", "smallthinker", "nemotron", "laguna", "kanana", "kimi"))


def builtin_task(name: str) -> str:
    """Task family of a built-in model name (train/step.py picks the
    loss and the batch wiring from it)."""
    name = name.lower()
    if _is_lfm2_name(name):
        return "causal_lm"
    return "mlm" if "bert" in name else "classification"


def register_model(name: str, *, task: str = "classification"):
    """Register a user model builder under ``model.name`` (decorator).

    The builder receives the full ModelConfig plus the same keyword
    context the built-ins get (``bn_axis_name``, ``mesh``) and returns a
    Flax module. The module's ``__call__`` MUST accept a ``train``
    keyword (the Trainer calls ``init(..., train=False)`` and
    ``apply(..., train=True, rngs={"dropout": ...})``) and its positional
    inputs must match ``task``: "classification" (images → logits),
    "mlm" ((ids, mask[, segment_ids]) → logits) or "causal_lm" ((ids[,
    segment_ids[, positions]]) → logits, next-token ``targets``) — the
    task picks the loss and batch wiring (train/step.py). An "mlm" module
    whose ``__call__`` also takes a ``labelled`` keyword
    (models/bert.LabelledWindows) is handed the targets in training and
    returns models/bert.HeadSums in place of logits: its head runs on the
    labelled positions only, as ``BertForMLM``'s does; one without it gets
    its (B, S, V) logits put through the loss. The builder owns the
    interpretation of every other ModelConfig knob (e.g. ``remat``).
    Built-in names cannot be shadowed, and duplicate registrations fail
    loudly.

        @register_model("my_net")
        def build(config, *, bn_axis_name=None, mesh=None):
            return MyNet(num_classes=config.num_classes)

        class MyNet(nn.Module):
            num_classes: int
            @nn.compact
            def __call__(self, x, *, train: bool = True):
                ...
    """
    key = name.lower()
    if task not in ("classification", "mlm", "causal_lm"):
        raise ValueError(f"unknown task {task!r} for model {name!r}")

    def deco(builder):
        if key in _CUSTOM_MODELS:
            raise ValueError(f"model {name!r} already registered")
        if _is_builtin_model_name(key):
            raise ValueError(f"model {name!r} shadows a built-in")
        _CUSTOM_MODELS[key] = (builder, task)
        return builder

    return deco


def decode_support_reason(model_config) -> str | None:
    """Why ``model_config`` cannot take the autoregressive decode path
    (None = supported) — re-exported from models/bert.py so the serving
    layer (serve/decode.py) need not import a model file directly."""
    from distributed_tensorflow_framework_tpu.models import bert

    if model_config.name.lower() in _CUSTOM_MODELS:
        return (f"custom model {model_config.name!r} has no causal decode "
                f"head (decode supports the dense bert family)")
    return bert.decode_support_reason(model_config)


def custom_model_task(name: str) -> str | None:
    """Task family of a registered custom model, or None if not custom."""
    entry = _CUSTOM_MODELS.get(name.lower())
    return entry[1] if entry else None


def get_model(config: ModelConfig, *, bn_axis_name=None, mesh=None,
              precision=None) -> Any:
    """Build a Flax module from a ModelConfig (name-based dispatch).

    ``bn_axis_name`` is only set when the caller will run the model inside
    shard_map and wants cross-replica BN statistics (see
    models/layers.py docstring); under jit it must stay None. ``mesh`` is
    required only for BERT with ``attention_impl="ring"`` (sequence-parallel
    attention needs the physical mesh for its nested shard_map).

    ``precision`` is the optional PrecisionConfig (core/config.py): its
    ``activation_dtype`` overrides ``model.dtype`` for the compute casts
    (params stay f32 masters either way), ``matmul_dtype`` selects the
    int8 block-codec matmul path, and ``remat_policy`` maps onto
    jax.checkpoint_policies in the remat-capable builders. None (the
    serving path) leaves every model exactly as before.
    """
    import jax.numpy as jnp

    dtype = jnp.dtype(config.dtype)
    matmul_dtype = ""
    ckpt_policy = None
    if precision is not None:
        if precision.activation_dtype:
            dtype = jnp.dtype(
                {"f32": jnp.float32, "bf16": jnp.bfloat16}[
                    precision.activation_dtype]
            )
        matmul_dtype = precision.matmul_dtype
        if precision.remat_policy != "none":
            from jax.ad_checkpoint import checkpoint_policies

            ckpt_policy = {
                # Save every matmul output, replay the cheap elementwise
                # tail: recompute ≈ free, roughly half the activation bytes.
                "dots_saveable": checkpoint_policies.dots_saveable,
                # Save only block/layer inputs, replay everything: the max
                # memory savings / max recompute point (long-context fit).
                "save_nothing": checkpoint_policies.nothing_saveable,
            }[precision.remat_policy]
    name = config.name.lower()
    if name in _CUSTOM_MODELS:
        if matmul_dtype or ckpt_policy is not None or (
                precision is not None and precision.activation_dtype):
            raise ValueError(
                f"precision.activation_dtype/matmul_dtype/remat_policy are "
                f"not threaded through custom model {config.name!r} — the "
                f"registered builder owns its ModelConfig interpretation"
            )
        return _CUSTOM_MODELS[name][0](
            config, bn_axis_name=bn_axis_name, mesh=mesh)
    is_bert = name in ("bert", "bert_base", "bert-base")
    if ckpt_policy is not None:
        if config.remat_policy != "full":
            raise ValueError(
                "precision.remat_policy conflicts with "
                f"model.remat_policy={config.remat_policy!r} — pick one "
                "spelling (the precision block is the cross-model one)"
            )
        if not config.remat and config.pipeline_stages <= 1:
            raise ValueError(
                "precision.remat_policy requires model.remat=true (the "
                "policy selects WHAT the per-block checkpoint saves; "
                "pipeline stages checkpoint their own layer applies and "
                "are exempt)"
            )
    if matmul_dtype and not (
            name in ("lenet", "lenet5", "lenet-5") or name.startswith("resnet")):
        raise ValueError(
            f"precision.matmul_dtype='int8' is wired for the dense/conv "
            f"image models (lenet, resnet), not {config.name!r}"
        )
    is_lfm2 = _is_lfm2_name(name)
    if config.remat and not (is_bert or is_lfm2 or name.startswith("resnet")
                             or name.startswith("inception")):
        # Honest failure beats a silently-ignored knob: activation remat is
        # wired for the transformer encoder stack (models/bert.py), the
        # decoder blocks (models/lfm2.py), the ResNet residual blocks
        # (models/resnet.py) and the Inception mixed/reduction blocks
        # (models/inception.py).
        raise ValueError(
            f"model.remat is only supported for the transformer (bert, "
            f"lfm2), resnet and inception models, not {config.name!r}"
        )
    if config.remat_policy != "full" and not (
            config.remat and name.startswith("resnet")):
        raise ValueError(
            f"model.remat_policy={config.remat_policy!r} requires "
            f"model.remat=true on a resnet model (the conv_saved policy "
            f"keys on the ConvBN conv_out tag; models/resnet.py)"
        )
    if config.remat and config.pipeline_stages > 1:
        raise ValueError(
            "model.remat inside the pipelined stack is unsupported — the "
            "GPipe stage body manages its own activation lifetime"
        )
    if config.space_to_depth_stem and not name.startswith("resnet"):
        raise ValueError(
            f"model.space_to_depth_stem is a ResNet ImageNet-stem "
            f"optimization, not supported for {config.name!r}"
        )
    if is_lfm2:
        if config.pipeline_stages > 1:
            raise ValueError(
                "pipeline parallelism is wired for the bert family only, "
                f"not {config.name!r}")
        from distributed_tensorflow_framework_tpu.models import lfm2

        return lfm2.build(config, mesh=mesh, dtype=dtype,
                          ckpt_policy=ckpt_policy)
    if name in ("lenet", "lenet5", "lenet-5"):
        from distributed_tensorflow_framework_tpu.models.lenet import LeNet5

        return LeNet5(num_classes=config.num_classes, dtype=dtype,
                      matmul_dtype=matmul_dtype)
    import re

    m = re.fullmatch(r"resnet-?(\d+)(_cifar|-cifar)?", name)
    if m:
        from distributed_tensorflow_framework_tpu.models.resnet import make_resnet

        return make_resnet(
            int(m.group(1)),
            num_classes=config.num_classes,
            dtype=dtype,
            bn_axis_name=bn_axis_name,
            cifar_stem=m.group(2) is not None,
            space_to_depth_stem=config.space_to_depth_stem,
            remat=config.remat,
            remat_policy=config.remat_policy,
            ckpt_policy=ckpt_policy,
            matmul_dtype=matmul_dtype,
        )
    if name in ("inception_v3", "inception-v3", "inceptionv3"):
        from distributed_tensorflow_framework_tpu.models.inception import InceptionV3

        return InceptionV3(
            num_classes=config.num_classes,
            dtype=dtype,
            bn_axis_name=bn_axis_name,
            remat=config.remat,
            ckpt_policy=ckpt_policy,
        )
    if is_bert:
        if config.pipeline_stages > 1:
            if config.num_experts > 0:
                raise ValueError(
                    "MoE inside the pipelined stack is unsupported "
                    "(num_experts>0 with pipeline_stages>1) — the stage "
                    "shard_map would need manual expert collectives"
                )
            from distributed_tensorflow_framework_tpu.parallel.pipeline import (
                PipelinedBert,
            )

            return PipelinedBert(
                vocab_size=config.vocab_size,
                hidden_size=config.hidden_size,
                num_layers=config.num_layers,
                num_heads=config.num_heads,
                mlp_dim=config.mlp_dim,
                max_seq_len=config.max_seq_len,
                dropout_rate=config.dropout_rate,
                dtype=dtype,
                mesh=mesh,
                num_stages=config.pipeline_stages,
                num_microbatches=config.pipeline_microbatches,
                attention_impl=config.attention_impl,
                fused_qkv=config.fused_qkv,
                schedule=config.pipeline_schedule,
                virtual_stages=config.pipeline_virtual_stages,
                ckpt_policy=ckpt_policy,
            )
        from distributed_tensorflow_framework_tpu.models.bert import BertForMLM

        return BertForMLM(
            vocab_size=config.vocab_size,
            hidden_size=config.hidden_size,
            num_layers=config.num_layers,
            num_heads=config.num_heads,
            mlp_dim=config.mlp_dim,
            max_seq_len=config.max_seq_len,
            dropout_rate=config.dropout_rate,
            dtype=dtype,
            attention_impl=config.attention_impl,
            mesh=mesh,
            fused_qkv=config.fused_qkv,
            num_experts=config.num_experts,
            moe_every=config.moe_every,
            expert_topk=config.expert_topk,
            capacity_factor=config.capacity_factor,
            moe_dispatch=config.moe_dispatch,
            moe_zloss_weight=config.moe_zloss_weight,
            remat=config.remat,
            ckpt_policy=ckpt_policy,
        )
    raise ValueError(f"Unknown model {config.name!r}")
