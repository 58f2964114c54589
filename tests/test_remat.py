"""Activation rematerialization (model.remat → nn.remat on per-model blocks).

jax.checkpoint replays the same OPS in the backward pass. On the small
BERT/ResNet stacks the replay happens to be bitwise (pinned below); XLA
is free to fuse the wrapped computation differently though, and on the
deep Inception BN cascade the measured ~1e-6/block refusion noise
amplifies chaotically in train mode — so Inception pins block-level
parity + eval equality + finite training instead of whole-model bitwise
gradients (see test_inception_remat_block_parity_and_trains).
"""

import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_policies

from distributed_tensorflow_framework_tpu.core.config import (
    ModelConfig, PrecisionConfig, load_config)
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as family
from distributed_tensorflow_framework_tpu.ops import flash_attention as fa


def _tiny_bert(remat: bool) -> ModelConfig:
    return ModelConfig(
        name="bert", vocab_size=256, hidden_size=32, num_layers=3,
        num_heads=4, mlp_dim=64, max_seq_len=32, dtype="float32",
        dropout_rate=0.1, remat=remat,
    )


@pytest.mark.slow
def test_remat_exact_logits_and_grads(devices):
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 256, (2, 16)),
                      jnp.int32)
    mask = jnp.ones((2, 16), jnp.int32)
    rng = jax.random.key(0)

    models = [get_model(_tiny_bert(r)) for r in (False, True)]
    vs = models[0].init({"params": rng, "dropout": rng}, ids, mask,
                        train=False)
    # Same params drive both variants (remat adds no parameters).
    outs, grads = [], []
    for m in models:
        def loss_fn(params):
            logits = m.apply({"params": params}, ids, mask, train=True,
                             rngs={"dropout": jax.random.key(7)})
            return (logits.astype(jnp.float32) ** 2).mean()

        out = m.apply(vs, ids, mask, train=False)
        l, g = jax.value_and_grad(loss_fn)(vs["params"])
        outs.append(np.asarray(out))
        grads.append(jax.device_get(g))

    np.testing.assert_array_equal(outs[0], outs[1])
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_remat_rejected_for_unwired_models():
    with pytest.raises(ValueError, match="transformer"):
        get_model(ModelConfig(name="lenet5", remat=True))


def test_remat_policy_rejected_off_resnet():
    # conv_saved keys on the ConvBN tag inside the resnet blocks; other
    # models (and remat=false) must reject it, not silently ignore it.
    with pytest.raises(ValueError, match="remat_policy"):
        get_model(ModelConfig(name="bert", remat=True,
                              remat_policy="conv_saved"))
    with pytest.raises(ValueError, match="remat_policy"):
        get_model(ModelConfig(name="resnet50", remat=False,
                              remat_policy="conv_saved"))
    with pytest.raises(ValueError, match="conv_saved"):
        get_model(ModelConfig(name="resnet50", remat=True,
                              remat_policy="typo"))


@pytest.mark.slow
def test_inception_remat_block_parity_and_trains(devices):
    """Per-block remat on the Inception mixed/reduction blocks.

    The remat transform is not guaranteed BITWISE on this backend (XLA
    may fuse the wrapped forward differently — measured ~1e-6 per
    block), and Inception's deep train-mode BatchNorm cascade chaotically
    amplifies a 1e-6 input perturbation to O(10%) logits at random init —
    so a whole-model gradient comparison cannot distinguish refusion
    noise from a real bug. Pin instead what IS meaningful: (a) one
    wrapped block's forward+gradients match the plain block tightly,
    (b) the full remat model's EVAL forward (running-stat BN, the
    non-chaotic mode) is bit-equal, (c) the remat model trains to a
    finite loss through the full train step."""
    import flax.linen as nn

    from distributed_tensorflow_framework_tpu.models.inception import InceptionA

    # (a) single-block parity, fwd + grads.
    xb = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 17, 17, 64)), jnp.float32)
    plain = InceptionA(32, train=True, dtype=jnp.float32)
    remat = nn.remat(InceptionA)(32, train=True, dtype=jnp.float32)
    vsb = plain.init(jax.random.key(0), xb)

    def block_loss(m):
        def f(params):
            y, _ = m.apply({"params": params,
                            "batch_stats": vsb["batch_stats"]},
                           xb, mutable=["batch_stats"])
            return (y.astype(jnp.float32) ** 2).mean()
        return f

    for (a, b) in zip(
            jax.tree.leaves(jax.grad(block_loss(plain))(vsb["params"])),
            jax.tree.leaves(jax.grad(block_loss(remat))(vsb["params"]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)

    # (b) full-model eval forward bit-equal; (c) trains finite.
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((2, 83, 83, 3)), jnp.float32)
    models = [
        get_model(ModelConfig(name="inception_v3", num_classes=10,
                              dtype="float32", remat=r))
        for r in (False, True)
    ]
    vs = models[0].init(jax.random.key(0), x, train=False)
    # Eval (running-stat BN) avoids the chaotic amplification; allow the
    # per-block refusion noise itself rather than demanding bitwise.
    np.testing.assert_allclose(
        np.asarray(models[0].apply(vs, x, train=False)),
        np.asarray(models[1].apply(vs, x, train=False)),
        rtol=1e-5, atol=1e-5)

    def loss_fn(params):
        out, _ = models[1].apply(
            {"params": params, "batch_stats": vs["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
            rngs={"dropout": jax.random.key(3)})
        return ((out["logits"].astype(jnp.float32) ** 2).mean()
                + 0.4 * (out["aux_logits"] ** 2).mean())

    loss, grads = jax.value_and_grad(loss_fn)(vs["params"])
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))


@pytest.mark.slow
def test_resnet_remat_exact_logits_grads_and_bn_stats(devices):
    """Per-block remat on the ResNet stack (the byte lever for the
    HBM-bound ImageNet step): identical logits, gradients AND BatchNorm
    running-stat updates — jax.checkpoint replays, never diverges.
    Covers both replay policies — "full" (save nothing) and "conv_saved"
    (keep conv outputs, replay only the BN/ReLU tail) — against ONE
    shared non-remat baseline."""
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 32, 32, 3)), jnp.float32)

    def run(remat, policy):
        m = get_model(ModelConfig(name="resnet18_cifar", num_classes=10,
                                  dtype="float32", remat=remat,
                                  remat_policy=policy))
        def loss_fn(params):
            logits, new_state = m.apply(
                {"params": params, "batch_stats": vs["batch_stats"]},
                x, train=True, mutable=["batch_stats"])
            return (logits.astype(jnp.float32) ** 2).mean(), new_state

        out = m.apply(vs, x, train=False)
        (_, new_state), g = jax.value_and_grad(loss_fn, has_aux=True)(
            vs["params"])
        return (np.asarray(out), jax.device_get(g),
                jax.device_get(new_state["batch_stats"]))

    vs = get_model(ModelConfig(name="resnet18_cifar", num_classes=10,
                               dtype="float32")).init(
        jax.random.key(0), x, train=False)
    base_out, base_grads, base_stats = run(False, "full")
    for policy in ("full", "conv_saved"):
        out, grads, stats = run(True, policy)
        np.testing.assert_array_equal(base_out, out, err_msg=policy)
        for a, b in zip(jax.tree.leaves(base_grads), jax.tree.leaves(grads)):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7,
                                       err_msg=policy)
        for a, b in zip(jax.tree.leaves(base_stats), jax.tree.leaves(stats)):
            np.testing.assert_array_equal(a, b, err_msg=policy)


def test_remat_rejected_with_pipeline():
    cfg = _tiny_bert(True)
    cfg.pipeline_stages = 2
    with pytest.raises(ValueError, match="pipelined"):
        get_model(cfg)


# ------------------------------------------------------------------------
# What ``model.remat`` keeps from a decoder layer's forward pass (PR 31):
# the attention kernels' output and logsumexp, so the forward kernel is
# dead code in the re-run forward pass.
DECODER_LAYERS = ["full_attention", "sliding_attention", "conv"]
DEC_S, DEC_VOCAB = 128, 256


def _decoder(layers=DECODER_LAYERS, *, remat=True, policy="none",
             impl="pallas"):
    """A global layer, a window layer and a short convolution over
    experts, at tiny widths; ``policy`` is ``precision.remat_policy``."""
    cfg = ModelConfig(
        name="smallthinker_moe", vocab_size=DEC_VOCAB, hidden_size=64,
        num_layers=len(layers), layer_types=list(layers),
        rope_layout=[int(k == "sliding_attention") for k in layers],
        sliding_window=24, num_dense_layers=0, num_heads=4, num_kv_heads=2,
        head_dim=32, qk_norm=False, moe_mlp_dim=32, num_experts=8,
        expert_topk=2, router_input="stream", router_score="softmax_topk",
        expert_activation="relu", tie_embeddings=False, embed_init_std=1.0,
        norm_eps=1e-6, rope_theta=1.5e6, dtype="float32",
        attention_impl=impl, dropout_rate=0.0, remat=remat)
    return get_model(cfg, precision=PrecisionConfig(remat_policy=policy))


def _decoder_inputs(segmented: bool):
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(0, DEC_VOCAB, (2, DEC_S)), jnp.int32)
    if not segmented:
        return (ids,)
    # two documents a row, cut where no block is aligned with them
    cuts = np.array([[50], [90]])
    return (ids, jnp.asarray(1 + (np.arange(DEC_S)[None, :] >= cuts),
                             jnp.int32))


def _decoder_grad(model, inputs):
    def loss(params):
        out = model.apply({"params": params}, *inputs)
        return (out["logits"].astype(jnp.float32) ** 2).mean()

    return jax.grad(loss)


@functools.cache
def _decoder_params(segmented: bool):
    return _decoder(remat=False).init(
        jax.random.key(0), *_decoder_inputs(segmented))["params"]


@functools.cache
def _decoder_grads(segmented: bool, remat: bool, policy: str):
    """Op by op, as XLA fuses nothing then: what is compared is the
    arithmetic the policies leave, not one compile against another."""
    inputs = _decoder_inputs(segmented)
    return jax.device_get(_decoder_grad(
        _decoder(remat=remat, policy=policy), inputs)(
            _decoder_params(segmented)))


def _forward_kernel_calls(fn, *args) -> tuple[int, int]:
    text = str(jax.make_jaxpr(fn)(*args))
    return (len(re.findall(r"name=_flash_fwd\b", text)),
            len(re.findall(r"name=_flash_bwd\b", text)))


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["whole_rows", "packed_rows"])
@pytest.mark.parametrize("other", [(True, "save_nothing"), (False, "none")],
                         ids=["save_nothing", "remat_off"])
def test_kept_attention_residuals_leave_every_gradient_alone(
        devices, segmented, other):
    """The backward kernels read the output and logsumexp the first
    forward pass produced, not an identical second copy: every
    parameter's gradient equals the full re-run's and the un-remat'd
    model's, to 0.0."""
    kept = _decoder_grads(segmented, True, "none")
    want = _decoder_grads(segmented, *other)
    flat_kept = dict(jax.tree_util.tree_leaves_with_path(kept))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert flat_kept.keys() == flat_want.keys() and len(flat_kept) > 20
    for path, g in flat_kept.items():
        assert np.any(g), jax.tree_util.keystr(path)
        np.testing.assert_array_equal(g, flat_want[path],
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["whole_rows", "packed_rows"])
@pytest.mark.parametrize("layers,remat,policy,forward_calls", [
    (DECODER_LAYERS, True, "none", 2),          # once an attention layer
    (DECODER_LAYERS, True, "save_nothing", 4),  # the full re-run: twice
    (DECODER_LAYERS, True, "dots_saveable", 4),  # what it says, no names
    (DECODER_LAYERS, False, "none", 2),
    (DECODER_LAYERS + ["conv", "conv"], True, "none", 2),   # conv adds none
    (["conv", "conv"], True, "none", 0),
], ids=["kept", "save_nothing", "dots_saveable", "remat_off", "more_conv",
        "conv_only"])
def test_forward_kernel_calls_in_the_gradients_jaxpr(
        devices, segmented, layers, remat, policy, forward_calls):
    inputs = _decoder_inputs(segmented)
    model = _decoder(layers, remat=remat, policy=policy)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), *inputs)["params"])
    attention_layers = sum(k != "conv" for k in layers)
    assert _forward_kernel_calls(_decoder_grad(model, inputs), params) == (
        forward_calls, attention_layers)


def test_the_xla_attention_has_no_names_to_keep(devices, monkeypatch):
    """``attention_impl: xla`` under the same policy: no kernel, no name,
    the layer re-runs whole as under ``save_nothing`` (the expert
    layer's names apart, PR 33: taken out here)."""
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    inputs = _decoder_inputs(True)
    texts = []
    for policy in ("none", "save_nothing"):
        model = _decoder(policy=policy, impl="xla")
        params = jax.eval_shape(
            lambda: model.init(jax.random.key(0), *inputs)["params"])
        text = str(jax.make_jaxpr(_decoder_grad(model, inputs))(params))
        assert "_flash_fwd" not in text
        assert not set(fa.RESIDUAL_NAMES) & set(
            re.findall(r"name=(\w+)", text))
        texts.append(re.sub(r"policy=.*", "policy=", text))
    assert texts[0] == texts[1]


def _fused_variant(kind: str, segmented: bool):
    if kind == "plain":
        return fa._FUSED[(segmented, False)]
    if kind == "return_lse":
        return fa._FUSED[(segmented, True)]
    if kind == "causal":
        return fa._FUSED_CAUSAL[segmented]
    return fa._fused_window(segmented, 48)


@pytest.mark.parametrize("segmented", [False, True],
                         ids=["unsegmented", "segmented"])
@pytest.mark.parametrize("kind", ["plain", "causal", "window", "return_lse"])
def test_every_fused_variant_names_both_residuals(devices, kind, segmented):
    """Each custom-VJP ``fwd`` rule tags the kernel's output and a
    lane-dense (B, H, S) logsumexp, and what ``bwd`` reads are the tagged
    values: under a policy that keeps the two names the forward kernel is
    traced once, under one that keeps nothing twice."""
    b, h, hk, s, d = 1, 4, 2, 128, 32
    fused = _fused_variant(kind, segmented)
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, hk, s, d), jnp.float32)
    operands = [q, kv, kv, jax.ShapeDtypeStruct((b, 1, s), jnp.float32)]
    if segmented:
        operands += [jax.ShapeDtypeStruct((b, 1, s), jnp.float32)] * 2

    def loss(*args):
        out = fused(*args)
        if kind == "return_lse":     # the ring merge differentiates both
            return out[0].sum() + out[1].sum()
        return out.sum()

    def grad_under(policy):
        return jax.grad(jax.checkpoint(loss, policy=policy),
                        argnums=(0, 1, 2))

    keep = checkpoint_policies.save_only_these_names(*fa.RESIDUAL_NAMES)
    text = str(jax.make_jaxpr(grad_under(keep))(*operands))
    named = {name: shape for shape, name in re.findall(
        r":f32\[([\d,]*)\] = name\[name=(\w+)\]", text)}
    assert named == {fa.ATTN_OUT_NAME: f"{b},{h},{s},{d}",
                     fa.ATTN_LSE_NAME: f"{b},{h},{s}"}
    assert _forward_kernel_calls(grad_under(keep), *operands) == (1, 1)
    assert _forward_kernel_calls(
        grad_under(checkpoint_policies.nothing_saveable), *operands) == (2, 1)
    # one name is not enough: the backward needs both from the kernel
    for name in fa.RESIDUAL_NAMES:
        assert _forward_kernel_calls(grad_under(
            checkpoint_policies.save_only_these_names(name)),
            *operands) == (2, 1)


def _bert_step_text():
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(base={
        "name": "remat-tags-test", "mesh": {"data": 1},
        "model": {"name": "bert", "vocab_size": 96, "hidden_size": 32,
                  "num_layers": 2, "num_heads": 2, "mlp_dim": 64,
                  "max_seq_len": 128, "dtype": "float32",
                  "dropout_rate": 0.0, "attention_impl": "pallas"},
        "data": {"name": "synthetic_mlm", "vocab_size": 64,
                 "global_batch_size": 2, "seq_len": 128, "mask_prob": 0.15},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.1},
        "train": {"total_steps": 2}})
    builder = StepBuilder(cfg, create_mesh(cfg.mesh,
                                           devices=jax.devices()[:1]))
    sample = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32)
              for k in ("input_ids", "attention_mask", "targets",
                        "segment_ids")}
    return _compiled_step(builder, sample)


def _decoder_step_text():
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(
        os.path.join(root, "configs", "smallthinker_21b_a3b.yaml"), [
            "model.num_layers=2",
            "model.layer_types=[full_attention,sliding_attention]",
            "model.rope_layout=[0,1]", "model.sliding_window=24",
            "model.hidden_size=64", "model.num_heads=4",
            "model.num_kv_heads=2", "model.head_dim=32",
            "model.moe_mlp_dim=32", "model.num_experts=8",
            "model.expert_topk=2", "model.expert_groups=4",
            f"model.vocab_size={DEC_VOCAB}", f"data.vocab_size={DEC_VOCAB}",
            f"data.seq_len={DEC_S}", "data.global_batch_size=2",
            "mesh.data=1", "model.dtype=float32", "model.remat=false"])
    assert cfg.model.attention_impl == "pallas" and not cfg.model.remat
    builder = StepBuilder(cfg, create_mesh(cfg.mesh,
                                           devices=jax.devices()[:1]))
    sample = {k: jax.ShapeDtypeStruct((2, DEC_S), jnp.int32)
              for k in ("input_ids", "targets", "segment_ids", "positions")}
    return _compiled_step(builder, sample)


def _compiled_step(builder, sample) -> str:
    """The train step compiled from shapes alone: nothing runs."""
    state = jax.eval_shape(builder._create_state,
                           jax.ShapeDtypeStruct((1,), jnp.uint32), sample)
    return builder.make_train_step(sample).lower(
        state, sample).compile().as_text()


@pytest.mark.parametrize("step_text", [_bert_step_text, _decoder_step_text],
                         ids=["bert", "decoder_without_remat"])
def test_the_tags_are_inert_without_a_policy_that_asks_for_them(
        devices, monkeypatch, step_text):
    """BERT's step and an un-remat'd decoder's compile to the program
    they compile to under the parent's rules (no tag, the logsumexp as
    the kernel left it), metadata and all: a name is no operation, and
    the lane-dense logsumexp is a reshape there and back that XLA folds."""
    texts = []
    for rules in (None, lambda o, lse: (o, lse)):
        jax.clear_caches()
        if rules is not None:
            monkeypatch.setattr(fa, "_name_residuals", rules)
        texts.append(step_text())
    tagged, untagged = texts
    assert "_flash_fwd" in tagged and "_flash_bwd" in tagged
    assert tagged == untagged


# ------------------------------------------------------------------------
# What ``model.remat`` keeps from an expert layer's forward pass (PR 33):
# the router's logits, the chosen experts and their scores and the sort by
# expert, so the product, the top-k, the scores' gather, the two argsorts
# and the count are dead code in the re-run forward pass.
EXP_B, EXP_S, EXP_LAYERS = 2, 256, 2     # two expert layers in each stack
EXPERT_STACKS = {
    # sigmoid scores with a selection bias, SwiGLU experts, a dense layer
    "lfm2": dict(
        name="lfm2_moe", num_layers=3,
        layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        num_heads=4, num_kv_heads=2, mlp_dim=128, moe_mlp_dim=32,
        num_experts=8, expert_topk=2),
    # softmax over the chosen logits, a router that reads the stream
    # before attention (``route_from``), ReGLU experts
    "smallthinker": dict(
        name="smallthinker_moe", num_layers=2,
        layer_types=["full_attention", "sliding_attention"],
        rope_layout=[0, 1], sliding_window=24, num_dense_layers=0,
        num_heads=4, num_kv_heads=2, head_dim=32, qk_norm=False,
        moe_mlp_dim=32, num_experts=8, expert_topk=2, router_input="stream",
        router_score="softmax_topk", expert_activation="relu",
        tie_embeddings=False, embed_init_std=1.0, norm_eps=1e-6,
        rope_theta=1.5e6),
    # layers of one sublayer: ungated experts in a latent beside a shared
    # expert, scaled weights
    "nemotron_h": dict(
        name="nemotron_h", num_layers=3,
        layer_types=["experts_only", "mamba2_only", "experts_only"],
        rope_layout=[0, 0, 0], num_dense_layers=0, num_heads=8,
        num_kv_heads=2, head_dim=16, qk_norm=False, mamba_num_heads=8,
        mamba_head_dim=8, mamba_groups=2, ssm_state_size=16, mamba_chunk=32,
        conv_kernel=4, moe_mlp_dim=24, moe_latent_dim=32, moe_shared_dim=48,
        num_experts=16, expert_topk=3, routed_scaling=5.0,
        router_score="sigmoid_bias", expert_activation="relu2",
        tie_embeddings=False, norm_eps=1e-5, rope_theta=10000.0),
}
EXPERT_CASES = [(kind, groups) for kind in EXPERT_STACKS for groups in (1, 4)]
EXPERT_IDS = [f"{kind}-groups{groups}" for kind, groups in EXPERT_CASES]
# ``model.remat`` and ``precision.remat_policy``
KEPT, SAVE_NOTHING, REMAT_OFF = (True, "none"), (True, "save_nothing"), \
    (False, "none")


def _expert_stack(kind: str, groups: int, remat: bool, policy: str):
    cfg = ModelConfig(
        vocab_size=DEC_VOCAB, hidden_size=64, dtype="float32",
        attention_impl="xla", dropout_rate=0.0, remat=remat,
        expert_groups=groups, **EXPERT_STACKS[kind])
    # one group runs ``_sorted_experts`` under JAX's own differentiation,
    # several the loop over windows with its hand-written backward
    rows = moe.held_rows(EXP_B * EXP_S * cfg.expert_topk,
                         cfg.num_experts // groups, cfg.num_experts)
    assert (rows < EXP_B * EXP_S * cfg.expert_topk) == (groups > 1)
    return get_model(cfg, precision=PrecisionConfig(remat_policy=policy))


def _expert_inputs():
    rng = np.random.default_rng(11)
    return (jnp.asarray(rng.integers(0, DEC_VOCAB, (EXP_B, EXP_S)),
                        jnp.int32),)


def _expert_loss(model):
    def loss(params):
        out = model.apply({"params": params}, *_expert_inputs())
        return (out["logits"].astype(jnp.float32) ** 2).mean(), {
            key: out[f"moe_{key}"] for key in family.MOE_COUNTERS}

    return loss


@functools.cache
def _expert_params(kind: str, groups: int):
    return _expert_stack(kind, groups, False, "none").init(
        jax.random.key(0), *_expert_inputs())["params"]


@functools.cache
def _expert_step(kind: str, groups: int, remat: bool, policy: str):
    """Loss, the five counters and every gradient, op by op (as
    ``_decoder_grads``: the arithmetic the policies leave is compared)."""
    (loss, counters), grads = jax.value_and_grad(
        _expert_loss(_expert_stack(kind, groups, remat, policy)),
        has_aux=True)(_expert_params(kind, groups))
    return jax.device_get((loss, counters, grads))


def _equations(jaxpr, counts=None) -> dict:
    """How often each primitive stands in ``jaxpr``, the equations of
    every sub-jaxpr (a ``pjit``, the re-run pass's ``checkpoint``, a
    loop's body) counted where they stand."""
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _equations(sub, counts)
    return counts


@pytest.mark.parametrize("kind,groups", EXPERT_CASES, ids=EXPERT_IDS)
@pytest.mark.parametrize("remat,policy,passes", [
    (*KEPT, 1),             # once an expert layer
    (*SAVE_NOTHING, 2),     # the full re-run: twice
    (True, "dots_saveable", 2),   # what it says: the logits, not the choice
    (*REMAT_OFF, 1),
], ids=["kept", "save_nothing", "dots_saveable", "remat_off"])
def test_routing_equations_in_the_gradients_jaxpr(
        devices, kind, groups, remat, policy, passes):
    """``lax.top_k`` and the two argsorts of ``sort_by_expert`` stand
    once an expert layer in the gradient's jaxpr under the default policy
    and twice under the full re-run; the router's product stands a third
    time only where the logits are not kept."""
    model = _expert_stack(kind, groups, remat, policy)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          _expert_params(kind, groups))
    grad = jax.grad(lambda p: _expert_loss(model)(p)[0])
    counts = _equations(jax.make_jaxpr(grad)(params).jaxpr)
    assert counts["top_k"] == EXP_LAYERS * passes
    assert counts["sort"] == 2 * EXP_LAYERS * passes
    if (remat, policy) != SAVE_NOTHING:
        return
    kept = _equations(jax.make_jaxpr(jax.grad(lambda p: _expert_loss(
        _expert_stack(kind, groups, *KEPT))(p)[0]))(params).jaxpr)
    # the logits' product and the count (a scatter-add of ones) as well,
    # and the gather of the chosen sigmoid scores (the softmax router
    # takes ``top_k``'s values)
    assert counts["dot_general"] - kept["dot_general"] == EXP_LAYERS
    assert counts["scatter-add"] - kept["scatter-add"] == EXP_LAYERS
    assert counts["gather"] - kept["gather"] == (
        0 if kind == "smallthinker" else EXP_LAYERS)
    if groups > 1:       # and the pad of ``order`` to whole windows
        assert counts["pad"] - kept["pad"] == EXP_LAYERS


@pytest.mark.parametrize("kind,groups", EXPERT_CASES, ids=EXPERT_IDS)
@pytest.mark.parametrize("other", [SAVE_NOTHING, REMAT_OFF],
                         ids=["save_nothing", "remat_off"])
def test_kept_routing_leaves_loss_gradients_and_counters_alone(
        devices, kind, groups, other):
    """The re-run pass reads the first pass's own logits, choice and
    sort, not an identical second copy: loss, every parameter's gradient
    and the five ``moe_*`` counters equal the full re-run's and the
    un-remat'd model's, to 0.0."""
    loss, counters, grads = _expert_step(kind, groups, *KEPT)
    want_loss, want_counters, want_grads = _expert_step(kind, groups, *other)
    assert loss == want_loss and np.isfinite(loss)
    assert counters.keys() == set(family.MOE_COUNTERS) and all(
        counters[key] == want_counters[key] for key in counters)
    assert counters["dropped"] == 0.0 and counters["local_share"] == (
        1.0 if groups == 1 else pytest.approx(0.25, abs=0.1))
    flat = dict(jax.tree_util.tree_leaves_with_path(grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat.keys() == flat_want.keys() and len(flat) > 15
    for path, g in flat.items():
        name = jax.tree_util.keystr(path)
        # the selection bias enters the choice only
        assert np.any(g) != ("expert_bias" in name), name
        np.testing.assert_array_equal(g, flat_want[path], err_msg=name)


# What one kept name leaves out of the re-run pass, as the difference of
# the equation counts from a policy that keeps nothing.
ONE_NAME = {
    moe.LOGITS_NAME: {"dot_general": 1},
    moe.EXPERTS_NAME: {"top_k": 1},
    # ``take_along_axis`` of the sigmoid scores
    moe.CHOSEN_NAME: {"gather": 1},
    # ``inverse`` is the argsort of the order as sorted, not as named
    moe.ORDER_NAME: {"pad": 1},
    moe.INVERSE_NAME: {"sort": 1},
    moe.GROUP_SIZES_NAME: {"scatter-add": 1},
}


@pytest.mark.parametrize("score,groups", [
    ("sigmoid_bias", 1), ("sigmoid_bias", 4), ("softmax_topk", 4)])
@pytest.mark.parametrize("names", [
    *[(name,) for name in moe.ROUTING_NAMES],
    (moe.ORDER_NAME, moe.INVERSE_NAME), moe.ROUTING_NAMES],
    ids=lambda names: "+".join(n.removeprefix("moe_") for n in names)
    if len(names) < len(moe.ROUTING_NAMES) else "all")
def test_each_routing_name_keeps_what_it_names(devices, score, groups, names):
    """``DroplessMoE`` under ``jax.checkpoint`` with one name in the
    policy: only the operation that made the named value leaves the
    re-run pass; ``order`` and ``inverse`` together take both argsorts."""
    assert set(ONE_NAME) == set(moe.ROUTING_NAMES)
    layer = moe.DroplessMoE(num_experts=8, mlp_dim=32, topk=2, groups=groups,
                            dtype=jnp.float32, score=score)
    x = jax.ShapeDtypeStruct((EXP_B, EXP_S, 64), jnp.float32)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jnp.zeros(x.shape))["params"])

    def counts_under(*kept):
        run = jax.checkpoint(
            lambda p, x: layer.apply({"params": p}, x)[0].sum(),
            policy=checkpoint_policies.save_only_these_names(*kept))
        return _equations(
            jax.make_jaxpr(jax.grad(run, argnums=(0, 1)))(params, x).jaxpr)

    nothing, got = counts_under(), counts_under(*names)
    assert (nothing["top_k"], nothing["sort"]) == (2, 4)
    want: dict = {}
    for name in names:
        for op, n in ONE_NAME[name].items():
            want[op] = want.get(op, 0) + n
    if groups == 1:
        want.pop("pad", None)        # one group slices no window
    if score == "softmax_topk":
        # ``top_k`` makes both the choice and its scores there: it leaves
        # when both are kept, and there is no gather to leave
        want.pop("gather", None)
        if not {moe.EXPERTS_NAME, moe.CHOSEN_NAME} <= set(names):
            want.pop("top_k", None)
    if {moe.ORDER_NAME, moe.INVERSE_NAME} <= set(names):
        want["sort"] = 2
    gone = {op: nothing[op] - got[op]
            for op in ("dot_general", "top_k", "gather", "sort", "scatter-add",
                       "pad")
            if nothing[op] != got[op]}
    assert gone == want
    # a kept value's identity equation stands once, not twice
    assert nothing["name"] - got["name"] == len(names)


def _resnet_step_text():
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(base={
        "name": "remat-tags-test", "mesh": {"data": 1},
        "model": {"name": "resnet18_cifar", "num_classes": 10,
                  "dtype": "float32", "remat": True},
        "data": {"name": "cifar10", "num_classes": 10, "image_size": 32,
                 "global_batch_size": 2},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.1},
        "train": {"total_steps": 2}})
    builder = StepBuilder(cfg, create_mesh(cfg.mesh,
                                           devices=jax.devices()[:1]))
    sample = {"image": jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
              "label": jax.ShapeDtypeStruct((2,), jnp.int32)}
    return _compiled_step(builder, sample)


@pytest.mark.parametrize("step_text,experts", [
    (_bert_step_text, False), (_resnet_step_text, False),
    (_decoder_step_text, True)],
    ids=["bert", "resnet", "decoder_without_remat"])
def test_the_routings_names_are_inert_without_a_policy_that_asks_for_them(
        devices, monkeypatch, step_text, experts):
    """BERT's step, ResNet's (under its own ``nn.remat``) and an
    un-remat'd decoder's compile to the program they compile to with no
    routing value named, metadata and all: a name is no operation."""
    texts = []
    for named in (True, False):
        jax.clear_caches()
        if not named:
            monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
        texts.append(step_text())
    tagged, untagged = texts
    assert ("moe/router" in tagged) == experts
    assert tagged == untagged
