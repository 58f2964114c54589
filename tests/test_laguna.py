"""Laguna-S-2.1 through the decoder family (models/lfm2.py: window and
global attention layers with query-head counts and rotary rules of their
own, a per-head output gate, softmax-routed SwiGLU experts with scaled
weights beside a gated shared expert, a chip's share of the heads and of
the hidden units in mixer-then-feed-forward layers) against its plain
float32 reference (benchmarks/reference/laguna.py), at tiny widths on
the CPU: loss and every gradient on packed rows whose documents outrun
the window, the YaRN frequencies against numbers worked by hand, each
assumed equation's alternative, the shares of heads, units and experts,
what a remat'd layer keeps, and that the three decoders already in the
benchmark lower to the step they lowered to before."""

import collections
import dataclasses
import functools
import math
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from distributed_tensorflow_framework_tpu.core.config import (
    ModelConfig, load_config)
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as family
from distributed_tensorflow_framework_tpu.train import losses


@pytest.fixture(autouse=True, scope="module")
def compile_without_most_optimizations():
    """Every comparison here compiles a program and a reference once and
    runs them once on a few hundred tokens: the compiler's optimisation
    passes cost several times what they save. Float32 semantics stay."""
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", False)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML = os.path.join(ROOT, "configs", "laguna_s_2_1.yaml")
KINDS = ["full_attention", "sliding_attention", "full_attention"]
ROUTED, TOPK, S, VOCAB, WINDOW, HEAD = 16, 3, 128, 256, 24, 16
FACTOR = 0.1 * math.log(8.0) + 1.0       # attention_factor of a YaRN factor 8
# The cell's cut of the published model (benchmarks/configs/...json).
CUT = ["model.num_layers=5",
       "model.layer_types=[full_attention,sliding_attention,"
       "sliding_attention,sliding_attention,full_attention]",
       "model.expert_groups=32", "model.expert_group=0",
       "model.tensor_groups=8", "model.tensor_group=0",
       "model.vocab_size=12544"]


def model_config(**over) -> ModelConfig:
    """A dense global layer, a window layer and a global layer with
    experts: 4 query heads in a global layer and 6 in a window layer over
    2 key/value heads of 16 dims; the global layers rotate the first 8
    dims of a head with YaRN frequencies whose ramp lies inside them
    (theta 100 over 32 original positions: low 0, high 2), the window
    layers the whole head."""
    base = dict(
        name="laguna", vocab_size=VOCAB, hidden_size=64,
        num_layers=len(KINDS), layer_types=list(KINDS), num_dense_layers=1,
        num_heads=4, sliding_num_heads=6, num_kv_heads=2, head_dim=HEAD,
        qk_norm=False, attention_gate="per_head", sliding_window=WINDOW,
        rope_theta=100.0, rope_fraction=0.5, rope_yarn_factor=8.0,
        rope_yarn_original_len=32, rope_yarn_beta_fast=32.0,
        rope_yarn_beta_slow=1.0, rope_attention_factor=FACTOR,
        sliding_rope_theta=50.0, sliding_rope_fraction=1.0,
        mlp_dim=96, moe_mlp_dim=24, moe_shared_dim=32, num_experts=ROUTED,
        expert_topk=TOPK, routed_scaling=2.5, router_score="softmax_topk",
        expert_activation="silu", tie_embeddings=False, norm_eps=1e-6,
        dtype="float32", attention_impl="xla", dropout_rate=0.0)
    base.update(over)
    return ModelConfig(**base)


def rope_parameters(cfg: ModelConfig) -> dict:
    return {
        "full_attention": {
            "rope_theta": cfg.rope_theta, "rope_type": "yarn",
            "factor": cfg.rope_yarn_factor,
            "original_max_position_embeddings": cfg.rope_yarn_original_len,
            "beta_slow": cfg.rope_yarn_beta_slow,
            "beta_fast": cfg.rope_yarn_beta_fast,
            "attention_factor": cfg.rope_attention_factor,
            "partial_rotary_factor": cfg.rope_fraction},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": cfg.sliding_rope_theta,
            "partial_rotary_factor": cfg.sliding_rope_fraction}}


def hparams(cfg: ModelConfig) -> dict:
    """What the configuration's file tells the reference, from the
    program's own account of its share."""
    model = get_model(cfg)
    share = model.tensor_share() or {
        "attention": {"held": range(cfg.num_heads),
                      "kv_held": range(cfg.num_kv_heads)},
        "attention_window": {"held": range(cfg.sliding_num_heads)},
        "dense_ffn": {"held": [0, cfg.mlp_dim]},
        "shared_expert": {"held": [0, cfg.moe_shared_dim]}}
    units = lambda run: run[1] - run[0]  # noqa: E731
    return {
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.sliding_window,
        "rope_parameters": rope_parameters(cfg),
        "num_experts_per_tok": cfg.expert_topk,
        "moe_routed_scaling_factor": cfg.routed_scaling,
        "experts_routed": cfg.num_experts,
        "experts_held": list(moe.held_experts(
            cfg.num_experts, cfg.expert_groups, cfg.expert_group)),
        "heads_held": {
            "full_attention": list(share["attention"]["held"]),
            "sliding_attention": list(share["attention_window"]["held"]),
            "key_value": list(share["attention"]["kv_held"])},
        "dense_units_held": units(share["dense_ffn"]["held"]),
        "shared_units_held": units(share["shared_expert"]["held"])}


def packed_batch(seed=0, rows=2, s=S):
    """Three documents in each row (a padded tail in the first), each
    longer than the window of 24 but the second row's first."""
    rng = np.random.default_rng(seed)
    cuts = np.array([[37, 80, 119], [5, 64, s]])[:rows]
    idx = np.arange(s)[None, :]
    seg = (1 + (idx >= cuts[:, :1]) + (idx >= cuts[:, 1:2])) * (
        idx < cuts[:, 2:3])
    starts = np.where(idx >= cuts[:, 1:2], cuts[:, 1:2],
                      np.where(idx >= cuts[:, :1], cuts[:, :1], 0))
    last = (idx == cuts[:, :1] - 1) | (idx == cuts[:, 1:2] - 1) | (
        idx == cuts[:, 2:3] - 1)
    tokens = rng.integers(0, VOCAB, size=(rows, s))
    real = seg > 0
    return {
        "input_ids": jnp.asarray(np.where(real, tokens, 0), jnp.int32),
        "targets": jnp.asarray(np.where(real & ~last,
                                        np.roll(tokens, -1, 1), -1), jnp.int32),
        "segment_ids": jnp.asarray(seg, jnp.int32),
        "positions": jnp.asarray(np.where(real, idx - starts, 0), jnp.int32)}


def init(cfg, batch, seed=0):
    """The model and seeded parameters of its tree's shapes, drawn here
    (the program's own initialisers compile for seconds; the trainer test
    runs them): kernels normal over the square root of their fan-in, the
    gates' three times that (so that a gate is not 0.5 everywhere),
    scales around 1."""
    model = get_model(cfg)
    shapes = jax.eval_shape(
        functools.partial(model.init, train=False), jax.random.key(0),
        batch["input_ids"], batch["segment_ids"],
        batch["positions"])["params"]
    rng = np.random.default_rng(seed)

    def draw(path, leaf):
        name = path[-1].key if path[-1].key != "kernel" else path[-2].key
        normal = rng.standard_normal(leaf.shape)
        if name == "scale":
            value = 1.0 + 0.1 * normal
        elif name in ("embedding", "lm_head"):
            value = 0.1 * normal
        elif name == "gate":             # the router's and the heads' gates
            value = 3.0 * normal / np.sqrt(leaf.shape[-2])
        else:
            value = normal / np.sqrt(leaf.shape[-2])
        return jnp.asarray(value, jnp.float32)

    return model, jax.tree_util.tree_map_with_path(draw, shapes)


def program_loss(model, params, batch):
    out = model.apply({"params": params}, batch["input_ids"],
                      batch["segment_ids"], batch["positions"], train=True)
    return losses.causal_lm_loss(out["logits"], batch["targets"])[0]


def assert_gradients_close(got, want, *, atol):
    """Leaf by leaf, each scaled by the wanted leaf's largest entry."""
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert flat_got.keys() == flat_want.keys()
    for path, g in flat_got.items():
        w = np.asarray(flat_want[path])
        scale = float(np.max(np.abs(w))) + 1e-8
        np.testing.assert_allclose(np.asarray(g) / scale, w / scale,
                                   atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


_CONFIGS: dict = {}


@functools.lru_cache(maxsize=None)
def _reference_of(cfg_repr: str, seed: int):
    cfg = _CONFIGS[cfg_repr]
    batch = packed_batch(seed)
    _, params = init(cfg, batch, seed)
    h = hparams(cfg)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch, h)))(params)


def reference_loss_and_gradients(cfg, seed):
    """The reference's loss and gradients for ``cfg``'s own parameters,
    computed once per configuration and seed in a worker."""
    _CONFIGS[repr(cfg)] = cfg
    return _reference_of(repr(cfg), seed)


def assert_model_matches_reference(cfg, *, program=None, tol=2e-5, seed=0,
                                   loss_first=False):
    """Parameters and reference from ``cfg``; the program from ``program``
    (a configuration or anything with ``apply``) where one is put in its
    place, applied to the same parameters. ``loss_first`` compares the
    loss before the gradients are computed at all (for a program that is
    expected to fail)."""
    batch = packed_batch(seed)
    model, params = init(cfg, batch, seed)
    if program is not None:
        model = get_model(program) if isinstance(program,
                                                 ModelConfig) else program
    want, want_g = reference_loss_and_gradients(cfg, seed)
    with jax.default_matmul_precision("highest"):
        if loss_first:
            got = jax.jit(functools.partial(program_loss, model))(params,
                                                                  batch)
            np.testing.assert_allclose(float(got), float(want), rtol=tol)
        got, got_g = jax.jit(jax.value_and_grad(
            functools.partial(program_loss, model)))(params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    assert_gradients_close(got_g, want_g, atol=20 * tol)
    return got_g


SHARES = {"whole": {},
          "experts1of4": {"expert_groups": 4, "expert_group": 1},
          "heads1of2": {"tensor_groups": 2, "tensor_group": 1},
          "both": {"expert_groups": 4, "expert_group": 3,
                   "tensor_groups": 2, "tensor_group": 0}}


@pytest.mark.parametrize("impl,share", [
    ("xla", "whole"), ("xla", "experts1of4"), ("xla", "heads1of2"),
    ("pallas", "both")])
def test_loss_and_gradients_match_the_reference(devices, impl, share):
    """Loss and every gradient leaf, float32, packed rows with three
    documents each, most of them longer than the window; whole and as a
    share of experts, of heads and units, of both."""
    grads = assert_model_matches_reference(
        model_config(attention_impl=impl, **SHARES[share]))
    for layer, module in (("layer0", "attn"), ("layer1", "attn_window"),
                          ("layer2", "attn")):
        for name in ("query", "key", "value", "gate", "attn_out"):
            leaf = jax.tree.leaves(grads[layer][module][name])[0]
            assert np.any(np.asarray(leaf)), (layer, name)
    for name in ("mlp_in", "mlp_up", "mlp_out"):
        assert np.any(np.asarray(grads["layer0"][name]["kernel"])), name
    for name in ("gate", "w1", "w3", "w2", "shared"):
        for leaf in jax.tree.leaves(grads["layer1"]["moe"][name]):
            assert np.any(np.asarray(leaf)), name
    assert "expert_bias" not in grads["layer1"]["moe"]


def test_bfloat16_activations_stay_near_the_reference(devices):
    """The step as configurations run it (bfloat16 over float32
    parameters) stays within 2e-3 of the float32 loss at this size: the
    band a cell's tighter, measured limits start from."""
    cfg = model_config()
    batch = packed_batch(3)
    _, params = init(cfg, batch, 3)
    got = jax.jit(functools.partial(
        program_loss, get_model(model_config(dtype="bfloat16"))))(
            params, batch)
    want, _ = reference_loss_and_gradients(cfg, 3)
    assert abs(float(got) - float(want)) < 2e-3 * float(want)


# ------------------------------------------------------------------- remat --
def _equations(jaxpr, counts=None) -> dict:
    counts = collections.Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _equations(sub, counts)
    return counts


def test_a_remat_layer_runs_neither_the_kernel_nor_the_routing_twice(devices):
    """Under ``model.remat`` a gated layer keeps PR 31's and PR 33's
    names: the gradient's jaxpr holds one forward kernel an attention
    layer and one top-k and two sorts an expert layer, as without remat
    (``save_nothing`` holds each twice), while the gate's product is in
    the re-run pass; loss, gradients and counters are the plain model's."""
    from distributed_tensorflow_framework_tpu.core.config import (
        PrecisionConfig)

    share = SHARES["experts1of4"]
    batch = packed_batch(4)
    model, params = init(model_config(attention_impl="pallas", **share),
                         batch, 4)
    kept = get_model(model_config(attention_impl="pallas", remat=True,
                                  **share))
    nothing = get_model(
        model_config(attention_impl="pallas", remat=True, **share),
        precision=PrecisionConfig(remat_policy="save_nothing"))

    def value_and_grad(m):
        def of(p):
            out = m.apply({"params": p}, batch["input_ids"],
                          batch["segment_ids"], batch["positions"])
            loss = losses.causal_lm_loss(out.pop("logits"),
                                         batch["targets"])[0]
            return loss, out
        return jax.value_and_grad(of, has_aux=True)

    def counts(m):
        jaxpr = jax.make_jaxpr(value_and_grad(m))(params)
        eqns = _equations(jaxpr.jaxpr)
        return (len(re.findall(r"name=_flash_fwd\b", str(jaxpr))),
                eqns["top_k"], eqns["sort"], eqns["logistic"])

    attention, experts = len(KINDS), len(KINDS) - 1
    plain = counts(model)
    assert plain[:3] == (attention, experts, 2 * experts)
    again = counts(kept)
    assert again[:3] == plain[:3]
    assert again[3] > plain[3]           # the gate's sigmoid is re-run
    assert counts(nothing)[:3] == (2 * attention, 2 * experts, 4 * experts)

    ((a, ca), ga), ((b, cb), gb) = (jax.jit(value_and_grad(m))(params)
                                    for m in (model, kept))
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    assert "attn_gate_mean" in ca and "moe_compact" in ca
    assert {k: float(v) for k, v in ca.items()} == {
        k: float(v) for k, v in cb.items()}
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# ------------------------------------------------------- the rotary rules --
def test_yarn_frequencies_are_the_numbers_worked_by_hand(devices):
    """The published global rule (factor 128 over 8192 positions, betas
    32 and 1, 64 rotated dims at theta 5e5): ``d(32) = 64 ln(8192 / 64
    pi) / (2 ln 5e5) = 9.04`` and ``d(1) = 17.49``, so the ramp runs from
    pair 9 to pair 18: pairs 0-9 keep ``f_i = 5e5^(-i/32)``, pairs 18-31
    are ``f_i / 128`` and pair ``i`` between is ``f_i (1 - (i - 9) / 9 x
    127 / 128)``; the attention factor is ``0.1 ln 128 + 1``. Program and
    reference alike."""
    assert family.yarn_correction_range(64, 5e5, 8192, 32.0, 1.0) == (9, 18)
    rule = family.RotaryRule(fraction=0.5, yarn_factor=128.0,
                             yarn_original_len=8192)
    got = np.asarray(family.yarn_inv_freq(64, 5e5, rule), np.float64)
    plain = 5e5 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], plain[18:] / 128.0, rtol=1e-6)
    # by hand: f_12 = 5e5^(-0.375) = 7.29266e-3, ramp 1/3: x (1 - 127/384)
    np.testing.assert_allclose(got[12], 4.88077e-3, rtol=1e-5)
    # f_15 = 5e5^(-15/32) = 2.13112e-3, ramp 2/3: x (1 - 254/384)
    np.testing.assert_allclose(got[15], 7.21473e-4, rtol=1e-5)
    published = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                 "original_max_position_embeddings": 8192, "beta_slow": 1,
                 "beta_fast": 32, "attention_factor": 1.4852030263919618,
                 "partial_rotary_factor": 0.5}
    want, factor = ref.inv_frequencies(published, 64)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-6)
    assert ref.yarn_range(published, 64) == (9, 18)
    assert factor == pytest.approx(0.1 * math.log(128.0) + 1.0, rel=1e-12)


def test_the_rotation_turns_the_first_dims_and_passes_the_rest(devices):
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, HEAD))
    positions = jnp.arange(8)[None, :]
    rule = family.RotaryRule(fraction=0.5, attention_factor=1.5)
    got = family.rotary(x, positions, 100.0, rule)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    # position 0: cos = 1, sin = 0, so the rotated dims are scaled alone
    np.testing.assert_allclose(np.asarray(got[:, 0, :, :8]),
                               1.5 * np.asarray(x[:, 0, :, :8]), rtol=1e-6)
    whole = family.rotary(x[..., :8], positions, 100.0)
    np.testing.assert_allclose(np.asarray(got[..., :8]),
                               1.5 * np.asarray(whole), rtol=1e-5, atol=1e-6)


# --------------------------------------------- what fails the comparison --
def _intercepted(edit, cfg=None):
    """The program with ``edit(module, next_fun, args, stream)`` in place
    of every module call; ``stream`` is the un-normed stream entering
    the sublayer the module sits in."""
    seen = {}

    def interceptor(next_fun, args, kwargs, context):
        module = context.module
        if context.method_name != "__call__":
            return next_fun(*args, **kwargs)
        if isinstance(module, family.RMSNorm) and module.name in (
                "mixer_norm", "ffn_norm"):
            seen["stream"] = args[0]
        out = edit(module, next_fun, args, seen.get("stream"))
        return next_fun(*args, **kwargs) if out is None else out

    class Edited:
        def __init__(self, given):
            self.model = get_model(cfg or given)
            self.seen = seen

        def apply(self, *args, **kwargs):
            with nn.intercept_methods(interceptor):
                return self.model.apply(*args, **kwargs)

    return Edited


def _on(cls, make):
    def edit(module, next_fun, args, stream):
        return make(next_fun, args, stream) if isinstance(module,
                                                          cls) else None
    return edit


def _with_parameters(cfg: ModelConfig, extra):
    """``cfg``'s model, applied to the given parameters plus
    ``extra(layer's parameters)`` for each layer: the parameters an
    alternative has and the model does not."""
    class Given:
        model = get_model(cfg)

        def apply(self, variables, *args, **kwargs):
            params = {name: extra(name, sub) if name.startswith("layer")
                      else sub for name, sub in variables["params"].items()}
            return self.model.apply({"params": params}, *args, **kwargs)

    return Given()


def _unit_qk_norms(name, layer):
    scope = "attn" if "attn" in layer else "attn_window"
    scale = {"scale": jnp.ones((HEAD,), jnp.float32)}
    return {**layer, scope: {**layer[scope], "q_norm": scale,
                             "k_norm": scale}}


def _zero_selection_bias(name, layer):
    if "moe" not in layer:
        return layer
    return {**layer, "moe": {**layer["moe"],
                             "expert_bias": jnp.zeros((ROUTED,))}}


def _a_gate_for_each_channel(out, u, kernel):
    """``n x head_dim`` gates: each head's column of ``W_g`` fans out to
    its channels, steeper from one channel to the next."""
    d = out.shape[-1]
    wide = jnp.repeat(kernel, d, axis=1) * jnp.tile(
        jnp.linspace(0.5, 1.5, d), kernel.shape[1])
    g = jax.nn.sigmoid(u.astype(jnp.float32) @ wide).reshape(out.shape)
    return out.astype(jnp.float32) * g, g.mean(axis=-1)


def _interleaved_pairs(x, positions, theta, rule=None):
    """The same frequencies and factor, turning the pairs ``(2i, 2i +
    1)`` of the rotated dims instead of ``(i, i + rot / 2)``."""
    rule = rule or family.RotaryRule()
    d = x.shape[-1]
    rot = int(d * rule.fraction)
    first = jnp.concatenate([x[..., 0:rot:2], x[..., 1:rot:2]], axis=-1)
    halves = _ROTARY(jnp.concatenate([first, x[..., rot:]], axis=-1),
                     positions, theta, rule)
    a, b = jnp.split(halves[..., :rot], 2, axis=-1)
    turned = jnp.stack([a, b], axis=-1).reshape(*x.shape[:-1], rot)
    return jnp.concatenate([turned, halves[..., rot:]], axis=-1)


_ROTARY = family.rotary
_ATTENTION = family.causal_attention_xla


def _factor_on_the_softmax_scale(q, k, v, segment_ids=None,
                                 dtype=jnp.float32, window=None):
    """cos and sin unscaled (the configuration beside it), the global
    layers' scores times the factor's square instead."""
    if window is None:
        return _ATTENTION(q * FACTOR ** 2, k, v, segment_ids, dtype)
    return _ATTENTION(q, k, v, segment_ids, dtype, window=window)


def _tokens(stream, like):
    return stream.reshape(like.shape).astype(like.dtype)


def _shared_gate(f, a, stream):
    x = a[0]
    return jax.nn.sigmoid(
        x.astype(jnp.float32).sum(-1, keepdims=True) / 8.0) * f(*a)


# name -> (configuration in the program's place or None, patches, program
# factory or None)
ALTERNATIVES = {
    "a_gate_for_each_channel": dict(
        patches=[(family, "gate_heads", _a_gate_for_each_channel)]),
    "the_gate_reads_the_unnormed_stream": dict(program="stream_gate"),
    "q_and_k_normed_before_the_rotation": dict(
        program=lambda cfg: _with_parameters(
            dataclasses.replace(cfg, qk_norm=True), _unit_qk_norms)),
    "interleaved_pairs_rotate": dict(
        patches=[(family, "rotary", _interleaved_pairs)]),
    "attention_factor_on_the_softmax_scale_alone": dict(
        program=lambda cfg: dataclasses.replace(
            cfg, rope_attention_factor=1.0),
        patches=[(family, "causal_attention_xla",
                  _factor_on_the_softmax_scale)]),
    "a_sigmoid_router_with_a_selection_bias": dict(
        program=lambda cfg: _with_parameters(
            dataclasses.replace(cfg, router_score="sigmoid_bias"),
            _zero_selection_bias)),
    "reglu_experts": dict(
        program=lambda cfg: dataclasses.replace(
            cfg, expert_activation="relu")),
    "a_gate_on_the_shared_expert": dict(
        program=_intercepted(_on(moe.SharedExpert, _shared_gate))),
    "the_shared_expert_reads_the_unnormed_stream": dict(
        program=_intercepted(_on(
            moe.SharedExpert,
            lambda f, a, stream: f(_tokens(stream, a[0]))))),
    "the_windows_edge_one_key_further": dict(
        program=lambda cfg: dataclasses.replace(
            cfg, sliding_window=WINDOW + 1)),
    "no_scaling_factor_on_the_routed_weights": dict(
        program=lambda cfg: dataclasses.replace(cfg, routed_scaling=1.0)),
    "the_whole_head_rotates_in_a_global_layer": dict(
        program=lambda cfg: dataclasses.replace(cfg, rope_fraction=1.0)),
    "plain_frequencies_in_a_global_layer": dict(
        program=lambda cfg: dataclasses.replace(cfg, rope_yarn_factor=0.0)),
}


def _stream_gate_program(cfg, monkeypatch):
    """The gate reads the stream as it enters the layer, before the
    norm: the interceptor sees it, the seam uses it."""
    program = _intercepted(lambda *a: None)(cfg)
    gate = family.gate_heads
    monkeypatch.setattr(
        family, "gate_heads",
        # (``init`` traces the plain model, which records no stream)
        lambda out, u, kernel: gate(out, program.seen.get("stream", u),
                                    kernel))
    return program


@pytest.mark.parametrize("what", sorted(ALTERNATIVES))
def test_an_assumed_equations_alternative_fails_the_comparison(
        devices, monkeypatch, what):
    """Each item of the configuration's ``assumed`` (and each number of
    the two rotary rules), its alternative put in the PROGRAM's place,
    must fail the float32 comparison that the model itself passes
    (``test_the_seams_themselves_change_nothing``,
    ``test_loss_and_gradients_match_the_reference[xla-whole]``)."""
    cfg = model_config()
    how = ALTERNATIVES[what]
    for where, name, alternative in how.get("patches", ()):
        monkeypatch.setattr(where, name, alternative)
    program = how.get("program")
    if program == "stream_gate":
        program = _stream_gate_program(cfg, monkeypatch)
    elif program is not None:
        program = program(cfg)
    with pytest.raises(AssertionError):
        assert_model_matches_reference(cfg, program=program, loss_first=True)


def test_the_seams_themselves_change_nothing(devices, monkeypatch):
    """The interceptor handing every module what it was handed, and the
    patched seams calling what they replace, pass: the failures above
    are the alternatives'."""
    calls = []
    same = _intercepted(lambda module, f, a, stream: calls.append(
        type(module).__name__))(model_config())
    gate, rotary, attention = (family.gate_heads, family.rotary,
                               family.causal_attention_xla)
    monkeypatch.setattr(family, "gate_heads",
                        lambda *a: calls.append("gate") or gate(*a))
    monkeypatch.setattr(family, "rotary",
                        lambda *a: calls.append("rotary") or rotary(*a))
    monkeypatch.setattr(
        family, "causal_attention_xla",
        lambda *a, **k: calls.append("attention") or attention(*a, **k))
    assert_model_matches_reference(model_config(), program=same)
    assert {"DroplessMoE", "SharedExpert", "GroupedQueryAttention", "gate",
            "rotary", "attention"} <= set(calls)


# ---------------------------------------------------------------- the share --
def attention_share_of(full, heads, cfg, groups, group):
    d = cfg.head_dim
    q = family.held_heads(heads, groups, group)
    per_kv = heads // cfg.num_kv_heads
    kv = sorted({i // per_kv for i in q})
    q_cols = np.arange(q.start * d, q.stop * d)
    kv_cols = np.concatenate([np.arange(i * d, (i + 1) * d) for i in kv])
    return {"query": {"kernel": full["query"]["kernel"][:, q_cols]},
            "key": {"kernel": full["key"]["kernel"][:, kv_cols]},
            "value": {"kernel": full["value"]["kernel"][:, kv_cols]},
            "gate": full["gate"][:, q.start:q.stop],
            "attn_out": {"kernel": full["attn_out"]["kernel"][q_cols]}}


def gated_unit_share_of(full, names, units):
    """A run of a gated unit's hidden units: columns of its two
    in-projections, rows of its out-projection."""
    gate, up, down = names
    cols = slice(units.start, units.stop)
    return {gate: {"kernel": full[gate]["kernel"][:, cols]},
            up: {"kernel": full[up]["kernel"][:, cols]},
            down: {"kernel": full[down]["kernel"][cols]}}


def expert_layer_share_of(full, tensor, expert):
    groups, group = tensor
    units = family.held_heads(32, groups, group)
    held = moe.held_experts(ROUTED, *expert)
    return {"gate": full["gate"],
            **{w: full[w][held.start:held.stop] for w in ("w1", "w3", "w2")},
            "shared": gated_unit_share_of(
                full["shared"], ("gate", "up", "down"), units)}


@pytest.mark.parametrize("tensor_groups,expert_groups", [(2, 4), (2, 8)])
def test_the_shares_add_up_to_the_uncut_layers(devices, tensor_groups,
                                               expert_groups):
    """All ``tensor_groups x expert_groups`` shares of a deployment: each
    tensor group's query heads of a global and of a window layer (their
    own counts, the key/value heads they read, their columns of the
    gate), its run of the dense feed-forward's hidden units, each expert
    group's experts' part of the routed sum and each tensor group's run
    of the shared expert's units add up to the uncut reference layers;
    the router and the norms, which every chip computes alike, enter
    once."""
    cfg = model_config(num_heads=8, sliding_num_heads=12, num_kv_heads=4)
    batch = packed_batch(2)
    _, params = init(cfg, batch, 2)
    segments, positions = batch["segment_ids"], batch["positions"]
    u = jax.random.normal(jax.random.key(3), (2, S, cfg.hidden_size))
    h = hparams(cfg)
    tg, eg = tensor_groups, expert_groups
    dense_names = ("mlp_in", "mlp_up", "mlp_out")
    with jax.default_matmul_precision("highest"):
        attention = {"attn": 0.0, "attn_window": 0.0}
        dense = routed = shared = 0.0
        local = 0.0
        for t in range(tg):
            part = get_model(dataclasses.replace(
                cfg, tensor_groups=tg, tensor_group=t))
            share = part.tensor_share()
            for layer, (kind, scope, heads) in {
                    "layer2": ("full_attention", "attn", 8),
                    "layer1": ("sliding_attention", "attn_window", 12),
            }.items():
                n, nkv, d = part._attention_held(kind)
                key = "attention" if scope == "attn" else "attention_window"
                assert (len(share[key]["held"]), len(share[key]["kv_held"])
                        ) == (n, nkv) == (heads // tg, 4 // tg)
                theta, rule = part._rotary(kind)
                module = family.GroupedQueryAttention(
                    n, nkv, theta, cfg.norm_eps, jnp.float32, "xla",
                    head_dim=d, qk_norm=False, rope_rule=rule,
                    gate="per_head",
                    window=WINDOW if scope == "attn_window" else None)
                out, gate_mean = jax.jit(module.apply)(
                    {"params": attention_share_of(
                        params[layer][scope], heads, cfg, tg, t)},
                    u, segments, positions)
                assert 0.0 < float(gate_mean) < 1.0
                attention[scope] = attention[scope] + out
            units = family.held_heads(cfg.mlp_dim, tg, t)
            assert share["dense_ffn"] == {
                "units": 96, "held": [units.start, units.stop]}
            mine = gated_unit_share_of(params["layer0"], dense_names, units)
            dense = dense + ref.swiglu(
                *(mine[name]["kernel"] for name in dense_names), u)
            assert share["shared_expert"]["units"] == 32
            mine = expert_layer_share_of(params["layer1"]["moe"], (tg, t),
                                         (1, 0))["shared"]
            shared = shared + jax.jit(moe.SharedExpert(
                32 // tg, jnp.float32, activation="silu").apply)(
                    {"params": mine}, u)
        for e in range(eg):
            # chip (e mod tg, e): its experts' part of the routed sum and
            # its run of the shared units, which is counted above
            t = e % tg
            layer = moe.DroplessMoE(
                num_experts=ROUTED, mlp_dim=24, topk=TOPK, groups=eg,
                group=e, dtype=jnp.float32, score="softmax_topk",
                activation="silu", shared_dim=32 // tg, weight_scale=2.5)
            mine = expert_layer_share_of(params["layer1"]["moe"], (tg, t),
                                         (eg, e))
            out, counters = jax.jit(layer.apply)({"params": mine}, u)
            own_shared = jax.jit(moe.SharedExpert(
                32 // tg, jnp.float32, activation="silu").apply)(
                    {"params": mine["shared"]}, u)
            routed = routed + out - own_shared
            local += float(counters["local_assignments"])
            assert float(counters["dropped"]) == 0.0
        want = {
            kind: jax.jit(functools.partial(
                ref.attention, kind=kind, h=h))(
                    params[layer][scope], u, segments, positions)
            for kind, layer, scope in (
                ("full_attention", "layer2", "attn"),
                ("sliding_attention", "layer1", "attn_window"))}
        want_dense = ref.swiglu(
            *(params["layer0"][name]["kernel"] for name in dense_names), u)
        want_experts = jax.jit(lambda p: ref.expert_layer(p, u, h))(
            params["layer1"]["moe"])
    assert local == 2 * S * TOPK          # every assignment computed once
    for got, wanted in (
            (attention["attn"], want["full_attention"]),
            (attention["attn_window"], want["sliding_attention"]),
            (dense, want_dense), (routed + shared, want_experts)):
        wanted = np.asarray(wanted)
        np.testing.assert_allclose(np.asarray(got), wanted,
                                   atol=5e-6 * np.abs(wanted).max())


def test_the_dense_layer_of_a_share_holds_its_run_of_units(devices):
    """Through the model itself: a tensor share's layer 0 has ``mlp_dim /
    groups`` hidden units, its expert layers ``moe_shared_dim / groups``,
    its attention layers their kind's heads."""
    cfg = model_config(tensor_groups=2, tensor_group=1, expert_groups=4)
    batch = packed_batch(0)
    model = get_model(cfg)
    shapes = jax.eval_shape(
        functools.partial(model.init, train=False), jax.random.key(0),
        batch["input_ids"])["params"]
    assert shapes["layer0"]["mlp_in"]["kernel"].shape == (64, 48)
    assert shapes["layer0"]["mlp_out"]["kernel"].shape == (48, 64)
    assert shapes["layer0"]["attn"]["query"]["kernel"].shape == (64, 2 * HEAD)
    assert shapes["layer0"]["attn"]["key"]["kernel"].shape == (64, HEAD)
    assert shapes["layer0"]["attn"]["gate"].shape == (64, 2)
    assert shapes["layer1"]["attn_window"]["query"]["kernel"].shape == (
        64, 3 * HEAD)
    assert shapes["layer1"]["attn_window"]["gate"].shape == (64, 3)
    assert shapes["layer1"]["moe"]["shared"]["gate"]["kernel"].shape == (
        64, 16)
    assert shapes["layer1"]["moe"]["w1"].shape == (4, 64, 24)
    assert model.tensor_share() == {
        "groups": 2, "group": 1,
        "attention": {"heads": 4, "held": [2, 3], "kv_heads": 2,
                      "kv_held": [1]},
        "attention_window": {"heads": 6, "held": [3, 4, 5], "kv_heads": 2,
                             "kv_held": [1]},
        "dense_ffn": {"units": 96, "held": [48, 96]},
        "shared_expert": {"units": 32, "held": [16, 32]}}


@pytest.mark.parametrize("bad,says", [
    (dict(tensor_groups=4), "whole, even shares"),   # 6 window heads
    (dict(tensor_groups=2, mlp_dim=97), "dense"),
    (dict(tensor_groups=2, moe_shared_dim=33), "shared"),
    (dict(tensor_groups=2, tensor_group=2), "is not one of"),
    (dict(layer_types=["conv", "full_attention", "full_attention"],
          tensor_groups=2), "conv layer"),
    (dict(layer_types=["conv", "full_attention", "full_attention"],
          out_proj_init_std=0.01), "conv layer"),
    (dict(sliding_num_heads=5), "sliding_num_heads"),
    (dict(attention_gate="per_channel"), "attention_gate"),
    (dict(rope_fraction=0.3), "rope_fraction"),
    (dict(sliding_rope_fraction=1.5), "sliding_rope_fraction"),
    (dict(rope_yarn_original_len=0), "rope_yarn"),
    (dict(rope_yarn_factor=0.5), "rope_yarn"),
    (dict(rope_attention_factor=0.0), "rope_attention_factor")])
def test_bad_configurations_are_refused_by_name(devices, bad, says):
    with pytest.raises(ValueError, match=says):
        get_model(model_config(**bad))


# ------------------------------------------------- the published model's cut --
def test_the_published_yaml_is_the_118b_model(devices):
    cfg = load_config(YAML, []).model
    kinds = cfg.layer_types
    assert (len(kinds), kinds.count("full_attention"),
            kinds.count("sliding_attention")) == (48, 12, 36)
    assert kinds[:5] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert (cfg.hidden_size, cfg.num_heads, cfg.sliding_num_heads,
            cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) == (
                3072, 48, 72, 8, 128, 100352)
    assert (cfg.sliding_window, cfg.attention_gate, cfg.qk_norm) == (
        512, "per_head", False)
    assert (cfg.rope_theta, cfg.rope_fraction, cfg.rope_yarn_factor,
            cfg.rope_yarn_original_len, cfg.rope_yarn_beta_fast,
            cfg.rope_yarn_beta_slow) == (5e5, 0.5, 128.0, 8192, 32.0, 1.0)
    assert cfg.rope_attention_factor == pytest.approx(
        0.1 * math.log(128.0) + 1.0, rel=1e-15)
    assert (cfg.sliding_rope_theta, cfg.sliding_rope_fraction) == (1e4, 1.0)
    assert (cfg.num_dense_layers, cfg.mlp_dim, cfg.num_experts,
            cfg.expert_topk, cfg.moe_mlp_dim, cfg.moe_shared_dim,
            cfg.routed_scaling) == (1, 12288, 256, 10, 1024, 1024, 2.5)
    assert (cfg.router_score, cfg.expert_activation, cfg.router_input) == (
        "softmax_topk", "silu", "ffn_norm")
    assert cfg.remat and cfg.attention_impl == "pallas"
    assert not cfg.tie_embeddings and cfg.norm_eps == 1e-6
    # 0.02 / sqrt(2 x 48), under unit-variance embeddings
    assert cfg.embed_init_std == 1.0
    assert cfg.out_proj_init_std == pytest.approx(0.02 / math.sqrt(96),
                                                  rel=1e-3)


def test_the_kernel_check_has_the_cells_window_call():
    """``scripts/verify_flash_kernels.py`` (the smoke's kernel leg) runs
    the window layers' call of ``laguna_s_s16384``: a window of 512,
    narrower than the kernels' key tile, nine query heads over one
    key/value head of 128, 16384 keys."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
    from scripts import verify_flash_kernels as vfk

    assert vfk._window_cases()["window512_d128_s16384"] == (
        16384, None, jnp.bfloat16, 512, None)
    assert vfk.WINDOW_CASE_HEADS["window512_d128_s16384"] == (9, 1)
    tile = fa.select_dispatch(16384, 16384, jnp.bfloat16, 128)
    assert (tile.family, tile.backward, tile.bwd_block_k) == (
        "stream", "two_pass", 1024)
    # two key tiles a row block, or one: 47 of 272 causal visits
    assert fa.window_block_counts(
        16384, 16384, tile.bwd_block_q, tile.bwd_block_k, 512) == (47, 272)


def test_the_grid_share_is_the_kernels_count(devices):
    """ISSUE 37's number at the cell's shapes: a window of 512 on
    512 x 1024 tiles launches 32*2 + 32*2 + 16*3 = 176 programs a head
    and row over the forward, dq and dk/dv kernels, 141 of them visits;
    absent without the kernels."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    cfg = load_config(YAML, CUT).model
    model = get_model(cfg)
    assert model.window_grid_share(16384) == pytest.approx(141 / 176)
    grid = fa.window_grid(
        16384, 16384, cfg.sliding_window,
        fa.select_dispatch(16384, 16384, jnp.bfloat16, cfg.head_dim))
    assert (grid["k_axis"], grid["q_axis"], grid["launched"]) == (2, 3, 176)
    assert get_model(load_config(
        YAML, [*CUT, "model.attention_impl=xla"]).model).window_grid_share(
            16384) is None


def test_the_cells_cut_is_436_million_parameters(devices):
    """The cut of benchmarks/configs/laguna_s_2_1.json: shapes only,
    nothing of this size is built."""
    cfg = load_config(YAML, CUT).model
    model = get_model(cfg)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros(ids.shape, ids.dtype),
                           train=False))["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    norms = 2 * 3072
    # 6 of 48 query heads over their key/value head, the gate's 6 columns
    global_attention = 2 * 3072 * 768 + 2 * 3072 * 128 + 3072 * 6
    window_attention = 2 * 3072 * 1152 + 2 * 3072 * 128 + 3072 * 9
    assert (global_attention, window_attention) == (5_523_456, 7_891_968)
    experts = 3072 * 256 + 3 * 3072 * 128 + 8 * 3 * 3072 * 1024
    assert experts == 77_463_552
    assert count(shapes["layer0"]) == (
        global_attention + 3 * 3072 * 1536 + norms)            # 19.68M
    for i in (1, 2, 3):
        assert count(shapes[f"layer{i}"]) == (
            window_attention + experts + norms)                # 85.36M
    assert count(shapes["layer4"]) == global_attention + experts + norms
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == (
        2 * 12544 * 3072)
    assert round(count(shapes) / 1e6, 1) == 435.8
    assert model.expert_share()["held"] == list(range(8))
    share = model.tensor_share()
    assert share["attention"] == {"heads": 48, "held": [0, 1, 2, 3, 4, 5],
                                  "kv_heads": 8, "kv_held": [0]}
    assert share["attention_window"] == {
        "heads": 72, "held": list(range(9)), "kv_heads": 8, "kv_held": [0]}
    assert share["dense_ffn"] == {"units": 12288, "held": [0, 1536]}
    assert share["shared_expert"] == {"units": 1024, "held": [0, 128]}


def test_family_names_and_task():
    from distributed_tensorflow_framework_tpu.models import builtin_task
    from distributed_tensorflow_framework_tpu.models.bert import (
        decode_support_reason)

    assert builtin_task("laguna") == "causal_lm"
    assert "trains only" in decode_support_reason(model_config())


def tiny_cut() -> list:
    return [
        "model.num_layers=3",
        "model.layer_types=[full_attention,sliding_attention,full_attention]",
        "model.hidden_size=64", "model.num_heads=4",
        "model.sliding_num_heads=6", "model.num_kv_heads=2",
        f"model.head_dim={HEAD}", f"model.sliding_window={WINDOW}",
        "model.rope_yarn_original_len=32", "model.rope_yarn_factor=8.0",
        "model.rope_theta=100.0", "model.mlp_dim=96", "model.moe_mlp_dim=24",
        "model.moe_shared_dim=32", f"model.num_experts={ROUTED}",
        f"model.expert_topk={TOPK}", "model.expert_groups=4",
        "model.expert_group=1", "model.tensor_groups=2",
        "model.tensor_group=1", f"model.vocab_size={VOCAB}",
        f"data.vocab_size={VOCAB}", f"data.seq_len={S}",
        "data.global_batch_size=2", "mesh.data=1", "model.dtype=float32"]


def test_the_trainer_step_gives_the_references_loss_and_grad_norm(devices):
    """``StepBuilder`` from the shipped YAML with a tiny cut, the
    ``causal_lm`` task, AdamW and the clip, remat and the kernels on, a
    share of heads, units and experts: the step's ``loss`` and
    ``grad_norm`` are the reference's, and the counters ride its metrics:
    the gate's mean over the three attention layers, the expert counters
    over the two expert layers, the window kernels' block share."""
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(YAML, tiny_cut())
    assert cfg.model.remat and cfg.model.attention_impl == "pallas"
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:1])
    batch = packed_batch(6)
    sample = to_global({k: np.asarray(v) for k, v in batch.items()}, mesh)
    builder = StepBuilder(cfg, mesh)
    assert builder.task == "causal_lm"
    state = builder.init_state(0, sample)
    params = jax.tree.map(jnp.copy, state.params)
    with jax.default_matmul_precision("highest"):
        _, metrics = builder.make_train_step(sample)(state, sample)
        want_loss, want_norm = ref.loss_and_grad_norm(
            params, batch, hparams(cfg.model))
    assert abs(float(metrics["loss"]) - float(want_loss)) < 2e-5 * float(
        want_loss)
    assert abs(float(metrics["grad_norm"]) - float(want_norm)) < 2e-4 * float(
        want_norm)
    assert float(metrics["moe_dropped"]) == 0.0
    assert 0.3 < float(metrics["attn_gate_mean"]) < 0.7
    assert 0.0 < float(metrics["attn_window_block_share"]) <= 1.0
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    model = get_model(cfg.model)
    assert float(metrics["attn_window_grid_share"]) == pytest.approx(
        model.window_grid_share(S))
    window_calls = [e for e in fa.dispatch_log()
                    if e["s"] == S and e["window"] == cfg.model.sliding_window]
    grid = fa.window_grid(S, S, cfg.model.sliding_window,
                          fa.select_dispatch(S, S, jnp.float32,
                                             cfg.model.head_dim))
    assert window_calls and all(
        (e["k_axis"], e["q_axis"]) == (grid["k_axis"], grid["q_axis"])
        for e in window_calls)
    assert all(e["k_axis"] is None and e["q_axis"] is None
               for e in fa.dispatch_log() if e["window"] is None)
    assert float(metrics["moe_local_assignments"]) == pytest.approx(
        float(metrics["moe_local_share"]) * 2 * S * TOPK)


def test_scopes_name_the_parts_the_benchmark_reads(devices):
    """The named scopes docs/OBSERVABILITY.md lists, in the lowered
    step's debug names: the gate under ``attn_gate`` inside each
    attention scope, the rotation under ``qk_norm_rope``, the shared
    expert under ``moe/shared``."""
    cfg = model_config()
    batch = packed_batch(0)
    model, params = init(cfg, batch)
    text = jax.jit(functools.partial(program_loss, model)).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("layer0/attn/attn_gate", "layer1/attn_window/attn_gate",
                  "layer0/attn/qk_norm_rope",
                  "layer1/attn_window/qk_norm_rope", "layer0/attn/query",
                  "layer0/mlp_in", "layer0/mlp_out", "layer1/moe/router",
                  "layer1/moe/dispatch", "layer1/moe/experts",
                  "layer1/moe/combine", "layer1/moe/shared/gate",
                  "layer1/moe/shared/down", "lm_head"):
        assert scope in text, scope


# ------------------------- the decoders already there lower to the same step --
# sha256 of the lowered train step (StableHLO text, no locations) of tiny
# cuts of the three decoder configurations' shipped YAMLs
# (tests/test_nemotron_h.py ``TINY``, by its ``lowered_step_digest``), read
# on the parent of this PR (36dde26) before any file changed;
# ``smallthinker`` again at PR 37, for the one constant output it adds
# (tests/test_nemotron_h.py ``PARENT_STEP``).
PARENT_STEP = {
    "lfm2":
        "4877857c625d8295825fcda9302f4bd009d820a71c8844f72776f4d204c0757f",
    "smallthinker":
        "0d60ff459318fff1d65e5ff40ce02e546e9028a3e9102c93b8fd9f9eca1d79a3",
    "nemotron":
        "92ffaac8050fde1ad85cd3ef7f541d43154edc2c2a1e670a6f94936d66a92e36",
}


@pytest.mark.parametrize("which", sorted(PARENT_STEP))
def test_the_three_decoders_lower_to_the_parents_step(devices, which):
    """``lfm2_8b_a1b``, ``smallthinker_21b_a3b`` and
    ``nemotron3_super_120b_a12b`` (tiny cuts of their shipped YAMLs,
    bfloat16, remat, the kernels): the lowered train step is the parent's
    text, byte for byte. Heads and rotary rules by layer kind, the gate,
    the shared expert and the shares of this PR all default to what they
    run."""
    from test_nemotron_h import lowered_step_digest

    assert lowered_step_digest(which) == PARENT_STEP[which]
