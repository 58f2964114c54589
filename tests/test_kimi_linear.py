"""Kimi-Linear-48B-A3B through the decoder family (models/lfm2.py: Kimi
Delta Attention layers, the gated delta rule with a decay per key channel
as a chunked scan, ops/delta_rule.py, beside latent attention without
positions, over sigmoid-routed SwiGLU experts with scaled weights and an
unscaled shared expert, a chip's share of heads, units and experts)
against a float32 recurrence token by token and the plain float32
reference (benchmarks/reference/kimi_linear.py), at tiny widths on the
CPU: the scan's output and gradients across documents, chunks, padding
and extreme decays and rates; the model's loss and every gradient on
packed rows; each assumed equation's alternative; the shares adding up to
the uncut layers; the counters and scopes the benchmark reads; the
published YAML and the cell's cut; and that kanana's step lowers as it
did."""

import dataclasses
import functools
import hashlib
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import kimi_linear as ref
from distributed_tensorflow_framework_tpu.core.config import (
    ModelConfig, load_config)
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as family
from distributed_tensorflow_framework_tpu.ops import delta_rule
from test_laguna import (
    _intercepted, _on, assert_gradients_close, packed_batch, program_loss)


@pytest.fixture(autouse=True, scope="module")
def compile_without_most_optimizations():
    """Each comparison compiles a program and a reference once for a few
    hundred tokens: the optimisation passes cost more than they save."""
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", False)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML = os.path.join(ROOT, "configs", "kimi_linear_48b_a3b.yaml")
KINDS = ["kda", "kda", "latent_attention"]
ROUTED, TOPK, S, VOCAB = 16, 3, 128, 256
HEADS, D = 4, 16                             # kda: 4 heads of 16
RANK, NOPE, ROPE, V = 32, 16, 8, 16          # mla: q/k heads of 24 over v of 16
# The cell's cut of the published model (benchmarks/configs/...json).
CUT = ["model.num_layers=5",
       "model.layer_types=[kda,kda,kda,latent_attention,kda]",
       "model.expert_groups=32", "model.expert_group=0",
       "model.tensor_groups=4", "model.tensor_group=0",
       "model.vocab_size=20480", "model.rope_layout=[0,0,0,0,0]"]


# ------------------------------------------------------ the chunked scan --
def recurrence_by_token(q, k, v, g, beta, segment_ids, scale):
    """The definition, a token at a time, float32: ``S`` zeroed at each
    document's start, decayed by ``exp g_t`` per key channel, written
    along ``k_t`` at the rate ``β_t``; ``o_t = scale S_tᵀ q_t``."""
    first = jnp.concatenate([jnp.ones_like(segment_ids[:, :1], bool),
                             segment_ids[:, 1:] != segment_ids[:, :-1]], 1)

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t, first_t = at
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        state = jnp.exp(g_t)[..., None] * state
        error = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + b_t[..., None, None] * k_t[..., None] \
            * error[..., None, :]
        return state, scale * jnp.einsum("bhkv,bhk->bhv", state, q_t)

    b, _, h, dk = q.shape
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), tuple(
        jnp.moveaxis(jnp.asarray(t), 1, 0)
        for t in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o, 0, 1)


# Decays (log, per channel) and write rates by case: α from about 1 down
# to the largest A_log a head draws (log 16) over a large softplus,
# α = e^-16 per step of the pre-activation; β near 0 and near 1.
SCAN_CASES = {
    "mixed": dict(decay=(-3.0, math.log(16.0)), softplus=4.0, beta=3.0),
    "alpha_near_1": dict(decay=(-9.0, -7.0), softplus=1.0, beta=3.0),
    "alpha_to_0": dict(decay=(math.log(16.0), math.log(16.0)),
                       softplus=40.0, beta=3.0),
    "beta_near_0": dict(decay=(-3.0, 1.0), softplus=4.0, beta=-9.0),
    "beta_near_1": dict(decay=(-3.0, 1.0), softplus=4.0, beta=9.0),
}


def scan_inputs(case, seed=0, b=2, s=45, h=2, dk=8, dv=6):
    """Unit q and k, decays ``-exp(a) softplus(x)`` with ``a`` drawn in
    the case's range, rates around the case's logit; three documents
    in the first row (one crossing both chunk boundaries of 16), two and
    padding in the second; 45 tokens, padded to 48 by the scan."""
    c = SCAN_CASES[case]
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, s, h, dk)) for _ in range(2))
    q, k = (x / np.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = rng.standard_normal((b, s, h, dv))
    x = rng.standard_normal((b, s, h, dk)) * c["softplus"]
    if case == "alpha_to_0":
        x = np.abs(x) + c["softplus"]
    g = -np.exp(rng.uniform(*c["decay"], (b, s, h, 1))) * np.log1p(np.exp(x))
    spread = 1.0 if abs(c["beta"]) < 5 else 0.1
    logit = c["beta"] + spread * rng.standard_normal((b, s, h))
    beta = 1.0 / (1.0 + np.exp(-logit))
    seg = np.array([[1] * 10 + [2] * 20 + [3] * 15,
                    [1] * 3 + [2] * 35 + [0] * 7])
    return [jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta)], \
        jnp.asarray(seg, jnp.int32)


@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_the_scan_is_the_recurrence_forward_and_backward(case):
    """Output and the gradients of ``q, k, v, g, β``, float32, chunks of
    16 in sub-chunks of 4: several documents a row, one across chunk
    boundaries, a row padded to a whole chunk; every gradient finite
    where a chunk decays by thousands of nats."""
    args, seg = scan_inputs(case)
    if case == "alpha_to_0":
        assert float(jnp.min(args[3])) < -500
    scale = 8 ** -0.5
    w = jax.random.normal(jax.random.key(1), args[2].shape[:3] + (6,))
    with jax.default_matmul_precision("highest"):
        want = recurrence_by_token(*args, seg, scale)
        got = jax.jit(functools.partial(
            delta_rule.chunked_delta_rule, chunk=16, sub=4))(*args, seg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5 * float(jnp.max(jnp.abs(want))))
        grads = [jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a) * w), argnums=tuple(range(5))))(*args)
            for f in (lambda *a: recurrence_by_token(*a, seg, scale),
                      lambda *a: delta_rule.chunked_delta_rule(
                          *a, seg, chunk=16, sub=4))]
    for name, want_g, got_g in zip("qkvgb", *grads):
        assert np.all(np.isfinite(np.asarray(got_g))), name
        np.testing.assert_allclose(
            np.asarray(got_g), np.asarray(want_g), err_msg=name,
            atol=2e-5 * (float(jnp.max(jnp.abs(want_g))) + 1e-6))


def test_bfloat16_products_stay_near_the_recurrence():
    """With bf16 ``q``, ``k``, ``v`` the scan's products take bf16
    operands over float32 states and sums: near the float32 recurrence."""
    args, seg = scan_inputs("mixed", seed=3)
    q, k, v, g, beta = args
    want = recurrence_by_token(*args, seg, 8 ** -0.5)
    got = delta_rule.chunked_delta_rule(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), g, beta, seg, chunk=16, sub=4)
    assert got.dtype == jnp.float32
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 0.03 * float(jnp.max(jnp.abs(want))), err


def test_a_chunk_must_hold_whole_sub_chunks():
    args, seg = scan_inputs("mixed")
    with pytest.raises(ValueError, match="sub-chunks"):
        delta_rule.chunked_delta_rule(*args, seg, chunk=24, sub=16)


# ------------------------------------------------------------- the model --
def model_config(**over) -> ModelConfig:
    """A dense layer and two expert layers: two kda layers of 4 heads of
    16, then latent attention of 4 heads without positions."""
    base = dict(
        name="kimi_linear", vocab_size=VOCAB, hidden_size=64,
        num_layers=len(KINDS), layer_types=list(KINDS), num_dense_layers=1,
        kda_heads=HEADS, kda_head_dim=D, kda_conv_kernel=4,
        num_heads=4, num_kv_heads=4, mla_kv_rank=RANK, mla_nope_dim=NOPE,
        mla_rope_dim=ROPE, mla_v_dim=V, rope_layout=[0] * len(KINDS),
        qk_norm=False,
        rope_theta=10000.0, mlp_dim=96, moe_mlp_dim=24, moe_shared_dim=32,
        num_experts=ROUTED, expert_topk=TOPK, routed_scaling=2.446,
        router_score="sigmoid_bias", expert_activation="silu",
        tie_embeddings=False, norm_eps=1e-5, dtype="float32",
        attention_impl="xla", dropout_rate=0.0)
    base.update(over)
    return ModelConfig(**base)


def hparams(cfg: ModelConfig) -> dict:
    """What the configuration's file tells the reference, from the
    program's own account of its share."""
    model = get_model(cfg)
    share = model.tensor_share() or {
        "attention": {"held": range(cfg.num_heads)},
        "kda": {"held": range(cfg.kda_heads)},
        "dense_ffn": {"held": [0, cfg.mlp_dim]},
        "shared_expert": {"held": [0, cfg.moe_shared_dim]}}
    units = lambda run: run[1] - run[0]  # noqa: E731
    return {
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers,
        "linear_attn_config": {"head_dim": cfg.kda_head_dim},
        "qk_nope_head_dim": cfg.mla_nope_dim,
        "qk_rope_head_dim": cfg.mla_rope_dim, "v_head_dim": cfg.mla_v_dim,
        "kv_lora_rank": cfg.mla_kv_rank, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "num_experts_per_token": cfg.expert_topk,
        "routed_scaling_factor": cfg.routed_scaling,
        "router_norm_eps": 1e-20, "experts_routed": cfg.num_experts,
        "experts_held": list(moe.held_experts(
            cfg.num_experts, cfg.expert_groups, cfg.expert_group)),
        "heads_held": list(share["attention"]["held"]),
        "kda_heads_held": list(share["kda"]["held"]),
        "dense_units_held": units(share["dense_ffn"]["held"]),
        "shared_units_held": units(share["shared_expert"]["held"])}


def init(cfg, batch, seed=0):
    """The model and seeded parameters of its tree's shapes: kernels
    normal over the square root of their fan-in (the router's three times
    that, so that its sigmoids spread), scales around 1, ``A_log`` over
    the published draw's range, ``dt_bias`` a unit normal (decays from
    about 1 to under 0.01 a token), taps within 1/sqrt(4)."""
    model = get_model(cfg)
    shapes = jax.eval_shape(
        functools.partial(model.init, train=False), jax.random.key(0),
        batch["input_ids"], batch["segment_ids"],
        batch["positions"])["params"]
    rng = np.random.default_rng(seed)

    def draw(path, leaf):
        name = path[-1].key if path[-1].key != "kernel" else path[-2].key
        normal = rng.standard_normal(leaf.shape)
        if name in ("scale", "o_norm"):
            value = 1.0 + 0.1 * normal
        elif name in ("embedding", "lm_head", "expert_bias"):
            value = 0.1 * normal
        elif name == "A_log":
            value = rng.uniform(0.0, math.log(16.0), leaf.shape)
        elif name == "dt_bias":
            value = normal
        elif name.endswith("_conv1d"):
            value = rng.uniform(-0.5, 0.5, leaf.shape)
        elif name == "gate" and leaf.ndim == 2 and leaf.shape[1] == ROUTED:
            value = 3.0 * normal / np.sqrt(leaf.shape[-2])
        else:
            value = normal / np.sqrt(leaf.shape[-2])
        return jnp.asarray(value, jnp.float32)

    return model, jax.tree_util.tree_map_with_path(draw, shapes)


_CONFIGS: dict = {}


@functools.lru_cache(maxsize=None)
def _reference_of(cfg_repr: str, seed: int):
    cfg = _CONFIGS[cfg_repr]
    batch = packed_batch(seed)
    _, params = init(cfg, batch, seed)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch, hparams(cfg))))(params)


def assert_model_matches_reference(cfg, *, program=None, tol=2e-5, seed=0,
                                   loss_first=False):
    """Parameters and reference from ``cfg``; the program from ``program``
    (a configuration or anything with ``apply``) where one is put in its
    place, applied to the same parameters. ``loss_first`` compares the
    loss alone (for a program expected to fail)."""
    batch = packed_batch(seed)
    model, params = init(cfg, batch, seed)
    if program is not None:
        model = get_model(program) if isinstance(program,
                                                 ModelConfig) else program
    _CONFIGS[repr(cfg)] = cfg
    want, want_g = _reference_of(repr(cfg), seed)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(program_loss, model))(params, batch)
        np.testing.assert_allclose(float(got), float(want), rtol=tol)
        if loss_first:
            return None
        got, got_g = jax.jit(jax.value_and_grad(
            functools.partial(program_loss, model)))(params, batch)
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    assert_gradients_close(got_g, want_g, atol=20 * tol)
    return got_g


SHARES = {"whole": {},
          "heads1of2_experts3of4": {"tensor_groups": 2, "tensor_group": 1,
                                    "expert_groups": 4, "expert_group": 3}}


@pytest.mark.parametrize("impl,share", [
    ("xla", "whole"), ("pallas", "heads1of2_experts3of4")])
def test_loss_and_gradients_match_the_reference(devices, impl, share):
    """Loss and every gradient leaf, float32, packed rows with three
    documents each (documents across the scan's chunks of 64, padding at
    a row's end); whole, and as a share of heads, units and experts with
    the latent layer through the kernels."""
    grads = assert_model_matches_reference(
        model_config(attention_impl=impl, **SHARES[share]))
    for layer in ("layer0", "layer1"):
        for name, leaf in grads[layer]["kda"].items():
            assert np.any(np.asarray(jax.tree.leaves(leaf)[0])), (layer, name)
    for name in ("q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"):
        leaf = jax.tree.leaves(grads["layer2"]["mla"][name])[0]
        assert np.any(np.asarray(leaf)), name


def test_bfloat16_activations_stay_near_the_reference(devices):
    cfg = model_config()
    batch = packed_batch(3)
    _, params = init(cfg, batch, 3)
    got = jax.jit(functools.partial(
        program_loss, get_model(model_config(dtype="bfloat16"))))(
            params, batch)
    _CONFIGS[repr(cfg)] = cfg
    want, _ = _reference_of(repr(cfg), 3)
    assert abs(float(got) - float(want)) < 2e-3 * float(want)


# --------------------------------------------- what fails the comparison --
_DELTA_RULE = delta_rule.chunked_delta_rule


def _unscaled(*args, **kwargs):
    return _DELTA_RULE(*args, **{**kwargs, "scale": 1.0})


def _rates_up_to_2(q, k, v, g, beta, *args, **kwargs):
    return _DELTA_RULE(q, k, v, g, 2.0 * beta, *args, **kwargs)


def _kda_layer(module, x, segment_ids, *, gate_first=False, gate_bias=None,
               dt_bias=True):
    """``KimiDeltaAttention`` written again from its parameters, with one
    of its equations changed: the gate inside the norm (``gate_first``),
    a bias on ``W_gb``, or no ``dt_bias`` in the decay."""
    p = module.variables["params"]
    b, s, _ = x.shape
    n, d = module.dims.heads, module.dims.head_dim
    q, k, v = (jax.nn.silu(family.causal_depthwise_conv(
        x @ p[f"{c}_proj"]["kernel"], p[f"{c}_conv1d"], segment_ids))
        .reshape(b, s, n, d) for c in "qkv")
    f = (x @ p["f_a_proj"]["kernel"]) @ p["f_b_proj"]["kernel"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        f.reshape(b, s, n, d) + (p["dt_bias"].reshape(n, d) if dt_bias
                                 else 0.0))
    beta = jax.nn.sigmoid(x @ p["b_proj"]["kernel"])
    o = _DELTA_RULE(family.l2_normed(q), family.l2_normed(k), v, g, beta,
                    segment_ids)
    gate = (x @ p["g_a_proj"]["kernel"]) @ p["g_b_proj"]["kernel"]
    if gate_bias is not None:
        gate = gate + gate_bias
    gate = jax.nn.sigmoid(gate.reshape(b, s, n, d))
    if gate_first:
        o = o * gate
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + module.norm_eps) * p["o_norm"]
    if not gate_first:
        o = o * gate
    return (o.reshape(b, s, n * d) @ p["o_proj"]["kernel"],
            {key: jnp.zeros(()) for key in family.KDA_COUNTERS})


def _kda_as(**change):
    def edit(module, next_fun, args, stream):
        if isinstance(module, family.KimiDeltaAttention):
            return _kda_layer(module, *args, **change)
        return None
    return _intercepted(edit)


def _scaled_shared_expert(f, a, stream):
    return 2.446 * f(*a)


# name -> (configuration in the program's place, patches)
ALTERNATIVES = {
    "no_output_scale": dict(
        patches=[(delta_rule, "chunked_delta_rule", _unscaled)]),
    "rates_up_to_2": dict(
        patches=[(delta_rule, "chunked_delta_rule", _rates_up_to_2)]),
    "no_l2_norm": dict(
        patches=[(family, "l2_normed",
                  lambda x, eps=0.0: x.astype(jnp.float32))]),
    "gate_inside_the_norm": dict(program=_kda_as(gate_first=True)),
    "a_bias_on_w_gb": dict(program=_kda_as(gate_bias=jax.random.normal(
        jax.random.key(7), (HEADS * D,)))),
    "no_dt_bias": dict(program=_kda_as(dt_bias=False)),
    "rotated_latent_attention": dict(
        program=lambda cfg: dataclasses.replace(cfg, rope_layout=[])),
    "softmax_routing": dict(
        program=lambda cfg: dataclasses.replace(
            cfg, router_score="softmax_topk")),
    "a_scaled_shared_expert": dict(
        program=_intercepted(_on(moe.SharedExpert, _scaled_shared_expert))),
}


@pytest.mark.parametrize("what", sorted(ALTERNATIVES))
def test_an_assumed_equations_alternative_fails_the_comparison(
        devices, monkeypatch, what):
    """Each equation the configuration's ``assumed`` takes from the
    family's code, its alternative put in the PROGRAM's place, fails the
    float32 comparison the model itself passes
    (``test_the_seams_themselves_change_nothing``)."""
    cfg = model_config()
    how = ALTERNATIVES[what]
    for where, name, alternative in how.get("patches", ()):
        monkeypatch.setattr(where, name, alternative)
    program = how.get("program")
    with pytest.raises(AssertionError):
        assert_model_matches_reference(
            cfg, program=None if program is None else program(cfg),
            loss_first=True)


def test_the_seams_themselves_change_nothing(devices, monkeypatch):
    """The layer written again with nothing changed, and the scan called
    through a wrapper, pass the comparison."""
    calls = []
    monkeypatch.setattr(delta_rule, "chunked_delta_rule",
                        lambda *a, **k: calls.append("scan")
                        or _DELTA_RULE(*a, **k))
    assert_model_matches_reference(model_config(), loss_first=True)
    assert calls and set(calls) == {"scan"}
    assert_model_matches_reference(model_config(), program=_kda_as()(
        model_config()), loss_first=True)


# ---------------------------------------------------------------- the share --
def _kda_share(full, held):
    """A kda layer's parameters for the heads ``held`` (a range): their
    columns of ``W_q``, ``W_k``, ``W_v``, ``W_fb``, ``W_gb`` and ``W_b``,
    their taps, ``dt_bias`` and ``A_log``, and ``W_o``'s rows; ``W_fa``,
    ``W_ga`` and the output norm's scale whole."""
    cols = np.arange(held.start * D, held.stop * D)
    heads = np.arange(held.start, held.stop)
    out = {name: {"kernel": full[name]["kernel"][:, cols]}
           for name in ("q_proj", "k_proj", "v_proj", "f_b_proj",
                        "g_b_proj")}
    out.update({f"{c}_conv1d": full[f"{c}_conv1d"][:, cols] for c in "qkv"})
    out.update(dt_bias=full["dt_bias"][cols], A_log=full["A_log"][heads],
               b_proj={"kernel": full["b_proj"]["kernel"][:, heads]},
               o_proj={"kernel": full["o_proj"]["kernel"][cols]},
               f_a_proj=full["f_a_proj"], g_a_proj=full["g_a_proj"],
               o_norm=full["o_norm"])
    return out


def test_the_shares_add_up_to_the_uncut_layers(devices):
    """The two tensor groups' heads of a kda layer and of a latent layer
    and runs of the shared expert's units, and the four expert groups'
    experts, add up to the uncut reference layers; what every chip
    computes alike (``W_fa``, ``W_ga``, the output norm's scale, ``W_kva``
    and the latent norm, the router) enters once."""
    cfg = model_config()
    batch = packed_batch(2)
    _, params = init(cfg, batch, 2)
    segments = batch["segment_ids"]
    u = jax.random.normal(jax.random.key(3), (2, S, cfg.hidden_size))
    h = hparams(cfg)
    dims = family.LatentDims(RANK, NOPE, ROPE, V)
    kda_full, mla_full = params["layer0"]["kda"], params["layer2"]["mla"]
    moe_full = params["layer1"]["moe"]
    with jax.default_matmul_precision("highest"):
        kda = latent = shared = routed = 0.0
        for t in range(2):
            share = get_model(dataclasses.replace(
                cfg, tensor_groups=2, tensor_group=t)).tensor_share()
            held = family.held_heads(HEADS, 2, t)
            assert share["kda"]["held"] == list(held)
            assert share["attention"]["held"] == list(held)
            out, _ = jax.jit(family.KimiDeltaAttention(
                family.KdaDims(2, D, 4), 1e-5,
                dtype=jnp.float32).apply)(
                    {"params": _kda_share(kda_full, held)}, u, segments)
            kda = kda + out
            qk = np.arange(held.start * (NOPE + ROPE),
                           held.stop * (NOPE + ROPE))
            kv = np.arange(held.start * (NOPE + V), held.stop * (NOPE + V))
            o = np.arange(held.start * V, held.stop * V)
            mine = {"q_proj": {"kernel": mla_full["q_proj"]["kernel"][:, qk]},
                    "kv_a_proj": mla_full["kv_a_proj"],
                    "kv_a_norm": mla_full["kv_a_norm"],
                    "kv_b_proj": {"kernel":
                                  mla_full["kv_b_proj"]["kernel"][:, kv]},
                    "o_proj": {"kernel": mla_full["o_proj"]["kernel"][o]}}
            latent = latent + jax.jit(family.LatentAttention(
                2, dims, 10000.0, 1e-5, jnp.float32, rope=False).apply)(
                    {"params": mine}, u, segments, batch["positions"])
            units = slice(*share["shared_expert"]["held"])
            sh = moe_full["shared"]
            shared = shared + ref.swiglu(
                sh["gate"]["kernel"][:, units], sh["up"]["kernel"][:, units],
                sh["down"]["kernel"][units], u.reshape(-1, 64))
        for e in range(4):
            rows = slice(4 * e, 4 * e + 4)
            mine = {"gate": moe_full["gate"],
                    "expert_bias": moe_full["expert_bias"],
                    **{w: moe_full[w][rows] for w in ("w1", "w3", "w2")}}
            out, counters = jax.jit(moe.DroplessMoE(
                num_experts=ROUTED, mlp_dim=24, topk=TOPK, groups=4,
                group=e, dtype=jnp.float32, weight_scale=2.446).apply)(
                    {"params": mine}, u)
            routed = routed + out
            assert float(counters["dropped"]) == 0.0
        wanted = (jax.jit(functools.partial(ref.kda, h=h))(
                      kda_full, u, segments),
                  jax.jit(functools.partial(ref.latent_attention, h=h))(
                      mla_full, u, segments),
                  jax.jit(lambda p: ref.expert_layer(p, u, h))(moe_full))
    for got, want in zip((kda, latent, routed + shared.reshape(u.shape)),
                         wanted):
        want = np.asarray(want)
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=5e-6 * np.abs(want).max())


@pytest.mark.parametrize("bad,says", [
    (dict(kda_heads=0), "kda_heads"),
    (dict(kda_conv_kernel=0), "kda_conv_kernel"),
    (dict(tensor_groups=2, kda_heads=3), "kda_heads"),
    (dict(tensor_groups=3), "whole"),
])
def test_bad_configurations_are_refused_by_name(devices, bad, says):
    with pytest.raises(ValueError, match=says):
        get_model(model_config(**bad))


# --------------------------------------------- counters, scopes, the step --
def test_the_counters_are_the_layers_means(devices):
    """``kda_resets`` counts the rows' document starts (padding is none);
    ``kda_decay_mean`` and ``kda_beta_mean`` are ``exp g`` over held
    channels and ``β`` over held heads, on real tokens, averaged over the
    kda layers, from the layer's own equations."""
    cfg = model_config()
    batch = packed_batch(0)
    model, params = init(cfg, batch)
    # with the normed stream into each layer, from the model's own
    # intermediates
    out, state = jax.jit(lambda p: model.apply(
        {"params": p}, batch["input_ids"], batch["segment_ids"],
        batch["positions"], capture_intermediates=lambda m, _: (
            isinstance(m, family.RMSNorm) and m.name == "mixer_norm"),
        mutable=["intermediates"]))(params)
    assert float(out["kda_resets"]) == 6.0      # 3 a row, padding aside
    real = np.asarray(batch["segment_ids"]) > 0
    decays, rates = [], []
    for i in range(2):
        p = params[f"layer{i}"]
        u = state["intermediates"][f"layer{i}"]["mixer_norm"]["__call__"][0]
        f = (u @ p["kda"]["f_a_proj"]["kernel"]) \
            @ p["kda"]["f_b_proj"]["kernel"]
        g = -jnp.exp(p["kda"]["A_log"])[:, None] * jax.nn.softplus(
            f.reshape(2, S, HEADS, D) + p["kda"]["dt_bias"].reshape(HEADS, D))
        beta = jax.nn.sigmoid(u @ p["kda"]["b_proj"]["kernel"])
        decays.append(np.asarray(jnp.exp(g))[real].mean())
        rates.append(np.asarray(beta)[real].mean())
    np.testing.assert_allclose(float(out["kda_decay_mean"]), np.mean(decays),
                               rtol=1e-5)
    np.testing.assert_allclose(float(out["kda_beta_mean"]), np.mean(rates),
                               rtol=1e-5)


def test_scopes_name_the_parts_the_benchmark_reads(devices):
    cfg = model_config()
    batch = packed_batch(0)
    model, params = init(cfg, batch)
    text = jax.jit(jax.grad(functools.partial(program_loss, model))).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("layer0/kda/qkv_proj", "layer0/kda/conv",
                  "layer0/kda/gates", "layer0/kda/delta_scan",
                  "layer1/kda/o_norm_gate", "layer1/kda/o_proj",
                  "layer2/mla/mla_latent", "layer2/mla/q_proj",
                  "layer1/moe/router", "layer1/moe/shared", "lm_head"):
        assert scope in text, scope
    # no positions in the latent layer: nothing runs under qk_norm_rope
    assert "qk_norm_rope" not in text


def tiny_cut() -> list:
    return [
        "model.num_layers=3",
        "model.layer_types=[kda,kda,latent_attention]",
        "model.hidden_size=64", f"model.kda_heads={HEADS}",
        f"model.kda_head_dim={D}", "model.rope_layout=[0,0,0]",
        "model.num_heads=4", "model.num_kv_heads=4",
        f"model.mla_kv_rank={RANK}", f"model.mla_nope_dim={NOPE}",
        f"model.mla_rope_dim={ROPE}", f"model.mla_v_dim={V}",
        "model.mlp_dim=96", "model.moe_mlp_dim=24",
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
    ``grad_norm`` are the reference's, and the kda and expert counters
    ride its metrics."""
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
    assert float(metrics["kda_resets"]) == 6.0
    assert 0.0 < float(metrics["kda_decay_mean"]) < 1.0
    assert 0.0 < float(metrics["kda_beta_mean"]) < 1.0


# ------------------------------------------------- the published model's cut --
def test_the_published_yaml_is_the_48b_model(devices):
    cfg = load_config(YAML, []).model
    kda = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23,
           25, 26]
    assert cfg.layer_types == ["kda" if i in kda else "latent_attention"
                               for i in range(1, 28)]
    assert [i + 1 for i, k in enumerate(cfg.layer_types)
            if k == "latent_attention"] == [4, 8, 12, 16, 20, 24, 27]
    assert (cfg.hidden_size, cfg.vocab_size, cfg.norm_eps) == (
        2304, 163840, 1e-5)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_kernel) == (
        32, 128, 4)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.mla_kv_rank,
            cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim) == (
        32, 32, 512, 128, 64, 128)
    assert cfg.rope_layout == [0] * 27 and not cfg.qk_norm
    assert (cfg.num_dense_layers, cfg.mlp_dim, cfg.num_experts,
            cfg.expert_topk, cfg.moe_mlp_dim, cfg.moe_shared_dim,
            cfg.routed_scaling) == (1, 9216, 256, 8, 1024, 1024, 2.446)
    assert (cfg.router_score, cfg.expert_activation, cfg.router_input) == (
        "sigmoid_bias", "silu", "ffn_norm")
    assert cfg.remat and cfg.attention_impl == "pallas"
    assert not cfg.tie_embeddings and cfg.embed_init_std == 1.0
    assert cfg.out_proj_init_std == pytest.approx(0.02 / math.sqrt(54),
                                                  rel=1e-3)


def _count(cfg) -> dict:
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 256), jnp.int32),
        train=False))["params"]
    return {name: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(sub))
            for name, sub in shapes.items()}


def test_the_cells_cut_is_396_million_parameters(devices):
    """The cut of benchmarks/configs/kimi_linear_48b_a3b.json: shapes
    only, nothing of this size is built. Tensor parallel 2 would hold
    464.7M, 8 would hold 361.4M."""
    cfg = load_config(YAML, CUT).model
    count = _count(cfg)
    kda = (3 * 2304 * 1024 + 3 * 4 * 1024 + 2304 * 128 + 128 * 1024 + 1024
           + 8 + 2304 * 8 + 2304 * 128 + 128 * 1024 + 128 + 1024 * 2304)
    mla = 2304 * 8 * 192 + 2304 * 576 + 512 + 512 * 8 * 256 + 8 * 128 * 2304
    experts = 2304 * 256 + 256 + 3 * 2304 * 256 + 8 * 3 * 2304 * 1024
    norms = 2 * 2304
    assert (kda, mla, experts) == (10_321_032, 8_274_432, 58_982_656)
    assert count["layer0"] == kda + 3 * 2304 * 2304 + norms
    for i in (1, 2, 4):
        assert count[f"layer{i}"] == kda + experts + norms
    assert count["layer3"] == mla + experts + norms
    assert count["embed"] + count["lm_head"] == 2 * 20480 * 2304
    assert sum(count.values()) == 395_811_616
    assert sum(count.values()) * 16 / 2 ** 30 == pytest.approx(5.90, abs=5e-3)
    model = get_model(cfg)
    assert model.expert_share()["held"] == list(range(8))
    share = model.tensor_share()
    assert share["kda"]["held"] == share["attention"]["held"] == list(
        range(8))
    assert share["dense_ffn"] == {"units": 9216, "held": [0, 2304]}
    assert share["shared_expert"] == {"units": 1024, "held": [0, 256]}
    for groups, millions in ((2, 464.7), (8, 361.4)):
        other = load_config(YAML, [*CUT, f"model.tensor_groups={groups}"])
        assert round(sum(_count(other.model).values()) / 1e6, 1) == millions


def test_family_names_and_task():
    from distributed_tensorflow_framework_tpu.models import builtin_task
    from distributed_tensorflow_framework_tpu.models.bert import (
        decode_support_reason)

    assert builtin_task("kimi_linear") == "causal_lm"
    assert "trains only" in decode_support_reason(model_config())


# ------------------------- the decoder already there lowers to the same step --
# sha256 of the lowered train step (StableHLO text, no locations) of
# tests/test_kanana.py's tiny cut of configs/kanana_2_30b_a3b.yaml in
# bfloat16, read on the parent of the change that added the kda layer and
# the latent layer's rope switch, before any file changed. The other four
# decoders: tests/test_laguna.py and tests/test_kanana.py.
PARENT_KANANA_STEP = \
    "87df96de7b97bbc62b40de5862311f80193429e5c12395fb664344ff75b91ef7"


def test_kanana_lowers_to_the_parents_step(devices):
    """The latent layer that rotates (``rope_layout`` empty, the
    default) traces what it traced before the switch."""
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder
    import test_kanana

    cfg = load_config(test_kanana.YAML,
                      [*test_kanana.tiny_cut(), "model.dtype=bfloat16"])
    assert not cfg.model.rope_layout
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:1])
    sample = to_global(
        {k: np.asarray(v) for k, v in packed_batch(0).items()}, mesh)
    builder = StepBuilder(cfg, mesh)
    state = builder.init_state(0, sample)
    text = builder.make_train_step(sample).lower(state, sample).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_KANANA_STEP
