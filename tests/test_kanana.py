"""kanana-2-30b-a3b through the decoder family (models/lfm2.py: multi-head
latent attention with a normed key/value latent, one rotated key a token
shared by every head, query/key heads wider than the value heads and
interleaved rotary pairs, over sigmoid-routed SwiGLU experts with scaled
weights beside an unscaled shared unit, a chip's share of heads, units
and experts) against its plain float32 reference
(benchmarks/reference/deepseek_v3.py), at tiny widths on the CPU: loss
and every gradient on packed rows, each assumed equation's alternative,
the shares adding up to the uncut layer, what a remat'd layer keeps, the
scopes the benchmark reads, the published YAML and the cell's cut, and
that Laguna's step lowers as it did."""

import dataclasses
import functools
import hashlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_v3 as ref
from distributed_tensorflow_framework_tpu.core.config import (
    ModelConfig, load_config)
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as family
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
YAML = os.path.join(ROOT, "configs", "kanana_2_30b_a3b.yaml")
KINDS = ["latent_attention"] * 3
ROUTED, TOPK, S, VOCAB = 16, 3, 128, 256
RANK, NOPE, ROPE, V = 32, 16, 8, 16        # q/k heads of 24 over v of 16
# The cell's cut of the published model (benchmarks/configs/...json).
CUT = ["model.num_layers=5", "model.layer_types=[latent_attention,"
       "latent_attention,latent_attention,latent_attention,latent_attention]",
       "model.expert_groups=16", "model.expert_group=0",
       "model.tensor_groups=2", "model.tensor_group=0",
       "model.vocab_size=16032"]


def model_config(**over) -> ModelConfig:
    """A dense layer and two expert layers, each mixing by latent
    attention of 4 heads."""
    base = dict(
        name="kanana", vocab_size=VOCAB, hidden_size=64,
        num_layers=len(KINDS), layer_types=list(KINDS), num_dense_layers=1,
        num_heads=4, num_kv_heads=4, mla_kv_rank=RANK, mla_nope_dim=NOPE,
        mla_rope_dim=ROPE, mla_v_dim=V, qk_norm=False, rope_theta=100.0,
        rope_pairs="interleaved", mlp_dim=96, moe_mlp_dim=24,
        moe_shared_dim=32, num_experts=ROUTED, expert_topk=TOPK,
        routed_scaling=2.448, router_score="sigmoid_bias",
        expert_activation="silu", tie_embeddings=False, norm_eps=1e-6,
        dtype="float32", attention_impl="xla", dropout_rate=0.0)
    base.update(over)
    return ModelConfig(**base)


def hparams(cfg: ModelConfig) -> dict:
    """What the configuration's file tells the reference, from the
    program's own account of its share."""
    model = get_model(cfg)
    share = model.tensor_share() or {
        "attention": {"held": range(cfg.num_heads)},
        "dense_ffn": {"held": [0, cfg.mlp_dim]},
        "shared_expert": {"held": [0, cfg.moe_shared_dim]}}
    units = lambda run: run[1] - run[0]  # noqa: E731
    return {
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers,
        "qk_nope_head_dim": cfg.mla_nope_dim,
        "qk_rope_head_dim": cfg.mla_rope_dim, "v_head_dim": cfg.mla_v_dim,
        "kv_lora_rank": cfg.mla_kv_rank, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "rope_interleave": True,
        "num_experts_per_tok": cfg.expert_topk,
        "routed_scaling_factor": cfg.routed_scaling,
        "router_norm_eps": 1e-20, "experts_routed": cfg.num_experts,
        "experts_held": list(moe.held_experts(
            cfg.num_experts, cfg.expert_groups, cfg.expert_group)),
        "heads_held": list(share["attention"]["held"]),
        "dense_units_held": units(share["dense_ffn"]["held"]),
        "shared_units_held": units(share["shared_expert"]["held"])}


def init(cfg, batch, seed=0):
    """The model and seeded parameters of its tree's shapes: kernels
    normal over the square root of their fan-in (the router's three times
    that, so that its sigmoids spread), scales around 1, a selection bias
    the size of the scores' spread."""
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
        elif name == "expert_bias":
            value = 0.1 * normal
        elif name == "gate" and leaf.ndim == 2 and leaf.shape[1] == ROUTED:
            value = 3.0 * normal / np.sqrt(leaf.shape[-2])
        else:
            value = normal / np.sqrt(leaf.shape[-2])
        return jnp.asarray(value, jnp.float32)

    return model, jax.tree_util.tree_map_with_path(draw, shapes)


@functools.lru_cache(maxsize=None)
def _reference_of(cfg_repr: str, seed: int):
    cfg = _CONFIGS[cfg_repr]
    batch = packed_batch(seed)
    _, params = init(cfg, batch, seed)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch, hparams(cfg))))(params)


_CONFIGS: dict = {}


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
    documents each; whole, and as a share of heads, units and experts
    through the kernels with value heads narrower than query/key heads."""
    grads = assert_model_matches_reference(
        model_config(attention_impl=impl, **SHARES[share]))
    for layer in ("layer0", "layer1", "layer2"):
        for name in ("q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                     "o_proj"):
            leaf = jax.tree.leaves(grads[layer]["mla"][name])[0]
            assert np.any(np.asarray(leaf)), (layer, name)
    for name in ("gate", "w1", "w3", "w2", "shared"):
        for leaf in jax.tree.leaves(grads["layer1"]["moe"][name]):
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


# ------------------------------------------------------- the rotary pairs --
def test_interleaved_pairs_turn_dims_2i_and_2i_plus_1(devices):
    """Pair ``(2i, 2i + 1)`` turns by ``p theta^(-2i/d)``; the same
    numbers as the source's de-interleave and half rotation, put back in
    place; the half rule is what every other configuration traces."""
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, ROPE))
    positions = jnp.arange(8)[None, :] * 3
    got = family.rotary(x, positions, 100.0,
                        family.RotaryRule(pairs="interleaved"))
    p, i = 5, 1                                  # position 15, pair 1
    angle = 15 * 100.0 ** (-2 * i / ROPE)
    a, b = np.asarray(x[0, p, 0, 2 * i]), np.asarray(x[0, p, 0, 2 * i + 1])
    np.testing.assert_allclose(
        np.asarray(got[0, p, 0, 2 * i:2 * i + 2]),
        [a * math.cos(angle) - b * math.sin(angle),
         b * math.cos(angle) + a * math.sin(angle)], rtol=1e-5)
    source = ref.rope(x, positions, {"rope_interleave": True,
                                     "rope_theta": 100.0})
    back = jnp.stack([source[..., :ROPE // 2], source[..., ROPE // 2:]],
                     axis=-1).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(back), rtol=1e-5,
                               atol=1e-6)
    assert family.RotaryRule() == family.RotaryRule(pairs="half")


# --------------------------------------------- what fails the comparison --
def _softmax_scale_of_the_unrotated_dims(q, k, v, segment_ids=None,
                                         dtype=jnp.float32, window=None):
    """Scores over sqrt(qk_nope_head_dim), the 128 of the published
    heads, in place of sqrt(nope + rope)."""
    return _ATTENTION(q * math.sqrt((NOPE + ROPE) / NOPE), k, v,
                      segment_ids, dtype)


_ATTENTION = family.causal_attention_xla


def _no_latent_norm(module, next_fun, args, stream):
    if isinstance(module, family.RMSNorm) and module.name == "kv_a_norm":
        return args[0].astype(jnp.float32)
    return None


def _one_rotary_key_a_head(module, next_fun, args, stream):
    """The layer with a rotated key of its own for every head (the
    rotary columns of ``W_kva`` as heads' keys, each head's taken from
    the columns turned by its index), in place of one shared key."""
    if not isinstance(module, family.LatentAttention):
        return None
    x, segments, positions = args
    p = module.variables["params"]
    b, s, _ = x.shape
    n = module.num_heads
    q = (x @ p["q_proj"]["kernel"]).reshape(b, s, n, NOPE + ROPE)
    latent = x @ p["kv_a_proj"]["kernel"]
    c, k_r = latent[..., :RANK], latent[..., RANK:]
    c = family.RMSNorm(1e-6).apply({"params": p["kv_a_norm"]}, c)
    kv = (c @ p["kv_b_proj"]["kernel"]).reshape(b, s, n, NOPE + V)
    k_r = jnp.stack([jnp.roll(k_r, h, axis=-1) for h in range(n)], axis=2)
    rule = module.rope_rule
    q = jnp.concatenate([q[..., :NOPE], family.rotary(
        q[..., NOPE:], positions, module.rope_theta, rule)], axis=-1)
    k = jnp.concatenate([kv[..., :NOPE], family.rotary(
        k_r, positions, module.rope_theta, rule)], axis=-1)
    out = _ATTENTION(q, k, kv[..., NOPE:], segments)
    return out.reshape(b, s, n * V) @ p["o_proj"]["kernel"]


def _scaled_shared_expert(f, a, stream):
    return 2.448 * f(*a)


# name -> (configuration in the program's place, patches, interceptor)
ALTERNATIVES = {
    "half_rotation": dict(
        program=lambda cfg: dataclasses.replace(cfg, rope_pairs="half")),
    "scale_of_the_unrotated_dims": dict(
        patches=[(family, "causal_attention_xla",
                  _softmax_scale_of_the_unrotated_dims)]),
    "no_latent_norm": dict(program=_intercepted(_no_latent_norm)),
    "a_rotary_key_per_head": dict(
        program=_intercepted(_one_rotary_key_a_head)),
    "softmax_routing": dict(
        program=lambda cfg: dataclasses.replace(
            cfg, router_score="softmax_topk")),
    "a_scaled_shared_expert": dict(
        program=_intercepted(_on(moe.SharedExpert, _scaled_shared_expert))),
}


@pytest.mark.parametrize("what", sorted(ALTERNATIVES))
def test_an_assumed_equations_alternative_fails_the_comparison(
        devices, monkeypatch, what):
    """Each item of the configuration's ``assumed``, its alternative put
    in the PROGRAM's place, fails the float32 comparison the model itself
    passes (``test_the_seams_themselves_change_nothing``)."""
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
    calls = []
    same = _intercepted(lambda module, f, a, stream: calls.append(
        type(module).__name__))(model_config())
    attention = family.causal_attention_xla
    monkeypatch.setattr(
        family, "causal_attention_xla",
        lambda *a, **k: calls.append("attention") or attention(*a, **k))
    assert_model_matches_reference(model_config(), program=same,
                                   loss_first=True)
    assert {"LatentAttention", "RMSNorm", "SharedExpert",
            "attention"} <= set(calls)


# ---------------------------------------------------------------- the share --
def test_the_shares_add_up_to_the_uncut_layers(devices):
    """The two tensor groups' heads (their columns of ``W_q`` and
    ``W_kvb``, their rows of ``W_o``; ``W_kva`` and the latent norm whole
    on both) and runs of the shared units, and the sixteen expert groups'
    experts, add up to the uncut reference layers; the router, computed
    alike everywhere, enters once."""
    cfg = model_config()
    batch = packed_batch(2)
    _, params = init(cfg, batch, 2)
    segments, positions = batch["segment_ids"], batch["positions"]
    u = jax.random.normal(jax.random.key(3), (2, S, cfg.hidden_size))
    h = hparams(cfg)
    full = params["layer1"]
    dims = family.LatentDims(RANK, NOPE, ROPE, V)
    rule = family.RotaryRule(pairs="interleaved")
    with jax.default_matmul_precision("highest"):
        attention = shared = routed = 0.0
        for t in range(2):
            share = get_model(dataclasses.replace(
                cfg, tensor_groups=2, tensor_group=t)).tensor_share()
            held = family.held_heads(4, 2, t)
            assert share["attention"]["held"] == list(held)
            qk = np.arange(held.start * (NOPE + ROPE),
                           held.stop * (NOPE + ROPE))
            kv = np.arange(held.start * (NOPE + V), held.stop * (NOPE + V))
            o = np.arange(held.start * V, held.stop * V)
            mine = {"q_proj": {"kernel": full["mla"]["q_proj"]["kernel"][:, qk]},
                    "kv_a_proj": full["mla"]["kv_a_proj"],
                    "kv_a_norm": full["mla"]["kv_a_norm"],
                    "kv_b_proj": {"kernel":
                                  full["mla"]["kv_b_proj"]["kernel"][:, kv]},
                    "o_proj": {"kernel": full["mla"]["o_proj"]["kernel"][o]}}
            attention = attention + jax.jit(family.LatentAttention(
                2, dims, 100.0, 1e-6, jnp.float32, rope_rule=rule).apply)(
                    {"params": mine}, u, segments, positions)
            units = slice(*share["shared_expert"]["held"])
            sh = full["moe"]["shared"]
            shared = shared + ref.swiglu(
                sh["gate"]["kernel"][:, units], sh["up"]["kernel"][:, units],
                sh["down"]["kernel"][units], u.reshape(-1, 64))
        for e in range(16):
            mine = {"gate": full["moe"]["gate"],
                    "expert_bias": full["moe"]["expert_bias"],
                    **{w: full["moe"][w][e:e + 1] for w in ("w1", "w3", "w2")}}
            out, counters = jax.jit(moe.DroplessMoE(
                num_experts=ROUTED, mlp_dim=24, topk=TOPK, groups=16,
                group=e, dtype=jnp.float32, weight_scale=2.448).apply)(
                    {"params": mine}, u)
            routed = routed + out
            assert float(counters["dropped"]) == 0.0
        want_attention = jax.jit(functools.partial(ref.attention, h=h))(
            full["mla"], u, segments, positions)
        want_experts = jax.jit(lambda p: ref.expert_layer(p, u, h))(
            full["moe"])
    for got, wanted in ((attention, want_attention),
                        (routed + shared.reshape(u.shape), want_experts)):
        wanted = np.asarray(wanted)
        np.testing.assert_allclose(np.asarray(got), wanted,
                                   atol=5e-6 * np.abs(wanted).max())


@pytest.mark.parametrize("bad,says", [
    (dict(mla_kv_rank=0), "mla_kv_rank"),
    (dict(mla_rope_dim=7), "mla_rope_dim"),
    (dict(qk_norm=True), "no q/k norm"),
    (dict(attention_gate="per_head"), "no q/k norm"),
    (dict(rope_pairs="pairwise"), "rope_pairs"),
    (dict(tensor_groups=3), "whole, even shares"),
])
def test_bad_configurations_are_refused_by_name(devices, bad, says):
    with pytest.raises(ValueError, match=says):
        get_model(model_config(**bad))


# ------------------------------------------------------------------- remat --
def test_a_remat_layer_keeps_the_kernels_output(devices):
    """Under ``model.remat`` the gradient's jaxpr holds one forward kernel
    a latent layer, as without remat; ``save_nothing`` holds two."""
    from distributed_tensorflow_framework_tpu.core.config import (
        PrecisionConfig)

    batch = packed_batch(4)
    model, params = init(model_config(attention_impl="pallas"), batch, 4)
    kept = get_model(model_config(attention_impl="pallas", remat=True))
    nothing = get_model(model_config(attention_impl="pallas", remat=True),
                        precision=PrecisionConfig(remat_policy="save_nothing"))

    def kernels(m):
        text = str(jax.make_jaxpr(jax.grad(
            functools.partial(program_loss, m)))(params, batch))
        return len(re.findall(r"name=_flash_fwd\b", text))

    assert kernels(model) == kernels(kept) == 3
    assert kernels(nothing) == 6


# ------------------------------------------------- the published model's cut --
def test_the_published_yaml_is_the_30b_model(devices):
    cfg = load_config(YAML, []).model
    assert cfg.layer_types == ["latent_attention"] * 48
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.vocab_size) == (2048, 32, 32, 128256)
    assert (cfg.mla_kv_rank, cfg.mla_nope_dim, cfg.mla_rope_dim,
            cfg.mla_v_dim) == (512, 128, 64, 128)
    assert (cfg.rope_theta, cfg.rope_pairs, cfg.qk_norm) == (
        1e6, "interleaved", False)
    assert (cfg.num_dense_layers, cfg.mlp_dim, cfg.num_experts,
            cfg.expert_topk, cfg.moe_mlp_dim, cfg.moe_shared_dim,
            cfg.routed_scaling) == (1, 6144, 128, 6, 768, 1536, 2.448)
    assert (cfg.router_score, cfg.expert_activation, cfg.router_input) == (
        "sigmoid_bias", "silu", "ffn_norm")
    assert cfg.remat and cfg.attention_impl == "pallas"
    assert not cfg.tie_embeddings and cfg.norm_eps == 1e-6
    assert cfg.out_proj_init_std == pytest.approx(0.02 / math.sqrt(96),
                                                  rel=1e-3)


def test_the_cells_cut_is_324_million_parameters(devices):
    """The cut of benchmarks/configs/kanana_2_30b_a3b.json: shapes only,
    nothing of this size is built."""
    cfg = load_config(YAML, CUT).model
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 256), jnp.int32),
        train=False))["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    attention = (2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256
                 + 16 * 128 * 2048)
    assert attention == 13_763_072
    assert count(shapes["layer0"]["mla"]) == attention
    experts = 2048 * 128 + 128 + 3 * 2048 * 768 + 8 * 3 * 2048 * 768
    norms = 2 * 2048
    assert count(shapes["layer0"]) == attention + 3 * 2048 * 3072 + norms
    for i in range(1, 5):
        assert count(shapes[f"layer{i}"]) == attention + experts + norms
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == (
        2 * 16032 * 2048)
    assert round(count(shapes) / 1e6, 1) == 324.3
    assert model.expert_share()["held"] == list(range(8))
    share = model.tensor_share()
    assert share["attention"]["held"] == list(range(16))
    assert share["dense_ffn"] == {"units": 6144, "held": [0, 3072]}
    assert share["shared_expert"] == {"units": 1536, "held": [0, 768]}


def tiny_cut() -> list:
    return [
        "model.num_layers=3",
        "model.layer_types=[latent_attention,latent_attention,"
        "latent_attention]",
        "model.hidden_size=64", "model.num_heads=4", "model.num_kv_heads=4",
        f"model.mla_kv_rank={RANK}", f"model.mla_nope_dim={NOPE}",
        f"model.mla_rope_dim={ROPE}", f"model.mla_v_dim={V}",
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
    ``grad_norm`` are the reference's, the expert counters ride its
    metrics and the kernels were called with value heads of their own
    width."""
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
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
    assert any(e["head_dim"] == NOPE + ROPE and e["v_head_dim"] == V
               and e["heads"] == 2 and e["causal"] for e in fa.dispatch_log())


def test_scopes_name_the_parts_the_benchmark_reads(devices):
    cfg = model_config()
    batch = packed_batch(0)
    model, params = init(cfg, batch)
    text = jax.jit(functools.partial(program_loss, model)).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("layer0/mla/q_proj", "layer0/mla/mla_latent/kv_a_proj",
                  "layer0/mla/mla_latent/kv_a_norm",
                  "layer0/mla/mla_latent/kv_b_proj",
                  "layer1/mla/qk_norm_rope", "layer1/mla/o_proj",
                  "layer0/mlp_in", "layer1/moe/router", "layer1/moe/shared",
                  "lm_head"):
        assert scope in text, scope


def test_the_kernel_check_has_the_cells_latent_call():
    """``scripts/verify_flash_kernels.py`` (the smoke's kernel leg) runs
    the latent layers' call of ``kanana2_s16384``: 16 heads of 192 over
    values of 128, causal over 16384 keys on the streaming kernels and the
    two-pass pair, and at 2048 keys through the fused backward."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
    from scripts import verify_flash_kernels as vfk

    assert (vfk.LATENT_HEADS, vfk.LATENT_D, vfk.LATENT_D_V) == (16, 192, 128)
    cases = vfk._latent_cases()
    assert cases["latent_d192_v128_s16384"] == (16384, None, jnp.bfloat16)
    tile = fa.select_dispatch(16384, 16384, jnp.bfloat16, 192, 128)
    assert (tile.family, tile.backward) == ("stream", "two_pass")
    assert cases["latent_d192_v128_s2048"][0] == 2048
    assert fa.select_dispatch(2048, 2048, jnp.bfloat16, 192,
                              128).backward == "fused"


def test_family_names_and_task():
    from distributed_tensorflow_framework_tpu.models import builtin_task
    from distributed_tensorflow_framework_tpu.models.bert import (
        decode_support_reason)

    assert builtin_task("kanana") == "causal_lm"
    assert "trains only" in decode_support_reason(model_config())


# ------------------------- the decoder already there lowers to the same step --
# sha256 of the lowered train step (StableHLO text, no locations) of
# tests/test_laguna.py's tiny cut of configs/laguna_s_2_1.yaml in bfloat16,
# read on the parent of the change that added the latent attention layer,
# before any file changed (the other three decoders: test_laguna.py).
PARENT_LAGUNA_STEP = \
    "fdb2b5d5ebccae8febe0b263d6e03a0b15a294522a023c0186c3506f5b89bbf2"


def test_laguna_lowers_to_the_parents_step(devices):
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder
    import test_laguna

    cfg = load_config(test_laguna.YAML,
                      [*test_laguna.tiny_cut(), "model.dtype=bfloat16"])
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:1])
    sample = to_global(
        {k: np.asarray(v) for k, v in packed_batch(0).items()}, mesh)
    builder = StepBuilder(cfg, mesh)
    state = builder.init_state(0, sample)
    text = builder.make_train_step(sample).lower(state, sample).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_LAGUNA_STEP
