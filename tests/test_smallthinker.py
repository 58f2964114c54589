"""SmallThinker through the decoder family (models/lfm2.py with window
and global attention layers, a head size of its own, a router that reads
the stream before attention, softmax-of-chosen weights, ReGLU experts
and an untied head) against its plain float32 reference
(benchmarks/reference/smallthinker.py), at tiny widths on the CPU: loss
and every gradient on packed rows longer than the window, the share of
an expert-parallel deployment, the published YAML's size, and that each
departure from the layer equations fails the comparison."""

import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import smallthinker as ref
from distributed_tensorflow_framework_tpu.core.config import (
    ModelConfig, load_config)
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as family
from distributed_tensorflow_framework_tpu.train import losses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML = os.path.join(ROOT, "configs", "smallthinker_21b_a3b.yaml")
LAYERS = ["full_attention", "sliding_attention", "sliding_attention"]
ROPE = [0, 1, 1]
ROUTED, TOPK, S, VOCAB, WINDOW, HEAD_DIM = 8, 2, 128, 256, 24, 32
# The cell's cut of the published model.
CUT = ["model.num_layers=4",
       "model.layer_types=[full_attention,sliding_attention,"
       "sliding_attention,sliding_attention]",
       "model.rope_layout=[0,1,1,1]", "model.expert_groups=8",
       "model.expert_group=0", "model.vocab_size=18992"]


def model_config(**over) -> ModelConfig:
    base = dict(
        name="smallthinker_moe", vocab_size=VOCAB, hidden_size=64,
        num_layers=len(LAYERS), layer_types=list(LAYERS),
        rope_layout=list(ROPE), sliding_window=WINDOW, num_dense_layers=0,
        num_heads=4, num_kv_heads=2, head_dim=HEAD_DIM, qk_norm=False,
        moe_mlp_dim=32, num_experts=ROUTED, expert_topk=TOPK,
        router_input="stream", router_score="softmax_topk",
        expert_activation="relu", tie_embeddings=False, embed_init_std=1.0,
        norm_eps=1e-6, rope_theta=1.5e6, dtype="float32",
        attention_impl="xla", dropout_rate=0.0)
    base.update(over)
    return ModelConfig(**base)


def hparams(cfg: ModelConfig) -> dict:
    held = moe.held_experts(cfg.num_experts, cfg.expert_groups,
                            cfg.expert_group)
    return {
        "layer_types": list(cfg.layer_types),
        "rope_layout": list(cfg.rope_layout),
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "sliding_window_size": cfg.sliding_window,
        "moe_num_active_primary_experts": cfg.expert_topk,
        "experts_routed": cfg.num_experts, "experts_held": list(held)}


def packed_batch(seed=0, rows=2, s=S):
    """Three documents in each row (a padded tail in the first), every
    one but the shortest longer than the window, boundaries where no
    block is aligned with them."""
    rng = np.random.default_rng(seed)
    cuts = np.array([[37, 80, 119], [5, 64, 128]])[:rows]
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
    model = get_model(cfg)
    params = model.init(jax.random.key(seed), batch["input_ids"],
                        batch["segment_ids"], batch["positions"],
                        train=False)["params"]
    return model, params


def program_loss(model, params, batch):
    out = model.apply({"params": params}, batch["input_ids"],
                      batch["segment_ids"], batch["positions"], train=True)
    logits = out["logits"] if isinstance(out, dict) else out
    return losses.causal_lm_loss(logits, batch["targets"])[0]


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


def assert_model_matches_reference(cfg, *, program=None, tol=2e-5, seed=0):
    """Parameters and reference from ``cfg``; the program from ``program``
    (a configuration or a ready module) where one is put in its place,
    applied to the same parameters (less the output matrix, for a program
    whose head is tied)."""
    batch = packed_batch(seed)
    model, params = init(cfg, batch, seed)
    theirs = params
    if program is not None:
        model = get_model(program) if isinstance(program,
                                                 ModelConfig) else program
        if getattr(model, "tie_embeddings", False):
            theirs = {k: v for k, v in params.items() if k != "lm_head"}
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            functools.partial(program_loss, model))(theirs, batch)
        want, want_g = jax.value_and_grad(ref.loss)(params, batch,
                                                    hparams(cfg))
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    assert_gradients_close(got_g, {k: want_g[k] for k in got_g},
                           atol=20 * tol)
    return got_g


@pytest.mark.parametrize("groups,group", [(1, 0), (4, 0), (4, 3)],
                         ids=["whole", "share0of4", "share3of4"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_loss_and_gradients_match_the_reference(devices, impl, groups, group):
    """A global layer without positions, two rotary window layers, the
    router on the entering stream, ReGLU experts and the untied head, on
    packed rows with documents longer than the window."""
    grads = assert_model_matches_reference(model_config(
        attention_impl=impl, expert_groups=groups, expert_group=group))
    assert set(grads) == {"embed", "layer0", "layer1", "layer2",
                          "final_norm", "lm_head"}
    assert set(grads["layer0"]) == {"mixer_norm", "attn", "ffn_norm", "moe"}
    assert set(grads["layer1"]) == {"mixer_norm", "attn_window", "ffn_norm",
                                    "moe"}
    assert set(grads["layer0"]["attn"]) == {"query", "key", "value",
                                            "attn_out"}       # no q/k norm
    assert grads["layer0"]["attn"]["query"]["kernel"].shape == (
        64, 4 * HEAD_DIM)
    assert set(grads["layer0"]["moe"]) == {"gate", "w1", "w2", "w3"}
    assert np.any(np.asarray(grads["layer1"]["moe"]["gate"]))


def test_bfloat16_activations_stay_near_the_reference(devices):
    cfg = model_config(dtype="bfloat16", attention_impl="pallas")
    batch = packed_batch(3)
    model, params = init(cfg, batch, 3)
    got = program_loss(model, params, batch)
    with jax.default_matmul_precision("highest"):
        want = ref.loss(params, batch, hparams(cfg))
    assert abs(float(got) - float(want)) / float(want) < 2e-3


def test_remat_leaves_values_alone(devices):
    batch = packed_batch(4)
    model, params = init(model_config(expert_groups=4), batch, 4)
    again = get_model(model_config(expert_groups=4, remat=True))
    apply = lambda m: jax.value_and_grad(  # noqa: E731
        lambda p: program_loss(m, p, batch))(params)
    (a, ga), (b, gb) = apply(model), apply(again)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# ------------------------------------------------ what fails the comparison --
def _rerouted(source: str):
    """The program with its router fed another tensor of the layer:
    ``normed`` (the attention's input, after ``mixer_norm``) or
    ``post_attention`` (the stream the expert norm reads)."""
    seen = {}

    def interceptor(next_fun, args, kwargs, context):
        module = context.module
        if context.method_name != "__call__":
            return next_fun(*args, **kwargs)
        if isinstance(module, family.RMSNorm):
            out = next_fun(*args, **kwargs)
            if module.name == "mixer_norm":
                seen["normed"] = out
            elif module.name == "ffn_norm":
                seen["post_attention"] = args[0]
            return out
        if isinstance(module, moe.DroplessMoE):
            return next_fun(args[0], seen[source].astype(args[1].dtype))
        return next_fun(*args, **kwargs)

    class Rerouted:
        tie_embeddings = False

        def __init__(self, cfg):
            self.model = get_model(cfg)

        def apply(self, *args, **kwargs):
            with nn.intercept_methods(interceptor):
                return self.model.apply(*args, **kwargs)

    return Rerouted


def _unnormalised_softmax(logits, topk):
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    chosen, experts = jax.lax.top_k(scores, topk)
    return experts.astype(jnp.int32), chosen


DEPARTURES = {
    "window_off_by_one": lambda cfg: model_config(sliding_window=WINDOW + 1),
    "window_layers_see_everything": lambda cfg: model_config(sliding_window=S),
    "rotary_on_the_global_layer": lambda cfg: model_config(rope_layout=[1] * 3),
    "no_rotary_on_a_window_layer":
        lambda cfg: model_config(rope_layout=[0, 0, 1]),
    "router_reads_the_normed_stream": lambda cfg: _rerouted("normed")(cfg),
    "router_reads_the_post_attention_stream":
        lambda cfg: _rerouted("post_attention")(cfg),
    "router_reads_what_the_experts_read":
        lambda cfg: model_config(router_input="ffn_norm"),
    "silu_for_relu": lambda cfg: model_config(expert_activation="silu"),
    "softmax_over_all_without_renormalising": "patch",
    "tied_head": lambda cfg: model_config(tie_embeddings=True),
}


@pytest.mark.parametrize("what", sorted(DEPARTURES))
def test_a_departure_from_the_equations_fails_the_comparison(
        devices, monkeypatch, what):
    """Each of these, put in the PROGRAM's place, must fail the
    comparison that the model itself passes."""
    cfg = model_config()
    assert_model_matches_reference(cfg)         # holds before the departure
    if DEPARTURES[what] == "patch":
        monkeypatch.setattr(moe, "route_softmax_topk", _unnormalised_softmax)
        program = None
    else:
        program = DEPARTURES[what](cfg)
    with pytest.raises(AssertionError):
        assert_model_matches_reference(cfg, program=program)


def test_the_rerouting_seam_itself_changes_nothing(devices):
    """The interceptor that feeds the router another tensor, fed the
    stream the model feeds it, passes: the failures above are the
    tensor's."""
    seen = {}

    def interceptor(next_fun, args, kwargs, context):
        if isinstance(context.module, moe.DroplessMoE) \
                and context.method_name == "__call__":
            seen["routed"] = len(args) == 2
            return next_fun(args[0], args[1] * 1.0)
        return next_fun(*args, **kwargs)

    class Same:
        tie_embeddings = False
        model = get_model(model_config())

        def apply(self, *args, **kwargs):
            with nn.intercept_methods(interceptor):
                return self.model.apply(*args, **kwargs)

    assert_model_matches_reference(model_config(), program=Same())
    assert seen["routed"]


# --------------------------------------------------------------- the layer --
def layer_case(seed=0, tokens=512, hidden=64, width=32):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (2, tokens // 2, hidden), jnp.float32)
    stream = jax.random.normal(k[1], (2, tokens // 2, hidden), jnp.float32)
    full = {
        "gate": jax.random.normal(k[2], (hidden, ROUTED)) / 4.0,
        "w1": jax.random.normal(k[3], (ROUTED, hidden, width)) / 8.0,
        "w3": jax.random.normal(k[4], (ROUTED, hidden, width)) / 8.0,
        "w2": jax.random.normal(k[5], (ROUTED, width, hidden)) / 6.0}
    return x, stream, full


def share_of(full: dict, held: range) -> dict:
    return {**full, **{w: full[w][held.start:held.stop]
                       for w in ("w1", "w3", "w2")}}


def layer_apply(params, x, stream, groups=1, group=0):
    layer = moe.DroplessMoE(
        num_experts=ROUTED, mlp_dim=params["w1"].shape[-1], topk=TOPK,
        groups=groups, group=group, dtype=jnp.float32,
        score="softmax_topk", activation="relu")
    return layer.apply({"params": params}, x, stream)


def reference_layer(full, x, stream, held=range(ROUTED)):
    h = {"experts_routed": ROUTED, "moe_num_active_primary_experts": TOPK,
         "experts_held": list(held)}
    chosen, weights = ref.route(full, stream, h)
    return ref.experts(share_of(full, held), x, chosen, weights, h)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_shares_add_up_to_the_uncut_layer(devices, groups):
    """The share test: each group routes the entering stream over all
    experts and computes its own experts' part on the tensor the experts
    read; the parts of all groups add up to what the uncut reference
    gives for the whole layer, and every assignment is computed once."""
    x, stream, full = layer_case(1)
    with jax.default_matmul_precision("highest"):
        parts, local = [], 0.0
        for g in range(groups):
            held = moe.held_experts(ROUTED, groups, g)
            out, counters = layer_apply(share_of(full, held), x, stream,
                                        groups, g)
            parts.append(out)
            local += float(counters["local_assignments"])
            assert float(counters["dropped"]) == 0.0
            np.testing.assert_allclose(
                np.asarray(out),
                np.asarray(reference_layer(full, x, stream, held)), atol=2e-5)
        whole = reference_layer(full, x, stream)
    assert local == 512 * TOPK
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=3e-5)


def test_the_routing_input_is_another_tensor_and_defaults_to_the_experts(
        devices):
    x, stream, full = layer_case(2)
    layer = moe.DroplessMoE(num_experts=ROUTED, mlp_dim=32, topk=TOPK,
                            dtype=jnp.float32, score="softmax_topk",
                            activation="relu")
    with jax.default_matmul_precision("highest"):
        default, _ = layer.apply({"params": full}, x)
        same, _ = layer.apply({"params": full}, x, x)
        other, _ = layer.apply({"params": full}, x, stream)
    np.testing.assert_array_equal(np.asarray(default), np.asarray(same))
    assert not np.allclose(np.asarray(default), np.asarray(other), atol=1e-3)
    # the router's gradient flows into the tensor it read, not the other
    g_x, g_stream = jax.grad(
        lambda x, s: jnp.sum(layer.apply({"params": full}, x, s)[0] ** 2),
        argnums=(0, 1))(x, stream)
    assert np.any(np.asarray(g_stream)) and np.any(np.asarray(g_x))


def test_router_weights_follow_the_equation(devices):
    logits = jax.random.normal(jax.random.key(0), (64, ROUTED)) * 3.0
    experts, weights = moe.route_softmax_topk(logits, 3)
    top = np.argsort(-np.asarray(logits), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(experts), top)
    # softmax over all, renormalised over the chosen: the same numbers
    full = np.asarray(jax.nn.softmax(logits, axis=-1))
    picked = np.take_along_axis(full, top, axis=-1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


# --------------------------------------------------------- the normal path --
def _parameters(overrides):
    cfg = load_config(YAML, ["model.attention_impl=xla", *overrides])
    model = get_model(cfg.model)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    shapes = jax.eval_shape(lambda i: model.init(jax.random.key(0), i), ids)
    return cfg, shapes["params"]


def test_the_published_yaml_is_the_21b_model(devices):
    cfg, params = _parameters([])
    m = cfg.model
    assert (m.num_layers, m.hidden_size, m.num_heads, m.num_kv_heads,
            m.head_dim, m.moe_mlp_dim, m.num_experts, m.expert_topk,
            m.vocab_size, m.sliding_window) == (
        52, 2560, 28, 4, 128, 768, 64, 6, 151936, 4096)
    assert m.layer_types == (["full_attention"] + ["sliding_attention"] * 3
                             ) * 13
    assert m.rope_layout == [0, 1, 1, 1] * 13
    assert (m.embed_init_std, m.tie_embeddings, m.qk_norm, m.router_input,
            m.router_score, m.expert_activation) == (
        1.0, False, False, "stream", "softmax_topk", "relu")
    assert cfg.model.remat and cfg.model.attention_impl == "xla"
    total = sum(x.size for x in jax.tree.leaves(params))
    assert total == pytest.approx(21.5e9, rel=1e-2)
    layer = sum(x.size for x in jax.tree.leaves(params["layer1"]))
    assert layer == pytest.approx(398.6e6, rel=1e-3)


def test_the_cells_cut_is_370_million_parameters(devices):
    _, params = _parameters(CUT)
    total = sum(x.size for x in jax.tree.leaves(params))
    assert total == pytest.approx(370.5e6, rel=1e-3)
    assert params["layer0"]["moe"]["w1"].shape == (8, 2560, 768)
    assert params["layer0"]["moe"]["gate"].shape == (2560, 64)
    assert params["layer2"]["attn_window"]["key"]["kernel"].shape == (
        2560, 4 * 128)
    assert params["lm_head"].shape == params["embed"]["embedding"].shape == (
        18992, 2560)


def test_family_names_and_task():
    from distributed_tensorflow_framework_tpu import models
    from distributed_tensorflow_framework_tpu.train.step import task_for_model

    for name in ("smallthinker", "smallthinker_moe", "SmallThinker-21BA3B"):
        assert models._is_builtin_model_name(name.lower())
        assert task_for_model(name) == "causal_lm"
    reason = models.decode_support_reason(model_config())
    assert "trains only" in reason and "sliding" in reason


@pytest.mark.parametrize("bad,says", [
    (dict(sliding_window=0), "sliding_window"),
    (dict(rope_layout=[0, 1]), "rope_layout"),
    (dict(rope_layout=[0, 1, 2]), "rope_layout"),
    (dict(router_input="attention"), "router_input"),
    (dict(router_score="sparsemax"), "router_score"),
    (dict(expert_activation="gelu"), "expert_activation"),
    (dict(layer_types=["full_attention", "window", "conv"]),
     "sliding_attention")])
def test_bad_configurations_are_refused_by_name(devices, bad, says):
    with pytest.raises(ValueError, match=says):
        init(model_config(**bad), packed_batch(0))


def test_the_trainer_step_gives_the_references_loss_and_grad_norm(devices):
    """``StepBuilder`` from the shipped YAML with a tiny cut, the
    ``causal_lm`` task, the kernels and remat: the step's ``loss`` and
    ``grad_norm`` are the reference's, and the counters ride its metrics,
    the window layers' block share among them."""
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(YAML, [
        "model.num_layers=3",
        "model.layer_types=[full_attention,sliding_attention,"
        "sliding_attention]", "model.rope_layout=[0,1,1]",
        f"model.sliding_window={WINDOW}", "model.hidden_size=64",
        "model.num_heads=4", "model.num_kv_heads=2",
        f"model.head_dim={HEAD_DIM}", "model.moe_mlp_dim=32",
        "model.num_experts=8", "model.expert_topk=2",
        "model.expert_groups=4", f"model.vocab_size={VOCAB}",
        f"data.vocab_size={VOCAB}", f"data.seq_len={S}",
        "data.global_batch_size=2", "mesh.data=1", "model.dtype=float32"])
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
    assert float(metrics["moe_compact"]) == 1.0
    # one 128 x 128 tile holds the whole row: nothing to skip at this size,
    # and every program of the one-block grid is a visit
    assert float(metrics["attn_window_block_share"]) == 1.0
    assert float(metrics["attn_window_grid_share"]) == 1.0
    logged = {(e["window"], e["head_dim"], e["heads"], e["kv_heads"],
               e["k_axis"], e["q_axis"])
              for e in fa.dispatch_log() if e["s"] == S}
    # the fused backward at this length: one key block on its axis, no
    # dk/dv kernel; a call without a window says neither
    assert {(None, HEAD_DIM, 4, 2, None, None),
            (WINDOW, HEAD_DIM, 4, 2, 1, None)} <= logged


def test_the_block_share_is_the_kernels_count(devices):
    """The model's counter at the cell's shapes is ISSUE 30's 140 / 272,
    absent without window layers and without the kernels."""
    cfg = load_config(YAML, CUT).model
    assert get_model(cfg).window_block_share(16384) == pytest.approx(140 / 272)
    assert get_model(load_config(
        YAML, [*CUT, "model.attention_impl=xla"]).model).window_block_share(
            16384) is None
    lfm2 = load_config(os.path.join(ROOT, "configs", "lfm2_8b_a1b.yaml"), [])
    assert get_model(lfm2.model).window_block_share(8192) is None


def test_the_grid_share_is_the_kernels_count(devices):
    """ISSUE 37's number at the cell's shapes: 420 of the 480 programs a
    head and row that the forward, dq and dk/dv kernels launch hold a
    pair (32*5 + 32*5 + 16*10 where the full-length grid had 3 * 512);
    absent without window layers and without the kernels, and at the
    whole-K forward's lengths a count of that kernel's grid."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    cfg = load_config(YAML, CUT).model
    model = get_model(cfg)
    assert model.window_grid_share(16384) == pytest.approx(420 / 480)
    tile = fa.select_dispatch(16384, 16384, jnp.bfloat16, cfg.head_dim)
    grid = fa.window_grid(16384, 16384, cfg.sliding_window, tile)
    assert (grid["k_axis"], grid["q_axis"]) == (5, 10)
    assert model.window_grid_share(16384) == grid["visited"] / grid["launched"]
    short = fa.window_grid(
        2048, 2048, cfg.sliding_window,
        fa.select_dispatch(2048, 2048, jnp.bfloat16, cfg.head_dim))
    assert model.window_grid_share(2048) == (
        short["visited"] / short["launched"])
    assert get_model(load_config(
        YAML, [*CUT, "model.attention_impl=xla"]).model).window_grid_share(
            16384) is None
    lfm2 = load_config(os.path.join(ROOT, "configs", "lfm2_8b_a1b.yaml"), [])
    assert get_model(lfm2.model).window_grid_share(8192) is None


def test_only_a_model_with_window_kernels_reports_the_grid_share(devices):
    """``attn_window_grid_share`` rides the model's outputs beside
    ``attn_window_block_share`` where there are window layers and the
    kernels, and is absent otherwise."""
    ids = jnp.zeros((1, S), jnp.int32)
    for over, there in ((dict(attention_impl="pallas"), True),
                        (dict(attention_impl="xla"), False),
                        (dict(attention_impl="pallas",
                              layer_types=["full_attention"] * len(LAYERS)),
                         False)):
        model = get_model(model_config(**over))
        out = jax.eval_shape(
            lambda model=model: model.apply(
                model.init(jax.random.key(0), ids), ids))
        assert ("attn_window_grid_share" in out) == there, over
        assert ("attn_window_block_share" in out) == there, over


def test_scopes_name_the_two_kinds_of_attention_layer(devices):
    """``layerN/attn`` for a global layer, ``layerN/attn_window`` for a
    window layer, the router under ``layerN/moe/router``: the names the
    benchmark's readers key on, in the lowered step's debug names."""
    batch = packed_batch(1)
    model, params = init(model_config(attention_impl="pallas"), batch, 1)
    text = jax.jit(jax.grad(functools.partial(program_loss, model))).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("layer0/attn/", "layer1/attn_window/", "layer2/attn_window/",
                  "layer0/moe/router", "layer1/moe/experts", "lm_head"):
        assert scope in text, scope
    assert "layer0/attn_window" not in text and "layer1/attn/" not in text
