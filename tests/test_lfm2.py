"""The LFM2-MoE decoder family (models/lfm2.py, models/moe.DroplessMoE)
against its plain float32 reference (benchmarks/reference/lfm2.py), at
tiny widths on the CPU: loss and every gradient on packed rows, the
share of an expert-parallel deployment, routing without dropped tokens,
the sort-based dispatcher against the one-hot path, and that each
departure from the layer equations fails a comparison."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2 as ref
from distributed_tensorflow_framework_tpu.core.config import ModelConfig
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as lfm2_model
from distributed_tensorflow_framework_tpu.train import losses

LAYERS = ["conv", "full_attention", "conv"]
ROUTED, TOPK, S, VOCAB = 8, 2, 128, 256


def model_config(**over) -> ModelConfig:
    base = dict(
        name="lfm2_moe", vocab_size=VOCAB, hidden_size=64,
        num_layers=len(LAYERS), layer_types=list(LAYERS), num_dense_layers=1,
        num_heads=4, num_kv_heads=2, mlp_dim=128,
        moe_mlp_dim=32, num_experts=ROUTED, expert_topk=TOPK,
        dtype="float32", attention_impl="xla", dropout_rate=0.0)
    base.update(over)
    return ModelConfig(**base)


def hparams(cfg: ModelConfig) -> dict:
    held = moe.held_experts(cfg.num_experts, cfg.expert_groups,
                            cfg.expert_group)
    return {
        "layer_types": list(cfg.layer_types),
        "num_dense_layers": cfg.num_dense_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.hidden_size // cfg.num_heads,
        "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "num_experts_per_tok": cfg.expert_topk,
        "experts_routed": cfg.num_experts, "experts_held": list(held),
        "router_norm_eps": 1e-6}


def packed_batch(seed=0, rows=2, s=S):
    """Three documents and a padded tail in each row, boundaries where no
    block or convolution window is aligned with them."""
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
    # The shipped selection bias is tiny (it evens the load); make it
    # large enough here that a handful of tokens cannot hide its absence.
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: p * 25.0 if "expert_bias" in jax.tree_util.keystr(
            path) else p, params)
    return model, params


def program_loss(model, params, batch):
    out = model.apply({"params": params}, batch["input_ids"],
                      batch["segment_ids"], batch["positions"], train=True)
    logits = out["logits"] if isinstance(out, dict) else out
    return losses.causal_lm_loss(logits, batch["targets"])[0]


def assert_model_matches_reference(cfg, *, tol=2e-5, seed=0):
    batch = packed_batch(seed)
    model, params = init(cfg, batch, seed)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            functools.partial(program_loss, model))(params, batch)
        want, want_g = jax.value_and_grad(ref.loss)(params, batch,
                                                    hparams(cfg))
    np.testing.assert_allclose(float(got), float(want), rtol=tol)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_g))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    assert flat_got.keys() == flat_want.keys()
    for path, g in flat_got.items():
        w = flat_want[path]
        scale = float(jnp.max(jnp.abs(w))) + 1e-8
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(w) / scale, atol=20 * tol,
            err_msg=jax.tree_util.keystr(path))
    return got_g


@pytest.mark.parametrize("groups,group", [(1, 0), (4, 0), (4, 3)],
                         ids=["whole", "share0of4", "share3of4"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_loss_and_gradients_match_the_reference(devices, impl, groups, group):
    """Every mixer kind, the dense and the expert feed-forward and the
    tied head, on packed rows with three documents and padding."""
    grads = assert_model_matches_reference(model_config(
        attention_impl=impl, expert_groups=groups, expert_group=group))
    bias = grads["layer1"]["moe"]["expert_bias"]
    assert not np.any(np.asarray(bias)), "the selection bias has a gradient"
    assert np.any(np.asarray(grads["layer2"]["short_conv"]["conv_kernel"]))


def test_bfloat16_activations_stay_near_the_reference(devices):
    cfg = model_config(dtype="bfloat16", attention_impl="pallas")
    batch = packed_batch(3)
    model, params = init(cfg, batch, 3)
    got = program_loss(model, params, batch)
    with jax.default_matmul_precision("highest"):
        want = ref.loss(params, batch, hparams(cfg))
    assert abs(float(got) - float(want)) / float(want) < 2e-3
    embed = model.apply({"params": params}, batch["input_ids"],
                        batch["segment_ids"], batch["positions"])
    assert embed["logits"].dtype == jnp.bfloat16


def test_remat_leaves_values_and_counters_alone(devices):
    batch = packed_batch(4)
    cfg = model_config()
    model, params = init(cfg, batch, 4)
    remat_model = get_model(model_config(remat=True))
    apply = lambda m: jax.value_and_grad(  # noqa: E731
        lambda p: program_loss(m, p, batch))(params)
    (a, ga), (b, gb) = apply(model), apply(remat_model)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)
    out = remat_model.apply({"params": params}, batch["input_ids"],
                            batch["segment_ids"], batch["positions"])
    assert float(out["moe_dropped"]) == 0.0
    assert float(out["moe_local_share"]) == 1.0
    assert float(out["moe_local_assignments"]) == 2 * S * TOPK


# ------------------------------------------------------------- the layer --
def layer_case(seed=0, tokens=512, hidden=64, width=32, bias_std=0.05):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (2, tokens // 2, hidden), jnp.float32)
    full = {
        "gate": jax.random.normal(k[1], (hidden, ROUTED)) / 8.0,
        "expert_bias": bias_std * jax.random.normal(k[2], (ROUTED,)),
        "w1": jax.random.normal(k[3], (ROUTED, hidden, width)) / 8.0,
        "w3": jax.random.normal(k[4], (ROUTED, hidden, width)) / 8.0,
        "w2": jax.random.normal(k[5], (ROUTED, width, hidden)) / 6.0}
    return x, full


def share_of(full: dict, groups: int, group: int) -> dict:
    held = moe.held_experts(ROUTED, groups, group)
    cut = lambda w: w[held.start:held.stop]  # noqa: E731
    return {**full, "w1": cut(full["w1"]), "w3": cut(full["w3"]),
            "w2": cut(full["w2"])}


def layer_apply(params, x, groups=1, group=0, topk=TOPK):
    layer = moe.DroplessMoE(num_experts=ROUTED, mlp_dim=params["w1"].shape[-1],
                            topk=topk, groups=groups, group=group,
                            dtype=jnp.float32)
    return layer.apply({"params": params}, x)


def reference_layer(full, x, held=range(ROUTED), topk=TOPK):
    h = {"experts_routed": ROUTED, "num_experts_per_tok": topk,
         "router_norm_eps": 1e-6, "experts_held": list(held)}
    p = {**full, **{w: full[w][held.start:held.stop]
                    for w in ("w1", "w3", "w2")}}
    return ref.experts(p, x, h)


def assert_layer_matches_reference(seed=0):
    x, full = layer_case(seed)
    with jax.default_matmul_precision("highest"):
        got, counters = layer_apply(full, x)
        want = reference_layer(full, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    return counters


def test_whole_layer_matches_the_reference(devices):
    counters = assert_layer_matches_reference()
    assert float(counters["dropped"]) == 0.0
    assert float(counters["local_assignments"]) == 512 * TOPK


@pytest.mark.parametrize("groups", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(devices, groups):
    """The share test: each group routes over all experts and computes its
    own experts' part; the parts of all groups add up to what the uncut
    reference gives for the whole layer. Router, scores and weights are
    computed alike by every group and counted once (they are not summed:
    only expert outputs are)."""
    x, full = layer_case(1)
    with jax.default_matmul_precision("highest"):
        parts, local = [], 0.0
        for g in range(groups):
            out, counters = layer_apply(share_of(full, groups, g), x,
                                        groups, g)
            parts.append(out)
            local += float(counters["local_assignments"])
            assert float(counters["dropped"]) == 0.0
            # the program's share equals the reference's same share
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(reference_layer(
                    full, x, moe.held_experts(ROUTED, groups, g))),
                atol=2e-5)
        whole = reference_layer(full, x)
    assert local == 512 * TOPK          # every assignment computed once
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=3e-5)


@pytest.mark.parametrize("groups,group", [(1, 0), (4, 0), (4, 1)])
def test_nothing_is_dropped_when_every_token_picks_the_same_experts(
        devices, groups, group):
    """A router forced onto experts 0 and 1 for every token: a capacity
    layer would drop most of them; here group 0 computes all ``T·K``
    assignments and every other group none."""
    x, full = layer_case(2)
    forced = {**full, "expert_bias": jnp.zeros(ROUTED).at[:2].set(100.0)}
    with jax.default_matmul_precision("highest"):
        out, counters = layer_apply(share_of(forced, groups, group), x,
                                    groups, group)
        want = reference_layer(forced, x,
                               moe.held_experts(ROUTED, groups, group))
    mine = 512 * TOPK if group == 0 else 0
    assert float(counters["local_assignments"]) == mine
    assert float(counters["dropped"]) == 0.0
    if mine:
        held = ROUTED // groups
        assert float(counters["load_max_mean"]) == pytest.approx(held / 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    if not mine:
        assert not np.any(np.asarray(out))


def test_sorted_dispatch_equals_the_one_hot_path_on_the_same_choices(devices):
    """``topk_dispatch``'s dense one-hot dispatch (capacity large enough
    to drop nothing) and the sort + grouped product, fed the same
    choices: with no selection bias the top-k of the sigmoid scores is
    the top-k of the softmax the capacity router takes."""
    x, full = layer_case(3, tokens=128)
    full = {**full, "expert_bias": jnp.zeros(ROUTED)}
    b, s, h = x.shape
    with jax.default_matmul_precision("highest"):
        logits = x @ full["gate"]
        dispatch, _, _ = moe.topk_dispatch(logits, TOPK, capacity=TOPK * s)
        experts, weights = moe.route_sigmoid_topk(
            logits.reshape(b * s, ROUTED), full["expert_bias"], TOPK)
        chosen = jax.nn.one_hot(experts, ROUTED).sum(1).reshape(b, s, ROUTED)
        np.testing.assert_array_equal(np.asarray(dispatch.sum(-1)),
                                      np.asarray(chosen))
        per_expert = (jax.nn.one_hot(experts, ROUTED)
                      * weights[..., None]).sum(1).reshape(b, s, ROUTED)
        xe = jnp.einsum("bsec,bsh->bech", dispatch, x)
        he = jax.nn.silu(jnp.einsum("bech,ehf->becf", xe, full["w1"])) \
            * jnp.einsum("bech,ehf->becf", xe, full["w3"])
        oe = jnp.einsum("becf,efh->bech", he, full["w2"])
        want = jnp.einsum("bsec,bse,bech->bsh", dispatch, per_expert, oe)
        got, _ = layer_apply(full, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def assert_router_follows_the_equation():
    """Scores so small that the normaliser's 1e-6 is a tenth of their
    sum: weights are ``s_e / (sum + 1e-6)``, not ``s_e / sum``."""
    logits = -14.5 + 0.5 * jax.random.normal(jax.random.key(5), (64, ROUTED))
    bias = 0.01 * jax.random.normal(jax.random.key(6), (ROUTED,))
    experts, weights = moe.route_sigmoid_topk(logits, bias, TOPK)
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    want_e = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :TOPK]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(want_e, -1))
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    want_w = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(weights), want_w, rtol=1e-5)
    assert 0.5 < float(weights.sum(-1).mean()) < 0.97


def test_router_weights_follow_the_equation(devices):
    assert_router_follows_the_equation()


def test_held_experts_are_contiguous_whole_shares():
    assert list(moe.held_experts(32, 4, 0)) == list(range(8))
    assert list(moe.held_experts(32, 4, 3)) == list(range(24, 32))
    assert list(moe.held_experts(8, 1, 0)) == list(range(8))
    for bad in ((32, 5, 0), (32, 4, 4), (32, 0, 0)):
        with pytest.raises(ValueError):
            moe.held_experts(*bad)


def test_the_model_says_which_experts_it_holds(devices, tmp_path):
    """``expert_share()`` is the model's own word, and the trainer's
    opening record carries it without knowing what an expert layer is."""
    import json
    import os

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.train import Trainer

    share = get_model(
        model_config(expert_groups=4, expert_group=1)).expert_share()
    assert share == {"num_experts": ROUTED, "groups": 4, "group": 1,
                     "held": [2, 3], "topk": TOPK}
    assert get_model(
        model_config(num_dense_layers=len(LAYERS))).expert_share() is None
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(os.path.join(root, "configs", "lfm2_8b_a1b.yaml"), [
        "model.num_layers=2", "model.layer_types=[conv,full_attention]",
        "model.num_dense_layers=1", "model.hidden_size=32",
        "model.num_heads=2", "model.num_kv_heads=1", "model.mlp_dim=64",
        "model.moe_mlp_dim=16", "model.num_experts=8", "model.expert_topk=2",
        "model.expert_groups=2", "model.expert_group=1",
        "model.attention_impl=xla", f"model.vocab_size={VOCAB}",
        f"data.vocab_size={VOCAB}", "data.seq_len=32",
        "data.global_batch_size=8",
        f"checkpoint.directory={tmp_path / 'run'}"])
    Trainer(cfg).build()
    with open(tmp_path / "run" / "events.jsonl") as fh:
        first = json.loads(fh.readline())
    assert first["kind"] == "run_meta"
    assert first["extra"]["expert_share"] == {
        "num_experts": 8, "groups": 2, "group": 1, "held": [4, 5, 6, 7],
        "topk": 2}


def test_conv_taps_stop_at_document_boundaries(devices):
    v = jnp.arange(1.0, 9.0).reshape(1, 8, 1)
    taps = jnp.array([[1.0], [10.0], [100.0]])
    seg = jnp.array([[1, 1, 1, 2, 2, 2, 2, 0]])
    got = lfm2_model.causal_depthwise_conv(v, taps, seg)[0, :, 0]
    want = [1, 12, 123, 4, 45, 456, 567, 8]
    np.testing.assert_allclose(np.asarray(got), want)
    plain = lfm2_model.causal_depthwise_conv(v, taps)[0, :, 0]
    np.testing.assert_allclose(np.asarray(plain),
                               [1, 12, 123, 234, 345, 456, 567, 678])


def test_positions_restart_at_each_document(devices):
    seg = jnp.array([[1, 1, 2, 2, 2, 0, 0], [4, 4, 4, 4, 9, 9, 9]])
    np.testing.assert_array_equal(
        np.asarray(lfm2_model.document_positions(seg)),
        [[0, 1, 0, 1, 2, 0, 1], [0, 1, 2, 3, 0, 1, 2]])
    np.testing.assert_array_equal(np.asarray(ref.positions_of(seg)),
                                  np.asarray(
                                      lfm2_model.document_positions(seg)))


# --------------------------------- departures from the equations are caught --
def _mutate(monkeypatch, what):
    if what == "bf16_router":
        plain = moe.route_sigmoid_topk
        monkeypatch.setattr(
            moe, "route_sigmoid_topk",
            lambda logits, bias, k: plain(
                logits.astype(jnp.bfloat16).astype(jnp.float32), bias, k))
    elif what == "bias_dropped":
        plain = moe.route_sigmoid_topk
        monkeypatch.setattr(
            moe, "route_sigmoid_topk",
            lambda logits, bias, k: plain(logits, jnp.zeros_like(bias), k))
    elif what == "softmax_for_sigmoid":
        def softmax_router(logits, bias, k):
            scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
            _, experts = jax.lax.top_k(scores + bias, k)
            chosen = jnp.take_along_axis(scores, experts, axis=-1)
            return experts.astype(jnp.int32), chosen / (
                chosen.sum(-1, keepdims=True) + moe.ROUTER_NORM_EPS)
        monkeypatch.setattr(moe, "route_sigmoid_topk", softmax_router)
    elif what == "normaliser_eps_dropped":
        monkeypatch.setattr(moe, "ROUTER_NORM_EPS", 0.0)
    elif what == "conv_tap_across_documents":
        plain = lfm2_model.causal_depthwise_conv
        monkeypatch.setattr(lfm2_model, "causal_depthwise_conv",
                            lambda v, taps, seg=None: plain(v, taps))
    elif what == "non_causal_attention":
        def full_attention(q, k, v, segment_ids=None, dtype=jnp.float32):
            g = q.shape[2] // k.shape[2]
            k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                jnp.float32(q.shape[-1]))
            same = (segment_ids[:, None, :, None]
                    == segment_ids[:, None, None, :])
            scores = jnp.where(same, scores, jnp.finfo(jnp.float32).min)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, -1), v)
        monkeypatch.setattr(lfm2_model, "causal_attention_xla",
                            full_attention)
    else:
        raise AssertionError(what)


_DETECTOR = {
    "bf16_router": assert_layer_matches_reference,
    "bias_dropped": lambda: assert_model_matches_reference(model_config()),
    "softmax_for_sigmoid": assert_layer_matches_reference,
    "normaliser_eps_dropped": assert_router_follows_the_equation,
    "conv_tap_across_documents":
        lambda: assert_model_matches_reference(model_config()),
    "non_causal_attention":
        lambda: assert_model_matches_reference(model_config()),
}


@pytest.mark.parametrize("what", sorted(_DETECTOR))
def test_a_departure_from_the_equations_fails_a_comparison(
        devices, monkeypatch, what):
    """Each of these, done to the PROGRAM, must fail the comparison that
    a test above passes: a router computed from bfloat16 logits, the
    selection bias left out, softmax in place of the sigmoid, the
    normaliser's 1e-6 dropped, a convolution tap read across a document
    boundary, attention without the causal mask."""
    _DETECTOR[what]()                     # holds before the mutation
    _mutate(monkeypatch, what)
    with pytest.raises(AssertionError):
        _DETECTOR[what]()


# ------------------------------------------------------- the normal path --
def test_family_names_and_task():
    from distributed_tensorflow_framework_tpu import models
    from distributed_tensorflow_framework_tpu.train.step import task_for_model

    for name in ("lfm2", "lfm2_moe", "LFM2-8B-A1B"):
        assert models._is_builtin_model_name(name.lower())
        assert task_for_model(name) == "causal_lm"
    assert task_for_model("bert") == "mlm"
    assert task_for_model("resnet50") == "classification"
    with pytest.raises(ValueError, match="shadows a built-in"):
        models.register_model("lfm2_mine")(lambda *a, **k: None)
    reason = models.decode_support_reason(model_config())
    assert "lfm2" in reason and "short convolution" in reason


@pytest.mark.parametrize("bad", [
    dict(layer_types=["conv"]), dict(layer_types=["conv", "window", "conv"]),
    dict(num_kv_heads=3), dict(moe_mlp_dim=0), dict(expert_topk=9),
    dict(expert_groups=3), dict(attention_impl="ring")])
def test_bad_configurations_are_refused(devices, bad):
    batch = packed_batch(0)
    with pytest.raises(ValueError):
        init(model_config(**bad), batch)


def test_the_trainer_step_gives_the_references_loss_and_grad_norm(devices):
    """``StepBuilder`` from the shipped YAML with a tiny cut, the
    ``causal_lm`` task, AdamW and the clip: the step's ``loss`` and
    ``grad_norm`` are the reference's, and the counters ride its metrics."""
    import os

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(os.path.join(root, "configs", "lfm2_8b_a1b.yaml"), [
        "model.num_layers=3", "model.layer_types=[conv,full_attention,conv]",
        "model.num_dense_layers=1", "model.hidden_size=64",
        "model.num_heads=4", "model.num_kv_heads=2",
        "model.mlp_dim=128", "model.moe_mlp_dim=32", "model.num_experts=8",
        "model.expert_topk=2", "model.expert_groups=4",
        f"model.vocab_size={VOCAB}", f"data.vocab_size={VOCAB}",
        f"data.seq_len={S}", "data.global_batch_size=2", "mesh.data=1",
        "model.dtype=float32"])
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
    assert 0.0 < float(metrics["moe_local_share"]) < 1.0
    assert float(metrics["moe_local_assignments"]) == pytest.approx(
        float(metrics["moe_local_share"]) * 2 * S * TOPK)


def test_synthetic_lm_labels_stay_inside_documents(devices):
    from distributed_tensorflow_framework_tpu.core.config import DataConfig
    from distributed_tensorflow_framework_tpu.data import get_dataset

    data = get_dataset(DataConfig(name="synthetic_lm", seq_len=64,
                                  vocab_size=100, global_batch_size=4))
    b = next(iter(data))
    assert set(b) == {"input_ids", "targets", "segment_ids", "positions"}
    seg, tgt, ids = b["segment_ids"], b["targets"], b["input_ids"]
    nxt_seg = np.roll(seg, -1, axis=1)
    labelled = tgt >= 0
    assert np.all(seg[labelled] == nxt_seg[labelled])
    assert np.all(tgt[labelled] == np.roll(ids, -1, axis=1)[labelled])
    assert np.all((seg[:, :-1] != seg[:, 1:]) == ~labelled[:, :-1])
    assert not labelled[:, -1].any()
    assert np.all(b["positions"][:, 0] == 0) and ids.max() < 100
