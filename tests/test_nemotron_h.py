"""Nemotron-H through the decoder family (models/lfm2.py with layers of
ONE sublayer: a Mamba-2 mixer as a chunked scan, attention without
positions, a LatentMoE of ungated squared-ReLU experts beside a shared
expert) against its plain float32 reference
(benchmarks/reference/nemotron_h.py), at tiny widths on the CPU: loss and
every gradient on packed rows, the scan against the whole-row form and
the token-by-token recurrence, the shares of experts and of heads, each
assumed equation's alternative, the two-product experts' hand-written
backward, and that the two decoders already in the benchmark lower to the
step they lowered to before."""

import dataclasses
import functools
import hashlib
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import nemotron_h as ref
from distributed_tensorflow_framework_tpu.core.config import (
    ModelConfig, load_config)
from distributed_tensorflow_framework_tpu.models import get_model, moe
from distributed_tensorflow_framework_tpu.models import lfm2 as family
from distributed_tensorflow_framework_tpu.ops.ssm_scan import chunked_ssm_scan
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
YAML = os.path.join(ROOT, "configs", "nemotron3_super_120b_a12b.yaml")
PATTERN = "EM*"
KINDS = {"M": "mamba2_only", "E": "experts_only", "*": "attention_only"}
ROUTED, TOPK, S, VOCAB, CHUNK = 16, 3, 128, 256, 24
# The cell's cut of the published model (benchmarks/configs/...json).
CUT = ["model.num_layers=11",
       "model.layer_types=[experts_only,mamba2_only,experts_only,"
       "mamba2_only,experts_only,mamba2_only,experts_only,mamba2_only,"
       "experts_only,mamba2_only,attention_only]",
       "model.rope_layout=[0,0,0,0,0,0,0,0,0,0,0]",
       "model.expert_groups=64", "model.expert_group=0",
       "model.tensor_groups=8", "model.tensor_group=0",
       "model.vocab_size=16384"]


def model_config(**over) -> ModelConfig:
    base = dict(
        name="nemotron_h", vocab_size=VOCAB, hidden_size=64,
        num_layers=len(PATTERN), layer_types=[KINDS[c] for c in PATTERN],
        rope_layout=[0] * len(PATTERN), num_dense_layers=0,
        num_heads=8, num_kv_heads=2, head_dim=16, qk_norm=False,
        mamba_num_heads=8, mamba_head_dim=8, mamba_groups=2,
        ssm_state_size=16, mamba_chunk=CHUNK, conv_kernel=4,
        moe_mlp_dim=24, moe_latent_dim=32, moe_shared_dim=48,
        num_experts=ROUTED, expert_topk=TOPK, routed_scaling=5.0,
        router_score="sigmoid_bias", expert_activation="relu2",
        tie_embeddings=False, norm_eps=1e-5, rope_theta=10000.0,
        dtype="float32", attention_impl="xla", dropout_rate=0.0)
    base.update(over)
    return ModelConfig(**base)


def hparams(cfg: ModelConfig) -> dict:
    model = get_model(cfg)
    share = model.tensor_share() or {
        "attention": {"held": range(cfg.num_heads),
                      "kv_held": range(cfg.num_kv_heads)},
        "mamba2": {"held": range(cfg.mamba_num_heads),
                   "bc_held": range(cfg.mamba_groups)}}
    letters = {v: k for k, v in KINDS.items()}
    return {
        "pattern": "".join(letters[k] for k in cfg.layer_types),
        "head_dim": cfg.head_dim, "layer_norm_epsilon": cfg.norm_eps,
        "mamba_head_dim": cfg.mamba_head_dim,
        "ssm_state_size": cfg.ssm_state_size,
        "num_experts_per_tok": cfg.expert_topk,
        "routed_scaling_factor": cfg.routed_scaling,
        "moe_latent_size": cfg.moe_latent_dim,
        "experts_routed": cfg.num_experts,
        "experts_held": list(moe.held_experts(
            cfg.num_experts, cfg.expert_groups, cfg.expert_group)),
        "heads_held": {
            "mamba": list(share["mamba2"]["held"]),
            "bc_groups": list(share["mamba2"]["bc_held"]),
            "attention": list(share["attention"]["held"]),
            "key_value": list(share["attention"]["kv_held"])}}


def packed_batch(seed=0, rows=2, s=S):
    """Three documents in each row (a padded tail in the first); ``s`` is
    no multiple of the scan's chunk (24) and every boundary lies inside
    one."""
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
    (the program's own initialisers compile for seconds; the trainer
    test runs them): kernels normal over the square root of their fan-in,
    scales and ``D`` around 1, ``A_log`` as the family draws it."""
    model = get_model(cfg)
    shapes = jax.eval_shape(
        functools.partial(model.init, train=False), jax.random.key(0),
        batch["input_ids"], batch["segment_ids"],
        batch["positions"])["params"]
    rng = np.random.default_rng(seed)

    def draw(path, leaf):
        name = path[-1].key if path[-1].key != "kernel" else path[-2].key
        normal = rng.standard_normal(leaf.shape)
        if name in ("scale", "norm_scale", "D"):
            value = 1.0 + 0.1 * normal
        elif name == "A_log":
            value = np.log(rng.uniform(1.0, 16.0, leaf.shape))
        elif name == "dt_bias":
            value = normal - 1.0
        elif name in ("expert_bias", "conv_bias"):
            value = 0.05 * normal
        elif name in ("embedding", "lm_head"):
            value = 0.1 * normal
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


@functools.lru_cache(maxsize=None)
def _reference_of(cfg_repr: str, seed: int):
    cfg = _CONFIGS[cfg_repr]
    batch = packed_batch(seed)
    _, params = init(cfg, batch, seed)
    h = hparams(cfg)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: ref.loss(p, batch, h)))(params)


_CONFIGS: dict = {}


def reference_loss_and_gradients(cfg, seed):
    """The reference's loss and gradients for ``cfg``'s own parameters,
    computed once per configuration and seed in a worker."""
    _CONFIGS[repr(cfg)] = cfg
    return _reference_of(repr(cfg), seed)


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
    documents each; whole and as a share of experts, of heads, of both."""
    grads = assert_model_matches_reference(
        model_config(attention_impl=impl, **SHARES[share]))
    for name in ("gate", "latent_in", "w1", "w2", "shared"):
        leaf = jax.tree.leaves(grads["layer0"]["moe"][name])[0]
        assert np.any(np.asarray(leaf)), name
    assert not np.any(np.asarray(grads["layer0"]["moe"]["expert_bias"]))
    for name in ("A_log", "D", "dt_bias", "conv_bias", "conv_kernel",
                 "norm_scale"):
        assert np.any(np.asarray(grads["layer1"]["mamba"][name])), name


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


def test_remat_leaves_values_and_counters_alone(devices):
    batch = packed_batch(4)
    model, params = init(model_config(**SHARES["experts1of4"]), batch, 4)
    again = get_model(model_config(remat=True, **SHARES["experts1of4"]))

    def apply(m):
        def of(p):
            out = m.apply({"params": p}, batch["input_ids"],
                          batch["segment_ids"], batch["positions"])
            loss = losses.causal_lm_loss(out.pop("logits"),
                                         batch["targets"])[0]
            return loss, out
        return jax.jit(jax.value_and_grad(of, has_aux=True))(params)

    ((a, ca), ga), ((b, cb), gb) = apply(model), apply(again)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)
    assert {k: float(v) for k, v in ca.items()} == {
        k: float(v) for k, v in cb.items()}
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-6)


# ---------------------------------------------------------------- the scan --
def scan_case(seed=0, rows=2, s=S, heads=4, width=8, groups=2, state=16):
    k = jax.random.split(jax.random.key(seed), 5)
    return {
        "x": jax.random.normal(k[0], (rows, s, heads, width)),
        "delta": jax.nn.softplus(jax.random.normal(k[1], (rows, s, heads))),
        "a": -jax.random.uniform(k[2], (heads,), minval=0.3, maxval=2.0),
        "b": jax.random.normal(k[3], (rows, s, groups, state)),
        "c": jax.random.normal(k[4], (rows, s, groups, state))}


def by_head(recurrence, case, segments):
    """A reference recurrence (one head at a time) over every head."""
    heads, groups = case["x"].shape[2], case["b"].shape[2]
    return jnp.stack([
        recurrence(case["x"][:, :, i], case["delta"][:, :, i], case["a"][i],
                   case["b"][:, :, i // (heads // groups)],
                   case["c"][:, :, i // (heads // groups)], segments)
        for i in range(heads)], axis=2)


def test_the_whole_row_reference_is_the_recurrence_token_by_token(devices):
    """The reference's (T x T) form against the definition written out as
    a loop over tokens, values and gradients: the reference itself is
    tied to the recurrence."""
    case, segments = scan_case(1), packed_batch(1)["segment_ids"]
    cot = jax.random.normal(jax.random.key(9), case["x"].shape)

    def of(recurrence):
        return jax.jit(jax.value_and_grad(lambda c: jnp.sum(
            by_head(recurrence, c, segments) * cot)))(case)

    with jax.default_matmul_precision("highest"):
        (got, got_g), (want, want_g) = (of(ref.recurrence_whole_row),
                                        of(ref.recurrence_by_token))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    assert_gradients_close(got_g, want_g, atol=2e-5)


@pytest.mark.parametrize("chunk", [24, 48, 256])
def test_the_chunked_scan_is_both_references(devices, chunk):
    """The program's chunked scan on rows of 128 tokens (five chunks of
    24 and a third, two of 48 and two thirds, half of one of 256) with
    every document boundary inside a chunk, against the whole-row form
    and the loop, values and gradients."""
    case, segments = scan_case(2), packed_batch(2)["segment_ids"]
    assert S % chunk
    cot = jax.random.normal(jax.random.key(5), case["x"].shape)

    def program(c):
        return chunked_ssm_scan(c["x"], c["delta"], c["a"], c["b"], c["c"],
                                segments, chunk=chunk)

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(
            lambda c: jnp.sum(program(c) * cot)))(case)
        np.testing.assert_allclose(
            np.asarray(jax.jit(program)(case)),
            np.asarray(jax.jit(functools.partial(
                by_head, ref.recurrence_by_token))(case, segments)),
            atol=2e-4)
        for recurrence in (ref.recurrence_whole_row,
                           ref.recurrence_by_token):
            want, want_g = jax.jit(jax.value_and_grad(lambda c: jnp.sum(
                by_head(recurrence, c, segments) * cot)))(case)
            # a sum of 8192 signed terms of size ~10
            np.testing.assert_allclose(float(got), float(want), atol=2e-3)
            assert_gradients_close(got_g, want_g, atol=2e-5)


def test_the_scan_starts_anew_at_each_document(devices):
    """A document's outputs do not depend on the documents before it,
    whichever chunk its first token falls in."""
    case, segments = scan_case(3), packed_batch(3)["segment_ids"]
    run = lambda c: chunked_ssm_scan(  # noqa: E731
        c["x"], c["delta"], c["a"], c["b"], c["c"], segments, chunk=CHUNK)
    first = np.asarray(segments) == 1
    other = {**case, "x": jnp.where(first[..., None, None],
                                    case["x"] + 3.0, case["x"])}
    a, b = np.asarray(run(case)), np.asarray(run(other))
    np.testing.assert_array_equal(a[~first], b[~first])
    assert np.any(a[first] != b[first])


def test_the_step_counts_the_scans_resets(devices):
    batch = packed_batch(0)
    model, params = init(model_config(), batch)
    out = jax.jit(model.apply)({"params": params}, batch["input_ids"],
                               batch["segment_ids"], batch["positions"])
    assert float(out["ssm_resets"]) == 6.0          # three documents a row
    assert float(out["moe_dropped"]) == 0.0


# --------------------------------------------------------------- the shares --
def moe_layer(groups=1, group=0, **over):
    kw = dict(num_experts=ROUTED, mlp_dim=24, topk=TOPK, groups=groups,
              group=group, dtype=jnp.float32, score="sigmoid_bias",
              activation="relu2", latent_dim=32, shared_dim=48,
              weight_scale=5.0)
    return moe.DroplessMoE(**{**kw, **over})


def moe_case(seed=0, tokens=256, hidden=64):
    x = jax.random.normal(jax.random.key(seed), (2, tokens // 2, hidden))
    full = jax.jit(moe_layer().init)(jax.random.key(seed + 1), x)["params"]
    return x, jax.tree.map(lambda p: p * 3.0, full)   # scores off 0.5


def expert_share_of(full, groups, group):
    held = moe.held_experts(ROUTED, groups, group)
    return {**full, **{w: full[w][held.start:held.stop]
                       for w in ("w1", "w2")}}


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_expert_shares_add_up_to_the_uncut_layer(devices, groups):
    """Each expert group routes over all experts and adds its own
    experts' part; latent projections, router and shared expert are
    whole on every group. The groups' parts, the shared expert counted
    once, add up to the uncut reference layer, every assignment computed
    once."""
    x, full = moe_case(1)
    h = {"experts_routed": ROUTED, "num_experts_per_tok": TOPK,
         "routed_scaling_factor": 5.0, "moe_latent_size": 32,
         "experts_held": list(range(ROUTED))}
    with jax.default_matmul_precision("highest"):
        shared = jax.jit(moe.SharedExpert(48, jnp.float32).apply)(
            {"params": full["shared"]}, x)
        total, local = 0.0, 0.0
        for g in range(groups):
            out, counters = jax.jit(moe_layer(groups, g).apply)(
                {"params": expert_share_of(full, groups, g)}, x)
            total = total + out - shared
            local += float(counters["local_assignments"])
            assert float(counters["dropped"]) == 0.0
        whole = jax.jit(lambda p: ref.latent_moe(p, x, h))(full)
    assert local == 256 * TOPK
    whole = np.asarray(whole)
    np.testing.assert_allclose(np.asarray(total + shared), whole,
                               atol=3e-6 * np.abs(whole).max())


def _columns(widths, picks):
    """Column indices of a fused projection: ``widths`` its parts'
    widths in order, ``picks`` the kept columns inside each part."""
    starts = np.concatenate([[0], np.cumsum(widths)[:-1]])
    return np.concatenate([s + np.asarray(p) for s, p in zip(starts, picks)])


def mamba_share_of(full, cfg, groups, group):
    """Head group ``group``'s slice of an uncut Mamba-2 layer's
    parameters: its heads' channels of z and x, its B/C groups, its
    heads' step sizes, and its rows of the out-projection."""
    p, n = cfg.mamba_head_dim, cfg.ssm_state_size
    heads = family.held_heads(cfg.mamba_num_heads, groups, group)
    bc = family.held_heads(cfg.mamba_groups, groups, group)
    d_in, d_bc = cfg.mamba_num_heads * p, cfg.mamba_groups * n
    channels = np.arange(heads.start * p, heads.stop * p)
    state = np.arange(bc.start * n, bc.stop * n)
    conv = _columns([d_in, d_bc, d_bc], [channels, state, state])
    cols = _columns([d_in, d_in, d_bc, d_bc, cfg.mamba_num_heads],
                    [channels, channels, state, state, np.asarray(heads)])
    return {
        "in_proj": {"kernel": full["in_proj"]["kernel"][:, cols]},
        "conv_kernel": full["conv_kernel"][:, conv],
        "conv_bias": full["conv_bias"][conv],
        "dt_bias": full["dt_bias"][heads.start:heads.stop],
        "A_log": full["A_log"][heads.start:heads.stop],
        "D": full["D"][heads.start:heads.stop],
        "norm_scale": full["norm_scale"][channels],
        "out_proj": {"kernel": full["out_proj"]["kernel"][channels]}}


def attention_share_of(full, cfg, groups, group):
    d = cfg.head_dim
    q = family.held_heads(cfg.num_heads, groups, group)
    per_kv = cfg.num_heads // cfg.num_kv_heads
    kv = sorted({i // per_kv for i in q})
    q_cols = np.arange(q.start * d, q.stop * d)
    kv_cols = np.concatenate([np.arange(i * d, (i + 1) * d) for i in kv])
    return {"query": {"kernel": full["query"]["kernel"][:, q_cols]},
            "key": {"kernel": full["key"]["kernel"][:, kv_cols]},
            "value": {"kernel": full["value"]["kernel"][:, kv_cols]},
            "attn_out": {"kernel": full["attn_out"]["kernel"][q_cols]}}


@pytest.mark.parametrize("groups", [2, 4, 8])
def test_the_head_shares_add_up_to_the_uncut_mixers(devices, groups):
    """Each head group's Mamba-2 layer (its heads, its B/C groups, a
    gated norm over its own groups), attention layer (its query heads
    with the key/value heads they read) and run of the shared expert's
    hidden units compute their part of the out-projection's sum: the
    parts add up to the uncut reference mixers and shared expert. With 8 groups a key/value head's 4 queries are split over
    two chips each, evenly."""
    cfg = model_config(mamba_num_heads=16, mamba_groups=8, num_heads=16,
                       num_kv_heads=4)
    batch = packed_batch(2)
    _, params = init(cfg, batch, 2)
    segments, positions = batch["segment_ids"], batch["positions"]
    u = jax.random.normal(jax.random.key(3), (2, S, cfg.hidden_size))
    h = hparams(cfg)
    with jax.default_matmul_precision("highest"):
        mamba = attention = shared = 0.0
        for g in range(groups):
            part = get_model(dataclasses.replace(
                cfg, tensor_groups=groups, tensor_group=g))
            kw = part.solo_sublayer("mamba2_only", 1)
            mamba = mamba + jax.jit(family.Mamba2Mixer(**kw).apply)(
                {"params": mamba_share_of(params["layer1"]["mamba"], cfg,
                                          groups, g)}, u, segments)
            held = len(part.tensor_share()["attention"]["held"])
            kv_held = len(part.tensor_share()["attention"]["kv_held"])
            assert (held, kv_held) == (16 // groups, max(1, 4 // groups))
            kw = part.solo_sublayer("attention_only", 2)
            assert (kw["num_heads"], kw["num_kv_heads"]) == (held, kv_held)
            attention = attention + jax.jit(family.GroupedQueryAttention(
                **kw).apply)(
                {"params": attention_share_of(params["layer2"]["attn"], cfg,
                                              groups, g)},
                u, segments, positions)
            units = family.held_heads(48, groups, g)
            full = params["layer0"]["moe"]["shared"]
            shared = shared + jax.jit(moe.SharedExpert(
                len(units), jnp.float32).apply)({"params": {
                    "up": {"kernel": full["up"]["kernel"][
                        :, units.start:units.stop]},
                    "down": {"kernel": full["down"]["kernel"][
                        units.start:units.stop]}}}, u)
            assert part.tensor_share()["shared_expert"] == {
                "units": 48, "held": [units.start, units.stop]}
        want_shared = ref.relu2(u @ full["up"]["kernel"]) \
            @ full["down"]["kernel"]
        want_mamba = jax.jit(lambda p: ref.mamba2(p, u, segments, h))(
            params["layer1"]["mamba"])
        whole = {**h, "rms_norm_eps": 1e-5, "num_attention_heads": 16,
                 "num_key_value_heads": 4}
        want_attention = jax.jit(lambda p: ref.attention(
            p, u, segments, positions, whole, window=None, rotates=False))(
                params["layer2"]["attn"])
    np.testing.assert_allclose(np.asarray(mamba), np.asarray(want_mamba),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(attention),
                               np.asarray(want_attention), atol=3e-5)
    np.testing.assert_allclose(np.asarray(shared), np.asarray(want_shared),
                               atol=3e-5)


@pytest.mark.parametrize("bad,says", [
    (dict(tensor_groups=4), "splits a B/C group"),            # 2 B/C groups
    (dict(tensor_groups=3), "whole, even shares"),
    (dict(tensor_groups=2, tensor_group=2), "is not one of"),
    (dict(tensor_groups=2, moe_shared_dim=49), "shared"),
    (dict(mamba_num_heads=6, mamba_groups=4), "multiple of"),
    (dict(layer_types=["experts_only"] * 2 + ["conv"], tensor_groups=2),
     "one-sublayer kinds"),
    (dict(expert_activation="gelu"), "expert_activation"),
    (dict(layer_types=["mamba2"] * 3), "layer_types")])
def test_bad_configurations_are_refused_by_name(devices, bad, says):
    with pytest.raises(ValueError, match=says):
        get_model(model_config(**bad))


# ------------------------------------------------ what fails the comparison --
def _intercepted(edit):
    """The program with ``edit(module, next_fun, args, stream)`` in place
    of every module call; ``stream`` is the un-normed stream of the
    layer the module sits in."""
    seen = {}

    def interceptor(next_fun, args, kwargs, context):
        module = context.module
        if context.method_name != "__call__":
            return next_fun(*args, **kwargs)
        if isinstance(module, family.RMSNorm) and module.name == "norm":
            seen["stream"] = args[0]
        out = edit(module, next_fun, args, seen.get("stream"))
        return next_fun(*args, **kwargs) if out is None else out

    class Edited:
        def __init__(self, cfg):
            self.model = get_model(cfg)

        def apply(self, *args, **kwargs):
            with nn.intercept_methods(interceptor):
                return self.model.apply(*args, **kwargs)

    return Edited


def _on(cls_or_name, make):
    """An edit of the calls of one class of module, or of the ``nn.Dense``
    of one name."""
    def edit(module, next_fun, args, stream):
        hit = module.name == cls_or_name if isinstance(cls_or_name, str) \
            else isinstance(module, cls_or_name)
        return make(next_fun, args, stream) if hit else None
    return edit


def _tokens(stream, like):
    return stream.reshape(like.shape).astype(like.dtype)


ALTERNATIVES = {
    # attention: the row carries rope_theta 10000, partial_rotary_factor 1
    "full_rotary_at_theta_10000":
        lambda cfg: model_config(rope_layout=[1] * len(PATTERN)),
    "router_reads_the_unnormed_stream": _intercepted(_on(
        moe.DroplessMoE, lambda f, a, stream: f(a[0], stream))),
    "latent_reads_the_unnormed_stream": _intercepted(_on(
        "latent_in", lambda f, a, stream: f(_tokens(stream, a[0])))),
    "shared_expert_reads_the_unnormed_stream": _intercepted(_on(
        moe.SharedExpert, lambda f, a, stream: f(_tokens(stream, a[0])))),
    "an_activation_inside_the_latent": _intercepted(_on(
        "latent_in", lambda f, a, stream: jax.nn.silu(f(*a)))),
    "a_norm_inside_the_latent": _intercepted(_on(
        "latent_in", lambda f, a, stream: ref.rms_norm(1.0, f(*a), 1e-5))),
    "scaling_factor_on_the_shared_expert_too": _intercepted(_on(
        moe.SharedExpert, lambda f, a, stream: 5.0 * f(*a))),
    "no_scaling_factor": lambda cfg: model_config(routed_scaling=1.0),
}


def _norm_then_gate(y, z, scale, groups, eps):
    lead, d = y.shape[:-1], y.shape[-1]
    y = y.astype(jnp.float32).reshape(*lead, groups, d // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                          + eps)
    return y.reshape(*lead, d) * scale * jax.nn.silu(z.astype(jnp.float32))


def _clamped_step_size(dt, dt_bias):
    # time_step_min .. time_step_max of the source's config
    return jnp.clip(jax.nn.softplus(dt.astype(jnp.float32) + dt_bias),
                    1e-3, 1e-1)


# ... and those that sit inside a module, put there by name.
PATCHES = {
    "norm_then_gate": (family, "gated_group_norm", _norm_then_gate),
    "a_clamped_step_size": (family, "step_size", _clamped_step_size),
    "a_plain_relu_in_the_experts": (
        moe.UNGATED_ACTIVATIONS, "relu2", jax.nn.relu),
}


@pytest.mark.parametrize("what", sorted({**ALTERNATIVES, **PATCHES}))
def test_an_assumed_equations_alternative_fails_the_comparison(
        devices, monkeypatch, what):
    """Each item of the configuration's ``assumed``, its alternative put
    in the PROGRAM's place, must fail the float32 comparison that the
    model itself passes (``test_the_seam_itself_changes_nothing``,
    ``test_loss_and_gradients_match_the_reference[xla-whole]``)."""
    cfg = model_config()
    program = None
    if what in PATCHES:
        where, name, alternative = PATCHES[what]
        if isinstance(where, dict):
            monkeypatch.setitem(where, name, alternative)
        else:
            monkeypatch.setattr(where, name, alternative)
    else:
        program = ALTERNATIVES[what](cfg)
    with pytest.raises(AssertionError):
        assert_model_matches_reference(cfg, program=program, loss_first=True)


def test_the_seam_itself_changes_nothing(devices):
    """The interceptor, handing every module what it was handed, passes:
    the failures above are the alternatives'."""
    calls = []
    same = _intercepted(lambda module, f, a, stream: calls.append(
        type(module).__name__))(model_config())
    assert_model_matches_reference(model_config(), program=same)
    assert {"DroplessMoE", "SharedExpert", "Mamba2Mixer"} <= set(calls)


# ------------------------------------- two-product experts in the windows --
def one_window_layer(params, x, groups, group):
    """The layer's routed part with a row for every one of the ``T·K``
    assignments, one window, plain indexing both ways (autodiff's
    scatter-adds): what the windows' hand-written backward must equal."""
    b, s, h = x.shape
    t = b * s
    mine = moe.held_experts(ROUTED, groups, group)
    tokens = x.reshape(t, h)
    logits = jnp.dot(tokens, params["gate"],
                     precision=jax.lax.Precision.HIGHEST)
    experts, weights = moe.route_sigmoid_topk(
        logits, params["expert_bias"], TOPK)
    flat = experts.reshape(-1)
    local = (flat >= mine.start) & (flat < mine.stop)
    key = jnp.where(local, flat - mine.start, len(mine))
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.bincount(key, length=len(mine) + 1)[:len(mine)].astype(
        jnp.int32)
    valid = (jnp.arange(t * TOPK) < sizes.sum())[:, None]
    xs = jnp.where(valid, jnp.repeat(tokens, TOPK, axis=0)[order], 0)
    dot = lambda lhs, w: jax.lax.ragged_dot(lhs, w, sizes)  # noqa: E731
    ys = jnp.where(valid, dot(ref.relu2(dot(xs, params["w1"])),
                              params["w2"]), 0)
    back = ys[inverse].reshape(t, TOPK, h)
    return jnp.einsum("tkh,tk->th", back, weights).reshape(b, s, h)


@pytest.mark.parametrize("sent", ["routed", "all"])
def test_the_windows_backward_with_two_product_experts(devices, sent):
    """Values and every gradient of the layer with ungated experts (no
    latent, no shared expert: the windows alone) against ``jax.grad`` of
    the one-window form, on the router's own choices and on a routing
    that sends a quarter-share every assignment there is: four windows
    of its buffer."""
    tokens, groups, group = 256, 4, 1
    rows = moe.held_rows(tokens * TOPK, ROUTED // groups, ROUTED)
    assert rows == 512 < tokens * TOPK
    layer = moe_layer(groups, group, latent_dim=0, shared_dim=0,
                      weight_scale=1.0)
    x = jax.random.normal(jax.random.key(0), (2, tokens // 2, 64))
    params = jax.jit(layer.init)(jax.random.key(1), x)["params"]
    assert set(params) == {"gate", "expert_bias", "w1", "w2"}
    if sent == "all":
        held = moe.held_experts(ROUTED, groups, group)
        bias = jnp.where(jnp.isin(jnp.arange(ROUTED), jnp.asarray(
            list(held)[:TOPK])), 2.0, 0.0)
        params = {**params, "expert_bias": bias}
    cot = jax.random.normal(jax.random.key(11), x.shape)

    def loss(fn):
        def of(params, x):
            out = fn(params, x)
            out, counters = out if isinstance(out, tuple) else (out, None)
            return jnp.sum(out * cot), (out, counters)
        return jax.jit(jax.value_and_grad(of, argnums=(0, 1), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, (got, counters)), got_g = loss(
            lambda p, x: layer.apply({"params": p}, x))(params, x)
        (_, (want, _)), want_g = loss(
            lambda p, x: one_window_layer(p, x, groups, group))(params, x)
    if sent == "all":
        assert float(counters["local_assignments"]) == tokens * TOPK
        assert float(counters["compact"]) == 0.0
    assert float(counters["dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.any(np.asarray(got))
    assert_gradients_close(got_g, want_g, atol=2e-5)


# ------------------------------------------------- the published model's cut --
def test_the_published_yaml_is_the_120b_model(devices):
    cfg = load_config(YAML, []).model
    kinds = cfg.layer_types
    assert (len(kinds), kinds.count("mamba2_only"),
            kinds.count("experts_only"), kinds.count("attention_only")) == (
                88, 40, 40, 8)
    assert not any(cfg.rope_layout) and len(cfg.rope_layout) == 88
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.vocab_size) == (4096, 32, 2, 128, 131072)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_groups,
            cfg.ssm_state_size, cfg.mamba_chunk, cfg.conv_kernel) == (
                128, 64, 8, 128, 128, 4)
    assert (cfg.num_experts, cfg.expert_topk, cfg.moe_mlp_dim,
            cfg.moe_latent_dim, cfg.moe_shared_dim, cfg.routed_scaling) == (
                512, 22, 2688, 1024, 5376, 5.0)
    assert cfg.remat and cfg.attention_impl == "pallas"
    # 0.02 / sqrt(2 x 88), under unit-variance embeddings
    assert (cfg.embed_init_std, cfg.out_proj_init_std) == (1.0, 0.0015)
    with pytest.raises(ValueError, match="one-sublayer kinds"):
        get_model(ModelConfig(**{**TINY_LFM2, "out_proj_init_std": 0.01}))


def test_the_cells_cut_is_508_million_parameters(devices):
    """The cut of benchmarks/configs/nemotron3_super_120b_a12b.json:
    shapes only, nothing of this size is built."""
    cfg = load_config(YAML, CUT).model
    model = get_model(cfg)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros(ids.shape, ids.dtype),
                           train=False))["params"]
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    norm = 4096
    assert count(shapes["layer1"]) == (4096 * 2320 + 1280 * 5 + 3 * 16 + 1024
                                       + 1024 * 4096 + norm)   # 13.71M
    assert count(shapes["layer10"]) == 5_242_880 + norm    # 5.25M
    # router, latent projections, 672 of the shared expert's 5376 units,
    # 8 experts: 60.03M (98.57M with the shared expert whole)
    assert count(shapes["layer0"]) - 512 == (
        4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 672
        + 8 * 2 * 1024 * 2688 + norm)
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 2 * 16384 * 4096
    assert round(count(shapes) / 1e6, 1) == 508.2
    assert model.expert_share()["held"] == list(range(8))
    share = model.tensor_share()
    assert share["mamba2"]["held"] == list(range(16))
    assert share["mamba2"]["bc_held"] == [0]
    assert share["attention"]["held"] == [0, 1, 2, 3]
    assert share["attention"]["kv_held"] == [0]
    assert share["shared_expert"] == {"units": 5376, "held": [0, 672]}


def test_family_names_and_task():
    from distributed_tensorflow_framework_tpu.models import builtin_task
    from distributed_tensorflow_framework_tpu.models.bert import (
        decode_support_reason)

    assert builtin_task("nemotron_h") == "causal_lm"
    assert "Mamba-2" in decode_support_reason(model_config())


def test_the_trainer_step_gives_the_references_loss_and_grad_norm(devices):
    """``StepBuilder`` from the shipped YAML with a tiny cut, the
    ``causal_lm`` task, AdamW and the clip, remat and the kernels on: the
    step's ``loss`` and ``grad_norm`` are the reference's, and the
    counters ride its metrics, the expert counters averaged over the
    layers that have experts."""
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(YAML, tiny_cut("nemotron"))
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
    assert float(metrics["ssm_resets"]) == 6.0
    # a mean over the two expert layers of the five, not over all five
    assert float(metrics["moe_local_assignments"]) == pytest.approx(
        float(metrics["moe_local_share"]) * 2 * S * TOPK)


def test_scopes_name_the_parts_the_benchmark_reads(devices):
    """The named scopes docs/OBSERVABILITY.md lists, in the lowered
    step's debug names."""
    cfg = model_config()
    batch = packed_batch(0)
    model, params = init(cfg, batch)
    text = jax.jit(functools.partial(program_loss, model)).lower(
        params, batch).as_text(debug_info=True)
    for scope in ("layer1/mamba/in_proj", "layer1/mamba/conv",
                  "layer1/mamba/scan", "layer1/mamba/gate_norm",
                  "layer1/mamba/out_proj", "layer2/attn/query",
                  "layer0/moe/router", "layer0/moe/latent_in",
                  "layer0/moe/dispatch", "layer0/moe/experts",
                  "layer0/moe/combine", "layer0/moe/latent_out",
                  "layer0/moe/shared/up", "lm_head"):
        assert scope in text, scope


# ------------------------- the decoders already there lower to the same step --
TINY = {
    "lfm2": ("lfm2_8b_a1b.yaml", [
        "model.num_layers=3", "model.layer_types=[conv,full_attention,conv]",
        "model.num_dense_layers=1", "model.hidden_size=64",
        "model.num_heads=4", "model.num_kv_heads=2", "model.mlp_dim=128",
        "model.moe_mlp_dim=32", "model.num_experts=8",
        "model.expert_topk=2", "model.expert_groups=4"]),
    "smallthinker": ("smallthinker_21b_a3b.yaml", [
        "model.num_layers=2",
        "model.layer_types=[full_attention,sliding_attention]",
        "model.rope_layout=[0,1]", "model.sliding_window=24",
        "model.hidden_size=64", "model.num_heads=4", "model.num_kv_heads=2",
        "model.head_dim=32", "model.moe_mlp_dim=32", "model.num_experts=8",
        "model.expert_topk=2", "model.expert_groups=4"]),
    "nemotron": ("nemotron3_super_120b_a12b.yaml", [
        "model.num_layers=5",
        "model.layer_types=[experts_only,mamba2_only,experts_only,"
        "mamba2_only,attention_only]", "model.rope_layout=[0,0,0,0,0]",
        "model.hidden_size=64", "model.num_heads=8", "model.num_kv_heads=2",
        "model.head_dim=16", "model.tensor_groups=2", "model.tensor_group=1",
        "model.mamba_num_heads=8", "model.mamba_head_dim=8",
        "model.mamba_groups=2", "model.ssm_state_size=16",
        "model.mamba_chunk=16", "model.moe_mlp_dim=24",
        "model.moe_latent_dim=32", "model.moe_shared_dim=48",
        f"model.num_experts={ROUTED}", f"model.expert_topk={TOPK}",
        "model.expert_groups=4", "model.expert_group=1"]),
}
# sha256 of the lowered train step (StableHLO text, no locations) of the
# two tiny cuts above, by ``lowered_step_digest``: at PR 33, whose remat'd
# layers keep the expert layer's routing and lower to another text than
# PR 31's (dc9e774: 935ebde3... and 4a57729c...), which is what PR 32's
# tree still lowered to. ``smallthinker`` at PR 37: PR 36's text (19d076bc...)
# but for one more output, the constant ``attn_window_grid_share`` (a diff
# of the two texts: the signature, one ``stablehlo.constant``, the return).
PARENT_STEP = {
    "lfm2":
        "4877857c625d8295825fcda9302f4bd009d820a71c8844f72776f4d204c0757f",
    "smallthinker":
        "0d60ff459318fff1d65e5ff40ce02e546e9028a3e9102c93b8fd9f9eca1d79a3",
}


TINY_LFM2 = dict(
    name="lfm2_moe", vocab_size=VOCAB, hidden_size=64, num_layers=2,
    layer_types=["conv", "full_attention"], num_dense_layers=2, num_heads=4,
    mlp_dim=128)


def tiny_cut(which: str) -> list:
    return [*TINY[which][1], f"model.vocab_size={VOCAB}",
            f"data.vocab_size={VOCAB}", f"data.seq_len={S}",
            "data.global_batch_size=2", "mesh.data=1",
            "model.dtype=float32"]


def lowered_step_digest(which: str) -> str:
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(os.path.join(ROOT, "configs", TINY[which][0]),
                      [*tiny_cut(which), "model.dtype=bfloat16"])
    mesh = create_mesh(cfg.mesh, devices=jax.devices()[:1])
    sample = to_global(
        {k: np.asarray(v) for k, v in packed_batch(0).items()}, mesh)
    builder = StepBuilder(cfg, mesh)
    state = builder.init_state(0, sample)
    text = builder.make_train_step(sample).lower(state, sample).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("which", sorted(PARENT_STEP))
def test_the_decoders_already_there_lower_to_the_parents_step(devices, which):
    """``lfm2_8b_a1b`` and ``smallthinker_21b_a3b`` (tiny cuts of their
    shipped YAMLs, bfloat16, remat, the kernels): the lowered train step
    is the recorded text, byte for byte. Every setting this family has
    gained since defaults to what they run."""
    assert lowered_step_digest(which) == PARENT_STEP[which]
