"""Quantized collectives — parallel.collective_dtype (bf16 + int8 EF).

Covers the bf16-wire gradient all-reduce (the original
train.grad_allreduce_dtype feature, now a deprecated spelling of the
knob), the int8 block-scaled all-reduce with error feedback, the
linearized multi-axis routing order, and the tier-1 acceptance gate:
int8 wire bytes on the dp+fsdp recipe drop >= 3x vs the f32 wire
(docs/PERFORMANCE.md)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
from distributed_tensorflow_framework_tpu.data.infeed import to_global
from distributed_tensorflow_framework_tpu.train.step import StepBuilder


def _shard_map_allreduce(mesh, accumulate_f32):
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_framework_tpu.parallel import collectives as coll

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def fn(x):
        return coll.allreduce_gradients(
            {"g": x}, ("data",), compute_dtype=jnp.bfloat16,
            accumulate_f32=accumulate_f32)["g"]

    return fn


@pytest.mark.parametrize("size", [8 * 37 + 3, 5, 1])  # ragged, < n, scalar-ish
def test_f32_accum_single_rounding(devices, size):
    """f32-accumulate mode: error vs the exact f32 mean is ONE bf16
    rounding of the mean, independent of replica count — strictly tighter
    than the pure-bf16 ('wire') reduction on the same data."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig

    mesh = create_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(1)
    # Per-replica values with wildly different magnitudes so narrow-dtype
    # partial sums actually lose bits.
    x = (rng.standard_normal((8, size)) * np.logspace(-3, 3, 8)[:, None]
         ).astype(np.float32)
    exact = x.mean(axis=0)

    got_f32 = np.asarray(_shard_map_allreduce(mesh, True)(jnp.asarray(x)))
    got_wire = np.asarray(_shard_map_allreduce(mesh, False)(jnp.asarray(x)))
    # Every replica holds the same reduced value.
    np.testing.assert_array_equal(got_f32[0], got_f32[1])

    one_rounding = np.abs(
        exact.astype(np.float32) - exact.astype(jnp.bfloat16).astype(np.float32))
    err_f32 = np.abs(got_f32[0] - exact)
    err_wire = np.abs(got_wire[0] - exact)
    # f32-accumulate == quantize-the-mean-once (up to f32 division order).
    assert np.all(err_f32 <= one_rounding + 1e-6 * np.abs(exact) + 1e-12)
    # And it is no worse than the wire-accumulated reduction anywhere.
    assert err_f32.sum() <= err_wire.sum() + 1e-12


def _base_cfg(wire_dtype: str, steps: int, accum: str,
              parallel: dict | None, mesh_cfg: dict | None) -> dict:
    base = {
        "name": "compressed-ar",
        "mesh": mesh_cfg or {"data": 8},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": {"total_steps": steps, "spmd_mode": "shard_map",
                  "grad_allreduce_accum": accum},
    }
    if wire_dtype:
        base["train"]["grad_allreduce_dtype"] = wire_dtype  # legacy knob
    if parallel is not None:
        base["parallel"] = parallel
    return base


def _build(wire_dtype: str, steps: int = 5, accum: str = "float32", *,
           parallel: dict | None = None, mesh_cfg: dict | None = None):
    cfg = load_config(base=_base_cfg(wire_dtype, steps, accum,
                                     parallel, mesh_cfg))
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    rng = np.random.default_rng(0)
    host = {
        "image": rng.standard_normal((64, 28, 28, 1)).astype(np.float32),
        "label": rng.integers(0, 10, 64).astype(np.int32),
    }
    batch = to_global(host, mesh)
    state = builder.init_state(0, batch)
    return builder, state, batch


def _run(wire_dtype: str, steps: int = 5, accum: str = "float32", *,
         parallel: dict | None = None, mesh_cfg: dict | None = None):
    from distributed_tensorflow_framework_tpu.parallel import collectives as coll

    builder, state, batch = _build(wire_dtype, steps, accum,
                                   parallel=parallel, mesh_cfg=mesh_cfg)
    step = builder.make_train_step(batch)
    losses = []
    with coll.tally() as t:  # counters record at trace time (first call)
        for _ in range(steps):
            state, metrics = step(state, batch)
            losses.append(float(jax.device_get(metrics["loss"])))
    return jax.device_get(state.params), losses, t.summary()


def _tally_for(parallel: dict | None, mesh_cfg: dict | None,
               legacy_wire: str = "") -> dict:
    """Trace-time collective byte tally of one train step — no compile,
    no execution, so the tier-1 acceptance gate stays cheap."""
    from distributed_tensorflow_framework_tpu.parallel import collectives as coll

    builder, state, batch = _build(legacy_wire, steps=1,
                                   parallel=parallel, mesh_cfg=mesh_cfg)
    step = builder.make_train_step(batch)
    with coll.tally() as t:
        step.lower(state, batch)
    return t.summary()


def test_wire_dtype_rejected_under_jit(devices):
    import pytest

    from distributed_tensorflow_framework_tpu.core.config import load_config

    cfg = load_config(base={
        "name": "bad", "mesh": {"data": 8},
        "model": {"name": "lenet5", "dtype": "float32"},
        "train": {"spmd_mode": "jit", "grad_allreduce_dtype": "bfloat16"},
    })
    mesh = create_mesh(cfg.mesh)
    with pytest.raises(ValueError, match="explicit collective"):
        StepBuilder(cfg, mesh)


@pytest.mark.slow
@pytest.mark.parametrize("accum", ["wire", "float32"])
def test_bf16_wire_close_to_f32(devices, accum):
    p32, l32, _ = _run("")
    p16, l16, _ = _run("bfloat16", accum=accum)
    # Trajectories track closely (bf16 has ~3 decimal digits) and training
    # still makes progress.
    assert all(np.isfinite(l) for l in l16)
    assert l16[-1] < l16[0]
    for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p16)):
        np.testing.assert_allclose(a, b, rtol=0.05, atol=1e-3)
    # And it is genuinely different arithmetic (the compression happened).
    flat32 = np.concatenate([np.ravel(x) for x in jax.tree.leaves(p32)])
    flat16 = np.concatenate([np.ravel(x) for x in jax.tree.leaves(p16)])
    assert not np.array_equal(flat32, flat16)


def test_bad_accum_rejected(devices):
    cfg = load_config(base={
        "name": "bad", "mesh": {"data": 8},
        "model": {"name": "lenet5", "dtype": "float32"},
        "train": {"spmd_mode": "shard_map",
                  "grad_allreduce_accum": "f16"},
    })
    mesh = create_mesh(cfg.mesh)
    with pytest.raises(ValueError, match="grad_allreduce_accum"):
        StepBuilder(cfg, mesh)


# ----------------------------------------------- int8 + error feedback ----


def test_int8_single_step_error_bound(devices):
    """One int8 block-scaled all-reduce: per-element error vs the exact
    f32 mean is bounded by one block rounding on the scatter phase plus
    one on the gather phase — each at most blockmax/254 <= maxabs/254."""
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.parallel import collectives as coll

    mesh = create_mesh(MeshConfig(data=8))
    rng = np.random.default_rng(2)
    x = (rng.standard_normal((8, 500)) * np.logspace(-2, 2, 8)[:, None]
         ).astype(np.float32)
    exact = x.mean(axis=0)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
    def fn(v):
        m, _ = coll.allreduce_gradients_ef({"g": v}, None, ("data",),
                                           block_size=64)
        return m["g"]

    got = np.asarray(fn(jnp.asarray(x)))
    np.testing.assert_array_equal(got[0], got[-1])  # replicas agree
    bound = 2 * np.abs(x).max() / 254 + 1e-6
    assert np.abs(got[0] - exact).max() <= bound


def test_linear_axis_index_matches_gather_order(devices):
    """linear_axis_index (first axis major) must match the row order of
    all_gather(tiled=False) over the same axis tuple — the EF all-reduce
    routes chunk ownership with one and reassembles with the other."""
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.parallel import collectives as coll

    mesh = create_mesh(MeshConfig(data=4, fsdp=2))

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(),
                       out_specs=P(), check_vma=False)
    def fn():
        idx = coll.linear_axis_index(("data", "fsdp"))
        return jax.lax.all_gather(idx, ("data", "fsdp"), tiled=False)

    np.testing.assert_array_equal(np.asarray(fn()), np.arange(8))


def test_int8_ef_dp_loss_parity(devices):
    """ACCEPTANCE (dp recipe): with error feedback on, the int8 loss
    curve tracks the f32 curve within tolerance, and the tally shows the
    wire actually narrowed."""
    _, l32, _ = _run("")
    p8, l8, s8 = _run("", parallel={"collective_dtype": "int8",
                                    "collective_block_size": 64})
    assert all(np.isfinite(l) for l in l8)
    assert l8[-1] < l8[0]
    np.testing.assert_allclose(l8, l32, rtol=0.02, atol=2e-3)
    # And the compression happened: int8 wire, f32 logical.
    assert s8["total_bytes"] * 3 <= s8["total_logical_bytes"]
    assert "allreduce_grads_q8_gather_bytes" in s8


@pytest.mark.slow
def test_int8_ef_fsdp_loss_parity(devices):
    """dp+fsdp recipe: the explicit-fsdp path (quantized param gather +
    combined-axis EF all-reduce + grad slice-back) tracks the same-mesh
    f32 explicit-fsdp trajectory."""
    mesh_cfg = {"data": 4, "fsdp": 2}
    _, l32, _ = _run("", steps=3, mesh_cfg=mesh_cfg)
    _, l8, _ = _run("", steps=3, mesh_cfg=mesh_cfg,
                    parallel={"collective_dtype": "int8",
                              "collective_block_size": 64})
    assert all(np.isfinite(l) for l in l8)
    np.testing.assert_allclose(l8, l32, rtol=0.02, atol=2e-3)


def test_int8_wire_bytes_drop_3x_dp_fsdp(devices):
    """ACCEPTANCE: on the dp+fsdp recipe the tallied wire bytes for the
    gradient all-reduce AND the fsdp param gather drop >= 3x vs the f32
    wire. Trace-time tally only — no compile, no steps."""
    mesh_cfg = {"data": 4, "fsdp": 2}
    f32 = _tally_for(None, mesh_cfg)
    q8 = _tally_for({"collective_dtype": "int8",
                     "collective_block_size": 64}, mesh_cfg)
    ratio = f32["total_bytes"] / q8["total_bytes"]
    assert ratio >= 3.0, (ratio, f32, q8)
    # Both halves of the story are on the wire: quantized grad exchange
    # and the quantized fsdp param gather.
    assert "allreduce_grads_q8_scatter_bytes" in q8
    assert "allreduce_grads_q8_gather_bytes" in q8
    assert q8["all_gather_bytes"] < f32["all_gather_bytes"]
    # The logical traffic is the same experiment on both sides, up to
    # the int8 path's block/chunk padding (zeros on the wire, counted at
    # their logical width).
    assert (abs(q8["total_logical_bytes"] - f32["total_logical_bytes"])
            <= 0.05 * f32["total_logical_bytes"])


def test_old_knob_routes_to_new_knob(devices):
    """train.grad_allreduce_dtype=bfloat16 (deprecated) and
    parallel.collective_dtype=bfloat16 must produce the identical
    collective traffic — the shim maps, it does not fork behavior."""
    old = _tally_for(None, {"data": 8}, legacy_wire="bfloat16")
    new = _tally_for({"collective_dtype": "bfloat16"}, {"data": 8})
    assert old == new
    assert old["total_bytes"] < old["total_logical_bytes"]
