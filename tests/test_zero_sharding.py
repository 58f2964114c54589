"""ZeRO weight-update sharding (optimizer.zero_sharding='shard_map').

ISSUE 9 tentpole: the monolithic shard_map all-reduce is replaced by a
bucketed reduce-scatter in reverse layer order, a per-replica optax
update on 1/(data*fsdp) of the flattened param tree, and a bucketed
all-gather of the UPDATES (params stay replicated master copies).
Pins: f32 parity with the replicated path, the (n, ceil(S/n)) stacked
slot layout with per-device shards at 1/n, the reverse-natural-sorted
bucket issue order (dispatch spy), the shard_opt_state deprecation shim,
checkpoint round-trip of the stacked slots, the int8 error-feedback
composition, and the KIND_ZERO_UPDATE telemetry rollup.
"""

import math

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_framework_tpu.ckpt import CheckpointManager
from distributed_tensorflow_framework_tpu.core import telemetry
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
from distributed_tensorflow_framework_tpu.data.infeed import to_global
from distributed_tensorflow_framework_tpu.parallel import zero
from distributed_tensorflow_framework_tpu.parallel.sharding import (
    pick_fsdp_dim,
)
from distributed_tensorflow_framework_tpu.train.step import StepBuilder


def _cfg(mesh_axes, zero_mode, *, optimizer=None, parallel=None, train=None):
    opt = {"name": "adam", "learning_rate": 0.01,
           "zero_sharding": zero_mode,
           # Tiny bucket so LeNet splits into several buckets — the
           # overlap structure (not just a single fused collective) is
           # what the parity and dispatch tests exercise.
           "zero_bucket_mb": 0.05}
    opt.update(optimizer or {})
    base = {
        "name": "zero-ud",
        "mesh": mesh_axes,
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": opt,
        "train": {"total_steps": 5, "log_interval": 5,
                  "spmd_mode": "shard_map", **(train or {})},
    }
    if parallel:
        base["parallel"] = parallel
    return load_config(base=base)


def _batch(mesh):
    rng = np.random.default_rng(0)
    host = {
        "image": rng.standard_normal((64, 28, 28, 1)).astype(np.float32),
        "label": rng.integers(0, 10, 64).astype(np.int32),
    }
    return to_global(host, mesh)


def _run(cfg, steps=3):
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    batch = _batch(mesh)
    state = builder.init_state(0, batch)
    step = builder.make_train_step(batch)
    metrics = {}
    for _ in range(steps):
        state, metrics = step(state, batch)
    return builder, state, jax.device_get(metrics)


# ----------------------------------------------------------- plan unit --
def test_natural_key_orders_digits_numerically():
    paths = ["layer_10/kernel", "layer_2/kernel", "layer_2/bias"]
    ordered = sorted(paths, key=zero.natural_key)
    assert ordered == ["layer_2/bias", "layer_2/kernel", "layer_10/kernel"]


def test_build_plan_reverse_order_and_chunk_math():
    params = {
        "layer_2": {"kernel": np.zeros((7, 3), np.float32)},
        "layer_10": {"kernel": np.zeros((5,), np.float32)},
        "head": {"bias": np.zeros((), np.float32)},
    }
    plan = zero.build_plan(params, n=4, bucket_mb=1e-6)
    # ceil division pads every leaf to n rows; scalars become one element
    # per replica's padded chunk.
    by_path = {lc.path: lc for lc in plan.leaf_chunks}
    assert by_path["layer_2/kernel"].chunk == math.ceil(21 / 4)
    assert by_path["layer_10/kernel"].chunk == math.ceil(5 / 4)
    assert by_path["head/bias"].chunk == 1
    # Tiny bucket budget → one bucket per leaf, issued in REVERSE
    # natural order (deepest layers first, matching backward).
    issue = [lc.path for bucket in plan.buckets for lc in bucket]
    assert issue == sorted(issue, key=zero.natural_key, reverse=True)
    assert plan.num_buckets == 3
    assert plan.shard_elements() == sum(
        lc.chunk for lc in plan.leaf_chunks)


def test_build_plan_accumulates_buckets_by_bytes():
    params = {f"l{i}": np.zeros((64,), np.float32) for i in range(8)}
    # 256 B per leaf; 512 B budget → leaves pair up two per bucket.
    plan = zero.build_plan(params, n=2, bucket_mb=512 / 2**20)
    assert plan.num_buckets == 4
    assert all(len(b) == 2 for b in plan.buckets)


# ------------------------------------------------- parity + slot layout --
def test_f32_parity_zero_vs_replicated(devices):
    _, s_off, m_off = _run(_cfg({"data": 8}, "off"))
    _, s_zero, m_zero = _run(_cfg({"data": 8}, "shard_map"))
    assert np.isfinite(float(m_zero["loss"]))
    np.testing.assert_allclose(
        float(m_off["loss"]), float(m_zero["loss"]), rtol=1e-6)
    # grad_norm comes from shard_global_norm on the zero path — same
    # quantity, computed from disjoint shards.
    np.testing.assert_allclose(
        float(m_off["grad_norm"]), float(m_zero["grad_norm"]), rtol=1e-5)
    # Same data, same mesh, f32 wire: the sharded update must reproduce
    # the replicated trajectory to reduction-order noise. That noise is
    # ~1e-8 in a gradient, but Adam divides by sqrt(v): where a gradient
    # element is itself near zero the two reduction orders can disagree
    # on m/sqrt(v) by a visible fraction, and the parameter then moves by
    # that fraction of the learning rate per step. So the bound is
    # absolute and scaled by what three steps at lr 1e-2 can move: 1e-3
    # of it (observed on jax 0.9.0: 2 of 48,000 elements of one leaf off
    # by 5.8e-6, every other element inside 1e-6). A wrong shard, bucket
    # or mask moves whole leaves by O(lr) = 1e-2, a thousand times more.
    for a, b in zip(jax.tree.leaves(jax.device_get(s_off.params)),
                    jax.tree.leaves(jax.device_get(s_zero.params))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=3e-5)


def test_zero_slots_stacked_and_sharded_one_over_n(devices):
    builder, state, _ = _run(_cfg({"data": 4, "fsdp": 2}, "shard_map"),
                             steps=1)
    plan = builder._zero_plan
    assert plan is not None and plan.n == 8
    valid_chunks = {lc.chunk for lc in plan.leaf_chunks}
    matched = 0
    for leaf in jax.tree.leaves(state.opt_state):
        if getattr(leaf, "ndim", 0) < 2:
            continue
        n, chunk = leaf.shape
        # Every stacked slot is (n, ceil(S/n)) for some param leaf S.
        assert n == 8 and chunk in valid_chunks, leaf.shape
        # Row dim sharded over data×fsdp: each device holds 1/8.
        assert leaf.sharding.spec == P(zero.DATA_AXES)
        shard = leaf.addressable_shards[0].data
        assert shard.shape == (1, chunk)
        matched += 1
    assert matched >= 10, "adam mu+nu slots should all be stacked"
    # Params stay replicated — ZeRO-1/2, not ZeRO-3.
    for leaf in jax.tree.leaves(state.params):
        assert leaf.addressable_shards[0].data.size == leaf.size


def test_zero_slot_rows_detected_for_refold(devices):
    builder, state, _ = _run(_cfg({"data": 8}, "shard_map"), steps=1)
    host = jax.device_get(state)
    assert zero.stacked_rows(host.opt_state, host.params) == 8


# ------------------------------------------------ bucketed issue order --
def test_bucketed_reduce_scatter_issue_order(devices, monkeypatch):
    cfg = _cfg({"data": 8}, "shard_map")
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    batch = _batch(mesh)
    state = builder.init_state(0, batch)
    calls = []
    real = zero._reduce_scatter_bucket

    def spy(mat, axes, *, wire, block_size, paths):
        calls.append(tuple(paths))
        return real(mat, axes, wire=wire, block_size=block_size, paths=paths)

    monkeypatch.setattr(zero, "_reduce_scatter_bucket", spy)
    step = builder.make_train_step(batch)
    state, _ = step(state, batch)  # trace fires the spy once per bucket
    assert len(calls) >= 2, "zero_bucket_mb=0.05 must split LeNet"
    plan = builder._zero_plan
    assert calls == [tuple(lc.path for lc in b) for b in plan.buckets]
    # The flattened issue sequence is reverse natural order — bucket k's
    # reduce-scatter is in program order before the params issued after
    # it, which is what lets XLA overlap it with the backward.
    flat = [p for bucket in calls for p in bucket]
    assert flat == sorted(flat, key=zero.natural_key, reverse=True)


# -------------------------------------------------- config shim + gates --
def test_shard_opt_state_conflict_rejected():
    with pytest.raises(ValueError, match="zero_sharding"):
        _cfg({"data": 4, "fsdp": 2}, "shard_map",
             optimizer={"shard_opt_state": True})


def test_shard_opt_state_maps_to_jit_with_warning(caplog):
    with caplog.at_level("WARNING"):
        cfg = _cfg({"data": 4, "fsdp": 2}, "off",
                   optimizer={"shard_opt_state": True},
                   train={"spmd_mode": "jit"})
    assert cfg.optimizer.zero_sharding == "jit"
    assert any("deprecated" in r.message for r in caplog.records)


def test_zero_shard_map_rejected_under_jit(devices):
    cfg = _cfg({"data": 8}, "shard_map", train={"spmd_mode": "jit"})
    with pytest.raises(ValueError, match="shard_map"):
        StepBuilder(cfg, create_mesh(cfg.mesh))


def test_zero_rejects_lars_and_grad_clip(devices):
    cfg = _cfg({"data": 8}, "shard_map",
               optimizer={"grad_clip_norm": 1.0})
    with pytest.raises(ValueError, match="grad_clip_norm"):
        StepBuilder(cfg, create_mesh(cfg.mesh))
    cfg = _cfg({"data": 8}, "shard_map",
               optimizer={"name": "lars", "learning_rate": 0.1})
    with pytest.raises(ValueError, match="lars"):
        StepBuilder(cfg, create_mesh(cfg.mesh))


def test_bad_zero_mode_rejected():
    with pytest.raises(ValueError, match="zero_sharding"):
        _cfg({"data": 8}, "zero3")


# -------------------------------------------------- checkpoint roundtrip --
def test_zero_opt_state_checkpoint_roundtrip(devices, tmp_path):
    cfg = _cfg({"data": 8}, "shard_map")
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    batch = _batch(mesh)
    state = builder.init_state(0, batch)
    step = builder.make_train_step(batch)
    state, _ = step(state, batch)
    cfg.checkpoint.directory = str(tmp_path / "ck")
    cfg.checkpoint.async_save = False
    mgr = CheckpointManager(cfg.checkpoint, mesh=mesh)
    assert mgr.save(1, state)
    mgr.wait_until_finished()
    restored = mgr.restore(builder.init_state(9, batch))
    mgr.close()
    assert restored is not None
    for a, b in zip(jax.tree.leaves(jax.device_get(state.opt_state)),
                    jax.tree.leaves(jax.device_get(restored.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Restored slots keep the stacked sharded layout.
    stacked = [leaf for leaf in jax.tree.leaves(restored.opt_state)
               if getattr(leaf, "ndim", 0) >= 2]
    assert stacked
    assert all(leaf.addressable_shards[0].data.shape[0] == 1
               for leaf in stacked)


# ------------------------------------------------------- int8 EF compose --
def test_zero_int8_error_feedback(devices):
    cfg = _cfg({"data": 8}, "shard_map",
               parallel={"collective_dtype": "int8",
                         "collective_block_size": 64})
    _, state, metrics = _run(cfg, steps=2)
    assert np.isfinite(float(metrics["loss"]))
    res = jax.tree.leaves(jax.device_get(state.collective_residual))
    assert res and any(np.abs(np.asarray(r)).max() > 0 for r in res)
    # The residual rows live on the replica axis (one EF carry per
    # replica), matching the quantized all-reduce contract.
    for r in jax.tree.leaves(state.collective_residual):
        assert r.shape[0] == 8


# ----------------------------------------------------- telemetry rollup --
def test_zero_update_event_rollup(tmp_path):
    events = str(tmp_path / "events.jsonl")
    writer = telemetry.TelemetryWriter(events)
    params = {"a": np.zeros((64, 64), np.float32),
              "b": np.zeros((128,), np.float32)}
    plan = zero.build_plan(params, n=8, bucket_mb=0.005)
    writer.emit(telemetry.KIND_ZERO_UPDATE, **zero.plan_summary(plan))
    writer.close()
    summary = telemetry.summarize_events(events)
    assert summary["zero"]["shards"] == 8
    assert summary["zero"]["buckets"] == plan.num_buckets
    assert summary["zero"]["rs_wire_bytes"] > 0
    text = telemetry.format_run_summary(summary)
    assert "zero update sharding" in text
    assert "overlap est" in text


def test_plan_summary_wire_bytes_scale_with_dtype():
    params = {"w": np.zeros((256, 16), np.float32)}
    plan = zero.build_plan(params, n=4, bucket_mb=4.0)
    f32 = zero.plan_summary(plan)
    bf16 = zero.plan_summary(plan, wire_dtype="bfloat16")
    i8 = zero.plan_summary(plan, wire_dtype="int8", block_size=64)
    assert f32["wire"] == "float32" and bf16["wire"] == "bfloat16"
    assert bf16["rs_wire_bytes"] * 2 == f32["rs_wire_bytes"]
    # int8 payload is 1/4 of f32 plus per-block scale overhead.
    assert i8["rs_wire_bytes"] < f32["rs_wire_bytes"] / 2
    assert f32["overlap_frac_est"] == 0.0  # single bucket: nothing hidden


# ------------------------------------------------- fsdp dim tie-break --
def test_pick_fsdp_dim_trailing_dim_wins_ties():
    # Square kernels used to depend on dict/scan order; the contract is
    # now explicit: equal-size candidates resolve to the TRAILING dim
    # (the output-features dim for conv/dense kernels).
    assert pick_fsdp_dim((3, 3, 8, 8), 2) == 3
    assert pick_fsdp_dim((8, 8), 4) == 1
    # Still prefers the LARGEST divisible dim when sizes differ.
    assert pick_fsdp_dim((16, 8), 4) == 0
    # Already-sharded dims (per-dim axis entries) are excluded.
    assert pick_fsdp_dim((8, 8), 4, taken=(None, "model")) == 0
    # No divisible dim → -1.
    assert pick_fsdp_dim((3, 5), 4) == -1
