"""Elastic resharding — checkpoints and supervision across mesh changes.

ISSUE 6 tentpole: a checkpoint written under one mesh restores onto a
different one (ckpt/reshard.py), and the supervisor's rc-84 contract
(core/supervision.py) refits the largest valid mesh onto a changed device
set. Fast tests pin the pure arithmetic (fit_axis_sizes,
rescale_for_devices, device reports, fault parsing) and one cheap LeNet
cross-mesh restore; the slow class runs the full parity matrix on
sharded BERT states.
"""

import json
import os

import jax
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.ckpt import (
    CheckpointManager,
    MeshTopologyError,
)
from distributed_tensorflow_framework_tpu.ckpt import manifest as mf
from distributed_tensorflow_framework_tpu.ckpt import reshard
from distributed_tensorflow_framework_tpu.core import (
    faults,
    supervision,
    telemetry,
)
from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.core.mesh import (
    MESH_AXES,
    MeshSizeError,
    create_mesh,
    fit_mesh,
)
from distributed_tensorflow_framework_tpu.data import get_dataset
from distributed_tensorflow_framework_tpu.data.infeed import to_global
from distributed_tensorflow_framework_tpu.train.step import StepBuilder


# -- pure arithmetic (stdlib supervision layer) ---------------------------
def test_axis_order_mirrors_mesh_axes():
    # supervision.py must stay stdlib-importable, so it carries its own
    # copy of the axis order; this pin is what stops the two drifting.
    assert supervision.MESH_AXIS_ORDER == MESH_AXES


def test_fit_axis_sizes_shrink_data():
    assert supervision.fit_axis_sizes({"data": 8}, 4) == {"data": 4}


def test_fit_axis_sizes_grow_data():
    assert supervision.fit_axis_sizes({"data": 4}, 8) == {"data": 8}


def test_fit_axis_sizes_preserves_inner_axes_first():
    # 4 devices cannot hold {fsdp:2, pipe:4}; among the feasible divisor
    # combinations the innermost (model-ward) axis keeps its size:
    # pipe:4 survives, fsdp drops to 1.
    fit = supervision.fit_axis_sizes({"data": 1, "fsdp": 2, "pipe": 4}, 4)
    assert fit == {"data": 1, "fsdp": 1, "pipe": 4}


def test_fit_axis_sizes_keeps_structure_when_data_absorbs():
    fit = supervision.fit_axis_sizes({"data": 2, "fsdp": 4}, 8)
    assert fit == {"data": 2, "fsdp": 4}
    fit = supervision.fit_axis_sizes({"data": 2, "fsdp": 4}, 4)
    assert fit == {"data": 1, "fsdp": 4}


def test_fit_axis_sizes_uses_all_devices():
    for n in (1, 2, 3, 4, 6, 8, 12):
        fit = supervision.fit_axis_sizes(
            {"data": 8, "fsdp": 2, "pipe": 2}, n)
        prod = 1
        for v in fit.values():
            prod *= v
        assert prod == n, fit


def test_fit_axis_sizes_treats_minus_one_as_absorbing():
    fit = supervision.fit_axis_sizes({"data": -1, "model": 2}, 6)
    assert fit == {"data": 3, "model": 2}


def test_fit_axis_sizes_errors():
    with pytest.raises(ValueError):
        supervision.fit_axis_sizes({"data": 8}, 0)
    with pytest.raises(ValueError):
        supervision.fit_axis_sizes({"data": 8, "pipe": 0}, 4)
    with pytest.raises(ValueError, match="no mesh"):
        # No data axis to absorb: pipe's divisors {1, 2, 4} never
        # multiply to 3.
        supervision.fit_axis_sizes({"pipe": 4}, 3)


def test_fit_mesh_delegates(devices):
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig

    fit = fit_mesh(MeshConfig(data=8), 4)
    assert fit["data"] == 4
    assert fit == supervision.fit_axis_sizes(
        MeshConfig(data=8).axis_sizes(), 4)


def test_rescale_preserves_effective_batch_on_shrink():
    # The acceptance drill's numbers: 64/1 at dp=8 -> 32/2 at dp=4
    # (per-device batch constant, effective batch 64 preserved).
    assert supervision.rescale_for_devices(64, 1, 8, 4) == (32, 2, True)


def test_rescale_growth_and_fallbacks():
    # Growth with accum slack: per-device preserved.
    assert supervision.rescale_for_devices(32, 4, 4, 8) == (64, 2, True)
    # Growth without accum slack: keep the global batch (still preserved).
    assert supervision.rescale_for_devices(64, 1, 8, 16) == (64, 1, True)
    # Nothing divides: unchanged, flagged not-preserved.
    assert supervision.rescale_for_devices(63, 1, 8, 4) == (63, 1, False)
    # No-op resize.
    assert supervision.rescale_for_devices(64, 2, 4, 4) == (64, 2, True)


def test_mask_host_device_count():
    masked = supervision.mask_host_device_count("", 4)
    assert masked == "--xla_force_host_platform_device_count=4"
    masked = supervision.mask_host_device_count(
        "--xla_force_host_platform_device_count=8 --foo=1", 2)
    assert masked == "--xla_force_host_platform_device_count=2 --foo=1"


def test_device_report_roundtrip(tmp_path):
    path = supervision.write_device_report(
        str(tmp_path / "ck"), visible_devices=4, needed=8,
        mesh={"data": 8})
    assert os.path.basename(path) == supervision.DEVICE_REPORT_NAME
    report = supervision.read_device_report(str(tmp_path / "ck"))
    assert report["visible_devices"] == 4
    assert report["needed"] == 8
    assert report["mesh"] == {"data": 8}
    assert supervision.read_device_report(str(tmp_path / "absent")) is None
    with open(path, "w") as fh:
        fh.write("{torn")
    assert supervision.read_device_report(str(tmp_path / "ck")) is None


def test_drop_devices_fault_parse():
    plan = faults.FaultPlan.parse("drop_devices:4:2")
    (fault,) = plan.faults
    assert fault.point == "relaunch"
    assert fault.devices == 4
    assert fault.step == 2
    # Default relaunch ordinal is 1 (the first launch).
    assert faults.FaultPlan.parse("drop_devices:4").faults[0].step == 1
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("drop_devices:zero")
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("drop_devices:0:1")


def test_drop_devices_fires_only_at_its_attempt():
    plan = faults.FaultPlan.parse("drop_devices:4:2")
    assert plan.fire("relaunch", step=1) == []
    handled = plan.fire("relaunch", step=2)
    assert [f.kind for f in handled] == ["drop_devices"]
    assert plan.fire("relaunch", step=2) == []  # once only


def test_parse_training_params_inside_dash_c_program():
    from scripts.train_resilient import parse_training_params

    cmd = ["python", "-c",
           "from x import main; main(['--set','mesh.data=8',"
           "'--set','mesh.pipe=2','--set','data.global_batch_size=48',"
           "'--set','train.grad_accum_steps=3'])"]
    sizes, batch, accum = parse_training_params(cmd)
    assert sizes["data"] == 8 and sizes["pipe"] == 2
    assert (batch, accum) == (48, 3)


# -- topology records and the restore gate --------------------------------
def test_describe_and_normalize_axes():
    assert reshard.describe_axes({"data": 8, "fsdp": 1}) == "{data:8}"
    assert reshard.describe_axes({"data": 1}) == "{1 device}"
    assert reshard.axes_equal({"data": 4}, {"data": 4, "pipe": 1})
    assert not reshard.axes_equal({"data": 4}, {"data": 8})
    assert not reshard.axes_equal(None, {"data": 4})


def test_mesh_size_error_names_counts(devices):
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig

    with pytest.raises(MeshSizeError) as ei:
        create_mesh(MeshConfig(data=8), devices=devices[:4])
    assert ei.value.available == 4
    assert ei.value.needed == 8
    assert "8 devices but 4 are available" in str(ei.value)


def test_mesh_topology_error_names_both_meshes_and_knob():
    err = MeshTopologyError(
        {"data": 8}, {"data": 4}, directory="/ck", step=30)
    msg = str(err)
    assert "{data:8}" in msg and "{data:4}" in msg
    assert "checkpoint.allow_reshard" in msg
    assert err.saved_axes == {"data": 8}
    assert err.requested_axes == {"data": 4}


def _lenet_state(devices, n, *, seed=0, batch_size=64):
    cfg = load_config(base={
        "name": "reshard-lenet",
        "mesh": {"data": n},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": batch_size,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": {"total_steps": 4},
    })
    mesh = create_mesh(cfg.mesh, devices=devices[:n])
    builder = StepBuilder(cfg, mesh)
    batch = to_global(next(get_dataset(cfg.data)), mesh)
    state = builder.init_state(seed, batch)
    return cfg, mesh, state


def _save(cfg, mesh, state, ckpt_dir, *, step=1):
    cfg.checkpoint.directory = ckpt_dir
    cfg.checkpoint.async_save = False
    mgr = CheckpointManager(cfg.checkpoint, mesh=mesh)
    assert mgr.save(step, state)
    mgr.wait_until_finished()
    mgr.close()


def _assert_trees_equal(saved, restored):
    s_leaves = jax.tree.leaves(jax.device_get(saved))
    r_leaves = jax.tree.leaves(jax.device_get(restored))
    assert len(s_leaves) == len(r_leaves)
    for a, b in zip(s_leaves, r_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_manifest_records_mesh_topology(devices, tmp_path):
    cfg, mesh, state = _lenet_state(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    manifest = mf.read_manifest(str(tmp_path / "ck" / "1"))
    record = manifest[reshard.MESH_RECORD_KEY]
    assert record["axes"]["data"] == 8
    assert record["device_count"] == 8
    assert record["process_count"] == 1
    assert record["spec_digest"] == reshard.spec_digest(state)


def test_restore_refuses_mesh_change_without_knob(devices, tmp_path):
    cfg, mesh, state = _lenet_state(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    cfg_b, _, template = _lenet_state(devices, 4, seed=9)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    mgr = CheckpointManager(cfg_b.checkpoint)
    with pytest.raises(MeshTopologyError) as ei:
        mgr.restore(template)
    mgr.close()
    assert "{data:8}" in str(ei.value) and "{data:4}" in str(ei.value)


def test_reshard_restore_lenet_8_to_4(devices, tmp_path):
    # The cheap end-to-end slice of the parity matrix; the sharded BERT
    # pairs live in the slow class below.
    cfg, mesh, state = _lenet_state(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    cfg_b, mesh_b, template = _lenet_state(devices, 4, seed=9)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    cfg_b.checkpoint.allow_reshard = True
    events = str(tmp_path / "events.jsonl")
    writer = telemetry.TelemetryWriter(events)
    mgr = CheckpointManager(
        cfg_b.checkpoint, telemetry_writer=writer, mesh=mesh_b)
    restored = mgr.restore(template)
    mgr.close()
    writer.close()
    assert restored is not None
    _assert_trees_equal(state.params, restored.params)
    _assert_trees_equal(state.opt_state, restored.opt_state)
    # Restored leaves live on the NEW mesh.
    leaf = jax.tree.leaves(restored.params)[0]
    assert dict(leaf.sharding.mesh.shape)["data"] == 4
    # The reshard is telemetered for analyze_trace.py.
    kinds = [ev["kind"] for ev in telemetry.read_events(events)]
    assert telemetry.KIND_CKPT_RESHARDED in kinds


def test_legacy_manifest_restores_with_warning(devices, tmp_path, caplog):
    cfg, mesh, state = _lenet_state(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    # Strip the topology record: a pre-elastic checkpoint. The manifest
    # file itself is not payload-hashed, so the rewrite stays committed.
    step_dir = str(tmp_path / "ck" / "1")
    manifest = mf.read_manifest(step_dir)
    manifest.pop(reshard.MESH_RECORD_KEY)
    with open(os.path.join(step_dir, mf.MANIFEST_NAME), "w") as fh:
        json.dump(manifest, fh)
    cfg_b, _, template = _lenet_state(devices, 4, seed=9)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    # Knob OFF: a legacy manifest must not brick the restore — one-line
    # warning, no gate (there is nothing recorded to gate on).
    mgr = CheckpointManager(cfg_b.checkpoint)
    with caplog.at_level("WARNING"):
        restored = mgr.restore(template)
    mgr.close()
    assert restored is not None
    assert any("no mesh topology record" in r.message for r in caplog.records)
    _assert_trees_equal(state.params, restored.params)


def test_same_mesh_restore_has_no_gate(devices, tmp_path):
    cfg, mesh, state = _lenet_state(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    cfg_b, _, template = _lenet_state(devices, 8, seed=9)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    mgr = CheckpointManager(cfg_b.checkpoint)  # allow_reshard defaults off
    restored = mgr.restore(template)
    mgr.close()
    _assert_trees_equal(state.params, restored.params)


def test_validate_restored_catches_shape_drift():
    template = {"w": np.zeros((4, 4), np.float32)}
    ok = reshard.validate_restored(
        template, {"w": np.zeros((4, 4), np.float32)}, step=1)
    assert ok == 1
    with pytest.raises(ValueError, match="global leaf shapes"):
        reshard.validate_restored(
            template, {"w": np.zeros((2, 4), np.float32)}, step=1)
    with pytest.raises(ValueError, match="tree structure"):
        reshard.validate_restored(
            template, {"w2": np.zeros((4, 4), np.float32)}, step=1)


# -- quantized-collective residual across save/restore/reshard ------------
def _lenet_state_int8(devices, n, *, seed=0, steps=2):
    # ISSUE 7: int8 collectives keep a per-replica error-feedback residual
    # (TrainState.collective_residual) that must survive checkpointing.
    # A couple of real steps make the residual nonzero so the assertions
    # below cannot pass vacuously.
    cfg = load_config(base={
        "name": "reshard-lenet-int8",
        "mesh": {"data": n},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.05},
        "train": {"total_steps": 4, "spmd_mode": "shard_map"},
        "parallel": {"collective_dtype": "int8",
                     "collective_block_size": 64},
    })
    mesh = create_mesh(cfg.mesh, devices=devices[:n])
    builder = StepBuilder(cfg, mesh)
    batch = to_global(next(get_dataset(cfg.data)), mesh)
    state = builder.init_state(seed, batch)
    if steps:
        step_fn = builder.make_train_step(batch)
        for _ in range(steps):
            state, _ = step_fn(state, batch)
    return cfg, mesh, builder, batch, state


def test_residual_roundtrip_same_mesh_bit_exact(devices, tmp_path):
    cfg, mesh, builder, batch, state = _lenet_state_int8(devices, 8)
    res = jax.tree.leaves(jax.device_get(state.collective_residual))
    assert res and any(np.abs(np.asarray(r)).max() > 0 for r in res)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    mgr = CheckpointManager(cfg.checkpoint)
    restored = mgr.restore(builder.init_state(0, batch))
    mgr.close()
    assert restored is not None
    _assert_trees_equal(state.collective_residual,
                        restored.collective_residual)
    _assert_trees_equal(state.params, restored.params)


def test_reshard_8_to_4_folds_residual_sum_preserving(devices, tmp_path):
    # A topology change cannot keep per-replica residuals as-is (the
    # replica axis shrank); reshard.fold_residual folds rows so the SUM
    # of pending corrections — the only quantity the EF update consumes —
    # is preserved exactly.
    cfg, mesh, _, _, state = _lenet_state_int8(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    old_sums = [np.asarray(r).sum(axis=0) for r in
                jax.tree.leaves(jax.device_get(state.collective_residual))]
    assert any(np.abs(s).max() > 0 for s in old_sums)
    cfg_b, mesh_b, builder_b, batch_b, _ = _lenet_state_int8(
        devices, 4, seed=9, steps=0)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    cfg_b.checkpoint.allow_reshard = True
    mgr = CheckpointManager(cfg_b.checkpoint, mesh=mesh_b)
    restored = mgr.restore(builder_b.init_state(0, batch_b))
    mgr.close()
    assert restored is not None
    new_res = jax.tree.leaves(jax.device_get(restored.collective_residual))
    assert new_res and all(r.shape[0] == 4 for r in new_res)
    for old_sum, new in zip(old_sums, new_res):
        np.testing.assert_allclose(
            new.sum(axis=0), old_sum, rtol=1e-6, atol=1e-7)
    _assert_trees_equal(state.params, restored.params)


# -- ZeRO stacked opt state across a grid change --------------------------
def _lenet_state_zero(devices, n, *, seed=0, steps=1):
    # ISSUE 9: zero_sharding='shard_map' stacks every optimizer slot as
    # (n, ceil(S/n)) rows over the data×fsdp replicas. A checkpoint
    # written at one grid must refold host-side to the new replica count
    # on a resharded restore (ckpt/reshard.refold_zero_opt_state).
    cfg = load_config(base={
        "name": "reshard-lenet-zero",
        "mesh": {"data": n},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "adam", "learning_rate": 0.01,
                      "zero_sharding": "shard_map"},
        "train": {"total_steps": 4, "spmd_mode": "shard_map"},
    })
    mesh = create_mesh(cfg.mesh, devices=devices[:n])
    builder = StepBuilder(cfg, mesh)
    batch = to_global(next(get_dataset(cfg.data)), mesh)
    state = builder.init_state(seed, batch)
    if steps:
        step_fn = builder.make_train_step(batch)
        for _ in range(steps):
            state, _ = step_fn(state, batch)
    return cfg, mesh, builder, batch, state


def test_zero_opt_state_reshard_8_to_4(devices, tmp_path):
    from distributed_tensorflow_framework_tpu.parallel import zero

    cfg, mesh, _, _, state = _lenet_state_zero(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    cfg_b, mesh_b, builder_b, batch_b, _ = _lenet_state_zero(
        devices, 4, seed=9, steps=0)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    cfg_b.checkpoint.allow_reshard = True
    events = str(tmp_path / "events.jsonl")
    writer = telemetry.TelemetryWriter(events)
    mgr = CheckpointManager(
        cfg_b.checkpoint, telemetry_writer=writer, mesh=mesh_b)
    restored = mgr.restore(builder_b.init_state(0, batch_b))
    mgr.close()
    writer.close()
    assert restored is not None
    _assert_trees_equal(state.params, restored.params)
    kinds = [ev["kind"] for ev in telemetry.read_events(events)]
    assert telemetry.KIND_CKPT_RESHARDED in kinds

    # Slots refolded to the NEW grid: (4, ceil(S/4)), data-sharded, and
    # element-for-element equal to the saved values on the true S prefix
    # (padding is inert by construction — zero grads meet zero params).
    old_host = jax.device_get(state)
    new_host = jax.device_get(restored)
    assert zero.stacked_rows(new_host.opt_state, new_host.params) == 4
    # map_slots pairs each slot with its param (None for step counters);
    # old and new opt states share a treedef, so the flatten orders zip.
    new_pairs = []
    zero.map_slots(lambda s, p: new_pairs.append((s, p)),
                   new_host.opt_state, new_host.params)
    old_leaves = [leaf for _, leaf in
                  jax.tree_util.tree_flatten_with_path(old_host.opt_state)[0]]
    assert len(old_leaves) == len(new_pairs)
    refolded = 0
    for (new_slot, param), old_slot in zip(new_pairs, old_leaves):
        if param is None or getattr(old_slot, "ndim", 0) != 2:
            np.testing.assert_array_equal(
                np.asarray(new_slot), np.asarray(old_slot))
            continue
        size = int(np.prod(param.shape)) if param.shape else 1
        assert new_slot.shape == (4, -(-size // 4)), new_slot.shape
        np.testing.assert_array_equal(
            np.asarray(new_slot).reshape(-1)[:size],
            np.asarray(old_slot).reshape(-1)[:size])
        refolded += 1
    assert refolded >= 10, "adam mu+nu slots should all be refolded"


def test_zero_toggle_across_resume_is_rejected(devices, tmp_path):
    # Saved ZeRO-stacked, restored replicated (same adam optimizer, same
    # mesh): the slot trees are shape-incompatible and the failure must
    # name the knob instead of surfacing an orbax tree error.
    cfg, mesh, _, _, state = _lenet_state_zero(devices, 8)
    _save(cfg, mesh, state, str(tmp_path / "ck"))
    cfg_b = load_config(base={
        "name": "reshard-lenet-zero-off",
        "mesh": {"data": 8},
        "model": {"name": "lenet5", "num_classes": 10, "dtype": "float32"},
        "data": {"name": "synthetic_images", "global_batch_size": 64,
                 "image_size": 28, "channels": 1},
        "optimizer": {"name": "adam", "learning_rate": 0.01},
        "train": {"total_steps": 4, "spmd_mode": "shard_map"},
    })
    mesh_b = create_mesh(cfg_b.mesh)
    builder_b = StepBuilder(cfg_b, mesh_b)
    batch_b = to_global(next(get_dataset(cfg_b.data)), mesh_b)
    cfg_b.checkpoint.directory = str(tmp_path / "ck")
    cfg_b.checkpoint.async_save = False
    mgr = CheckpointManager(cfg_b.checkpoint)
    with pytest.raises(ValueError, match="zero_sharding"):
        mgr.restore(builder_b.init_state(0, batch_b))
    mgr.close()


def test_opt_layout_digest_tracks_the_layout_not_the_mesh_size():
    """What the manifest records to refuse a slot-layout toggle: stacked
    rows refold across a reshard (same digest), but replicated vs stacked
    differ — without asking Orbax, whose shape errors name no leaf."""
    def slots(w, b):
        return {"mu": {"fc": {"kernel": np.zeros(w), "bias": np.zeros(b)}},
                "count": np.zeros(())}

    replicated = reshard.opt_layout_digest(slots((400, 120), (120,)))
    stacked8 = reshard.opt_layout_digest(slots((8, 6000), (8, 15)))
    stacked4 = reshard.opt_layout_digest(slots((4, 12000), (4, 30)))
    assert stacked8 == stacked4 != replicated


# -- cross-mesh parity matrix on genuinely sharded states -----------------
@pytest.mark.slow
class TestCrossMeshParityMatrix:
    """ISSUE 6 satellite: {data:8} -> {data:4}, {data:8} -> {fsdp:2,pipe:4},
    {fsdp:4,data:2} -> {data:8} — per-leaf bit-exact params AND opt state
    after gather."""

    def _bert_state(self, devices, mesh_axes, *, seed=0):
        n = 1
        for v in mesh_axes.values():
            n *= v
        cfg = load_config(base={
            "name": "reshard-bert",
            "mesh": mesh_axes,
            # No pipeline_stages: pipelining restructures the param tree
            # (stacked pipeline_layers) and requires stages == pipe size,
            # so a pipelined model cannot exist on both sides of a pipe
            # resize — the {fsdp:2, pipe:4} target is a mesh-SHAPE change
            # (params fsdp-sharded, replicated over the pipe axis).
            "model": {"name": "bert", "vocab_size": 64, "hidden_size": 32,
                      "num_layers": 4, "num_heads": 2, "mlp_dim": 64,
                      "max_seq_len": 16, "dtype": "float32"},
            "data": {"name": "synthetic_mlm", "vocab_size": 64,
                     "global_batch_size": 16, "seq_len": 16},
            "optimizer": {"name": "adamw", "learning_rate": 1e-3},
            "train": {"total_steps": 2},
        })
        mesh = create_mesh(cfg.mesh, devices=devices[:n])
        builder = StepBuilder(cfg, mesh)
        batch = to_global(next(get_dataset(cfg.data)), mesh)
        state = builder.init_state(seed, batch)
        return cfg, mesh, state

    def _reshard_roundtrip(self, devices, tmp_path, axes_a, axes_b):
        cfg_a, mesh_a, state = self._bert_state(devices, axes_a)
        _save(cfg_a, mesh_a, state, str(tmp_path / "ck"))
        cfg_b, mesh_b, template = self._bert_state(devices, axes_b, seed=7)
        cfg_b.checkpoint.directory = str(tmp_path / "ck")
        cfg_b.checkpoint.async_save = False
        cfg_b.checkpoint.allow_reshard = True
        mgr = CheckpointManager(cfg_b.checkpoint, mesh=mesh_b)
        restored = mgr.restore(template)
        mgr.close()
        assert restored is not None
        _assert_trees_equal(state.params, restored.params)
        _assert_trees_equal(state.opt_state, restored.opt_state)
        return restored

    def test_data8_to_data4(self, devices, tmp_path):
        self._reshard_roundtrip(
            devices, tmp_path, {"data": 8}, {"data": 4})

    def test_data8_to_fsdp2_pipe4(self, devices, tmp_path):
        # StepBuilder refuses mesh.pipe>1 without a pipelined model, and
        # pipelining restructures the param tree — so the {fsdp:2, pipe:4}
        # template is built by hand: host snapshot re-placed with specs
        # from infer_param_specs against mesh B. That is exactly the
        # host-side respecification contract reshard.py documents.
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_tensorflow_framework_tpu.core.config import (
            MeshConfig,
        )
        from distributed_tensorflow_framework_tpu.parallel.sharding import (
            infer_param_specs,
        )

        cfg_a, mesh_a, state = self._bert_state(devices, {"data": 8})
        _save(cfg_a, mesh_a, state, str(tmp_path / "ck"))
        mesh_b = create_mesh(
            MeshConfig(data=1, fsdp=2, pipe=4), devices=devices)
        host = jax.device_get(state)

        def _zero(h):  # typed PRNG-key leaves cannot become numpy zeros
            if jax.dtypes.issubdtype(
                    getattr(h, "dtype", np.float32), jax.dtypes.prng_key):
                return h
            return np.zeros_like(h)

        zeroed = jax.tree.map(_zero, host)
        rep = NamedSharding(mesh_b, P())
        template = jax.tree.map(lambda h: jax.device_put(h, rep), zeroed)
        specs = jax.tree.leaves(
            infer_param_specs(host.params, mesh_b),
            is_leaf=lambda x: isinstance(x, P))
        p_leaves, p_def = jax.tree_util.tree_flatten(zeroed.params)
        template = template.replace(params=jax.tree_util.tree_unflatten(
            p_def, [jax.device_put(h, NamedSharding(mesh_b, s))
                    for h, s in zip(p_leaves, specs)]))
        cfg_a.checkpoint.allow_reshard = True
        mgr = CheckpointManager(cfg_a.checkpoint, mesh=mesh_b)
        restored = mgr.restore(template)
        mgr.close()
        assert restored is not None
        _assert_trees_equal(state.params, restored.params)
        _assert_trees_equal(state.opt_state, restored.opt_state)
        leaves = jax.tree.leaves(restored.params)
        assert dict(leaves[0].sharding.mesh.shape) == {
            "data": 1, "fsdp": 2, "expert": 1, "pipe": 4, "seq": 1,
            "model": 1}
        assert any("fsdp" in str(leaf.sharding.spec) for leaf in leaves)

    def test_fsdp4_data2_to_data8(self, devices, tmp_path):
        self._reshard_roundtrip(
            devices, tmp_path, {"fsdp": 4, "data": 2}, {"data": 8})
