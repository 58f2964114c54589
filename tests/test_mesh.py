"""Mesh construction tests (SURVEY.md §2 rows 1–2 replacement)."""

import pytest

from distributed_tensorflow_framework_tpu.core.config import MeshConfig
from distributed_tensorflow_framework_tpu.core.mesh import (
    batch_sharding,
    create_mesh,
    initialize_runtime,
)


def test_default_mesh_uses_all_devices(devices):
    mesh = create_mesh()
    assert mesh.devices.size == 8
    assert dict(mesh.shape) == {"data": 8, "fsdp": 1, "expert": 1, "pipe": 1,
                                "seq": 1, "model": 1}


def test_explicit_axes(devices):
    mesh = create_mesh(MeshConfig(data=2, fsdp=2, model=2, seq=1))
    assert dict(mesh.shape) == {"data": 2, "fsdp": 2, "expert": 1, "pipe": 1,
                                "seq": 1, "model": 2}


def test_free_axis_inference(devices):
    mesh = create_mesh(MeshConfig(data=-1, model=2))
    assert mesh.shape["data"] == 4


def test_bad_shape_raises(devices):
    with pytest.raises(ValueError):
        create_mesh(MeshConfig(data=3, model=2))  # 6 != 8


def test_hybrid_shapes():
    from distributed_tensorflow_framework_tpu.core.mesh import hybrid_mesh_shapes

    sizes = {"data": 8, "fsdp": 2, "expert": 1, "pipe": 1, "seq": 1,
             "model": 4}
    ici, dcn = hybrid_mesh_shapes(sizes, 4)
    assert ici == {"data": 2, "fsdp": 2, "expert": 1, "pipe": 1, "seq": 1,
                   "model": 4}
    assert dcn == {"data": 4, "fsdp": 1, "expert": 1, "pipe": 1, "seq": 1,
                   "model": 1}
    # FSDP-dominant layout: slices spill onto fsdp when data can't cover.
    ici2, dcn2 = hybrid_mesh_shapes(
        {"data": 2, "fsdp": 8, "expert": 1, "pipe": 1, "seq": 1, "model": 1},
        4,
    )
    assert dcn2 == {"data": 2, "fsdp": 2, "expert": 1, "pipe": 1, "seq": 1,
                    "model": 1}
    assert ici2 == {"data": 1, "fsdp": 4, "expert": 1, "pipe": 1, "seq": 1,
                    "model": 1}
    with pytest.raises(ValueError, match="does not factor"):
        hybrid_mesh_shapes({"data": 3, "fsdp": 1, "expert": 1, "pipe": 1,
                            "seq": 1, "model": 1}, 4)


def test_runtime(devices):
    rt = initialize_runtime(MeshConfig(data=8))
    assert rt.is_chief
    assert rt.global_device_count == 8
    assert rt.data_parallel_size == 8
    sh = batch_sharding(rt.mesh)
    assert sh.spec == sh.spec  # constructible


def test_device_record_is_what_jax_reports(devices):
    """The three fields every run-meta record and bench row carry."""
    from distributed_tensorflow_framework_tpu.core.mesh import device_record

    assert device_record() == {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def test_mosaic_call_shapes_are_read_from_compiled_hlo():
    """scripts/multichip_check.py's reading of a per-device HLO, on a
    line recorded from an ahead-of-time v5e 2x2 compile of the BERT step
    at global batch 32: every rank-4 shape leads with 32/4 = 8 rows."""
    from scripts.multichip_check import mosaic_batch_dims

    line = (
        '  %_flash_fwd.12 = (bf16[8,12,512,64]{3,2,1,0:T(8,128)(2,1)S(1)}, '
        'f32[8,12,512,1]{3,2,1,0:T(8,128)}) custom-call(%bitcast.1296, '
        '%bitcast.1293, %bitcast.1290, %select_n.1057), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{bf16[8,12,512,64]{3,2,1,0}, bf16[8,12,512,64]{3,2,1,0}, '
        'bf16[8,12,512,64]{3,2,1,0}, f32[8,1,512]{2,1,0}}, '
        'metadata={op_name="jit(_train_step_jit)/jvp(BertForMLM)/layer0/'
        'attn/shard_map/jit(_flash_fwd)/pallas_call"}, '
        'backend_config={"custom_call_config":{"body":"f32[99,1,1,1]"}}')
    other = '  %fusion.1 = f32[32,512,768]{2,1,0} fusion(%p0), kind=kLoop'
    assert mosaic_batch_dims("\n".join([other, line, other])) == [[8]]
