"""End-to-end train smoke for the non-LeNet workloads (tiny shapes):
Inception-v3 with aux loss, BERT MLM with each attention impl, and BERT
tensor-parallel over the model axis."""

import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.core.config import load_config
from distributed_tensorflow_framework_tpu.train import Trainer


def tiny_bert_base(**model_overrides):
    model = {
        "name": "bert", "vocab_size": 512, "hidden_size": 64,
        "num_layers": 2, "num_heads": 4, "mlp_dim": 128,
        "max_seq_len": 128, "dtype": "float32", "attention_impl": "xla",
    }
    model.update(model_overrides)
    return {
        "name": "bert-tiny",
        "model": model,
        "data": {
            "name": "synthetic_mlm", "global_batch_size": 16, "seq_len": 128,
            "vocab_size": 512,
        },
        "optimizer": {"name": "adamw", "learning_rate": 3e-3,
                      "grad_clip_norm": 1.0},
        "train": {"total_steps": 10, "log_interval": 5, "seed": 1},
    }


@pytest.mark.parametrize("impl", ["xla", "pallas", "ring"])
def test_bert_trains(devices, impl):
    base = tiny_bert_base(attention_impl=impl)
    if impl == "ring":
        base["mesh"] = {"data": 1, "seq": 8}
    cfg = load_config(base=base)
    t = Trainer(cfg)
    metrics = t.train()
    assert np.isfinite(metrics["loss"])
    # vocab 512 → random CE ≈ ln(512) ≈ 6.24; must have moved down.
    assert metrics["loss"] < 6.0, metrics


def test_run_meta_lists_the_attention_dispatch(devices, tmp_path):
    """The run's opening record says which flash kernels the model's
    shapes selected (family, tile, backward), filled as ``build()``
    traces the forward — and it still opens the file."""
    import json

    base = tiny_bert_base(attention_impl="pallas")
    base["checkpoint"] = {"directory": str(tmp_path / "run")}
    t = Trainer(load_config(base=base))
    t.build()
    with open(tmp_path / "run" / "events.jsonl") as fh:
        first = json.loads(fh.readline())
    assert first["kind"] == "run_meta"
    meta = first["extra"]
    assert meta["pallas_kernels"] == "interpret"
    assert meta["expert_share"] is None      # no model of BERT's says one
    mine = [e for e in meta["flash_dispatch"]
            if (e["s"], e["s_k"], e["dtype"]) == (128, 128, "float32")]
    assert mine and all(
        (e["family"], e["block_q"], e["block_k"], e["backward"],
         e["bwd_block_q"], e["bwd_block_k"])
        == ("whole_k", 128, 128, "fused", 128, 128)
        for e in mine)


@pytest.mark.slow
def test_bert_tensor_parallel(devices):
    """model=4 TP: megatron-style sharded QKV/MLP; loss matches DP run."""
    import jax

    results = {}
    for mesh in ({"data": 8}, {"data": 2, "model": 4}):
        base = tiny_bert_base()
        base["mesh"] = mesh
        cfg = load_config(base=base)
        t = Trainer(cfg)
        metrics = t.train()
        results[str(mesh)] = metrics["loss"]
    a, b = results.values()
    np.testing.assert_allclose(a, b, rtol=1e-3)


@pytest.mark.slow
@pytest.mark.slowest
def test_inception_trains(devices):
    cfg = load_config(base={
        "name": "inception-tiny",
        "model": {"name": "inception_v3", "num_classes": 10, "dtype": "float32"},
        "data": {
            "name": "synthetic_images", "global_batch_size": 16,
            "image_size": 96, "channels": 3,
        },
        "optimizer": {"name": "sgd_momentum", "learning_rate": 0.01},
        "train": {"total_steps": 3, "log_interval": 1, "seed": 0},
    })
    t = Trainer(cfg)
    metrics = t.train()
    assert np.isfinite(metrics["loss"])
    assert "aux_loss" in metrics  # aux head active in training


@pytest.mark.slow
@pytest.mark.parametrize("chunk_impl", ["xla", "flash"])
def test_long_ring_config_recipe_builds_and_steps(devices, monkeypatch,
                                                  chunk_impl):
    """configs/bert_long_ring.yaml (the long-context recipe) drives the
    Trainer end to end when scaled down to CPU-mesh size: ring attention
    over seq=8 with remat on. The scaled chunk (32) would dispatch to the
    XLA chain, so the flash variant forces FLASH_CHUNK_MIN=0 to cover the
    Pallas-kernel branch the real 16k config (chunk 2048) takes."""
    from distributed_tensorflow_framework_tpu.parallel import ring

    monkeypatch.setattr(
        ring, "FLASH_CHUNK_MIN", 0 if chunk_impl == "flash" else 10**9)
    cfg = load_config("configs/bert_long_ring.yaml", overrides=[
        "mesh.data=1", "mesh.seq=8",
        "model.vocab_size=512", "model.hidden_size=32",
        "model.num_layers=2", "model.num_heads=2", "model.mlp_dim=64",
        "model.max_seq_len=256",
        "data.vocab_size=512", "data.seq_len=256",
        "data.global_batch_size=4",
        "train.total_steps=4", "train.log_interval=2",
        "checkpoint.directory=",
    ])
    t = Trainer(cfg)
    metrics = t.train()
    assert np.isfinite(metrics["loss"])
