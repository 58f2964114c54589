"""Packed-sequence (segment-aware) attention across all three impls.

Sequence packing concatenates documents into one row; attention must be
block-diagonal over the segment ids, equivalent to running each document
through attention separately. The reference here does exactly that —
slices each segment out and attends it alone — so the xla, pallas and
ring implementations are all checked against an independent construction,
not against each other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.models.bert import (
    dot_product_attention,
)

B, S, H, D = 2, 256, 2, 32
# Segment layout per row (crosses the 32-token ring-chunk boundaries and
# differs per batch row; 0 marks padding).
SEGS = np.zeros((B, S), np.int32)
SEGS[0, :100] = 1
SEGS[0, 100:180] = 2
SEGS[0, 180:230] = 3
SEGS[1, :130] = 1
SEGS[1, 130:256] = 2


def _qkv(key):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (B, S, H, D)
    return (jax.random.normal(kq, shape, jnp.float32),
            jax.random.normal(kk, shape, jnp.float32),
            jax.random.normal(kv, shape, jnp.float32))


def _per_segment_reference(q, k, v, segs):
    """Attend each segment separately and scatter back — the definition
    of packing correctness. Padding (seg 0) rows attend among themselves;
    their outputs are irrelevant (zero-weighted downstream) but computed
    the same way for comparison."""
    out = np.zeros(q.shape, np.float32)
    for b in range(q.shape[0]):
        for seg in np.unique(segs[b]):
            idx = np.where(segs[b] == seg)[0]
            o = dot_product_attention(
                jnp.asarray(q[b:b + 1, idx]), jnp.asarray(k[b:b + 1, idx]),
                jnp.asarray(v[b:b + 1, idx]))
            out[b, idx] = np.asarray(o)[0]
    return out


@pytest.fixture(scope="module")
def data():
    q, k, v = _qkv(jax.random.key(0))
    ref = _per_segment_reference(np.asarray(q), np.asarray(k),
                                 np.asarray(v), SEGS)
    return q, k, v, jnp.asarray(SEGS), ref


def test_xla_segmented_matches_per_segment(data):
    q, k, v, segs, ref = data
    out = dot_product_attention(q, k, v, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows", ["selected", "128-row"])
def test_flash_segmented_matches_per_segment(data, pin_whole_k_rows, rows):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    pin_whole_k_rows(rows, S)
    q, k, v, segs, ref = data
    out = flash_attention(q, k, v, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows", ["selected", "128-row"])
def test_flash_segmented_gradients_match_xla(data, pin_whole_k_rows, rows):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    pin_whole_k_rows(rows, S)
    q, k, v, segs, ref = data
    # Weight the loss by real-token positions so padding rows (whose
    # outputs legitimately differ in no way that matters) drop out.
    w = jnp.asarray((SEGS > 0).astype(np.float32))[..., None, None]

    def loss(attn_fn):
        def f(q, k, v):
            out = attn_fn(q, k, v).astype(jnp.float32)
            return jnp.sum(jnp.sin(out) * w)
        return f

    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, segment_ids=segs)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        loss(lambda q, k, v: dot_product_attention(
            q, k, v, segment_ids=segs)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("chunk_impl", ["xla", "flash"])
def test_ring_segmented_matches_per_segment(devices, monkeypatch,
                                            chunk_impl, data):
    """Segments cross ring-shard boundaries; the segment shard rotates
    with its K/V chunk, so the block-diagonal mask stays correct all the
    way around the ring — for both per-chunk implementations."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.parallel import ring
    from distributed_tensorflow_framework_tpu.parallel.ring import (
        ring_attention_sharded,
    )

    monkeypatch.setattr(
        ring, "FLASH_CHUNK_MIN", 0 if chunk_impl == "flash" else 10**9)
    mesh = create_mesh(MeshConfig(data=1, seq=8))
    q, k, v, segs, ref = data
    out = jax.jit(lambda q, k, v: ring_attention_sharded(
        q, k, v, mesh=mesh, segment_ids=segs))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_bert_accepts_segment_ids(devices):
    """End-to-end: the model forward with packing differs from unpacked
    (the mask bites) and matches the xla impl across attention impls."""
    from distributed_tensorflow_framework_tpu.core.config import ModelConfig
    from distributed_tensorflow_framework_tpu.models import get_model

    cfg = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
               mlp_dim=64, max_seq_len=64, dtype="float32", dropout_rate=0.0)
    ids = jnp.asarray(
        np.random.default_rng(0).integers(1, 128, (2, 64)), jnp.int32)
    mask = jnp.ones((2, 64), jnp.int32)
    segs = jnp.asarray(
        np.repeat([[1, 2, 3, 4]], 16, axis=0).T.reshape(1, 64).repeat(2, 0))

    outs = {}
    for impl in ("xla", "pallas"):
        m = get_model(ModelConfig(name="bert", attention_impl=impl, **cfg))
        vs = m.init(jax.random.key(1), ids, mask, train=False)
        packed = m.apply(vs, ids, mask, segs, train=False)
        unpacked = m.apply(vs, ids, mask, train=False)
        assert not np.allclose(np.asarray(packed), np.asarray(unpacked))
        outs[impl] = np.asarray(packed)
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=2e-4, atol=2e-4)


def test_packed_train_step_end_to_end(devices):
    """StepBuilder feeds segment_ids through to the model when the batch
    carries them (data.pack_factor>1 path): one train step runs and the
    loss is finite on an 8-replica mesh."""
    import jax as _jax

    from distributed_tensorflow_framework_tpu.core.config import load_config
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.data.infeed import to_global
    from distributed_tensorflow_framework_tpu.train.step import StepBuilder

    cfg = load_config(base={
        "name": "packed-step",
        "mesh": {"data": 8},
        "model": {"name": "bert", "vocab_size": 512, "hidden_size": 32,
                  "num_layers": 1, "num_heads": 2, "mlp_dim": 64,
                  "max_seq_len": 32, "dtype": "float32",
                  "attention_impl": "pallas"},
        # data.vocab_size must not exceed the model's — StepBuilder now
        # rejects the mismatch (the default-30522 stream would feed token
        # ids the 512-entry embedding clamps silently).
        "data": {"name": "synthetic_mlm", "vocab_size": 512,
                 "global_batch_size": 8, "seq_len": 32},
        "optimizer": {"name": "adamw", "learning_rate": 1e-3},
        "train": {"total_steps": 1},
    })
    mesh = create_mesh(cfg.mesh)
    builder = StepBuilder(cfg, mesh)
    rng = np.random.default_rng(0)
    tokens = rng.integers(200, 500, (8, 32)).astype(np.int32)
    tokens[:, 20:] = 0  # padding tail
    segs = np.zeros((8, 32), np.int32)
    segs[:, :8] = 1
    segs[:, 8:20] = 2
    targets = np.where(rng.random((8, 32)) < 0.15, tokens, -1).astype(np.int32)
    targets[:, 20:] = -1
    host = {
        "input_ids": tokens,
        "targets": targets,
        "attention_mask": (tokens != 0).astype(np.int32),
        "segment_ids": segs,
    }
    batch = to_global(host, mesh)
    state = builder.init_state(0, batch)
    step = builder.make_train_step(batch)
    state, metrics = step(state, batch)
    assert np.isfinite(float(_jax.device_get(metrics["loss"])))


def test_xla_segmented_bf16_no_nan():
    """Regression: fully-masked pad-query rows under bf16 scores must not
    NaN (f32-min rounds to -inf in bf16; masking now happens in f32)."""
    q, k, v, segs, _ = (None,) * 5
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((2, 32, 2, 16)), jnp.bfloat16)
    segs = np.zeros((2, 32), np.int32)
    segs[:, :20] = 1  # tail 12 positions are padding (segment 0)
    out = dot_product_attention(q, q, q, segment_ids=jnp.asarray(segs),
                                dtype=jnp.bfloat16)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    # Combined with a key mask (the production packed-batch shape).
    mask = jnp.asarray((segs > 0))[:, None, None, :]
    out = dot_product_attention(q, q, q, mask=mask,
                                segment_ids=jnp.asarray(segs),
                                dtype=jnp.bfloat16)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_packed_positions_reset_per_segment(devices):
    """A document packed at row offset c must see pos_embedding[0..len) —
    the model forward over a packed row equals the forward over each
    document in its own (unpacked) row."""
    from distributed_tensorflow_framework_tpu.core.config import ModelConfig
    from distributed_tensorflow_framework_tpu.models import get_model

    cfg = dict(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
               mlp_dim=64, max_seq_len=32, dtype="float32", dropout_rate=0.0)
    rng = np.random.default_rng(9)
    doc_a = rng.integers(1, 128, 12).astype(np.int32)
    doc_b = rng.integers(1, 128, 20).astype(np.int32)

    packed = np.concatenate([doc_a, doc_b])[None, :]          # (1, 32)
    segs = np.concatenate([np.full(12, 1), np.full(20, 2)])[None, :]
    mask_packed = np.ones((1, 32), np.int32)

    # Unpacked: each doc alone in a zero-padded row.
    rows = np.zeros((2, 32), np.int32)
    rows[0, :12] = doc_a
    rows[1, :20] = doc_b
    mask_rows = (rows != 0).astype(np.int32)

    m = get_model(ModelConfig(name="bert", attention_impl="xla", **cfg))
    vs = m.init(jax.random.key(0), jnp.asarray(packed),
                jnp.asarray(mask_packed), train=False)
    out_packed = np.asarray(m.apply(
        vs, jnp.asarray(packed), jnp.asarray(mask_packed),
        jnp.asarray(segs), train=False))
    out_rows = np.asarray(m.apply(
        vs, jnp.asarray(rows), jnp.asarray(mask_rows), train=False))

    np.testing.assert_allclose(out_packed[0, :12], out_rows[0, :12],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out_packed[0, 12:], out_rows[1, :20],
                               rtol=1e-5, atol=1e-5)
