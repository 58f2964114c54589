"""Attention implementation parity: pallas and ring vs the XLA reference
(SURVEY.md §4 numerics-parity strategy applied to the attention kernels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.models.bert import dot_product_attention


def _rand_qkv(key, b=2, s=256, h=4, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


@pytest.mark.parametrize("rows", ["selected", "128-row"])
def test_flash_attention_matches_xla(devices, pin_whole_k_rows, rows):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    pin_whole_k_rows(rows, 256)
    q, k, v = _rand_qkv(jax.random.key(0))
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_with_mask(devices):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    q, k, v = _rand_qkv(jax.random.key(1), s=128)
    mask = jnp.ones((2, 1, 1, 128), bool).at[:, :, :, 100:].set(False)
    ref = dot_product_attention(q, k, v, mask=mask)
    out = flash_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows", ["selected", "128-row"])
def test_flash_attention_backward_matches_xla(devices, pin_whole_k_rows, rows):
    """The Pallas backward (online recompute) must match XLA autodiff
    through the reference attention — for q, k AND v."""
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    pin_whole_k_rows(rows, 256)
    q, k, v = _rand_qkv(jax.random.key(3), s=256)
    mask = jnp.ones((2, 1, 1, 256), bool).at[:, :, :, 200:].set(False)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q, k, v):
        out = dot_product_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_flash_attention_backward_no_quadratic_residual(devices):
    """Structural check on the VJP residuals: nothing score-matrix-shaped
    (S×S) is saved between forward and backward."""
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    b, s, h, d = 2, 512, 4, 64
    q, k, v = _rand_qkv(jax.random.key(4), b=b, s=s, h=h, d=d)
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(q, k, v), q, k, v)
    # Residuals captured by the VJP closure: all must be O(S·D)/O(S) —
    # a score-shaped residual would have TWO sequence-length axes.
    leaves = [x for x in jax.tree.leaves(vjp) if hasattr(x, "shape")]
    assert leaves, "vjp closure has no residuals?"
    for leaf in leaves:
        seq_axes = sum(1 for dim in leaf.shape if dim == s)
        assert seq_axes <= 1, f"score-matrix-shaped residual: {leaf.shape}"
        assert leaf.size <= b * h * s * d, (
            f"residual {leaf.shape} larger than any O(S·D) tensor"
        )


@pytest.mark.parametrize("chunk_impl", ["xla", "flash"])
def test_ring_attention_matches_xla(devices, monkeypatch, chunk_impl):
    """Ring attention over a seq=8 mesh axis reproduces full attention —
    through BOTH per-chunk implementations (the FLASH_CHUNK_MIN dispatch
    picks by chunk length in production; tests force each path)."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.parallel import ring
    from distributed_tensorflow_framework_tpu.parallel.ring import (
        ring_attention_sharded,
    )

    monkeypatch.setattr(
        ring, "FLASH_CHUNK_MIN", 0 if chunk_impl == "flash" else 10**9)
    mesh = create_mesh(MeshConfig(data=1, seq=8))
    q, k, v = _rand_qkv(jax.random.key(2), b=2, s=256, h=2, d=32)
    ref = dot_product_attention(q, k, v)
    out = jax.jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh=mesh)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk_impl", ["xla", "flash"])
def test_ring_attention_mask_and_gradients(devices, monkeypatch, chunk_impl):
    """Ring attention under a key mask must match XLA attention for the
    output AND the q/k/v gradients (the training path differentiates
    through the ppermute ring; the flash variant additionally exercises
    the lse-cotangent path of the Pallas backward)."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.parallel import ring
    from distributed_tensorflow_framework_tpu.parallel.ring import (
        ring_attention_sharded,
    )

    monkeypatch.setattr(
        ring, "FLASH_CHUNK_MIN", 0 if chunk_impl == "flash" else 10**9)
    mesh = create_mesh(MeshConfig(data=1, seq=8))
    q, k, v = _rand_qkv(jax.random.key(5), b=2, s=256, h=2, d=32)
    # Mask out the last 40 keys (cuts across the final ring shard).
    mask = jnp.ones((2, 1, 1, 256), bool).at[:, :, :, 216:].set(False)

    def loss_ring(q, k, v):
        out = ring_attention_sharded(q, k, v, mesh=mesh, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q, k, v):
        out = dot_product_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    out_ring = jax.jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh=mesh, mask=mask)
    )(q, k, v)
    out_ref = dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("chunk_impl", ["xla", "flash"])
def test_ring_attention_data_seq_mesh_trailing_padding(devices, monkeypatch,
                                                       chunk_impl):
    """Ring on a COMBINED data×seq mesh (4×2) with document-style
    trailing padding — half the rows have their entire second KV chunk
    padded — through BOTH per-chunk implementations (an all-f32-min
    bias chunk must stay finite in the flash kernels too). Pinned by the
    round-5 dp+sp+ep forensics: this exact shape was suspected when a
    composed ring+MoE run went flat, and the probe that exonerated the
    op (fwd + all grads ≤1.1e-6 vs reference) is kept here so the
    composition's attention substrate stays provably exact. Loss weights
    valid positions only, like the MLM objective."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.parallel import ring
    from distributed_tensorflow_framework_tpu.parallel.ring import (
        ring_attention_sharded,
    )

    monkeypatch.setattr(
        ring, "FLASH_CHUNK_MIN", 0 if chunk_impl == "flash" else 10**9)
    mesh = create_mesh(MeshConfig(data=4, seq=2))
    B, S = 8, 256
    q, k, v = _rand_qkv(jax.random.key(23), b=B, s=S, h=2, d=32)
    valid = np.ones((B, S), bool)
    valid[:4, 80:] = False          # rows 0-3: 80-token docs → chunk 2 all pad
    mask = jnp.asarray(valid)[:, None, None, :]
    w = jnp.asarray(valid, jnp.float32)[:, :, None, None]

    def loss_ring(q, k, v):
        out = ring_attention_sharded(q, k, v, mesh=mesh, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)) * w)

    def loss_ref(q, k, v):
        out = dot_product_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)) * w)

    out_ring = jax.jit(
        lambda q, k, v: ring_attention_sharded(q, k, v, mesh=mesh, mask=mask)
    )(q, k, v)
    out_ref = dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(
        np.asarray(out_ring) * np.asarray(w), np.asarray(out_ref) * np.asarray(w),
        rtol=2e-5, atol=2e-5)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


def test_flash_chunk_guards(devices):
    """flash_attention_chunk must refuse shapes its grid would silently
    truncate: non-multiple-of-BLOCK_Q chunk lengths (e.g. seq/ring_shards
    = 192) and unequal shard lengths."""
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention_chunk,
    )

    def qkv(s, sk=None):
        sk = s if sk is None else sk
        q = jnp.zeros((1, s, 2, 8), jnp.float32)
        k = jnp.zeros((1, sk, 2, 8), jnp.float32)
        bias = jnp.zeros((1, sk), jnp.float32)
        return q, k, k, bias

    q, k, v, bias = qkv(192)  # > BLOCK_Q but not a multiple
    with pytest.raises(ValueError, match="multiple of"):
        flash_attention_chunk(q, k, v, bias)
    q, k, v, bias = qkv(128, sk=256)  # unequal shards
    with pytest.raises(ValueError, match="equal-length"):
        flash_attention_chunk(q, k, v, bias)
    # A legal sub-block chunk still runs (block_q clamps to s).
    q, k, v, bias = qkv(32)
    o, lse = flash_attention_chunk(q, k, v, bias)
    assert o.shape == (1, 32, 2, 8) and lse.shape == (1, 32, 2, 1)


def test_ring_chunk_dispatch_policy(devices):
    """The >MAX_SEQ_VMEM silent-fallback hole is closed (VERDICT r3 weak
    #2): small odd chunks still take the XLA chain; 128-multiple chunks
    above MAX_SEQ_VMEM take the K-blocked flash kernels; chunks above
    MAX_SEQ_VMEM the kernel can't take fail LOUDLY instead of
    materializing an O(chunk²) score block."""
    from distributed_tensorflow_framework_tpu.parallel.ring import (
        _chunk_attention,
    )

    # Non-multiple above the crossover but within VMEM: XLA chain, works.
    c = 2112
    q = jnp.zeros((1, c, 1, 8), jnp.float32)
    bias = jnp.zeros((1, c), jnp.float32)
    o, lse = _chunk_attention(q, q, q, bias)
    assert o.shape == (1, c, 1, 8) and lse.shape == (1, c, 1, 1)
    # Non-multiple above MAX_SEQ_VMEM: loud failure with mesh guidance.
    c = 8200
    q = jnp.zeros((1, c, 1, 8), jnp.float32)
    bias = jnp.zeros((1, c), jnp.float32)
    with pytest.raises(ValueError, match="mesh.seq"):
        _chunk_attention(q, q, q, bias)


def _streaming_reference(q, k, v, bias=None, segment_ids=None, block=128):
    """O(S·block)-memory full-attention reference (f32, logsumexp-stable):
    independent of both kernel families, cheap enough for S≫4096 where
    the (S,S)-materializing dot_product_attention reference would OOM."""
    b, s, h, d = q.shape
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3)   # (B,H,S,D)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    scale = 1.0 / (d ** 0.5)

    def one_block(qb_seg):
        qb, sb = qb_seg                                 # (B,H,block,D)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * scale
        if bias is not None:
            sc = sc + bias[:, None, None, :]
        if segment_ids is not None:
            sc = jnp.where(
                sb[:, None, :, None] == segment_ids[:, None, None, :],
                sc, jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vf)

    qs = qf.reshape(b, h, s // block, block, d).transpose(2, 0, 1, 3, 4)
    if segment_ids is not None:
        segs = segment_ids.reshape(b, s // block, block).transpose(1, 0, 2)
    else:
        segs = jnp.zeros((s // block, b, block), jnp.int32)
    out = jax.lax.map(one_block, (qs, segs))            # (nb,B,H,block,D)
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)    # (B,S,H,D)


def _allow_fused(monkeypatch, fused: bool):
    """The module's one seam for the backward: what the platform rule
    answers. ``False`` is a TPU generation off the verified list."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "fused_bwd_enabled", lambda: fused)


@pytest.mark.parametrize("backward", ["fused", "two_pass"])
def test_kblocked_kernels_match_whole_k(devices, monkeypatch, backward):
    """Forcing the K-blocked streaming forward (MAX_SEQ_VMEM→128) on a
    shape the whole-K forward handles must reproduce the XLA reference
    for output AND, through either streaming backward, q/k/v grads —
    with a key mask in play."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    _allow_fused(monkeypatch, backward == "fused")
    monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 128)
    # Pin the streaming tiles to 128 so s=384 gives a REAL 3-step
    # k-grid; the production 512/1024 targets would degenerate this
    # shape to one block and never exercise the running-softmax
    # cross-block math (init / corr rescale / finalize).
    monkeypatch.setattr(fa, "BLOCK_Q_KB", 128)
    monkeypatch.setattr(fa, "BLOCK_K_KB", 128)
    assert tuple(fa.select_dispatch(384, 384, jnp.float32)) == (
        "stream", 128, 128, backward, 128, 128)
    q, k, v = _rand_qkv(jax.random.key(7), b=2, s=384, h=2, d=32)
    mask = jnp.ones((2, 1, 1, 384), bool).at[:, :, :, 300:].set(False)

    def loss_flash(q, k, v):
        out = fa.flash_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q, k, v):
        out = dot_product_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    out = fa.flash_attention(q, k, v, mask=mask)
    ref = dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


def test_fused_streaming_backward_matches_two_pass(devices, monkeypatch):
    """The fused one-pass backward: on a forced
    streaming shape (MAX_SEQ_VMEM→128, 128-tiles, s=384 → real 3×3
    (q,k) block grid) the fused kernel's q/k/v grads must match BOTH the
    two-pass streaming kernels and the XLA reference — with a key mask,
    in bf16, and segmented."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 128)
    monkeypatch.setattr(fa, "BLOCK_Q_KB", 128)
    monkeypatch.setattr(fa, "BLOCK_K_KB", 128)
    q, k, v = _rand_qkv(jax.random.key(11), b=2, s=384, h=2, d=32)
    q = q.astype(jnp.bfloat16)
    k = k.astype(jnp.bfloat16)
    v = v.astype(jnp.bfloat16)
    mask = jnp.ones((2, 1, 1, 384), bool).at[:, :, :, 320:].set(False)
    seg = jnp.concatenate(
        [jnp.zeros((2, 200), jnp.int32), jnp.ones((2, 184), jnp.int32)],
        axis=1)

    def loss(q, k, v, segment_ids=None):
        out = fa.flash_attention(q, k, v, mask=mask,
                                 segment_ids=segment_ids)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q, k, v, segment_ids=None):
        attn_mask = mask
        if segment_ids is not None:
            same = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
            attn_mask = mask & same
        out = dot_product_attention(q, k, v, mask=attn_mask)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    for seg_ids in (None, seg):
        _allow_fused(monkeypatch, False)
        g_two = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, seg_ids)
        _allow_fused(monkeypatch, True)
        g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v, seg_ids)
        for name, a, b in zip("qkv", g_fused, g_two):
            # Identical block math, identical accumulation order → the
            # two backward paths should agree to bf16 round-off.
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-2, atol=2e-2,
                err_msg=f"d{name} seg={seg_ids is not None}")
        # And DIRECTLY against the XLA reference — agreement with the
        # two-pass path alone would not catch a defect shared by both
        # streaming backwards (delta/bias plumbing upstream of the
        # kernels).
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v, seg_ids)
        for name, a, b in zip("qkv", g_fused, g_ref):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=4e-2, atol=4e-2,
                err_msg=f"d{name} vs ref, seg={seg_ids is not None}")


def test_fused_streaming_backward_gate(devices, monkeypatch):
    """The fused path only engages below FUSED_BWD_MAX; above it the
    two-pass kernels run even where the platform allows it (VMEM
    accumulators would not fit) — pinned by checking grads still match
    the XLA reference with an absurdly low gate."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 128)
    monkeypatch.setattr(fa, "BLOCK_Q_KB", 128)
    monkeypatch.setattr(fa, "BLOCK_K_KB", 128)
    monkeypatch.setattr(fa, "FUSED_BWD_MAX", 256)  # s=384 exceeds it
    q, k, v = _rand_qkv(jax.random.key(13), b=1, s=384, h=2, d=32)

    # Spy on the fused builder: correctness alone cannot distinguish the
    # paths (both produce right grads at this shape) — pin the DISPATCH.
    calls = []
    orig = fa._flash_bwd_fused_kb
    monkeypatch.setattr(
        fa, "_flash_bwd_fused_kb",
        lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1])

    def loss_flash(q, k, v):
        out = fa.flash_attention(q, k, v)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q, k, v):
        out = dot_product_attention(q, k, v)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    assert not calls, "fused kernel ran above FUSED_BWD_MAX"
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{name}")
    # Raising the gate back over s flips the dispatch to the fused path.
    monkeypatch.setattr(fa, "FUSED_BWD_MAX", 8192)
    jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    assert calls, "fused kernel did not run below FUSED_BWD_MAX"


def _selection_case(s, segmented, dtype):
    """Inputs on the kernels' (B,H,S,D) layout plus the (B,S,S) additive
    bias that says the same thing to ``_xla_reference``: a key mask over
    the tail and, when segmented, the block-diagonal document mask."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    b, h, d = 1, 2, 32
    q, k, v = (t.astype(dtype) for t in _rand_qkv(
        jax.random.key(s), b=b, s=s, h=h, d=d))
    keep = jnp.arange(s) < s - s // 8
    seg = (jnp.searchsorted(jnp.asarray([0.15, 0.4, 0.8]) * s,
                            jnp.arange(s), side="right") + 1
           ).astype(jnp.int32)[None, :]
    full = jnp.broadcast_to(keep[None, None, :], (b, s, s))
    if segmented:
        full = full & (seg[:, :, None] == seg[:, None, :])
    bias = jnp.where(full, 0.0, fa.NEG_INF).astype(jnp.float32)
    mask = jnp.broadcast_to(keep[None, None, None, :], (b, 1, 1, s))
    return (q, k, v), mask, (seg if segmented else None), bias


def _assert_matches_xla_reference(s, segmented, dtype):
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    (q, k, v), mask, seg, bias = _selection_case(s, segmented, dtype)

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, segment_ids=seg)

    def reference(q, k, v):
        qt, kt, vt = (t.astype(jnp.float32).transpose(0, 2, 1, 3)
                      for t in (q, k, v))
        return fa._xla_reference(qt, kt, vt, bias).transpose(0, 2, 1, 3)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(attn(q, k, v).astype(jnp.float32)))

    exact = dtype == jnp.float32
    out_tol, grad_tol = (2e-5, 2e-4) if exact else (2e-2, 6e-2)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(reference(q, k, v)), rtol=out_tol, atol=out_tol)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(
        *(t.astype(jnp.float32) for t in (q, k, v)))
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b),
            rtol=grad_tol, atol=grad_tol, err_msg=f"d{name}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("s", [64, 128, 384, 512, 1024])
def test_selected_dispatch_matches_xla_reference(devices, s, segmented,
                                                 dtype):
    """What ``select_dispatch`` picks with no module global patched
    agrees with ``_xla_reference`` forward and backward, from a
    sub-block sequence to the widest whole-K row block."""
    _assert_matches_xla_reference(s, segmented, dtype)


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("s", [128, 384, 512, 1024])
def test_selection_on_an_unverified_platform_matches_xla_reference(
        devices, monkeypatch, s, segmented):
    """The same check with the fused backward refused, as on a TPU
    generation off the verified list (the default cases above cover the
    fused one): bf16 then takes the whole-K forward and the streaming
    two-pass pair at every length."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    _allow_fused(monkeypatch, False)
    picked = fa.select_dispatch(s, s, jnp.bfloat16)
    assert (picked.family, picked.backward) == ("whole_k", "two_pass")
    _assert_matches_xla_reference(s, segmented, jnp.bfloat16)
    logged = {(e["s"], e["dtype"], e["segmented"]): e
              for e in fa.dispatch_log()}
    entry = logged[(s, "bfloat16", segmented)]
    assert (entry["family"], entry["block_q"], entry["backward"]) == (
        "whole_k", picked.block_q, "two_pass")


# (s, s_k, dtype, fused backward allowed) -> the dispatch, at the shipped
# thresholds. ``True`` is what v5e and every backend that is not a TPU
# resolve to, ``False`` a TPU generation off the verified list.
_DISPATCH_TABLE = [
    ((64, 64, "bfloat16", True),          # key tile under one lane tile
     ("whole_k", 64, 64, "two_pass", 64, 64)),
    ((128, 128, "bfloat16", True),
     ("whole_k", 128, 128, "fused", 128, 128)),
    ((384, 384, "bfloat16", True),
     ("whole_k", 384, 384, "fused", 384, 384)),
    ((512, 512, "bfloat16", True),        # bert_s512
     ("whole_k", 512, 512, "fused", 512, 512)),
    ((512, 512, "bfloat16", False),
     ("whole_k", 512, 512, "two_pass", 512, 512)),
    ((512, 512, "float32", True),
     ("whole_k", 512, 512, "fused", 512, 512)),
    ((640, 640, "bfloat16", True),        # 5·128: only 128 divides
     ("whole_k", 128, 640, "fused", 128, 640)),
    ((1024, 1024, "bfloat16", True),
     ("whole_k", 512, 1024, "fused", 512, 1024)),
    ((2048, 2048, "bfloat16", True),
     ("whole_k", 256, 2048, "fused", 512, 1024)),
    ((2048, 2048, "float32", True),
     ("whole_k", 256, 2048, "fused", 512, 1024)),
    ((4096, 4096, "bfloat16", True),
     ("whole_k", 128, 4096, "fused", 512, 1024)),
    ((4096, 4096, "bfloat16", False),
     ("whole_k", 128, 4096, "two_pass", 512, 1024)),
    ((8192, 8192, "bfloat16", True),      # bert_s8192
     ("stream", 512, 1024, "fused", 512, 1024)),
    ((8192, 8192, "bfloat16", False),
     ("stream", 512, 1024, "two_pass", 512, 1024)),
    ((16384, 16384, "bfloat16", True),    # over FUSED_BWD_MAX
     ("stream", 512, 1024, "two_pass", 512, 1024)),
]
_TABLE_SHAPES = sorted({c[:3] for c, _ in _DISPATCH_TABLE}
                       | {(4096, 4096, "float32"), (8192, 8192, "float32")})


@pytest.mark.parametrize("case,want", _DISPATCH_TABLE,
                         ids=[f"{c[0]}-{c[2]}-{'fused' if c[3] else 'twopass'}"
                              for c, _ in _DISPATCH_TABLE])
def test_select_dispatch_table(monkeypatch, case, want):
    """The tile function itself: the table above, every tile a divisor
    of its sequence, and a whole-K score block never over the area the
    family proves at its upper edge (BLOCK_Q rows × MAX_SEQ_VMEM keys)."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    s, s_k, dtype, fused_allowed = case
    _allow_fused(monkeypatch, fused_allowed)
    got = fa.select_dispatch(s, s_k, jnp.dtype(dtype))
    assert tuple(got) == want
    assert s % got.block_q == 0 and s_k % got.block_k == 0
    assert s % got.bwd_block_q == 0 and s_k % got.bwd_block_k == 0
    area = fa.BLOCK_Q * fa.MAX_SEQ_VMEM
    if got.family == "whole_k":
        assert got.block_k == s_k and got.block_q * s_k <= area


def _on_a_tpu(monkeypatch, fa, device_kind):
    """The platform rule as a process on a real TPU of ``device_kind``
    resolves it, nothing memoised."""
    import types

    device = types.SimpleNamespace(device_kind=device_kind)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa.jax, "devices", lambda *a: [device])
    monkeypatch.setattr(fa, "fused_bwd_enabled",
                        fa.fused_bwd_enabled.__wrapped__)


@pytest.mark.parametrize("shape", _TABLE_SHAPES,
                         ids=[f"{s}-{d}" for s, _, d in _TABLE_SHAPES])
def test_selection_off_the_chip_is_the_verified_chips(monkeypatch, shape):
    """With nothing patched this suite (``JAX_PLATFORMS=cpu``) selects
    what a v5e selects, so tier-1 differentiates through the kernels the
    cells run and an ahead-of-time compile from a CPU backend is of the
    chip's program."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    assert jax.default_backend() == "cpu"
    here = fa.select_dispatch(*shape)
    _on_a_tpu(monkeypatch, fa, "TPU v5 lite")
    assert fa.select_dispatch(*shape) == here


def test_no_backward_holds_the_whole_opposing_sequence(monkeypatch):
    """Every backward streams: whatever the lengths, the dtype and the
    platform's answer, its tile is within the streaming targets."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    for fused_allowed in (True, False):
        _allow_fused(monkeypatch, fused_allowed)
        for shape in _TABLE_SHAPES:
            got = fa.select_dispatch(*shape)
            assert got.bwd_block_q <= fa.BLOCK_Q_KB, (shape, got)
            assert got.bwd_block_k <= fa.BLOCK_K_KB, (shape, got)
            assert fused_allowed or got.backward == "two_pass"


def test_fused_backward_is_on_for_the_verified_platforms_only(
        monkeypatch, caplog):
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    assert fa.fused_bwd_verified("TPU v5 lite")
    assert fa.fused_bwd_verified("TPU v5e")
    assert not fa.fused_bwd_verified("TPU v4")
    assert not fa.fused_bwd_verified("TPU v6 lite")
    # A real TPU off the list keeps the two-pass pair and says why,
    # naming the list and the script that puts a generation on it.
    _on_a_tpu(monkeypatch, fa, "TPU v6 lite")
    with caplog.at_level("WARNING", logger=fa.log.name):
        assert fa.select_dispatch(512, 512, jnp.bfloat16).backward == "two_pass"
    assert "FUSED_BWD_VERIFIED_PLATFORMS" in caplog.text
    assert "verify_flash_kernels.py" in caplog.text


def test_pick_block_divisor_policy():
    """Streaming-tile picker: largest 128-multiple ≤ target dividing s;
    sub-128 env targets clamp to 128 instead of dividing by zero; short
    sequences pass through whole."""
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        _pick_block,
    )

    assert _pick_block(8192, 1024) == 1024
    assert _pick_block(8192, 512) == 512
    assert _pick_block(4224, 1024) == 384      # 33·128: divisor fallback
    assert _pick_block(4352, 1024) == 256      # 34·128: 2·128 divides
    assert _pick_block(256, 64) == 128         # sub-128 target clamps
    assert _pick_block(96, 1024) == 96         # short chunk passes through


def test_bf16_inputs_match_f32_reference(devices, monkeypatch):
    """Production dtype through BOTH kernel regimes: the round-4 kernels
    dot in the INPUT dtype (bf16 on TPU) and downcast the p/ds softmax
    intermediates — paths every f32 test reduces to a no-op. Pin bf16
    fwd+grads against the f32 reference of the same bf16 values at
    bf16-resolution tolerance."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    # Distinct seq per regime: identical shapes would let the second
    # regime hit the first's jit cache and silently re-test whole-K.
    # s=384 under MAX_SEQ_VMEM=128 also makes the k-blocked arm a real
    # 3-step streaming grid.
    for regime, seq_vmem, s in (("whole-K", 4096, 256),
                                ("k-blocked", 128, 384)):
        q, k, v = _rand_qkv(jax.random.key(11), b=2, s=s, h=2, d=32,
                            dtype=jnp.bfloat16)
        mask = jnp.ones((2, 1, 1, s), bool).at[:, :, :, s - 56:].set(False)
        qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))

        def loss_flash(q, k, v):
            out = fa.flash_attention(q, k, v, mask=mask)
            return jnp.sum(jnp.sin(out.astype(jnp.float32)))

        def loss_ref(q, k, v):
            out = dot_product_attention(q, k, v, mask=mask)
            return jnp.sum(jnp.sin(out.astype(jnp.float32)))

        ref = dot_product_attention(qf, kf, vf, mask=mask)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(qf, kf, vf)
        with monkeypatch.context() as mp:
            mp.setattr(fa, "MAX_SEQ_VMEM", seq_vmem)
            mp.setattr(fa, "BLOCK_Q_KB", 128)
            mp.setattr(fa, "BLOCK_K_KB", 128)
            out = fa.flash_attention(q, k, v, mask=mask)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref),
                rtol=2e-2, atol=2e-2, err_msg=regime)
            g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            for name, a, b in zip("qkv", g_fl, g_ref):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b),
                    rtol=6e-2, atol=6e-2, err_msg=f"{regime} d{name}")


@pytest.mark.parametrize("fused", [False, True])
def test_kblocked_segmented_ring_matches_reference(devices, monkeypatch,
                                                   fused):
    """Packed segments + ring + K-blocked chunk kernels: force every ring
    chunk through the streaming kernels (MAX_SEQ_VMEM→64, FLASH_CHUNK_MIN
    →0) and pin output + grads against the segment-aware reference.
    ``fused=True`` repeats the composition through the one-pass backward —
    covering the ring-merge dlse→delta folding + segments on that path."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
    from distributed_tensorflow_framework_tpu.parallel import ring

    _allow_fused(monkeypatch, fused)
    # chunk = 256/4 = 64 > MAX_SEQ_VMEM(32) → K-blocked kernels with a
    # 16-wide block grid (nq = nk = 4), segments riding along.
    monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 32)
    monkeypatch.setattr(fa, "BLOCK_Q", 16)
    monkeypatch.setattr(fa, "BLOCK_Q_KB", 16)
    monkeypatch.setattr(fa, "BLOCK_K_KB", 16)
    monkeypatch.setattr(ring, "FLASH_CHUNK_MIN", 0)
    mesh = create_mesh(MeshConfig(data=2, seq=4))
    b, s = 2, 256
    q, k, v = _rand_qkv(jax.random.key(8), b=b, s=s, h=2, d=16)
    # Packed segments crossing the shard boundary at s/2.
    seg = jnp.concatenate([
        jnp.zeros((b, 96), jnp.int32),
        jnp.ones((b, 96), jnp.int32),
        jnp.full((b, 64), 2, jnp.int32),
    ], axis=1)

    def loss_ring(q, k, v):
        out = ring.ring_attention_sharded(q, k, v, mesh=mesh,
                                          segment_ids=seg)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q, k, v):
        out = _streaming_reference(q, k, v, segment_ids=seg, block=64)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    out = jax.jit(lambda q, k, v: ring.ring_attention_sharded(
        q, k, v, mesh=mesh, segment_ids=seg))(q, k, v)
    ref = _streaming_reference(q, k, v, segment_ids=seg, block=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


@pytest.mark.slow
def test_ring_chunk_8192_kblocked(devices):
    """The closed fallback, at the size that motivated it (VERDICT r3
    item 4): a ring whose per-shard chunk is 8192 (> MAX_SEQ_VMEM) runs
    the K-blocked flash kernels — fwd AND bwd — and matches the streaming
    reference. Interpret mode on the CPU mesh."""
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh
    from distributed_tensorflow_framework_tpu.parallel import ring

    mesh = create_mesh(MeshConfig(data=4, seq=2))
    b, s, h, d = 4, 16384, 1, 8                   # chunk = 8192 per shard
    q, k, v = _rand_qkv(jax.random.key(9), b=b, s=s, h=h, d=d)

    out = jax.jit(lambda q, k, v: ring.ring_attention_sharded(
        q, k, v, mesh=mesh))(q, k, v)
    ref = _streaming_reference(q, k, v, block=512)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss(q):
        out = ring.ring_attention_sharded(q, k, v, mesh=mesh)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def loss_ref(q):
        out = _streaming_reference(q, k, v, block=512)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    gq = jax.jit(jax.grad(loss))(q)
    gq_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gq_ref),
                               rtol=2e-4, atol=2e-4)


# -- several devices: the kernel runs under a shard_map (chip bring-up) ----
# On TPU a Pallas (Mosaic) kernel under a multi-device jit does not lower
# at all; flash_attention(mesh=) splits itself across the mesh instead.
# Interpret mode takes the same wrap, so the CPU mesh pins its numerics.


def _mesh_case(devices):
    from distributed_tensorflow_framework_tpu.core.config import MeshConfig
    from distributed_tensorflow_framework_tpu.core.mesh import create_mesh

    mesh = create_mesh(MeshConfig(data=4, model=2), devices=devices)
    q, k, v = _rand_qkv(jax.random.key(7), b=8, s=128, h=4, d=16)
    lengths = jnp.array([128, 96, 128, 64, 128, 128, 32, 128])
    mask = (jnp.arange(128)[None, :] < lengths[:, None])[:, None, None, :]
    seg = (jnp.arange(128)[None, :] >= 48).astype(jnp.int32) + jnp.zeros(
        (8, 1), jnp.int32)
    return mesh, (q, k, v), mask, seg


def test_flash_attention_on_a_mesh_matches_one_device(devices):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    mesh, qkv, mask, seg = _mesh_case(devices)

    def loss(q, k, v, m):
        out = flash_attention(q, k, v, mask=mask, segment_ids=seg, mesh=m)
        return jnp.sum(jnp.sin(out))

    # Batch over data (4), heads over model (2): both splits at once.
    want = jax.value_and_grad(loss, argnums=(0, 1, 2))(*qkv, None)
    got = jax.jit(jax.value_and_grad(
        lambda q, k, v: loss(q, k, v, mesh), argnums=(0, 1, 2)))(*qkv)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_flash_attention_inside_shard_map_is_called_as_is(devices):
    """Where the axes are already manual (the explicit-collective step,
    a pipeline stage) a second wrap would be an error; mesh= is inert."""
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    mesh, (q, k, v), _, _ = _mesh_case(devices)
    spec = P(("data", "fsdp", "expert"), None, "model", None)
    mapped = jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, mesh=mesh), mesh=mesh,
        in_specs=(spec,) * 3, out_specs=spec, check_vma=False)
    np.testing.assert_allclose(
        jax.jit(mapped)(q, k, v), flash_attention(q, k, v),
        rtol=2e-5, atol=2e-5)


def test_kernel_mode_off_the_chip_is_interpret(devices):
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    assert fa.kernel_mode() == "interpret"


def test_kernel_check_matrix_follows_the_module_thresholds():
    """scripts/verify_flash_kernels.py (the smoke's kernel leg) reaches
    every regime at the shipped thresholds, and the autotune plan's
    verify trials name cases it has."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa
    from scripts import verify_flash_kernels as vfk
    from tools.autotune.plan import compile_chip_window_plan

    bf16, f32 = jnp.bfloat16, jnp.float32
    cases = vfk._cases()
    assert cases == {
        "cell_s512": (512, None, bf16), "whole_k_short": (512, False, bf16),
        "whole_k_max": (4096, False, bf16), "kblocked": (8192, False, bf16),
        "fused": (8192, True, bf16), "fused_takeover_min": (128, True, bf16),
        "fused_takeover": (2048, True, bf16),
        "fused_takeover_max": (4096, True, bf16),
        "f32_s128": (128, None, f32), "f32_s512": (512, None, f32),
        "f32_s2048": (2048, None, f32), "f32_s4096": (4096, None, f32),
        "sub_tile": (64, None, bf16), "f32_sub_tile": (64, None, f32)}
    # Off the chip the float32 cases run the fused backward and the
    # sub-tile ones the only backward that compiles for them.
    for name, (seq, _, dtype) in cases.items():
        if name.endswith("sub_tile"):
            assert fa.select_dispatch(seq, seq, dtype).backward == "two_pass"
        elif name.startswith("f32_"):
            assert fa.select_dispatch(seq, seq, dtype).backward == "fused"
    assert vfk._causal_cases() == {
        "causal_gqa_s512": (512, None, bf16),
        "causal_gqa_s8192": (8192, None, bf16)}
    assert 12 % vfk.KV_GROUP == 0
    named = {a for t in compile_chip_window_plan()
             if "scripts/verify_flash_kernels.py" in t.argv
             for a in t.argv[2:]}
    assert named and named <= set(cases)
