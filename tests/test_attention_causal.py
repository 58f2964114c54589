"""Causal masking, grouped key/value heads and packed rows inside the
flash kernels (ops/flash_attention.py), every kernel family against
plain float32 attention, forward and backward; and the calls BERT makes
(``causal=False``, as many key/value heads as query heads) unchanged."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

S, H, HKV, D = 256, 8, 2, 32


def _case(seed, *, segmented, b=2, s=S, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, s, H, D), dtype)
    k = jax.random.normal(kk, (b, s, HKV, D), dtype)
    v = jax.random.normal(kv, (b, s, HKV, D), dtype)
    seg = None
    if segmented:
        # three documents and a padded tail per row, boundaries off the
        # block grid, the second row's elsewhere
        cuts = np.array([[70, 150, 230], [10, 140, 256]])[:b]
        pos = np.arange(s)[None, :]
        seg = (1 + (pos >= cuts[:, :1]) + (pos >= cuts[:, 1:2])).astype(
            np.int32) * (pos < cuts[:, 2:3])
        seg = jnp.asarray(seg)
    return q, k, v, seg


def reference_attention(q, k, v, seg, causal):
    """softmax(q k^T / sqrt(d)) v in float32, each key/value head
    repeated for its group of query heads, masks as (S, S) booleans."""
    g = q.shape[2] // k.shape[2]
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    allowed = jnp.ones((q.shape[0], 1, s, s), bool)
    if causal:
        allowed &= jnp.tril(jnp.ones((s, s), bool))[None, None]
    if seg is not None:
        allowed &= (seg[:, None, :, None] == seg[:, None, None, :])
    scores = jnp.where(allowed, scores, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _force(monkeypatch, family, backward):
    """Pin the kernels a call gets: the ``whole_k`` or the ``stream``
    forward, the two-pass or the fused backward (on 128-wide tiles, so
    S=256 crosses the diagonal and a block lies above it)."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "fused_bwd_enabled", lambda: backward == "fused")
    monkeypatch.setattr(fa, "BLOCK_Q_KB", 128)
    monkeypatch.setattr(fa, "BLOCK_K_KB", 128)
    if family == "stream":
        monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 0)
    return fa


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("family,backward", [
    ("whole_k", "two_pass"), ("whole_k", "fused"),
    ("stream", "two_pass"), ("stream", "fused")])
def test_causal_grouped_kernels_match_float32_attention(
        devices, monkeypatch, family, backward, segmented):
    fa = _force(monkeypatch, family, backward)
    picked = fa.select_dispatch(S, S, jnp.float32)
    assert picked.family == family and picked.backward == backward
    q, k, v, seg = _case(5, segmented=segmented)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v).astype(jnp.float32)
            if seg is not None:        # padding rows carry no loss
                out = out * (seg > 0)[:, :, None, None]
            return jnp.sum(jnp.sin(out)), out
        return f

    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, segment_ids=seg, causal=True)
    ref = lambda q, k, v: reference_attention(q, k, v, seg, True)  # noqa: E731
    (_, out), grads = jax.value_and_grad(
        loss(flash), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        loss(ref), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert grads[1].shape == k.shape and grads[2].shape == v.shape
    for name, a, b in zip("qkv", grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=f"d{name}")
    logged = [e for e in fa.dispatch_log()
              if e["causal"] and e["kv_heads"] == HKV and e["s"] == S
              and e["segmented"] == segmented]
    assert logged and all(e["heads"] == H for e in logged)


def test_a_future_key_changes_nothing(devices, monkeypatch):
    """Causality itself: changing keys and values after position t leaves
    the outputs up to t as they were (non-causal attention fails this)."""
    fa = _force(monkeypatch, "stream", "fused")
    q, k, v, _ = _case(7, segmented=False, b=1)
    t = 100
    k2 = k.at[:, t + 1:].set(k[:, t + 1:] * -3.0)
    v2 = v.at[:, t + 1:].set(v[:, t + 1:] + 5.0)
    a = fa.flash_attention(q, k, v, causal=True)
    b = fa.flash_attention(q, k2, v2, causal=True)
    np.testing.assert_array_equal(np.asarray(a[:, :t + 1]),
                                  np.asarray(b[:, :t + 1]))
    plain = fa.flash_attention(q, k2, v2)
    assert not np.allclose(np.asarray(a[:, :t + 1]),
                           np.asarray(plain[:, :t + 1]), atol=1e-3)


def test_grouped_heads_alone_match_repeated_heads(devices, monkeypatch):
    """Grouped key/value heads without a causal mask equal the same call
    with k and v repeated in memory, values and all three gradients."""
    fa = _force(monkeypatch, "stream", "fused")
    q, k, v, seg = _case(9, segmented=True)
    g = H // HKV

    def grouped(q, k, v):
        return jnp.sum(jnp.cos(fa.flash_attention(q, k, v, segment_ids=seg)))

    def repeated(q, k, v):
        return jnp.sum(jnp.cos(fa.flash_attention(
            q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
            segment_ids=seg)))

    got = jax.grad(grouped, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(repeated, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_heads_must_divide(devices):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention,
    )

    q = jnp.zeros((1, 128, 6, 32))
    kv = jnp.zeros((1, 128, 4, 32))
    with pytest.raises(ValueError, match="whole group size"):
        flash_attention(q, kv, kv, causal=True)


@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("family,backward", [
    ("whole_k", "two_pass"), ("stream", "two_pass"), ("stream", "fused")])
def test_plain_calls_trace_to_the_kernels_they_always_did(
        devices, monkeypatch, family, backward, segmented):
    """BERT's calls (``causal=False``, equal head counts) must build the
    kernels the parent built: the jaxpr of forward and backward holds no
    iota (the causal mask), no integer division (grouped heads), no
    min/max of a block index (the clamped fetch), and its ``pallas_call``s
    carry no ``causal`` in their kernel partials."""
    fa = _force(monkeypatch, family, backward)
    kq = jax.random.key(0)
    q = jax.random.normal(kq, (1, S, 2, D), jnp.float32)
    seg = (1 + (jnp.arange(S) >= 100)).astype(jnp.int32)[None] \
        if segmented else None

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, segment_ids=seg))

    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, q, q))
    assert "pallas_call" in text
    for word in ("iota", "causal"):
        assert word not in text, word
    # index arithmetic beyond the plain kernels' (a multiply for the
    # accumulator rows): none on integer scalars
    assert not re.search(r"i32\[\] = (div|min|max|rem|floor)\b", text)
