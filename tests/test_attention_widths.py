"""Value heads of another width than the query/key heads inside the flash
kernels (ops/flash_attention.py: q and k of D, v of D_v; o, dO and dV of
D_v, dQ and dK of D; the scale 1/sqrt(D)): forward and the three
cotangents of every kernel family the dispatch can pick against plain
float32 attention, the fused backward's gate over both widths, and the
calls whose heads are alike traced as before, equation for equation."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.models.lfm2 import (
    causal_attention_xla)

# 384 rows: three tiles of 128, and a length no other attention test
# logs in float32 (the dispatch log is one per process).
S, D, D_V = 384, 96, 64


def _case(seed, *, heads, kv_heads, d, d_v, segmented, s=S,
          cuts=(107, 230, 353)):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (1, s, heads, d), jnp.float32)
    k = jax.random.normal(kk, (1, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(kv, (1, s, kv_heads, d_v), jnp.float32)
    seg = None
    if segmented:
        # three documents and a padded tail, boundaries off the block grid
        pos = np.arange(s)[None, :]
        seg = jnp.asarray(((1 + (pos >= cuts[0]) + (pos >= cuts[1]))
                           * (pos < cuts[2])).astype(np.int32))
    return q, k, v, seg


def _force(monkeypatch, backward, family):
    """The backward (fused or the two-pass pair) and the forward (whole-K
    or streaming) on 128-wide tiles, so a row has blocks above the
    diagonal, on it and behind a window."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "fused_bwd_enabled", lambda: backward == "fused")
    monkeypatch.setattr(fa, "BLOCK_Q_KB", 128)
    monkeypatch.setattr(fa, "BLOCK_K_KB", 128)
    monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 0 if family == "stream" else 4096)
    return fa


def _loss(fn, seg):
    def f(q, k, v):
        out = fn(q, k, v).astype(jnp.float32)
        if seg is not None:            # padding rows carry no loss
            out = out * (seg > 0)[:, :, None, None]
        return jnp.sum(jnp.sin(out)), out
    return f


# mask -> (causal, window, segmented, heads, kv_heads)
MASKS = {"plain": (False, None, False, 4, 4),
         "causal_packed": (True, None, True, 4, 2),
         "window_packed": (True, 100, True, 4, 4)}


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("family", ["whole_k", "stream"])
@pytest.mark.parametrize("backward", ["fused", "two_pass"])
def test_narrower_value_heads_match_float32_attention(
        devices, monkeypatch, backward, family, mask):
    """q and k of 96 dims over v of 64: the output is 64 wide, and it and
    the cotangents of q, k and v are plain attention's under the scale
    1/sqrt(96), in every forward and backward the dispatch can pick, with
    and without causality, a window, segments and grouped heads."""
    causal, window, segmented, heads, kv_heads = MASKS[mask]
    fa = _force(monkeypatch, backward, family)
    picked = fa.select_dispatch(S, S, jnp.float32, D, D_V)
    assert (picked.family, picked.backward) == (family, backward)
    q, k, v, seg = _case(len(mask), heads=heads, kv_heads=kv_heads, d=D,
                         d_v=D_V, segmented=segmented)
    kw = {} if window is None else {"window": window}
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, segment_ids=seg, causal=causal, **kw)
    if causal:
        ref = lambda q, k, v: causal_attention_xla(  # noqa: E731
            q, k, v, seg, **kw)
    else:
        ref = lambda q, k, v: fa._xla_reference(  # noqa: E731
            *(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
            jnp.zeros((1, 1, S))).transpose(0, 2, 1, 3)
    (_, out), grads = jax.value_and_grad(
        _loss(flash, seg), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.value_and_grad(
            _loss(ref, seg), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.shape == (1, S, heads, D_V)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, a, b, t in zip("qkv", grads, want_grads, (q, k, v)):
        assert a.shape == t.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=f"d{name}")
    logged = [e for e in fa.dispatch_log()
              if e["head_dim"] == D and e["v_head_dim"] == D_V
              and e["causal"] == causal and e["heads"] == heads]
    assert logged


def test_the_scale_is_the_query_key_widths(devices, monkeypatch):
    """Scores are divided by sqrt(D), the width q and k share, and not
    by sqrt(D_v): with v the identity's rows the output's first row is
    the softmax itself."""
    fa = _force(monkeypatch, "fused", "stream")
    q, k, _, _ = _case(5, heads=2, kv_heads=2, d=D, d_v=D_V,
                       segmented=False)
    v = jnp.tile(jnp.eye(S, D_V)[None, :, None, :], (1, 1, 2, 1))
    out = fa.flash_attention(q, k, v, causal=True)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(D)
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(out[0, :, 0, :]),
                               np.asarray(probs[0, 0, :, :D_V]),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("s_k,d,d_v,dtype,want", [
    (8192, 64, 64, jnp.bfloat16, "fused"),       # the gate's edge, alike
    (8192, 64, 128, jnp.bfloat16, "two_pass"),   # wider values overflow it
    (2048, 192, 128, jnp.bfloat16, "fused"),
    (4096, 192, 128, jnp.bfloat16, "two_pass"),
    (16384, 192, 128, jnp.bfloat16, "two_pass"),  # the kanana cell's call
    (4096, 128, 128, jnp.bfloat16, "fused"),
    (4096, 128, None, jnp.bfloat16, "fused"),
])
def test_the_fused_backwards_gate_counts_both_widths(devices, s_k, d, d_v,
                                                     dtype, want):
    """The full-length dk and dv scratch is keys x (D + D_v) x 4 B; the
    gate holds keys x both widths x the input's bytes to what 8192 keys
    of 64 dims each and 2 bytes come to, so a call whose heads are alike
    is judged as it was."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    assert fa.select_dispatch(s_k, s_k, dtype, d, d_v).backward == want


# sha256 of the jaxpr text of jax.grad of a sum over flash_attention, on
# q/k/v of (1, 256, 8, 64) (key/value heads 2 where causal), float32,
# under _force's tiles, read on the parent of the change that made the
# value width a shape of its own, before any file changed.
PARENT_JAXPR = {
    "fused-stream-causal-packed": "627a6534b99723598d0f3d66e1748904a269acc2827de23f810b29b13bbf1b04",
    "fused-stream-causal-unpacked": "8fde917cd45107eae71057c42a7fb4c0889cc97f53c81ce5821b7f1646daf08f",
    "fused-stream-plain-packed": "bc564b3ed70b5536a3a15cc3a61ef168cc809889ac89e22a1b2836160ea25f1e",
    "fused-stream-plain-unpacked": "1f29795d87938593031efe12ebc6102c84664ddb9edaba1adc8a14bb161d774f",
    "fused-stream-window-packed": "839e659ab558c1d8b7265fa0ea5f6c32be70db165f8ac9d09c4ccbeed84d74c5",
    "fused-stream-window-unpacked": "3a8e6d76058065d29c1e6a673c125ea8731ea35aef2cebb385a5e01bbf927360",
    "fused-whole_k-causal-packed": "4c273612c37a450dd623bb3323dfeb54f5ae1b97ffe1d98de88ec7c5bb1a973e",
    "fused-whole_k-causal-unpacked": "cbefdfd19da02408d78b1a04a8b56c9345060965be1807800028a8fd0b62693a",
    "fused-whole_k-plain-packed": "f9563e92f0f22219c48f39148a153ee10b82de16d2c81d18bca43af8345aaa11",
    "fused-whole_k-plain-unpacked": "8c5d4b8eddcf98d1c6048aa96810ebe440e8072b24874e63ac1148d7c44ab315",
    "fused-whole_k-window-packed": "a2df0ac4e1edaf76a1a235e15588bff86740cbb060e46b2eb554fb0ea7a3bc07",
    "fused-whole_k-window-unpacked": "032ce9d09177cf591ccd44aeefdbc8c83e747ac0bfea332bb9d27cde2ebb9ab7",
    "two_pass-stream-causal-packed": "87a059392974d1a7b0e9d3c66f6ff40895345183d288435d2cb89929faacc8e0",
    "two_pass-stream-causal-unpacked": "f215a0b12daded6952492e4e880feeb575686007c20166fd62aa23d80897269a",
    "two_pass-stream-plain-packed": "1742672cbd9bf842996dc0a3b55380b3d057b2e8da7b1ec316e686ec34d01c59",
    "two_pass-stream-plain-unpacked": "319c2e043afece3d331a67a35e01a8b340ddf103df2ffcf4a46c35049709b457",
    "two_pass-stream-window-packed": "0f6faf84e33c9e440a2805c48a273f895c7c6e2ce1d3de42dae4a729730b56b2",
    "two_pass-stream-window-unpacked": "0643bdc7fb4a83d42c7dd0cbac72166e5412a9bfd6ebe8dfbc922ed29219dbb2",
    "two_pass-whole_k-causal-packed": "c9c9c4319ce1cf23a355e92adbcf700f7d01c2a8a16852acd6ed524799f88b3d",
    "two_pass-whole_k-causal-unpacked": "eb03955b037b7f0cb188267b92d6e1ea1e3ec2047f8fd133d189d8b21d82382d",
    "two_pass-whole_k-plain-packed": "ef1527564efecf3e0363cdbea1ae8341ff062fddfe13c674610bcc2251365f13",
    "two_pass-whole_k-plain-unpacked": "4af4a8087697cd709c0586eeb456752d0d1595aa24212b3a6dbf778b824e8ae9",
    "two_pass-whole_k-window-packed": "b53f67d3e4d16dc167d75ec9557417eea64c105dec0ed8c38364941720888be0",
    "two_pass-whole_k-window-unpacked": "a1c27d3471405613229bfa40159408204c99e814314e77d157a7d96b15365bcf",
}


@pytest.mark.parametrize("which", sorted(PARENT_JAXPR))
def test_heads_alike_trace_the_parents_kernels(devices, monkeypatch, which):
    """Value heads as wide as the query/key heads: the jaxpr of forward
    and backward (kernels, grids, index maps and scratch) is the parent's
    text, byte for byte, in every family, mask and backward."""
    backward, family, mask, packing = which.split("-")
    fa = _force(monkeypatch, backward, family)
    causal = mask != "plain"
    q, k, v, seg = _case(0, heads=8, kv_heads=2 if causal else 8, d=64,
                         d_v=64, segmented=packing == "packed", s=256,
                         cuts=(70, 150, 230))
    kw = {"window": 100} if mask == "window" else {}
    fa._flash_fwd.clear_cache()
    fa._flash_bwd.clear_cache()

    def f(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, segment_ids=seg,
                                          causal=causal, **kw))

    text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_JAXPR[which]
