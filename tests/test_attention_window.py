"""The window inside the flash kernels (ops/flash_attention.py,
``flash_attention(..., window=W)``: a query at ``i`` sees a key at ``j``
iff ``j <= i``, same document and ``i - j < W``): forward and all
cotangents of every kernel family against plain float32 attention, the
blocks the kernels skip against the counter the model reports, the
short sequential axis of a window call (PR 37) against the full-length
one it replaced, and the calls without a window unchanged."""

import contextlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.models.lfm2 import (
    causal_attention_xla)

S = 256


def _case(seed, *, heads, kv_heads, d, segmented, b=1, s=S,
          cuts=(70, 150, 230)):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (b, s, heads, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kv_heads, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, kv_heads, d), jnp.float32)
    seg = None
    if segmented:
        # three documents and a padded tail, boundaries off the block grid
        pos = np.arange(s)[None, :]
        seg = jnp.asarray(((1 + (pos >= cuts[0]) + (pos >= cuts[1]))
                           * (pos < cuts[2])).astype(np.int32).repeat(b, 0))
    return q, k, v, seg


def _force(monkeypatch, backward, *, stream=True, tile=128, tile_k=None):
    """Pin the backward (fused or the two-pass pair) on 128-wide tiles, so
    S=256 has blocks above the diagonal, on it and behind a window; the
    streaming forward unless ``stream`` is off."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "fused_bwd_enabled", lambda: backward == "fused")
    monkeypatch.setattr(fa, "BLOCK_Q_KB", tile)
    monkeypatch.setattr(fa, "BLOCK_K_KB", tile_k or tile)
    if stream:
        monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 0)
    return fa


def _loss(fn, seg):
    def f(q, k, v):
        out = fn(q, k, v).astype(jnp.float32)
        if seg is not None:            # padding rows carry no loss
            out = out * (seg > 0)[:, :, None, None]
        return jnp.sum(jnp.sin(out)), out
    return f


@pytest.mark.parametrize("backward", ["fused", "two_pass"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (8, 2)])
@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("window", [64, 192, S + 64])
def test_window_kernels_match_float32_attention(
        devices, monkeypatch, window, segmented, heads, kv_heads, d,
        backward):
    """Output and the cotangents of q, k and v (and through them the
    bias's path) for a window of half a tile, one that is no multiple of
    a tile and one wider than the row."""
    fa = _force(monkeypatch, backward)
    picked = fa.select_dispatch(S, S, jnp.float32, d)
    assert picked.family == "stream" and picked.backward == backward
    q, k, v, seg = _case(window + d, heads=heads, kv_heads=kv_heads, d=d,
                         segmented=segmented)
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, segment_ids=seg, causal=True, window=window)
    ref = lambda q, k, v: causal_attention_xla(  # noqa: E731
        q, k, v, seg, window=window)
    (_, out), grads = jax.value_and_grad(
        _loss(flash, seg), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.value_and_grad(
            _loss(ref, seg), argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4, err_msg=f"d{name}")
    logged = [e for e in fa.dispatch_log()
              if e["window"] == window and e["head_dim"] == d
              and e["heads"] == heads and e["segmented"] == segmented]
    assert logged and all(e["causal"] and e["kv_heads"] == kv_heads
                          for e in logged)


def test_the_whole_k_forward_masks_the_window_too(devices, monkeypatch):
    """Rows short enough for the whole-K forward: no block to skip, the
    window masked from indices."""
    fa = _force(monkeypatch, "fused", stream=False)
    assert fa.select_dispatch(S, S, jnp.float32, 64).family == "whole_k"
    q, k, v, seg = _case(3, heads=8, kv_heads=2, d=64, segmented=True)
    out = fa.flash_attention(q, k, v, segment_ids=seg, causal=True,
                             window=100)
    with jax.default_matmul_precision("highest"):
        want = causal_attention_xla(q, k, v, seg, window=100)
    real = np.asarray(seg > 0)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out) * real,
                               np.asarray(want) * real, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backward", ["fused", "two_pass"])
@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
def test_a_window_as_wide_as_the_row_is_causal_bit_for_bit(
        devices, monkeypatch, segmented, backward):
    fa = _force(monkeypatch, backward)
    q, k, v, seg = _case(11, heads=8, kv_heads=2, d=64, segmented=segmented)

    def run(**window):
        flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
            q, k, v, segment_ids=seg, causal=True, **window)
        (_, out), grads = jax.value_and_grad(
            _loss(flash, seg), argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    for a, b in zip(run(window=S), run()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("call", ["bert", "lfm2"])
def test_no_window_traces_the_program_it_always_did(devices, monkeypatch,
                                                    call):
    """``window=None`` is the call without the keyword, equation for
    equation, for a BERT call (no mask of positions, equal head counts)
    and an LFM2 call (causal, grouped heads, packed): the jaxpr of forward
    and backward is the same text and names no window; a window changes
    it."""
    fa = _force(monkeypatch, "fused", stream=call == "lfm2")
    causal = call == "lfm2"
    q, k, v, seg = _case(1, heads=8, kv_heads=2 if causal else 8, d=64,
                         segmented=True)

    def text(**kw):
        def f(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, segment_ids=seg,
                                              causal=causal, **kw))
        return str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v))

    plain = text()
    assert text(window=None) == plain
    assert "pallas_call" in plain and "window" not in plain
    if causal:
        assert text(window=64) != plain
    else:
        with pytest.raises(ValueError, match="causal"):
            text(window=64)


def test_the_decoder_without_a_window_passes_no_keyword(devices, monkeypatch):
    """The LFM2 model's attention (``GroupedQueryAttention`` with every
    new setting at its default) calls ``flash_attention`` as it always
    did: no ``window`` keyword reaches it."""
    from distributed_tensorflow_framework_tpu.models import lfm2
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    seen = []

    def spy(q, k, v, **kw):
        seen.append(sorted(kw))
        return q

    monkeypatch.setattr(fa, "flash_attention", spy)
    x = jnp.zeros((1, 128, 64))
    seg = jnp.ones((1, 128), jnp.int32)
    pos = jnp.arange(128)[None]
    for window in (None, 32):
        layer = lfm2.GroupedQueryAttention(4, 2, attention_impl="pallas",
                                           dtype=jnp.float32, window=window)
        layer.init(jax.random.key(0), x, seg, pos)
    assert seen == [["causal", "mesh", "segment_ids"],
                    ["causal", "mesh", "segment_ids", "window"]]


def _needed_pairs(s, bq, bk, window):
    """(qi, ki) of every block that holds a pair inside causal + window,
    from the (S, S) mask itself."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    allowed = (j <= i) & (i - j < window)
    return {(qi, ki) for qi in range(s // bq) for ki in range(s // bk)
            if allowed[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk].any()}


@pytest.mark.parametrize("s,bq,bk,window", [
    (1024, 128, 256, 192), (1024, 256, 128, 300), (2048, 512, 1024, 512),
    (1024, 128, 128, 1), (1024, 128, 128, 4096)])
def test_the_visited_blocks_are_the_needed_ones_and_the_counter_counts_them(
        devices, s, bq, bk, window):
    """``_block_needed`` (what the kernels run), the index maps (what the
    pipeline fetches for a skipped visit) and ``window_block_counts``
    (what the model reports) against the mask itself."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    want = _needed_pairs(s, bq, bk, window)
    grid = list(itertools.product(range(s // bq), range(s // bk)))
    visited = {(qi, ki) for qi, ki in grid
               if bool(fa._block_needed(qi, ki, bq, bk, window=window))}
    assert visited == want
    causal = {(qi, ki) for qi, ki in grid
              if bool(fa._block_needed(qi, ki, bq, bk))}
    assert fa.window_block_counts(s, s, bq, bk, window) == (
        len(want), len(causal))
    # the full-length k-axis the fused backward keeps under a window
    k_blk = fa._last_k_block(True, bq, bk, window)
    for qi, ki in grid:
        fetched_k = int(k_blk(qi, ki))
        if (qi, ki) in want:           # a visit fetches its own block
            assert fetched_k == ki
        else:                          # a skipped one, a block it needs
            assert (qi, fetched_k) in want


def _walk_axis(axis, n_parallel, n_blocks):
    """Every program of a window call's sequential axis (``fa._k_axis``
    or ``fa._q_axis``'s triple), as the kernel and the index map see it:
    ``{(p, r): (block it stands for, reached, block fetched)}``, with
    every fetched index inside ``range(n_blocks)``."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    length, reach, fetch = axis
    seen = {}
    for p, r in itertools.product(range(n_parallel), range(length)):
        block, reached = fa._visit(reach, p, r)
        fetched = int(fetch(p, r))
        assert 0 <= fetched < n_blocks
        seen[p, r] = (block, bool(reached), fetched)
    return seen


@pytest.mark.parametrize("s,bq,bk,window,k_axis,q_axis", [
    (16384, 512, 1024, 4096, 5, 10),   # smallthinker_s16384's window calls
    (16384, 512, 1024, 512, 2, 3),     # laguna_s_s16384's
    (16384, 512, 1024, 16384, 16, 32),  # as wide as the row: causal's grid
    (1024, 128, 256, 192, 2, 4), (1024, 256, 128, 300, 5, 3),
    (2048, 512, 1024, 512, 2, 3), (1024, 128, 128, 1, 1, 1),
    (1024, 128, 128, 128, 2, 2), (1024, 128, 128, 4096, 8, 8)])
def test_a_window_calls_axis_holds_each_needed_block_once(
        devices, s, bq, bk, window, k_axis, q_axis):
    """Brute force over every ``(qi, kr)`` of the forward and dq kernels'
    grid and every ``(ki, qr)`` of the dk/dv kernel's: the programs that
    compute are the needed blocks, each exactly once and in rising
    order, each fetching its own block; a program past the reach
    computes nothing and fetches the last block its neighbour needed;
    no index leaves its range; the axis is as long as the widest reach
    and no longer."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    want = _needed_pairs(s, bq, bk, window)
    n_q, n_k = s // bq, s // bk
    for axis, n_par, n_blocks, pair, length in (
            (fa._k_axis(True, bq, bk, window, n_q, n_k), n_q, n_k,
             lambda p, blk: (p, blk), k_axis),
            (fa._q_axis(True, bq, bk, window, n_q, n_k), n_k, n_q,
             lambda p, blk: (blk, p), q_axis)):
        assert axis[0] == length
        seen = _walk_axis(axis, n_par, n_blocks)
        computed = [(p, block) for (p, r), (block, reached, _)
                    in sorted(seen.items())
                    if reached and bool(fa._block_needed(
                        *pair(p, block), bq, bk, window=window))]
        assert len(computed) == len(set(computed))      # once each
        assert {pair(p, blk) for p, blk in computed} == want
        assert computed == sorted(computed)             # in rising order
        widest = 0
        for p in range(n_par):
            mine = [seen[p, r] for r in range(length)]
            reach = [blk for blk, reached, _ in mine if reached]
            widest = max(widest, len(reach))
            for blk, reached, fetched in mine:
                assert fetched == (blk if reached else reach[-1])
        assert widest == length


@pytest.mark.parametrize("window,visited,launched", [
    (4096, 420, 480), (512, 141, 176), (16384, 816, 1536)])
def test_the_cells_grid_counts(devices, window, visited, launched):
    """ISSUE 37's numbers at 16,384 keys on 512 x 1024 tiles over the
    forward, dq and dk/dv kernels: 32*5 + 32*5 + 16*10 programs a head
    and row at a window of 4096 where there were 3 * 512, 32*2 + 32*2 +
    16*3 at 512; a window as wide as the row launches causal's grid."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    tile = fa.select_dispatch(16384, 16384, jnp.bfloat16, 128)
    grid = fa.window_grid(16384, 16384, window, tile)
    assert (grid["visited"], grid["launched"]) == (visited, launched)
    assert 3 * fa.window_block_counts(16384, 16384, 512, 1024, window)[0] \
        == visited


def test_the_grid_count_follows_the_dispatch(devices, monkeypatch):
    """Where the fused backward runs (its scratch keeps every key block
    on the axis) and under the whole-K forward (a program a row block)
    the count is of those kernels' grids, not of the two-pass pair's."""
    fa = _force(monkeypatch, "fused")
    tile = fa.select_dispatch(512, 512, jnp.float32, 64)
    assert (tile.family, tile.backward) == ("stream", "fused")
    visited = fa.window_block_counts(512, 512, 128, 128, 128)[0]
    assert fa.window_grid(512, 512, 128, tile) == dict(
        k_axis=4, q_axis=None, visited=2 * visited, launched=4 * 2 + 16)
    monkeypatch.setattr(fa, "fused_bwd_enabled", lambda: False)
    monkeypatch.setattr(fa, "MAX_SEQ_VMEM", 4096)
    tile = fa.select_dispatch(512, 512, jnp.float32, 64)
    assert (tile.family, tile.block_q, tile.backward) == (
        "whole_k", 128, "two_pass")
    assert fa.window_grid(512, 512, 128, tile) == dict(
        k_axis=2, q_axis=2, visited=4 + 2 * visited, launched=4 + 8 + 8)


def _old_first_q_block(causal, block_q, block_k, window=None, n_q=0):
    """``_first_q_block`` as the kernels had it before PR 37."""
    if not causal:
        return lambda ki, qi: qi
    if window is None:
        return lambda ki, qi: jnp.maximum(qi, (ki * block_k) // block_q)
    return lambda ki, qi: jnp.clip(
        qi, (ki * block_k) // block_q,
        jnp.minimum((ki * block_k + (block_k - 1) + (window - 1)) // block_q,
                    n_q - 1))


@contextlib.contextmanager
def _full_length_axes(fa):
    """The streaming kernels on the grid they had before PR 37: every
    key (row) block a program, the index maps in their old forms, the
    kernels counting from ``pl.program_id(3)``. Yields the axis lengths
    it handed out; the jitted wrappers forget their programs on both
    sides, since the axes are no part of their keys."""
    handed = []

    def k_axis(causal, bq, bk, window, n_q, n_k):
        handed.append(n_k)    # the map the fused backward still takes
        return n_k, None, fa._last_k_block(causal, bq, bk, window)

    def q_axis(causal, bq, bk, window, n_q, n_k):
        handed.append(n_q)
        return n_q, None, _old_first_q_block(causal, bq, bk, window, n_q)

    def forget():
        fa._flash_fwd.clear_cache()
        fa._flash_bwd.clear_cache()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "_k_axis", k_axis)
        patch.setattr(fa, "_q_axis", q_axis)
        forget()
        try:
            yield handed
        finally:
            forget()


# rows of 1024 on 128 x 256 tiles (a key tile of two row tiles, as the
# cells' 512 x 1024): a window narrower than a key tile, one key tile,
# one that spans several and is no multiple of either, the row itself
@pytest.mark.parametrize("heads", [7, 9])
@pytest.mark.parametrize("segmented", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("window", [100, 256, 400, 1024])
def test_the_short_axis_computes_what_the_full_one_did_bit_for_bit(
        devices, monkeypatch, window, segmented, heads):
    """Forward, dq, dk and dv of a window call on the grid that holds
    only the blocks its window reaches equal the same kernels' on the
    full-length grid exactly: every visited block computes what it did,
    in the same order. One key/value head under 7 and 9 query heads (the
    cells' groups)."""
    s = 1024
    fa = _force(monkeypatch, "two_pass", tile=128, tile_k=256)
    tile = fa.select_dispatch(s, s, jnp.float32, 64)
    assert (tile.family, tile.backward, tile.block_q, tile.block_k) == (
        "stream", "two_pass", 128, 256)
    q, k, v, seg = _case(window + heads, heads=heads, kv_heads=1, d=64,
                         segmented=segmented, s=s, cuts=(270, 600, 950))

    def run():
        flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
            q, k, v, segment_ids=seg, causal=True, window=window)
        (_, out), grads = jax.value_and_grad(
            _loss(flash, seg), argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out, *grads)

    grid = fa.window_grid(s, s, window, tile)
    with _full_length_axes(fa) as handed:
        want = run()
    assert sorted(handed) == [4, 4, 8]       # forward, dq; dk/dv
    got = run()
    assert (grid["k_axis"] < 4 and grid["q_axis"] < 8) == (window < s)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    logged = [e for e in fa.dispatch_log()
              if e["window"] == window and e["heads"] == heads
              and e["s"] == s and e["segmented"] == segmented]
    assert logged and all(
        (e["k_axis"], e["q_axis"]) == (grid["k_axis"], grid["q_axis"])
        for e in logged)


def _pallas_calls(jaxpr):
    """``(grid, index maps' text, kernel's text)`` of every pallas_call
    under ``jaxpr``, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            mapping = eqn.params["grid_mapping"]
            found.append((mapping.grid,
                          [str(b.index_map_jaxpr)
                           for b in mapping.block_mappings],
                          str(eqn.params["jaxpr"])))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


@pytest.mark.parametrize("call", ["bert", "lfm2"])
@pytest.mark.parametrize("backward", ["fused", "two_pass"])
def test_no_window_builds_the_grids_and_index_maps_it_did(
        devices, monkeypatch, call, backward):
    """A call without a window under the helpers' new forms and under
    their old ones: the same jaxpr text, the same grid tuples, the same
    traced index maps and kernels, for the streaming forward with either
    backward; a window shortens the two-pass kernels' grids and leaves
    the fused backward's."""
    fa = _force(monkeypatch, backward)
    causal = call == "lfm2"
    q, k, v, seg = _case(1, heads=8, kv_heads=2 if causal else 8, d=64,
                         segmented=True, s=512)

    def trace(**kw):
        def f(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, segment_ids=seg,
                                              causal=causal, **kw))
        fa._flash_fwd.clear_cache()
        fa._flash_bwd.clear_cache()
        closed = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
        return str(closed), _pallas_calls(closed.jaxpr)

    text, calls = trace()
    with _full_length_axes(fa) as handed:
        old_text, old_calls = trace()
    assert handed
    assert text == old_text and calls == old_calls
    assert [c[0] for c in calls] == [(1, 8, 4, 4)] * len(calls)
    assert len(calls) == (2 if backward == "fused" else 3)
    if causal:
        grids = [c[0] for c in trace(window=100)[1]]
        assert grids == ([(1, 8, 4, 2), (1, 8, 4, 4)] if backward == "fused"
                         else [(1, 8, 4, 2)] * 3)


def test_the_cells_static_count(devices):
    """ISSUE 30's number: 140 of 272 causal visits on 512 x 1024 tiles at
    16,384 keys with a window of 4096."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    tile = fa.select_dispatch(16384, 16384, jnp.bfloat16, 128)
    assert (tile.family, tile.bwd_block_q, tile.bwd_block_k) == (
        "stream", 512, 1024)
    assert fa.window_block_counts(16384, 16384, 512, 1024, 4096) == (140, 272)


def test_a_document_boundary_inside_the_window_is_respected(devices,
                                                            monkeypatch):
    """Keys of the previous document lie inside the window of the next
    one's first queries; changing them changes nothing there. Keys past
    the window change nothing either, and one inside it does."""
    fa = _force(monkeypatch, "fused")
    q, k, v, seg = _case(7, heads=4, kv_heads=4, d=64, segmented=True)
    window = 96
    attend = lambda k, v: fa.flash_attention(  # noqa: E731
        q, k, v, segment_ids=seg, causal=True, window=window)
    base = attend(k, v)
    # document 2 is rows 70..149: its first queries' windows reach back
    # into document 1
    other = attend(k.at[:, :70].multiply(-3.0), v.at[:, :70].add(5.0))
    np.testing.assert_array_equal(np.asarray(base[:, 70:]),
                                  np.asarray(other[:, 70:]))
    # document 3 is rows 150..229: row 229 sees keys 134..229 of which
    # 150..229 are its own; key 150 is inside, and the same key is past
    # the window of nothing else in the document but itself at W=60
    nudged = attend(k, v.at[:, 150].add(5.0))
    assert not np.allclose(np.asarray(base[:, 229]), np.asarray(nudged[:, 229]))
    narrow = lambda v: fa.flash_attention(  # noqa: E731
        q, k, v, segment_ids=seg, causal=True, window=60)
    np.testing.assert_array_equal(
        np.asarray(narrow(v)[:, 210:230]),
        np.asarray(narrow(v.at[:, 150].add(5.0))[:, 210:230]))


def test_window_needs_causal_and_a_key(devices):
    from distributed_tensorflow_framework_tpu.ops.flash_attention import (
        flash_attention)

    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=16)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, causal=True, window=0)


@pytest.mark.parametrize("s_k,head_dim,dtype,want", [
    (8192, 64, jnp.bfloat16, "fused"),       # every cell before PR 30
    (4096, 64, jnp.float32, "fused"),
    (8192, 64, jnp.float32, "two_pass"),
    (4096, 128, jnp.bfloat16, "fused"),      # 128-wide heads: half the keys
    (8192, 128, jnp.bfloat16, "two_pass"),
    (16384, 128, jnp.bfloat16, "two_pass"),  # smallthinker_s16384's calls
    (16384, 64, jnp.bfloat16, "two_pass")])
def test_the_fused_backwards_gate_counts_its_scratch(devices, s_k, head_dim,
                                                     dtype, want):
    """Keys x head dims x input bytes against ``FUSED_BWD_MAX`` keys of 64
    dims and 2 bytes: the full-length dk/dv scratch (keys x head dims x
    4 B x 2) doubles with the head size, so 128-wide heads stop at half
    the keys."""
    from distributed_tensorflow_framework_tpu.ops import flash_attention as fa

    assert fa.select_dispatch(s_k, s_k, dtype, head_dim).backward == want
    if head_dim == fa.FUSED_BWD_HEAD_DIM:     # the default is the old rule
        assert fa.select_dispatch(s_k, s_k, dtype).backward == want
