"""Named collective wrappers — the XLA/ICI analogue of the NCCL call sites.

The reference's gradient aggregation is NCCL all-reduce hidden inside
``SyncReplicasOptimizer`` (SURVEY.md §2 row 3 + native rows); its variable
traffic is grpc to the PS. Under SPMD both collapse into XLA collectives
emitted inside jit/shard_map and scheduled on ICI (intra-slice) or DCN
(inter-slice) by the compiler. These wrappers exist so call sites name the
intent (``allreduce_gradients``) rather than the primitive, and so the
shard_map training path reads like the reference's pipeline.

Quantized wire formats (``parallel.collective_dtype``, docs/PERFORMANCE.md):
``all_gather`` / ``reduce_scatter`` / the gradient all-reduce accept a
``wire_dtype`` — ``bfloat16`` casts the payload, ``int8`` applies the
EQuARX block-scaled protocol (parallel/quantization.py): per-block max-abs
scales ride the wire next to the int8 payload, partials are dequantized and
accumulated in f32, and the reduced result is requantized for the gather
phase. ``allreduce_gradients_ef`` adds the error-feedback residual so the
compression error is compensated on the next step rather than accumulated.
"""

from __future__ import annotations

import contextlib
import logging
from typing import Any, Iterator, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_framework_tpu.parallel.quantization import (
    DEFAULT_BLOCK_SIZE,
    SCALE_BYTES,
    dequantize_blockwise,
    quantize_blockwise,
)

log = logging.getLogger(__name__)

DATA_AXES = ("data", "fsdp")

# The tally's grand-total fields — every one must surface in the
# core/telemetry.py rollups (audited by tests/test_marker_audit.py).
TALLY_TOTAL_FIELDS = ("total_bytes", "total_logical_bytes")


def axis_size(axis_name) -> int:
    """Static mesh-axis size inside shard_map."""
    return lax.axis_size(axis_name)


def _axes_tuple(axis_names) -> tuple:
    return (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)


def _axes_size(axis_names) -> int:
    n = 1
    for a in _axes_tuple(axis_names):
        n *= axis_size(a)
    return n


def linear_axis_index(axis_names) -> jax.Array:
    """Linearized device index over an axis tuple, first axis major —
    the same ordering multi-axis collectives use to stack/route shards
    (asserted against ``all_gather(tiled=False)`` row order in
    tests/test_compressed_allreduce.py)."""
    idx = jnp.zeros((), jnp.int32)
    for a in _axes_tuple(axis_names):
        idx = idx * axis_size(a) + lax.axis_index(a)
    return idx


class CollectiveTally:
    """Per-collective call and byte counters, recorded at JAX *trace* time.

    Every wrapper below reports (kind, wire bytes, logical bytes) for each
    leaf it lowers while a tally is active. Because jit traces once per
    shape, wrap the FIRST dispatch (or an explicit lower/compile) in
    ``tally()`` and the numbers describe every subsequent step of that
    executable.

    Byte convention — per-device bytes crossing the links, with the
    topology-dependent ``(n-1)/n`` ring factor dropped:

      * all-reduce (psum/pmean): 2 × payload (reduce-scatter phase +
        all-gather phase of the ring algorithm);
      * reduce-scatter / all_to_all / ppermute: 1 × input payload;
      * all-gather: 1 × OUTPUT payload (each device receives the full
        gathered array, n × its shard).

    ``wire`` bytes are at the collective's wire dtype plus any block-scale
    overhead (parallel/quantization.py); ``logical`` bytes are the same
    traffic at the operand's logical dtype — their ratio is the wire
    compression the telemetry rollup reports.
    """

    def __init__(self) -> None:
        self.calls: dict[str, int] = {}
        self.bytes: dict[str, int] = {}          # wire bytes
        self.logical_bytes: dict[str, int] = {}

    def record(self, kind: str, nbytes: int, logical_bytes: int | None = None) -> None:
        self.calls[kind] = self.calls.get(kind, 0) + 1
        self.bytes[kind] = self.bytes.get(kind, 0) + int(nbytes)
        self.logical_bytes[kind] = self.logical_bytes.get(kind, 0) + int(
            nbytes if logical_bytes is None else logical_bytes)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes.values())

    @property
    def total_logical_bytes(self) -> int:
        return sum(self.logical_bytes.values())

    def summary(self) -> dict[str, int]:
        """Flat dict for the telemetry event's ``collectives`` field.

        ``{kind}_bytes`` is the wire traffic; ``{kind}_logical_bytes``
        appears only when a narrow wire format made it differ, so the
        uncompressed common case stays compact.
        """
        out: dict[str, int] = {}
        for kind in sorted(self.calls):
            out[f"{kind}_calls"] = self.calls[kind]
            out[f"{kind}_bytes"] = self.bytes[kind]
            if self.logical_bytes[kind] != self.bytes[kind]:
                out[f"{kind}_logical_bytes"] = self.logical_bytes[kind]
        out["total_bytes"] = self.total_bytes
        out["total_logical_bytes"] = self.total_logical_bytes
        return out


_TALLY_STACK: list[CollectiveTally] = []


@contextlib.contextmanager
def tally() -> Iterator[CollectiveTally]:
    """Collect collective byte counters from wrappers traced inside."""
    t = CollectiveTally()
    _TALLY_STACK.append(t)
    try:
        yield t
    finally:
        _TALLY_STACK.remove(t)


def _record(kind: str, leaf: Any, *, wire_dtype: Any = None,
            logical_dtype: Any = None, multiplier: int = 1,
            overhead_bytes: int = 0) -> None:
    """Tally one collective over ``leaf``.

    ``multiplier`` carries the convention factor (2 for all-reduce, the
    axis size for all-gather's output payload); ``overhead_bytes`` is the
    extra wire traffic of a block-scaled format (the f32 scales). A leaf
    with no size/dtype (python scalar etc.) is SKIPPED with a debug log —
    it lowers to a scalar fast-path, and the old silent assume-4-bytes
    fallback miscounted exactly the compressed paths this tally exists
    to A/B.
    """
    if not _TALLY_STACK:
        return
    size = getattr(leaf, "size", None)
    ldt = logical_dtype if logical_dtype is not None else getattr(leaf, "dtype", None)
    if size is None or ldt is None:
        log.debug("collective tally: skipping non-array %s operand of type %s",
                  kind, type(leaf).__name__)
        return
    logical = int(size) * jnp.dtype(ldt).itemsize * multiplier
    # Wire dtype: explicit > the leaf's own dtype (a pre-narrowed operand
    # like the bf16 gather phase) > the logical dtype.
    wdt = (wire_dtype if wire_dtype is not None
           else getattr(leaf, "dtype", ldt))
    wire = int(size) * jnp.dtype(wdt).itemsize * multiplier + overhead_bytes
    for t in _TALLY_STACK:
        t.record(kind, wire, logical)


def _canon_wire(wire_dtype: Any):
    """None/"" → None, else a jnp dtype."""
    if wire_dtype is None or wire_dtype == "":
        return None
    return jnp.dtype(wire_dtype)


def _pad_to(flat: jax.Array, multiple: int) -> jax.Array:
    pad = (-flat.size) % multiple
    return jnp.pad(flat, (0, pad)) if pad else flat


# --------------------------------------------------------- all-reduce ----
def allreduce_gradients(
    grads: Any,
    axis_names: Sequence[str] = DATA_AXES,
    *,
    compute_dtype: Any = None,
    accumulate_f32: bool = True,
    block_size: int = DEFAULT_BLOCK_SIZE,
) -> Any:
    """Mean-reduce gradients across data-parallel replicas (sync-DP core).

    ``compute_dtype`` (e.g. jnp.bfloat16) compresses the all-reduce wire
    format — the block-free core of the EQuARX idea (PAPERS.md: quantized
    all-reduce). Two accumulation modes:

    ``accumulate_f32=True`` (default): reduce-scatter the gradients at
    full precision (f32 adds), then all-gather the reduced shard in the
    narrow dtype. Wire bytes: 6/8 of an f32 ring all-reduce. Precision
    loss is dominated by ONE rounding of the final mean to the narrow
    dtype — effectively independent of replica count (the f32 adds still
    round at f32 eps, ~2^-15 below the bf16 quantum) — safe at the
    multislice/DCN scale (n≫8) this feature targets.

    ``accumulate_f32=False`` (opt-in): pure narrow-dtype pmean. Wire
    bytes: 4/8 of f32 — the maximum bf16 compression — but both the wire
    AND the reduction are narrow: each of the ~log2(n) reduction adds
    contributes bf16-level relative error, so the mean degrades with
    replica count (the bf16-vs-f32 trajectory test bounds it at n=8).

    ``compute_dtype=int8`` dispatches to the block-scaled protocol
    (:func:`allreduce_gradients_ef` without a residual): ~2/8 of f32
    wire bytes, f32 accumulation of dequantized partials. For training
    use the error-feedback variant so the block rounding is compensated.
    """
    wire = _canon_wire(compute_dtype)
    if wire == jnp.int8:
        means, _ = allreduce_gradients_ef(
            grads, None, axis_names, block_size=block_size)
        return means
    if wire is None:
        def reduce(g):
            _record("allreduce_grads_pmean", g, multiplier=2)
            return lax.pmean(g, axis_names)

        return jax.tree.map(reduce, grads)

    if not accumulate_f32 or wire.itemsize >= 4:
        def reduce(g):
            _record("allreduce_grads_pmean_narrow", g, wire_dtype=wire,
                    multiplier=2)
            return lax.pmean(g.astype(wire), axis_names).astype(g.dtype)

        return jax.tree.map(reduce, grads)

    axes = _axes_tuple(axis_names)
    n = _axes_size(axes)

    def reduce(g):
        flat = g.astype(jnp.float32).reshape(-1)
        flat = _pad_to(flat, n)
        # Exact f32 adds on the scatter; the only lossy step is the final
        # narrow-dtype representation of the already-reduced mean.
        _record("allreduce_grads_scatter_f32", flat)
        shard = lax.psum_scatter(flat, axes, scatter_dimension=0, tiled=True) / n
        narrow = shard.astype(wire)
        _record("allreduce_grads_gather_narrow", narrow, logical_dtype=jnp.float32,
                multiplier=n)
        full = lax.all_gather(narrow, axes, axis=0, tiled=True)
        return full[: g.size].astype(g.dtype).reshape(g.shape)

    return jax.tree.map(reduce, grads)


def allreduce_gradients_ef(
    grads: Any,
    residuals: Any | None,
    axis_names: Sequence[str] = DATA_AXES,
    *,
    block_size: int = DEFAULT_BLOCK_SIZE,
) -> tuple[Any, Any | None]:
    """Block-scaled int8 all-reduce-mean with error feedback.

    The EQuARX protocol per leaf, all inside one shard_map trace:

      1. compensate: ``c = g + r`` (``r`` is this device's residual);
      2. quantize ``c`` blockwise, int8 payload + f32 scales;
      3. scatter: one ``all_to_all`` routes chunk ``p`` of every device
         to device ``p`` (the reduce-scatter phase, int8 on the wire);
      4. accumulate the dequantized partials in f32, divide by n;
      5. requantize the reduced chunk, ``all_gather`` it (int8 wire);
      6. dequantize everyone's chunks — every device now holds the same
         compressed mean ``D(Q(m))``.

    The new residual carries BOTH lossy steps forward so nothing is
    silently dropped: ``r' = e1 + n·e2[own chunk]`` where ``e1`` is the
    local quantization error ``c - D(Q(c))`` and ``e2`` the chunk owner's
    requantization error ``m - D(Q(m))``. Summed over devices,
    ``mean(r') = mean(e1) + e2 = true_mean - D(Q(m))`` — exactly the
    gradient signal this step's update missed, re-injected next step.

    ``residuals=None`` disables error feedback (single-shot mean, new
    residual returned as None). Padding to a whole number of blocks per
    chunk adds zero elements whose quantization error is exactly zero.
    """
    axes = _axes_tuple(axis_names)
    n = _axes_size(axes)
    idx = linear_axis_index(axes)

    def reduce(g, r):
        flat = _pad_to(g.astype(jnp.float32).reshape(-1), n * block_size)
        if r is not None:
            flat = flat + _pad_to(r.astype(jnp.float32).reshape(-1),
                                  n * block_size)
        chunk = flat.size // n
        rows = flat.reshape(n, chunk)
        q, scales = jax.vmap(lambda v: quantize_blockwise(v, block_size))(rows)
        _record("allreduce_grads_q8_scatter", q, wire_dtype=jnp.int8,
                logical_dtype=jnp.float32,
                overhead_bytes=scales.size * SCALE_BYTES)
        # Device p receives row p of every device: all partials of chunk p.
        qx = lax.all_to_all(q, axes, split_axis=0, concat_axis=0, tiled=False)
        sx = lax.all_to_all(scales, axes, split_axis=0, concat_axis=0,
                            tiled=False)
        partials = jax.vmap(
            lambda qq, ss: dequantize_blockwise(qq, ss, block_size))(qx, sx)
        mean_chunk = partials.sum(axis=0) / n
        q2, s2 = quantize_blockwise(mean_chunk, block_size)
        _record("allreduce_grads_q8_gather", q2, wire_dtype=jnp.int8,
                logical_dtype=jnp.float32, multiplier=n,
                overhead_bytes=n * s2.size * SCALE_BYTES)
        qg = lax.all_gather(q2, axes, axis=0, tiled=False)   # row j = chunk j
        sg = lax.all_gather(s2, axes, axis=0, tiled=False)
        mean_full = jax.vmap(
            lambda qq, ss: dequantize_blockwise(qq, ss, block_size))(qg, sg)
        mean = mean_full.reshape(-1)[: g.size].astype(g.dtype).reshape(g.shape)
        if r is None:
            return mean, None
        # e1 everywhere, plus n·e2 on the chunk this device reduced (the
        # n· undoes next step's mean so e2 is re-injected at full weight).
        e1 = flat - jax.vmap(
            lambda qq, ss: dequantize_blockwise(qq, ss, block_size)
        )(q, scales).reshape(-1)
        e2 = mean_chunk - dequantize_blockwise(q2, s2, block_size)
        own = lax.dynamic_slice(e1, (idx * chunk,), (chunk,))
        new_r = lax.dynamic_update_slice(e1, own + n * e2, (idx * chunk,))
        return mean, new_r[: g.size].reshape(g.shape).astype(jnp.float32)

    if residuals is None:
        means = jax.tree.map(lambda g: reduce(g, None)[0], grads)
        return means, None
    pairs = jax.tree.map(reduce, grads, residuals)
    means = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda p: isinstance(p, tuple))
    new_res = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda p: isinstance(p, tuple))
    return means, new_res


# ------------------------------------------------------ other wrappers ----
def psum(x: Any, axis_names: Sequence[str] | str) -> Any:
    def op(v):
        _record("psum", v, multiplier=2)
        return lax.psum(v, axis_names)

    return jax.tree.map(op, x)


def pmean(x: Any, axis_names: Sequence[str] | str) -> Any:
    def op(v):
        _record("pmean", v, multiplier=2)
        return lax.pmean(v, axis_names)

    return jax.tree.map(op, x)


def all_gather(x: jax.Array, axis_name, *, axis: int = 0, tiled: bool = True,
               wire_dtype: Any = None,
               block_size: int = DEFAULT_BLOCK_SIZE,
               kind: str = "all_gather") -> jax.Array:
    """All-gather with an optional narrow wire format.

    ``bfloat16`` casts the payload (lossy for f32 operands — no error
    feedback exists for gathered values, see docs/PERFORMANCE.md);
    ``int8`` ships block-scaled int8 and dequantizes on arrival. The
    fsdp param gather (train/step.py) is the hot call site. ``kind``
    relabels the tally row for call sites that need their bytes
    attributed separately (the ZeRO update gather, parallel/zero.py).
    """
    wire = _canon_wire(wire_dtype)
    n = _axes_size(axis_name)
    if wire is None or wire == x.dtype:
        _record(kind, x, multiplier=n)
        return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    if wire != jnp.int8:
        _record(kind, x, wire_dtype=wire, multiplier=n)
        return lax.all_gather(x.astype(wire), axis_name, axis=axis,
                              tiled=tiled).astype(x.dtype)
    flat = _pad_to(x.astype(jnp.float32).reshape(-1), block_size)
    q, scales = quantize_blockwise(flat, block_size)
    _record(kind, x, wire_dtype=jnp.int8, multiplier=n,
            overhead_bytes=n * scales.size * SCALE_BYTES)
    qg = lax.all_gather(q, axis_name, axis=0, tiled=False)       # (n, padded)
    sg = lax.all_gather(scales, axis_name, axis=0, tiled=False)
    deq = jax.vmap(lambda qq, ss: dequantize_blockwise(qq, ss, block_size))(qg, sg)
    stacked = deq[:, : x.size].reshape((n,) + x.shape).astype(x.dtype)
    if not tiled:
        return jnp.moveaxis(stacked, 0, axis)
    moved = jnp.moveaxis(stacked, 0, axis)  # (..., n, shard_k, ...)
    shape = list(x.shape)
    shape[axis] = n * x.shape[axis]
    return moved.reshape(shape)


def reduce_scatter(x: jax.Array, axis_name, *, scatter_axis: int = 0,
                   wire_dtype: Any = None,
                   block_size: int = DEFAULT_BLOCK_SIZE,
                   kind: str = "reduce_scatter") -> jax.Array:
    """Reduce-scatter (sum) with an optional narrow wire format.

    The int8 path quantizes each destination's chunk independently (so
    scales travel with their chunk), routes chunks with one
    ``all_to_all``, and accumulates the dequantized partials in f32 —
    the scatter half of the EQuARX all-reduce, usable standalone for
    ZeRO-2-style scattered grad updates. ``kind`` relabels the tally row
    for call sites needing separate byte attribution (parallel/zero.py).
    """
    wire = _canon_wire(wire_dtype)
    if wire is None or wire == x.dtype:
        _record(kind, x)
        return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis,
                                tiled=True)
    if wire != jnp.int8:
        # Narrow-float wire AND accumulation (document at call sites).
        _record(kind, x, wire_dtype=wire)
        return lax.psum_scatter(
            x.astype(wire), axis_name, scatter_dimension=scatter_axis,
            tiled=True).astype(x.dtype)
    axes = _axes_tuple(axis_name)
    n = _axes_size(axes)
    if x.shape[scatter_axis] % n:
        raise ValueError(
            f"reduce_scatter axis {scatter_axis} of shape {x.shape} does "
            f"not divide the axis size {n}")
    moved = jnp.moveaxis(x.astype(jnp.float32), scatter_axis, 0)
    rows = moved.reshape(n, -1)                      # row p = chunk for dev p
    rows = jax.vmap(lambda v: _pad_to(v, block_size))(rows)
    q, scales = jax.vmap(lambda v: quantize_blockwise(v, block_size))(rows)
    _record(kind, x, wire_dtype=jnp.int8,
            overhead_bytes=scales.size * SCALE_BYTES)
    qx = lax.all_to_all(q, axes, split_axis=0, concat_axis=0, tiled=False)
    sx = lax.all_to_all(scales, axes, split_axis=0, concat_axis=0, tiled=False)
    partials = jax.vmap(
        lambda qq, ss: dequantize_blockwise(qq, ss, block_size))(qx, sx)
    chunk_elems = moved.size // n
    summed = partials.sum(axis=0)[:chunk_elems]
    shard_shape = (moved.shape[0] // n,) + moved.shape[1:]
    return jnp.moveaxis(summed.reshape(shard_shape), 0,
                        scatter_axis).astype(x.dtype)


def ppermute_shift(x: jax.Array, axis_name: str, *, shift: int = 1) -> jax.Array:
    """Ring shift: send to (i + shift) mod N — the ring-attention primitive."""
    _record("ppermute", x)
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str) -> jax.Array:
    return lax.axis_index(axis_name)


def global_norm(tree: Any) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
