"""Parameter and activation sharding rules.

Replaces ``tf.train.replica_device_setter`` (SURVEY.md §2 row 2): instead of
pinning variables to parameter-server processes, every parameter gets a
`PartitionSpec` over the canonical mesh axes:

  * **DP** (reference parity): all params replicated, batch sharded over
    ``data`` — XLA turns the grad mean into a cross-replica-sum over ICI,
    which is the SyncReplicasOptimizer+NCCL pipeline with zero user code.
  * **FSDP**: each param's largest divisible axis additionally sharded over
    ``fsdp`` (ZeRO-3-style; cf. SURVEY.md §7 hard part 5 / the
    cross-replica weight-update sharding paper in PAPERS.md).
  * **TP**: transformer kernels get megatron-style column/row splits over
    ``model`` via name-pattern rules.

Rules are name-pattern based so models don't need flax partitioning
metadata threaded through every module (they may still provide it; explicit
metadata wins).
"""

from __future__ import annotations

import re
from typing import Any, Callable

import jax
import numpy as np
# The ``with mesh:`` context the step paths enter is only readable through
# this private name on the installed JAX (0.9.0). Imported at module scope
# on purpose: a JAX that moves it fails this import loudly, where a lazy
# try/except would quietly turn every activation sharding hint off.
from jax._src.mesh import thread_resources
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Megatron-style TP rules for the transformer models: column-parallel QKV and
# MLP-in (shard output features), row-parallel attn-out and MLP-out (shard
# input features). Patterns are matched against "/".join(param path).
TP_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    # Fused projection (models/bert.py fused_qkv): kernel is (H, 3, H)
    # with q/k/v interleaved on the middle axis precisely so TP shards
    # the LAST axis — each model shard then holds its own q/k/v column
    # slice and the in-layer split is shard-local (no resharding). Rules
    # apply only at matching rank (_match_rules), so a flat (H, 3H) qkv
    # from an external model still takes the rank-2 rule below.
    (r".*qkv/kernel$", (None, None, "model")),
    (r".*(query|key|value|qkv)/kernel$", (None, "model")),
    (r".*attn_out/kernel$", ("model", None)),
    (r".*mlp_(in|up)/kernel$", (None, "model")),
    (r".*mlp_out/kernel$", ("model", None)),
    (r".*embed/embedding$", (None, "model")),
]

# MoE expert weights (models/moe.py): leading num_experts dim over the
# ``expert`` axis, hidden dims megatron-split over ``model`` (column-parallel
# wi, row-parallel wo). The gate stays replicated. Applied whenever the
# pattern matches — on an expert=1 mesh the axis is a no-op.
MOE_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    (r".*moe/wi$", ("expert", None, "model")),
    (r".*moe/wo$", ("expert", "model", None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _match_rules(
    path: str,
    shape: tuple[int, ...],
    mesh: Mesh,
    rules: list[tuple[str, tuple[str | None, ...]]],
) -> P | None:
    for pattern, spec in rules:
        if re.match(pattern, path):
            if len(spec) != len(shape):
                # Rank-mismatched rule: keep looking (e.g. the rank-3
                # fused-qkv rule must not half-apply to a rank-2 kernel
                # via zip truncation — silent TP loss).
                continue
            # Drop axes that are absent/trivial in the mesh or don't divide
            # evenly (falls back to replication on that dim, not failure).
            fixed = []
            for dim, axis in zip(shape, spec):
                if (
                    axis is not None
                    and mesh.shape.get(axis, 1) > 1
                    and dim % mesh.shape[axis] == 0
                ):
                    fixed.append(axis)
                else:
                    fixed.append(None)
            return P(*fixed)
    return None


def _apply_tp(path: str, shape: tuple[int, ...], mesh: Mesh) -> P | None:
    if mesh.shape.get("model", 1) <= 1:
        return None
    return _match_rules(path, shape, mesh, TP_RULES)


def pick_fsdp_dim(shape: tuple[int, ...], fsdp: int,
                  taken: tuple = ()) -> int:
    """Dim index to shard over fsdp, or -1 if none qualifies.

    The LARGEST still-unsharded dim divisible by ``fsdp`` wins; among
    equal-size candidates the TRAILING dim wins — matching the TP rules'
    column/row convention (kernels shard their last dim first) and, more
    importantly, DETERMINISTIC: the old first-dim tie-break depended on
    scan order alone, so a square kernel's layout could flip between a
    spec computed here and one computed by a caller iterating
    differently. ``taken`` marks already-sharded dims (per-dim axis
    entries; None = free).
    """
    axes = tuple(taken) + (None,) * (len(shape) - len(tuple(taken)))
    best, best_size = -1, 0
    for i, (dim, axis) in enumerate(zip(shape, axes)):
        if axis is None and dim and dim % fsdp == 0 and dim >= best_size:
            best, best_size = i, dim
    return best


def _apply_fsdp(spec: P | None, shape: tuple[int, ...], mesh: Mesh) -> P | None:
    fsdp = mesh.shape.get("fsdp", 1)
    if fsdp <= 1:
        return spec
    dims = spec if spec is not None else (None,) * len(shape)
    dims = tuple(dims) + (None,) * (len(shape) - len(tuple(dims)))
    best = pick_fsdp_dim(shape, fsdp, dims)
    if best < 0:
        return spec
    new = list(dims)
    new[best] = "fsdp"
    return P(*new)


def infer_param_specs(
    params: Any,
    mesh: Mesh,
    *,
    fsdp: bool | None = None,
    tensor_parallel: bool | None = None,
) -> Any:
    """PartitionSpec pytree for a param pytree under the given mesh.

    Defaults: TP rules apply iff the mesh's ``model`` axis > 1; FSDP applies
    iff the ``fsdp`` axis > 1. Anything unmatched is replicated — the
    reference-parity DP layout.
    """
    use_tp = tensor_parallel if tensor_parallel is not None else mesh.shape.get("model", 1) > 1
    use_fsdp = fsdp if fsdp is not None else mesh.shape.get("fsdp", 1) > 1

    def rule(path, leaf) -> P:
        shape = tuple(np.shape(leaf))
        p = _path_str(path)
        # Pipelined layer stacks (parallel/pipeline.py STACK_KEY): leading
        # num_layers dim over ``pipe``, nothing else — the stage shard_map
        # owns these leaves, so FSDP/TP must not touch them. Substring
        # match so optimizer-state mirrors (mu/nu/...) get the same layout.
        if "pipeline_layers" in p and len(shape) >= 1:
            return P("pipe", *([None] * (len(shape) - 1)))
        # Expert weights next: their layout is fixed by the MoE dispatch
        # regardless of whether TP is on.
        spec: P | None = _match_rules(p, shape, mesh, MOE_RULES)
        if spec is None and use_tp:
            spec = _apply_tp(p, shape, mesh)
        if use_fsdp:
            spec = _apply_fsdp(spec, shape, mesh)
        if spec is None:
            spec = P()
        return spec

    return jax.tree_util.tree_map_with_path(rule, params)


def specs_to_shardings(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_pytree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """Place a host pytree onto the mesh with the given specs."""
    shardings = specs_to_shardings(specs, mesh)
    return jax.tree.map(jax.device_put, tree, shardings)


def tree_map_specs(fn: Callable[[P], P], specs: Any) -> Any:
    return jax.tree.map(fn, specs, is_leaf=lambda x: isinstance(x, P))


def constrain_activation(x: jax.Array, *axes: Any) -> jax.Array:
    """Best-effort ``with_sharding_constraint`` for model-internal
    activations (e.g. the MoE (B, E, C, H) expert tensors, whose backward
    otherwise hits XLA SPMD "involuntary full rematerialization" — the
    partitioner can't see that the cotangents should stay expert-sharded).

    No-ops when there is no mesh context (plain CPU tests, ``init``,
    the shard_map twin — which never enters one) or when any named axis
    in the spec is absent from the context mesh, so callers can hint
    unconditionally. The jit step paths enter their mesh via
    ``with self.mesh:`` (train/step.py) to arm it.

    ``None`` in the spec means REPLICATED (with_sharding_constraint has
    no unconstrained marker for named specs) — only pin dims whose
    layout you know; a wrong ``None`` forces an all-gather.
    """
    m = thread_resources.env.physical_mesh
    if m.empty:
        return x
    names = set(m.axis_names)
    for a in axes:
        for name in (a,) if isinstance(a, str) else tuple(a or ()):
            if name not in names:
                return x
    return jax.lax.with_sharding_constraint(x, P(*axes))
