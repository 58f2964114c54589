"""Cross-topology checkpoint restore — make checkpoints mesh-independent.

PR 5 proved checkpoints move freely across pipeline SCHEDULES; this module
makes them move across mesh TOPOLOGIES (ROADMAP item 4, TF-Replicator's
researcher-facing elasticity): a state saved under ``{data:8}`` restores
onto ``{data:4}`` or ``{fsdp:2, pipe:4}`` — the "survive losing a slice"
half of the resilience ladder (docs/RESILIENCE.md).

How a reshard actually happens: orbax's ``StandardRestore`` already loads
into whatever shardings the restore *template* carries, and the trainer
builds its template with ``StepBuilder.init_state`` — partition specs
re-derived by ``parallel/sharding.infer_param_specs`` against the CURRENT
mesh. So the mechanical scatter/gather is host-side respecification the
storage layer performs for free; what was missing, and what this module
owns, is the *contract* around it:

  * ``state_topology`` — the mesh descriptor (ordered axis sizes, device
    and process counts, a sha256 digest of every leaf's partition spec)
    the CheckpointManager records in the manifest commit record at save;
  * ``check_restore_topology`` — the restore-time gate: same axes →
    normal restore; different axes with ``checkpoint.allow_reshard`` off
    → a typed :class:`MeshTopologyError` naming saved vs requested mesh
    and the knob (instead of an opaque orbax sharding failure); with the
    knob on → a reshard plan the manager executes and telemeters
    (``ckpt_resharded``). Legacy manifests without a topology record
    restore with a one-line warning — pre-elastic stores must not brick;
  * ``validate_restored`` — leaf-by-leaf GLOBAL-shape validation after a
    cross-mesh load: resharding redistributes bytes, it must never
    reshape them.

Nothing here touches the PR-2 integrity contract (verify/quarantine/
fallback run before any topology check sees the step) or the PR-3 async
save path (the topology record is computed from the live sharded state
BEFORE the device→host snapshot, then rides the ordinary manifest commit).

The DATA plane has a parallel gate: data/shard.py writes a data-state
record (``DATA_RECORD_KEY``) into the same manifest commit record, and
its ``check_restore_data`` plays for the sample stream the role
``check_restore_topology`` plays for the parameter state — same-count →
resume, refit → repartition plan or a typed refusal.
"""

from __future__ import annotations

import hashlib
import logging
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from distributed_tensorflow_framework_tpu.core.mesh import MESH_AXES
from distributed_tensorflow_framework_tpu.parallel.sharding import (
    infer_param_specs,
)

log = logging.getLogger(__name__)

# Manifest commit-record field carrying the saver's topology (manifest.py
# ``extra``): absent in legacy manifests, which restore with a warning.
MESH_RECORD_KEY = "mesh"


class MeshTopologyError(ValueError):
    """Restore refused: the checkpoint was saved under a different mesh.

    Raised instead of letting orbax fail deep inside ``StandardRestore``
    with a sharding/layout error that names neither mesh. Carries both
    descriptors and names the knob (``checkpoint.allow_reshard``) that
    turns the refusal into a reshard. ``hint`` lets a caller that holds
    a more specific knob append its own one-liner — the serving export
    path names ``serve.allow_reshard`` (serve/export.py), since telling
    an inference operator to flip a checkpoint.* training knob sends
    them to the wrong config block.
    """

    def __init__(self, saved_axes: dict, requested_axes: dict, *,
                 directory: str, step: int, hint: str | None = None):
        self.saved_axes = dict(saved_axes)
        self.requested_axes = dict(requested_axes)
        self.directory = directory
        self.step = step
        self.hint = hint
        super().__init__(
            f"Checkpoint at step {step} in {directory} was saved under "
            f"mesh {describe_axes(saved_axes)} but the run is configured "
            f"for mesh {describe_axes(requested_axes)}. Set "
            f"checkpoint.allow_reshard=true to reshard the state onto the "
            f"new mesh (partition specs are re-derived against it), or "
            f"restore on matching hardware. docs/RESILIENCE.md 'losing a "
            f"slice' covers the elastic-supervisor path that does this "
            f"automatically." + (f" {hint}" if hint else "")
        )


def describe_axes(axes: dict) -> str:
    """Compact human form: {'data': 8, 'fsdp': 1, ...} -> ``{data:8}``."""
    parts = [f"{a}:{int(axes[a])}" for a in MESH_AXES
             if a in axes and int(axes[a]) != 1]
    parts += [f"{a}:{int(v)}" for a, v in axes.items()
              if a not in MESH_AXES and int(v) != 1]
    return "{" + ", ".join(parts) + "}" if parts else "{1 device}"


def normalize_axes(axes: dict) -> dict[str, int]:
    """Canonical ordered axis dict, missing axes filled with 1 — so a
    record written before a new axis name existed still compares equal to
    a mesh where that axis has size 1."""
    out = {a: int(axes.get(a, 1)) for a in MESH_AXES}
    for a, v in axes.items():
        if a not in MESH_AXES:
            out[a] = int(v)
    return out


def axes_equal(a: dict | None, b: dict | None) -> bool:
    if a is None or b is None:
        return False
    return normalize_axes(a) == normalize_axes(b)


def state_mesh(state: Any) -> Mesh | None:
    """The mesh the state's arrays live on (first NamedSharding leaf)."""
    for leaf in jax.tree.leaves(state):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return sharding.mesh
    return None


def spec_digest(state: Any) -> str:
    """sha256 over every leaf's (tree path, partition spec) — a compact
    fingerprint of the full sharding layout. Same axes + same digest means
    the restore is layout-identical; same axes + different digest (e.g.
    ``train.shard_opt_state`` toggled) still restores — orbax respecifies
    within a mesh — so the digest is recorded for forensics, not gated on.
    """
    h = hashlib.sha256()
    leaves, _ = jax.tree_util.tree_flatten_with_path(state)
    for path, leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        spec = getattr(sharding, "spec", None)
        h.update(f"{jax.tree_util.keystr(path)}={spec}\n".encode())
    return h.hexdigest()


def opt_layout_digest(opt_state: Any) -> str:
    """sha256 over every optimizer-slot leaf's (tree path, rank) — which
    slot LAYOUT the state uses, independent of the mesh's size: slots
    shaped like their params (replicated / jit-sharded), ZeRO-stacked
    ``(n, chunk)`` rows (every slot rank 2), or per-bucket states
    (precision.fused_update — another tree). A reshard keeps it (8 rows
    refold to 4); toggling the layout across a resume changes it, which
    the restore refuses by name before Orbax sees the template."""
    h = hashlib.sha256()
    leaves, _ = jax.tree_util.tree_flatten_with_path(opt_state)
    for path, leaf in leaves:
        h.update(f"{jax.tree_util.keystr(path)}:"
                 f"{len(getattr(leaf, 'shape', ()))}\n".encode())
    return h.hexdigest()


def state_topology(state: Any, *, mesh: Mesh | None = None,
                   process_count: int | None = None) -> dict | None:
    """The manifest topology record for a (sharded) state, or None when
    no leaf carries a NamedSharding (nothing meaningful to record)."""
    mesh = mesh if mesh is not None else state_mesh(state)
    if mesh is None:
        return None
    return {
        "axes": {a: int(s) for a, s in mesh.shape.items()},
        "device_count": int(mesh.devices.size),
        "process_count": int(
            jax.process_count() if process_count is None else process_count),
        "spec_digest": spec_digest(state),
        "opt_layout": opt_layout_digest(state.opt_state),
    }


def plan_reshard(saved: dict, template: Any, *, step: int) -> dict:
    """The reshard plan/record for telemetry: saved vs target axes, leaf
    count, target spec digest, and how many param leaves the target
    template agrees with a fresh ``infer_param_specs`` derivation on (an
    informational cross-check that the template really is the canonical
    sharding for the new mesh — spmd-mode templates that intentionally
    deviate, e.g. shard_map's all-replicated specs, just score low)."""
    mesh = state_mesh(template)
    target = state_topology(template, mesh=mesh) or {}
    match = total = 0
    if mesh is not None:
        derived = infer_param_specs(template.params, mesh)
        spec_leaves = jax.tree.leaves(
            derived, is_leaf=lambda x: isinstance(x, PartitionSpec))
        for spec, leaf in zip(spec_leaves, jax.tree.leaves(template.params)):
            total += 1
            sharding = getattr(leaf, "sharding", None)
            if isinstance(sharding, NamedSharding) and sharding.spec == spec:
                match += 1
    return {
        "step": int(step),
        "from_axes": dict(saved.get("axes") or {}),
        "to_axes": dict(target.get("axes") or {}),
        "from_spec_digest": saved.get("spec_digest"),
        "to_spec_digest": target.get("spec_digest"),
        "leaf_count": len(jax.tree.leaves(template)),
        "respec_agreement": f"{match}/{total}",
    }


def check_restore_topology(saved: dict | None, template: Any, *,
                           allow_reshard: bool, directory: str,
                           step: int) -> dict | None:
    """The restore-time topology gate.

    Returns None for a same-mesh (or legacy, unrecorded) restore, a
    reshard plan dict when the meshes differ and ``allow_reshard`` is on,
    and raises :class:`MeshTopologyError` when they differ with the knob
    off.
    """
    if not saved or not saved.get("axes"):
        log.warning(
            "checkpoint step %d in %s has no mesh topology record (saved "
            "before the elastic layer) — restoring without a topology "
            "check", step, directory,
        )
        return None
    target = state_topology(template)
    if target is None or axes_equal(saved["axes"], target["axes"]):
        if target is not None and \
                saved.get("spec_digest") not in (None, target["spec_digest"]):
            log.info(
                "checkpoint step %d: same mesh, different partition-spec "
                "digest (sharding knobs changed) — orbax respecifies "
                "within the mesh", step,
            )
        return None
    if not allow_reshard:
        raise MeshTopologyError(
            saved["axes"], target["axes"], directory=directory, step=step)
    plan = plan_reshard(saved, template, step=step)
    log.warning(
        "resharding checkpoint step %d: %s -> %s (%d leaves, "
        "respec agreement %s)", step,
        describe_axes(plan["from_axes"]), describe_axes(plan["to_axes"]),
        plan["leaf_count"], plan["respec_agreement"],
    )
    return plan


def fold_residual(tree: Any, n_new: int) -> Any:
    """Fold a stored ``(n_old, *shape)`` error-feedback residual
    (train/state.TrainState.collective_residual) onto ``n_new`` replica
    rows, preserving each leaf's column sum Σ_i r_i — the quantity error
    feedback owes the optimizer (parallel/collectives.py): the mean
    gradient trajectory is unchanged by HOW the total residual is
    distributed over replicas, only by losing part of it.

    Even shrinks (``n_old % n_new == 0``) sum ``k = n_old/n_new``
    consecutive rows per new row; any other topology change collapses the
    total into row 0 and restarts the remaining replicas from a zero
    residual.
    """

    def fold(leaf):
        n_old = leaf.shape[0]
        if n_old == n_new:
            return leaf
        if n_old % n_new == 0:
            k = n_old // n_new
            return leaf.reshape((n_new, k) + leaf.shape[1:]).sum(axis=1)
        total = jnp.sum(leaf, axis=0, keepdims=True)
        pad = jnp.zeros((n_new - 1,) + leaf.shape[1:], leaf.dtype)
        return jnp.concatenate([total, pad], axis=0)

    return jax.tree.map(fold, tree)


def refold_zero_opt_state(stored: Any, params: Any, n_new: int) -> Any:
    """Re-chunk ZeRO-stacked optimizer slots for a new replica count.

    The shard_map ZeRO path (parallel/zero.py) stores each slot as
    ``(n_old, ceil(size/n_old))`` — flattened param values zero-padded to
    the row grid. A cross-mesh restore must re-grid to
    ``(n_new, ceil(size/n_new))``: flatten, TRUNCATE to the true element
    count (dropping the old grid's padding), re-pad for the new grid.
    The padding is provably inert — padded grad AND param positions are
    exactly zero, so every optax rule we allow under ZeRO produces a
    zero update there (rmsprop's ``initial_scale=1.0`` slot refolds to 0
    in pad cells, which only affects those same zero-update cells).

    ``params`` pairs slots to their true sizes via
    :func:`parallel.zero.map_slots`; non-mirroring leaves (optax step
    counters) pass through untouched.
    """
    from distributed_tensorflow_framework_tpu.parallel import zero

    def refold(slot, param):
        if param is None or getattr(slot, "ndim", 0) != 2:
            return slot
        size = int(math.prod(param.shape)) if param.shape else 1
        chunk = -(-size // n_new)
        if tuple(slot.shape) == (n_new, chunk):
            return slot
        flat = jnp.asarray(slot).reshape(-1)[:size]
        pad = n_new * chunk - size
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(n_new, chunk)

    return zero.map_slots(refold, stored, params)


def validate_restored(template: Any, restored: Any, *, step: int) -> int:
    """Leaf-by-leaf global-shape validation after a cross-mesh restore.

    Resharding moves bytes between devices; the GLOBAL array a leaf
    represents must be identical. Any shape/dtype drift here means the
    checkpoint does not actually hold this model's state — fail with the
    offending paths named instead of letting a reshaped leaf poison the
    run. Returns the validated leaf count.
    """
    t_leaves, t_def = jax.tree_util.tree_flatten_with_path(template)
    r_leaves, r_def = jax.tree_util.tree_flatten_with_path(restored)
    if t_def != r_def:
        raise ValueError(
            f"resharded restore at step {step} returned a different tree "
            f"structure than the template: {t_def} vs {r_def}"
        )
    errors = []
    for (path, t), (_, r) in zip(t_leaves, r_leaves):
        t_shape = getattr(t, "shape", None)
        r_shape = getattr(r, "shape", None)
        if t_shape != r_shape:
            errors.append(
                f"{jax.tree_util.keystr(path)}: template {t_shape} vs "
                f"restored {r_shape}"
            )
        elif getattr(t, "dtype", None) != getattr(r, "dtype", None):
            errors.append(
                f"{jax.tree_util.keystr(path)}: template dtype "
                f"{getattr(t, 'dtype', None)} vs restored "
                f"{getattr(r, 'dtype', None)}"
            )
    if errors:
        raise ValueError(
            f"resharded restore at step {step} changed global leaf "
            f"shapes ({len(errors)} of {len(t_leaves)}): "
            + "; ".join(errors[:5])
        )
    return len(t_leaves)
