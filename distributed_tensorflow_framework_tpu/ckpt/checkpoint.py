"""Orbax-backed checkpointing of the full training state.

Replaces the reference's ``tf.train.Saver`` under MonitoredTrainingSession
(SURVEY.md §5 "Checkpoint / resume": chief-only writes, global_step-suffixed
files, latest-checkpoint auto-restore) with Orbax:

  * step-numbered directories + ``latest_step()`` resolution,
  * async save pipeline (``checkpoint.async_save``, docs/PERFORMANCE.md):
    at a save step the training thread pays only a device→host snapshot
    of the TrainState; a background saver thread (ckpt/async_saver.py)
    then performs the orbax write, the manifest hashing, the fsync and
    the atomic commit — the loop never stalls on disk. A new save waits
    for the previous commit, and every exit path drains the in-flight
    commit before the process returns (``wait_until_finished``).
    ``async_save=false`` runs the identical commit sequence inline on
    the training thread (the sync fallback — also the path multi-host
    sharded saves use, since the snapshot is a full host copy).
  * saves MORE than the reference: params, BN stats, optimizer state, step,
    RNG key AND the data-iterator position, so resume is exact
    (SURVEY.md §7 hard part 3 — tested by tests/test_ckpt.py).

All processes call save/restore (Orbax coordinates internally; process 0
writes metadata) — the multi-host analogue of "chief writes".

Integrity layer (docs/RESILIENCE.md): after every committed save the chief
hashes the step directory into a ``manifest.json`` commit record
(ckpt/manifest.py — write-to-tmp + fsync + atomic rename). ``latest_step``
and ``all_steps`` only report manifested steps, restore re-hashes before
reading, and a torn/corrupt step is quarantined (renamed ``<step>.corrupt``)
with automatic fallback to the newest verified older step — a SIGKILL
racing a save can cost at most one checkpoint interval, never the run.
The async pipeline preserves that contract bit-for-bit: the commit
sequence is the same code, merely executed on the saver thread, so a kill
at any point still leaves either a manifested step or an uncommitted
directory restore refuses. Quarantine/rename decisions are chief-only;
non-chief processes follow the shared filesystem state.

Per-save telemetry (``ckpt_save`` events): ``ckpt_save_blocked_ms`` is the
wall time the TRAINING thread spent inside ``save`` (wait-for-previous +
snapshot); ``ckpt_save_total_ms`` is submit→commit-landed. Async saves
show blocked ≪ total; the sync fallback shows blocked == total.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
import orbax.checkpoint as ocp

from distributed_tensorflow_framework_tpu.ckpt import manifest as mf
from distributed_tensorflow_framework_tpu.ckpt import reshard
from distributed_tensorflow_framework_tpu.ckpt.async_saver import AsyncSaver
from distributed_tensorflow_framework_tpu.core import faults, telemetry
from distributed_tensorflow_framework_tpu.core.config import CheckpointConfig
from distributed_tensorflow_framework_tpu.parallel import zero
from distributed_tensorflow_framework_tpu.data import shard as data_shard
from distributed_tensorflow_framework_tpu.data.pipeline import HostDataset
from distributed_tensorflow_framework_tpu.train.state import TrainState

log = logging.getLogger(__name__)


def _pack(state: TrainState) -> Any:
    """Make the state orbax-serializable (typed PRNG keys → raw key data)."""
    return state.replace(rng=jax.random.key_data(state.rng))


def _unpack(raw: Any, like: TrainState) -> TrainState:
    impl = jax.random.key_impl(like.rng)
    return raw.replace(rng=jax.random.wrap_key_data(raw.rng, impl=impl))


def _param_key_names(tree: Any) -> set[str]:
    """Every dict-key name appearing anywhere in a params pytree."""
    names: set[str] = set()
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        for p in path:
            k = getattr(p, "key", None)
            if isinstance(k, str):
                names.add(k)
    return names


def _attention_layout(key_names: set[str]) -> str | None:
    """'fused' / 'unfused' QKV projection layout, or None if the tree has
    no attention projections at all (conv nets). The fused module stores
    one ``attn/qkv`` kernel; unfused stores ``attn/{query,key,value}``
    (models/bert.py) — require the full triple so a stray generic 'key'
    entry can't misclassify."""
    if "qkv" in key_names:
        return "fused"
    if {"query", "key", "value"} <= key_names:
        return "unfused"
    return None


class CheckpointManager:
    def __init__(self, config: CheckpointConfig, *, is_chief: bool = True,
                 telemetry_writer: telemetry.TelemetryWriter | None = None,
                 mesh=None, process_count: int | None = None):
        """``mesh``/``process_count`` identify the topology this manager
        saves under (recorded in every manifest commit record,
        ckpt/reshard.py); when omitted they are derived from the state's
        own shardings at save time."""
        if not config.directory:
            raise ValueError("CheckpointConfig.directory must be set")
        self.config = config
        self.is_chief = is_chief
        self._mesh = mesh
        self._process_count = process_count
        self._telemetry = telemetry_writer
        path = self._path = os.path.abspath(config.directory)
        os.makedirs(path, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            path,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=config.max_to_keep,
                # Orbax's own async layer stays OFF either way: asynchrony
                # is owned by ckpt/async_saver.py, whose worker runs the
                # WHOLE commit sequence (orbax write + manifest + fsync)
                # so the integrity manifest always hashes a finished
                # directory — no deferred-manifest bookkeeping.
                enable_async_checkpointing=False,
            ),
        )
        self._saver = AsyncSaver() if config.async_save else None
        # Exactly-once data plumbing (data/shard.py): the Trainer wires in
        # the live infeed's watermark() and the dataset's repartition
        # capability so every manifest commit record can describe the
        # saved iterator state, and the restore gate knows whether an
        # N→M host refit may repartition it.
        self._watermark_source = None
        self._data_repartition = data_shard.REPARTITION_NONE
        self._data_resume_strict = True

    def set_data_sources(self, *, watermark_source=None,
                         repartition: str | None = None,
                         resume_strict: bool | None = None) -> None:
        """Wire the data plane into save/restore commit records.

        ``watermark_source`` is the live infeed's ``watermark()`` (batches
        prefetched ahead at save time — telemetry only); ``repartition``
        the dataset's capability tag; ``resume_strict`` the
        ``data.resume_strict`` knob gating the restore-time digest /
        host-count checks. The Trainer calls this before restore (tag +
        strictness) and again at train start (watermark), clearing the
        watermark source in its shutdown path — a dead infeed's queue
        must not be polled by a final save.
        """
        self._watermark_source = watermark_source
        if repartition is not None:
            self._data_repartition = repartition
        if resume_strict is not None:
            self._data_resume_strict = bool(resume_strict)

    def _emit(self, kind: str, **fields: Any) -> None:
        if self._telemetry is not None:
            self._telemetry.emit(kind, **fields)

    # ----------------------------------------------------- commit records --
    def _drain(self) -> None:
        """Barrier on the in-flight background commit (no-op when sync or
        idle). Every read of the step listing and every new save funnels
        through here, so directory views are never taken mid-commit and a
        background failure surfaces on the training thread."""
        if self._saver is not None:
            self._saver.wait()

    def _write_and_commit(self, step: int, packed_state: Any,
                          dataset_state: dict | None, *, force: bool,
                          t_begin: float, blocked_s: float | None,
                          topology: dict | None = None,
                          watermark: int = 0) -> bool:
        """The full durable commit sequence — orbax write, fault points,
        manifest hash + fsync + atomic rename, telemetry. Runs on the
        saver thread (async) or inline (sync fallback); identical either
        way, which is what keeps the crash/quarantine drills bit-exact
        across the ``async_save`` knob."""
        args = {"state": ocp.args.StandardSave(packed_state)}
        if dataset_state is not None:
            args["data_iter"] = ocp.args.JsonSave(dataset_state)
        saved = self._mgr.save(step, args=ocp.args.Composite(**args),
                               force=force)
        if not saved:
            return False
        step_dir = os.path.join(self._path, str(step))
        if self.is_chief and os.path.isdir(step_dir) \
                and mf.read_manifest(step_dir) is None:
            # A crash_in_save fault here leaves a written directory with
            # NO manifest — exactly the torn-"latest" artifact the restore
            # path must refuse (docs/RESILIENCE.md drill). In async mode
            # it fires on the saver thread (SIGKILL still takes the whole
            # process — core/faults.py).
            faults.fire("ckpt_in_save", step=step)
            extra: dict = {}
            if topology:
                extra[reshard.MESH_RECORD_KEY] = topology
            if dataset_state is not None:
                # Data-state commit record (data/shard.py): sha256 of the
                # saved iterator state + repartition capability + prefetch
                # watermark, living in the SAME manifest as the weight
                # hashes — "where was the data stream?" shares the
                # integrity contract with "which bytes are the weights?".
                extra[data_shard.DATA_RECORD_KEY] = data_shard.data_state_record(
                    dataset_state,
                    process_count=(self._process_count
                                   if self._process_count is not None
                                   else jax.process_count()),
                    repartition=self._data_repartition,
                    watermark=watermark)
            mf.write_manifest(step_dir, step, extra=extra or None)
            for fault in faults.fire("ckpt_committed", step=step):
                if fault.kind == "corrupt_ckpt":
                    faults.corrupt_checkpoint_dir(step_dir)
        total_ms = (time.perf_counter() - t_begin) * 1e3
        blocked_ms = total_ms if blocked_s is None else blocked_s * 1e3
        self._emit(
            telemetry.KIND_CKPT_SAVE, step=step,
            metrics={"ckpt_save_blocked_ms": round(blocked_ms, 3),
                     "ckpt_save_total_ms": round(total_ms, 3)},
            async_save=self._saver is not None,
        )
        if self.is_chief:
            log.info("Saved checkpoint at step %d (%s, blocked %.0f ms / "
                     "total %.0f ms)", step,
                     "async" if self._saver is not None else "sync",
                     blocked_ms, total_ms)
        return saved

    def save(self, step: int, state: TrainState, *,
             dataset_state: dict | None = None, force: bool = False) -> bool:
        """``dataset_state`` must be the iterator snapshot aligned with
        ``step`` (see data/infeed.py) — NOT the live dataset's state, which
        the prefetcher has advanced past the training step.

        Async mode returns as soon as the snapshot is queued; the True
        return means "accepted for commit", and any commit failure is
        re-raised at the next save/barrier (ckpt/async_saver.py)."""
        t0 = time.perf_counter()
        self._drain()  # a new save waits for the previous commit
        if step in self._mgr.all_steps():
            return False  # already saved (e.g. final save on an interval step)
        # Topology record for the manifest (ckpt/reshard.py): computed from
        # the LIVE sharded state, before any device→host snapshot (the host
        # copy no longer carries NamedShardings).
        topology = reshard.state_topology(
            state, mesh=self._mesh, process_count=self._process_count)
        # Prefetch watermark at the moment of save (the training thread —
        # the same instant the snapshot pairs with), not at commit time on
        # the saver thread, when the producer has run further ahead.
        watermark = 0
        if self._watermark_source is not None and dataset_state is not None:
            try:
                watermark = int(self._watermark_source())
            except Exception:
                log.warning("infeed watermark probe failed", exc_info=True)
        if self._saver is None:
            return self._write_and_commit(
                step, _pack(state), dataset_state, force=force,
                t_begin=t0, blocked_s=None, topology=topology,
                watermark=watermark)
        # Async: the training thread pays only the device→host snapshot.
        # device_get also syncs on the step that produced `state`, so the
        # snapshot is taken at a well-defined step boundary; the loop may
        # donate/overwrite the device buffers freely afterwards.
        host_state = jax.device_get(_pack(state))
        # The iterator snapshot is a small JSON-able dict the trainer
        # rebinds each step; deep-copy via JSON so a hook mutating its
        # live dict can never tear the queued snapshot.
        ds_state = (None if dataset_state is None
                    else json.loads(json.dumps(dataset_state)))
        blocked_s = time.perf_counter() - t0
        self._saver.submit(
            lambda: self._write_and_commit(
                step, host_state, ds_state, force=force,
                t_begin=t0, blocked_s=blocked_s, topology=topology,
                watermark=watermark),
            step=step)
        return True

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return max(steps) if steps else None

    def all_steps(self) -> list[int]:
        """Steps with a complete, COMMITTED checkpoint: saved by Orbax and
        carrying an integrity manifest (post max_to_keep GC). A directory
        Orbax lists but the manifest layer never committed — a save torn by
        a kill — is excluded here and quarantined at restore time.

        Back-compat: a directory with checkpoints but no manifests anywhere
        predates the integrity layer; its steps are trusted as-is (with a
        warning) rather than bricking every pre-manifest run.
        """
        self._drain()
        orbax_steps = sorted(self._mgr.all_steps())
        committed = set(mf.committed_steps(self._path))
        if not committed and orbax_steps:
            log.warning(
                "checkpoint directory %s has no integrity manifests "
                "(pre-manifest checkpoints?) — steps %s are trusted "
                "unverified", self._path, orbax_steps,
            )
            return orbax_steps
        return [s for s in orbax_steps if s in committed]

    def restore(self, template: TrainState, *,
                dataset: HostDataset | None = None,
                step: int | None = None) -> TrainState | None:
        """Restore into the template's shardings; None if no checkpoint.

        Tolerates ``optimizer.ema_decay`` being toggled across a resume:
        the stored tree's ``ema_params`` entry ({} vs params-shaped) may
        not match the template's. On mismatch the restore is retried with
        the opposite EMA shape and reconciled — EMA re-seeded from the
        restored params when newly enabled, dropped when newly disabled —
        instead of failing mid-experiment on a template/tree mismatch.
        """
        if step is not None:
            # Explicitly requested snapshot: fail loudly on corruption (the
            # caller pinned THIS step; silently reading another would be the
            # exact fallback restore_step exists to prevent).
            errors = self._verify(step)
            if errors:
                raise ValueError(
                    f"checkpoint step {step} in {self._path} failed "
                    f"integrity verification: {'; '.join(errors)}"
                )
        else:
            step = self._verified_latest()
        if step is None:
            return None
        self._check_attention_layout(step, template)
        # Topology gate (ckpt/reshard.py): same mesh → normal restore;
        # different mesh → typed MeshTopologyError unless
        # checkpoint.allow_reshard, in which case orbax restores into the
        # template's (new-mesh) shardings and the plan is validated +
        # telemetered below. Runs AFTER integrity verification — a torn
        # step must quarantine, not "reshard".
        saved_manifest = mf.read_manifest(
            os.path.join(self._path, str(step))) or {}
        saved_topo = saved_manifest.get(reshard.MESH_RECORD_KEY)
        reshard_plan = reshard.check_restore_topology(
            saved_topo, template, allow_reshard=self.config.allow_reshard,
            directory=self._path, step=step)

        saved_layout = (saved_topo or {}).get("opt_layout")
        if saved_layout is not None and saved_layout != \
                reshard.opt_layout_digest(template.opt_state):
            # Compared from the manifest's own record, before Orbax sees
            # the template: its shape/rank errors carry no tree path and
            # change wording between releases.
            raise ValueError(
                f"checkpoint step {step} in {self._path} stores an "
                f"optimizer state whose slot layout does not match this "
                f"run's: toggling optimizer.zero_sharding between "
                f"'shard_map' and another mode (or precision.fused_update, "
                f"which regroups the slots per ZeRO bucket) across a resume "
                f"is unsupported (replicated, ZeRO-stacked and per-bucket "
                f"slot layouts are incompatible) — restore with the "
                f"settings the checkpoint was saved under"
            )

        want_ema = bool(jax.tree.leaves(template.ema_params))
        want_res = bool(jax.tree.leaves(template.collective_residual))
        n_want = (jax.tree.leaves(template.collective_residual)[0].shape[0]
                  if want_res else 0)

        def _residual_read_tmpl() -> Any:
            """Template subtree for READING a stored shaped residual: the
            concrete (sharded) template when the replica count matches,
            else host-side ShapeDtypeStructs at the STORED shape — folded
            onto the new replica rows or dropped after the read."""
            axes = (saved_topo or {}).get("axes") or {}
            if not axes:
                raise ValueError(
                    f"checkpoint step {step} in {self._path} stores a "
                    f"collective_residual but its manifest has no mesh "
                    f"topology record — cannot derive the stored replica "
                    f"dimension to fold/drop it"
                )
            n_saved = int(axes.get("data", 1)) * int(axes.get("fsdp", 1))
            if want_res and n_saved == n_want:
                return template.collective_residual
            return jax.tree.map(
                lambda p: jax.ShapeDtypeStruct((n_saved,) + p.shape,
                                               jnp.float32),
                template.params)

        # ZeRO-stacked optimizer slots (parallel/zero.py): detected
        # structurally from the template — (n, ceil(size/n)) rows per
        # param-mirroring slot. A cross-mesh restore must READ them at
        # the STORED row grid and refold host-side (the row count is the
        # data×fsdp replica count, exactly like the EF residual above).
        zero_rows = zero.stacked_rows(template.opt_state, template.params)

        def _zero_saved_rows() -> int | None:
            axes = (saved_topo or {}).get("axes") or {}
            if not axes:
                return None
            return int(axes.get("data", 1)) * int(axes.get("fsdp", 1))

        def _zero_read_tmpl() -> Any:
            n_saved = _zero_saved_rows()
            if n_saved is None:
                raise ValueError(
                    f"checkpoint step {step} in {self._path} is being "
                    f"resharded with ZeRO-stacked optimizer state but its "
                    f"manifest has no mesh topology record — cannot derive "
                    f"the stored shard grid"
                )
            if n_saved == zero_rows:
                return template.opt_state

            def tmpl(slot, param):
                if param is None or getattr(slot, "ndim", 0) != 2:
                    return slot
                size = int(math.prod(param.shape)) if param.shape else 1
                return jax.ShapeDtypeStruct(
                    (n_saved, -(-size // n_saved)), slot.dtype)

            return zero.map_slots(tmpl, template.opt_state, template.params)

        def tmpl_for(stored_ema: bool, stored_res: str) -> TrainState:
            """Restore template matching the stored tree's EMA and
            error-feedback-residual presence."""
            t = template
            if want_ema and not stored_ema:
                log.warning(
                    "Checkpoint at step %d has no EMA params (ema_decay "
                    "enabled after it was saved) — will re-seed EMA from "
                    "the restored params", step,
                )
                t = t.replace(ema_params={})
            if stored_ema and not want_ema:
                # Stored EMA must be read into a params-shaped template and
                # discarded below (orbax's Standard handler has no partial
                # restore) — a one-time params-sized I/O cost on the rare
                # disable-EMA-mid-experiment resume. Leaves are only a
                # restore template, so aliasing params is fine.
                log.warning(
                    "Checkpoint at step %d carries EMA params but ema_decay "
                    "is now disabled — dropping them", step,
                )
                t = t.replace(ema_params=template.params)
            if stored_res == "shaped":
                if not want_res:
                    log.warning(
                        "Checkpoint at step %d carries a collective "
                        "error-feedback residual but quantized collectives "
                        "are now off — dropping it", step,
                    )
                t = t.replace(collective_residual=_residual_read_tmpl())
            else:
                if want_res:
                    log.warning(
                        "Checkpoint at step %d has no collective residual "
                        "(quantized collectives enabled after it was saved) "
                        "— starting from a zero residual", step,
                    )
                t = t.replace(collective_residual={})
            if reshard_plan is not None and zero_rows:
                t = t.replace(opt_state=_zero_read_tmpl())
            return t

        def attempt(t: TrainState, *, legacy: bool):
            item = _pack(t)
            if legacy:
                # Pre-residual checkpoint: flax dataclasses serialize as
                # dicts, so restore into the historical six-key dict and
                # rebuild the TrainState afterwards.
                item = {
                    "step": item.step, "params": item.params,
                    "batch_stats": item.batch_stats,
                    "opt_state": item.opt_state, "rng": item.rng,
                    "ema_params": item.ema_params,
                }
            args = {"state": ocp.args.StandardRestore(item)}
            if dataset is not None:
                args["data_iter"] = ocp.args.JsonRestore()
            return self._mgr.restore(step, args=ocp.args.Composite(**args)), \
                item

        stored_ema = self._stored_has_ema(step, default=want_ema)
        stored_res = self._stored_residual_presence(
            step, default="shaped" if want_res else "empty")
        ema_flipped = res_flipped = False
        while True:
            tmpl = tmpl_for(stored_ema, stored_res)
            try:
                restored, item = attempt(tmpl,
                                         legacy=(stored_res == "missing"))
                break
            except ValueError as e:
                # Fallbacks for when a metadata probe misjudged (the JSON
                # layout is orbax-private and may change): a tree-structure
                # mismatch naming the field means the stored presence is
                # the opposite of what we assumed — flip and retry, once
                # per field.
                msg = str(e)
                if "ema_params" in msg and not ema_flipped:
                    log.warning(
                        "EMA-presence probe disagreed with the stored tree "
                        "(%s); retrying restore with the flipped EMA "
                        "template", e,
                    )
                    ema_flipped, stored_ema = True, not stored_ema
                    continue
                if "collective_residual" in msg and not res_flipped:
                    log.warning(
                        "residual-presence probe disagreed with the stored "
                        "tree (%s); retrying restore with the flipped "
                        "residual template", e,
                    )
                    res_flipped = True
                    stored_res = ("empty" if stored_res == "shaped"
                                  else "shaped")
                    continue
                raise
        if reshard_plan is not None:
            # Cross-mesh load succeeded mechanically; confirm it moved
            # bytes without reshaping them, then record the reshard in the
            # run's event stream (analyze_trace.py surfaces it).
            leaf_count = reshard.validate_restored(
                item, restored["state"], step=step)
            self._emit(
                telemetry.KIND_CKPT_RESHARDED, step=step,
                from_axes=reshard_plan["from_axes"],
                to_axes=reshard_plan["to_axes"],
                leaf_count=leaf_count,
                from_spec_digest=reshard_plan["from_spec_digest"],
                to_spec_digest=reshard_plan["to_spec_digest"],
                respec_agreement=reshard_plan["respec_agreement"],
            )
            log.warning(
                "restored checkpoint step %d RESHARDED %s -> %s "
                "(%d leaves validated)", step,
                reshard.describe_axes(reshard_plan["from_axes"]),
                reshard.describe_axes(reshard_plan["to_axes"]), leaf_count,
            )
        raw = restored["state"]
        if stored_res == "missing":
            # Legacy dict (pre-residual) → TrainState; collective_residual
            # takes its {} default and is reconciled below.
            raw = TrainState(**raw)
        state = _unpack(raw, tmpl)
        if reshard_plan is not None and zero_rows:
            n_saved = _zero_saved_rows()
            if n_saved != zero_rows:
                refolded = reshard.refold_zero_opt_state(
                    state.opt_state, template.params, zero_rows)
                state = state.replace(opt_state=jax.tree.map(
                    lambda f, t: (jax.device_put(f, t.sharding)
                                  if hasattr(t, "sharding") else f),
                    refolded, template.opt_state))
                log.warning(
                    "ZeRO optimizer state re-gridded %d -> %d shard rows "
                    "(padding truncated and re-derived) across the "
                    "reshard", n_saved, zero_rows,
                )
        if want_res and stored_res == "shaped":
            n_saved = jax.tree.leaves(state.collective_residual)[0].shape[0]
            if n_saved != n_want:
                folded = reshard.fold_residual(
                    state.collective_residual, n_want)
                state = state.replace(collective_residual=jax.tree.map(
                    lambda f, t: jax.device_put(f, t.sharding),
                    folded, template.collective_residual))
                log.warning(
                    "collective_residual folded %d -> %d replica rows "
                    "(sum-preserving) across the reshard", n_saved, n_want,
                )
        elif want_res:
            state = state.replace(
                collective_residual=template.collective_residual)
        elif jax.tree.leaves(state.collective_residual):
            state = state.replace(collective_residual={})
        if want_ema and not stored_ema:
            # Real copies, not aliases: params and ema_params both live in
            # the donated TrainState — aliased buffers would be donated
            # twice in the first train step.
            state = state.replace(ema_params=jax.tree.map(jnp.copy, state.params))
        elif stored_ema and not want_ema:
            state = state.replace(ema_params={})
        if dataset is not None and restored.get("data_iter") is not None:
            # Data-state restore gate (data/shard.py): digest-check the
            # restored iterator state against its manifest commit record
            # and decide whether a host-count change may repartition it.
            # Runs BEFORE the state reaches the dataset, so a failed gate
            # leaves the dataset untouched at its initial state.
            data_plan = data_shard.check_restore_data(
                saved_manifest.get(data_shard.DATA_RECORD_KEY),
                restored["data_iter"],
                process_count=(self._process_count
                               if self._process_count is not None
                               else jax.process_count()),
                resume_strict=self._data_resume_strict)
            if data_plan is not None:
                self._emit(telemetry.KIND_DATA_STATE, step=step,
                           plan=data_plan)
                if data_plan["action"] != "resume":
                    log.warning(
                        "data state restored at step %d: %s (%s -> %s "
                        "hosts)", step, data_plan["action"],
                        data_plan.get("from_processes"),
                        data_plan.get("to_processes"))
            dataset.restore(restored["data_iter"])
        return state

    # ------------------------------------------------ integrity / fallback --
    def _verify(self, step: int) -> list[str]:
        """Integrity errors for one step ([] = safe to restore)."""
        step_dir = os.path.join(self._path, str(step))
        manifest = mf.read_manifest(step_dir)
        if manifest is None:
            if not mf.committed_steps(self._path):
                # Pre-manifest directory: nothing to verify against.
                log.warning(
                    "restoring step %d without integrity verification "
                    "(no manifests in %s)", step, self._path,
                )
                return []
            return ["no committed manifest (save did not complete)"]
        if not self.config.verify_restore:
            return []  # manifest presence (commit record) is still required
        return mf.verify_step_dir(step_dir, manifest)

    def _verified_latest(self) -> int | None:
        """Newest step that passes verification, quarantining every newer
        step that does not — the automatic-fallback half of the integrity
        contract. Returns None when no restorable checkpoint remains."""
        self._drain()
        candidates = sorted(self._mgr.all_steps(), reverse=True)
        if not candidates:
            return None
        if not mf.committed_steps(self._path):
            return candidates[0]  # legacy store; _verify logs the warning
        newest = candidates[0]
        quarantined = False
        chosen = None
        for s in candidates:
            errors = self._verify(s)
            if not errors:
                chosen = s
                break
            log.error(
                "checkpoint step %d in %s is corrupt/torn: %s",
                s, self._path, "; ".join(errors[:3]),
            )
            reason = ("uncommitted save" if "no committed manifest" in errors[0]
                      else "integrity verification failed")
            if self.is_chief:
                mf.quarantine(self._path, s, reason, errors)
                quarantined = True
            self._emit(
                telemetry.KIND_CKPT_QUARANTINED, step=s,
                health={"reason": reason, "errors": "; ".join(errors[:3]),
                        "directory": self._path},
            )
        if quarantined:
            # Orbax caches its step listing; the renames just invalidated it.
            try:
                self._mgr.reload()
            except Exception:
                log.warning("orbax manager reload after quarantine failed",
                            exc_info=True)
        if chosen is not None and chosen != newest:
            log.warning(
                "restore falling back from corrupt step %d to verified "
                "step %d", newest, chosen,
            )
            self._emit(
                telemetry.KIND_RESTORE_FALLBACK, step=chosen,
                health={"from_step": newest, "to_step": chosen,
                        "directory": self._path},
            )
        return chosen

    def _stored_has_ema(self, step: int, *, default: bool) -> bool:
        """Whether the stored state tree carries EMA param leaves.

        Reads the step's PyTree ``_METADATA`` JSON directly (the manager's
        ``item_metadata`` returns nothing before the item registry is
        populated). A state saved with EMA disabled stores a single
        empty-Dict marker at ``('ema_params',)``; real EMA state stores
        nested array entries ``('ema_params', <module>, ...)``.
        """
        import json

        path = os.path.join(self._path, str(step), "state", "_METADATA")
        try:
            with open(path) as fh:
                tree_meta = json.load(fh).get("tree_metadata", {})
        except Exception as e:  # probe is best-effort; restore() retries
            log.warning("EMA-presence probe failed reading %s (%s) — "
                        "assuming template shape", path, e)
            return default
        for entry in tree_meta.values():
            keys = entry.get("key_metadata") or []
            if keys and keys[0].get("key") == "ema_params" and len(keys) > 1:
                return True
        return False

    def _stored_residual_presence(self, step: int, *, default: str) -> str:
        """Whether the stored tree carries a collective_residual subtree:
        ``"missing"`` (pre-residual checkpoint — no such key), ``"empty"``
        (the {} marker: quantized collectives were off) or ``"shaped"``
        (per-replica residual arrays). Same best-effort ``_METADATA``
        probe as ``_stored_has_ema``; ``default`` on unreadable metadata.
        """
        import json

        path = os.path.join(self._path, str(step), "state", "_METADATA")
        try:
            with open(path) as fh:
                tree_meta = json.load(fh).get("tree_metadata", {})
        except Exception as e:
            log.warning("residual-presence probe failed reading %s (%s) — "
                        "assuming template shape", path, e)
            return default
        found = False
        for entry in tree_meta.values():
            keys = entry.get("key_metadata") or []
            if keys and keys[0].get("key") == "collective_residual":
                found = True
                if len(keys) > 1:
                    return "shaped"
        return "empty" if found else "missing"

    def _stored_param_key_names(self, step: int) -> set[str] | None:
        """Dict-key names under the stored tree's ``params`` subtree, from
        the step's PyTree ``_METADATA`` JSON; None when unreadable (the
        probe is best-effort, like ``_stored_has_ema``)."""
        import json

        path = os.path.join(self._path, str(step), "state", "_METADATA")
        try:
            with open(path) as fh:
                tree_meta = json.load(fh).get("tree_metadata", {})
        except Exception:
            return None
        names: set[str] = set()
        for entry in tree_meta.values():
            keys = [k.get("key") for k in (entry.get("key_metadata") or [])]
            if keys and keys[0] == "params":
                names.update(k for k in keys[1:] if isinstance(k, str))
        return names or None

    def _check_attention_layout(self, step: int, template: TrainState) -> None:
        """Fail fast, with the fix named, when the stored params use the
        opposite ``model.fused_qkv`` layout from the restore template.

        Without this the mismatch surfaces as an opaque Orbax tree-structure
        error deep inside StandardRestore ('user-provided restore item and
        on-disk value metadata tree structures do not match'), long after
        the config change that caused it.
        """
        stored_keys = self._stored_param_key_names(step)
        if stored_keys is None:
            return
        stored = _attention_layout(stored_keys)
        want = _attention_layout(_param_key_names(template.params))
        if stored is None or want is None or stored == want:
            return
        raise ValueError(
            f"Checkpoint at step {step} in {self._path} stores "
            f"{stored} attention projections but the model is configured "
            f"for {want} (model.fused_qkv="
            f"{'true' if want == 'fused' else 'false'}). Set model."
            f"fused_qkv to match the checkpoint, or transplant the params "
            f"— the fused kernel is stack([query, key, value], axis=1) of "
            f"the unfused kernels, see tests/test_models.py::"
            f"test_fused_qkv_transplant_parity and docs/MIGRATING.md."
        )

    def wait_until_finished(self) -> None:
        """The exit/preemption barrier: returns only once every accepted
        save has durably committed (manifest written + fsync'd). Called by
        CheckpointHook.on_end so normal completion AND the SIGTERM
        graceful-preempt path (rc 83) never exit with a commit in flight."""
        self._drain()
        self._mgr.wait_until_finished()

    def close(self) -> None:
        try:
            self._drain()
        finally:
            if self._saver is not None:
                try:
                    self._saver.close()
                except Exception:
                    log.warning("async saver close failed", exc_info=True)
            self._mgr.close()
