"""The training loop — MonitoredTrainingSession, SPMD-style.

SURVEY.md §3.1: the reference's hot loop is ``while not stop:
session.run(train_op)`` under MonitoredTrainingSession (checkpoint restore
on start, hooks each step, chief-only services). The Trainer keeps that
contract: build → maybe-restore → step loop with hooks → final save, with
two differences that matter on TPU:

  * metrics are fetched only at log intervals — each step returns device
    arrays that are NOT synced unless a hook needs them, so the loop stays
    ahead of the device (async dispatch);
  * there are no session/graph handles: the "session" is a compiled
    function and the "server" is the mesh.
"""

from __future__ import annotations

import collections
import logging
import os
import sys
import time
from typing import Any

import jax

from distributed_tensorflow_framework_tpu.core.config import ExperimentConfig
from distributed_tensorflow_framework_tpu.core import (
    cluster, faults, goodput, memstats, profiling, supervision, telemetry,
    tracing)
from distributed_tensorflow_framework_tpu.core.mesh import (
    MeshRuntime, device_record, initialize_runtime)
from distributed_tensorflow_framework_tpu.core.metrics import MetricWriter, setup_logging
from distributed_tensorflow_framework_tpu.data import get_dataset, packing
from distributed_tensorflow_framework_tpu.data import shard as data_shard
from distributed_tensorflow_framework_tpu.data.infeed import (
    InfeedStallError, prefetch_to_device, to_global)
from distributed_tensorflow_framework_tpu.ops import flash_attention
from distributed_tensorflow_framework_tpu.parallel import collectives as coll
from distributed_tensorflow_framework_tpu.train import anomaly as anomaly_lib
from distributed_tensorflow_framework_tpu.train import hooks as hooks_lib
from distributed_tensorflow_framework_tpu.train import schedules
from distributed_tensorflow_framework_tpu.train.step import StepBuilder

log = logging.getLogger(__name__)


def _poison_batch(batch: dict) -> dict:
    """nan_grads fault effect: NaN every floating-point input array so the
    step's loss and gradients go non-finite and the NaN-provenance path
    (NaNGuardHook → failure telemetry → abort) is exercised end-to-end."""
    import jax.numpy as jnp

    return {
        k: v * jnp.asarray(float("nan"), dtype=v.dtype)
        if jnp.issubdtype(v.dtype, jnp.floating) else v
        for k, v in batch.items()
    }


def _scale_batch(batch: dict, factor: float) -> dict:
    """loss_spike fault effect: blow up the floating-point inputs by a
    large FINITE factor — the loss jumps orders of magnitude but stays
    finite, so only the EWMA z-score rung of the detector can catch it
    (the non-finite check must not)."""
    import jax.numpy as jnp

    return {
        k: v * jnp.asarray(factor, dtype=v.dtype)
        if jnp.issubdtype(v.dtype, jnp.floating) else v
        for k, v in batch.items()
    }


class Trainer:
    def __init__(self, config: ExperimentConfig, runtime: MeshRuntime | None = None):
        setup_logging()
        # Startup-latency clock: construction → the first dispatch of the
        # step returning (the step itself is still in flight then) covers
        # restore + input build + compile, the relaunch cost a supervisor
        # pays on every preemption (emitted as a KIND_STARTUP event), and
        # whatever the caller does while it holds the trainer in between.
        self._init_t = time.perf_counter()
        self._init_mono = time.monotonic()  # train.startup span backfill
        # What the process had already spent when construction began
        # (interpreter, imports, backend start, config), by the OS's
        # record of when it started the process.
        self._process_s = profiling.process_age_s()
        self._startup_emitted = False
        self._restored_step: int | None = None
        self.config = config
        # The loop's recorder (core/profiling.py): every stretch of an
        # iteration runs under one of its phases — the per-phase totals
        # become ``time_*_ms`` at every log interval and feed the goodput
        # ledger, each occurrence lands in a ring (the loop timeline,
        # dumped beside the flight recorder) and in any profile as a
        # trace annotation. The cheap always-on signal for "is the input
        # pipeline the wall?" (SURVEY.md §7 hard part 1), and for "which
        # span of which iteration was long?", without capturing a trace.
        # Made first: every stretch from here to the first dispatch runs
        # under one of its ``startup:*`` spans (ring and annotation, no
        # total: the goodput ledger charges that wall as one bucket), and
        # its compile log says what JAX traced, compiled and loaded.
        self.timer = profiling.StepTimer()
        if runtime is None:
            with self.timer.span("startup:runtime"):
                runtime = initialize_runtime(config.mesh)
        self.runtime = runtime
        self.mesh = self.runtime.mesh
        with self.timer.span("startup:dataset"):
            self.dataset = get_dataset(
                config.data,
                process_index=self.runtime.process_index,
                process_count=self.runtime.process_count,
            )
        self.builder = StepBuilder(config, self.mesh)
        # With a log directory the chief's writer brings in TensorBoard's
        # event writer: seconds of imports on the first use in a process.
        with self.timer.span("startup:writer"):
            self.writer = MetricWriter(
                logdir=(config.checkpoint.directory or None),
                is_chief=self.runtime.is_chief,
                process_index=self.runtime.process_index,
                process_count=self.runtime.process_count,
            )
        self.run_id = self.writer.run_id
        # In-process recovery ladder (train/anomaly.py): detect → rollback
        # → re-warmup → escalate. None when resilience.rollback=false —
        # the loop then behaves exactly as before this rung existed
        # (NaNGuardHook aborts, supervisor relaunches from checkpoint).
        self.recovery = (
            anomaly_lib.RecoveryManager(
                config.resilience, telemetry_writer=self.writer.telemetry)
            if config.resilience.rollback else None
        )
        # Wall-clock accountant (core/goodput.py): absorbs StepTimer
        # phases and listens on the telemetry stream (ckpt_save blocked-ms
        # from the saver thread), so every second of this process lands in
        # a KIND_GOODPUT bucket. Backdated to _init_t: the runtime/dataset
        # build above must be inside the wall the startup bucket charges.
        self.goodput = goodput.GoodputLedger(
            self.writer.telemetry,
            interval_s=config.train.goodput_interval_s,
            t0_perf=self._init_t,
            process_id=(self.runtime.process_index
                        if self.runtime.process_count > 1 else None))
        self._startup_accounted = False
        self._judged = None  # the newest ring entry ``slow_step`` has judged
        # ``recompile`` events: what the compile log read at the last
        # check and when that was, and the spans under which the loop
        # itself said "compile".
        self._compiles_seen = self.timer.compiles.logged
        self._compiles_seen_ns = time.time_ns()
        self._announced: list[tuple[int, int]] = []
        self._self_charged: set[str] = set()  # hook phases goodput skips
        # Periodic HBM sampling (core/memstats.py): device.memory_stats()
        # where the backend has it, host RSS where it doesn't.
        self.memstats = memstats.MemoryMonitor(
            self.writer.telemetry,
            interval_s=config.train.memory_interval_s, source="train")
        # Distributed tracing (core/tracing.py): spans for this worker's
        # run/startup/step-windows/ckpt-saves/rollbacks, parented on the
        # gang supervisor's attempt span when DTF_TRACE_CTX is set — the
        # whole gang then reconstructs as ONE supervisor-rooted tree.
        self.tracer = tracing.Tracer(
            self.writer.telemetry if config.trace.enabled else None,
            service=f"worker{self.runtime.process_index}")
        self._trace_parent = tracing.env_context()
        self.tracer.adopt(self._trace_parent)
        self.run_span: tracing.Span | None = None  # opened by train()
        # Flight recorder: recent telemetry ring, dumped on anomaly
        # escalation, graceful preemption, or SIGUSR1 — forensics that
        # survive a SIGKILLed or torn-JSONL attempt.
        self.flightrec = tracing.FlightRecorder(
            config.trace.ring_size,
            dump_dir=(config.trace.dump_dir
                      or config.checkpoint.directory or None),
            tracer=self.tracer).attach(self.writer.telemetry)
        self.flightrec.install_sigusr1()
        # Set by _rebuild_with_rewarmup: the next dispatch re-jits, so its
        # wall time belongs in the recompile bucket, not step_compute.
        self._recompile_pending = False
        self.state: Any = None
        self.host_step = 0
        self._ckpt_manager = None
        # True once a SIGTERM was honored gracefully (in-flight step
        # finished, checkpoint saved by CheckpointHook.on_end) — the CLI
        # exits supervision.GRACEFUL_PREEMPT_RC on it.
        self.preempted = False
        # Per-collective (calls, bytes) recorded while tracing the train
        # step; None until the first dispatch compiles. Shape-static, so
        # one trace describes every step of the executable.
        self.collectives_summary: dict[str, int] | None = None
        # Iterator snapshot aligned with host_step (see data/infeed.py).
        self.data_ckpt_state: dict = self.dataset.state()

    # -------------------------------------------------------------- setup --
    def build(self) -> None:
        # Shard-assignment record (data/shard.py): validate this host's
        # slice of every global batch against the gang AND the mesh's
        # data-parallel extent before the first batch moves, and put the
        # layout in the telemetry record (KIND_DATA_SHARD) — the exactly-
        # once drill reads it back per attempt.
        mesh_shape = {k: int(v) for k, v in self.mesh.shape.items()}
        data_parallel = (mesh_shape.get("data", 1)
                         * mesh_shape.get("fsdp", 1)) or None
        span = self.timer.span
        try:
            with span("startup:sample"):
                shard_layout = data_shard.shard_plan(
                    data_shard.ShardAssignment(
                        process_index=self.runtime.process_index,
                        process_count=self.runtime.process_count),
                    global_batch=self.config.data.global_batch_size,
                    data_parallel=data_parallel,
                    shard_mode=self.config.data.shard_mode)
                # Peek one batch for shapes, then restore the stream to
                # the start.
                start_state = self.dataset.state()
                host_batch = next(self.dataset)
                self.dataset.restore(start_state)
                sample = to_global(host_batch, self.mesh)
                # Kept for post-rollback re-jitting (LR re-warmup rebuilds
                # the optimizer, which needs a recompile against the same
                # shapes).
                self._sample = sample
            with span("startup:init_state"):
                self.state = self.builder.init_state(
                    self.config.train.seed, sample)
        finally:
            # The run's opening record, written whether or not the init
            # above succeeded. It waits for the init because that traces
            # the model's forward, which is when the attention kernels
            # choose family and tile from the shapes they are given.
            self.writer.telemetry.emit_run_meta(
                argv=list(sys.argv),
                config_name=self.config.name,
                spmd_mode=self.config.train.spmd_mode,
                model=self.config.model.name,
                dataset=self.config.data.name,
                global_batch_size=self.config.data.global_batch_size,
                mesh=mesh_shape,
                process_count=self.runtime.process_count,
                process_index=self.runtime.process_index,
                # Where the run landed and how its Pallas kernels compile
                # there ("mosaic" on a TPU, "interpret" anywhere else) —
                # a CPU run must never read as a chip run — and, per
                # distinct attention shape traced, the kernel family,
                # tile and backward it was given.
                **device_record(),
                pallas_kernels=flash_attention.kernel_mode(),
                flash_dispatch=flash_attention.dispatch_log(),
                # Which experts this process holds, of how many groups,
                # as the model says it; None where it has no such layer.
                expert_share=getattr(self.builder.model, "expert_share",
                                     lambda: None)(),
                # Which heads of each mixer, likewise (a tensor-parallel
                # share); None for the whole model.
                tensor_share=getattr(self.builder.model, "tensor_share",
                                   lambda: None)(),
            )
        self.writer.telemetry.emit(
            telemetry.KIND_DATA_SHARD, step=self.host_step,
            shard=shard_layout)
        stages = int(getattr(self.config.model, "pipeline_stages", 0) or 0)
        if stages > 0:
            # One record of the resolved schedule so step-time rollups
            # (telemetry.summarize_events) read against the right bubble.
            from distributed_tensorflow_framework_tpu.parallel import (
                schedule as pipe_sched,
            )

            name = self.config.model.pipeline_schedule
            micro = (self.config.model.pipeline_microbatches or stages)
            virtual = pipe_sched.resolve_virtual(
                name, stages, micro,
                self.config.model.pipeline_virtual_stages,
                self.config.model.num_layers)
            self.writer.telemetry.emit(
                telemetry.KIND_PIPELINE,
                schedule=name, stages=stages, microbatches=micro,
                virtual_stages=virtual,
                bubble_frac=pipe_sched.bubble_frac(
                    name, stages, micro, virtual),
                peak_inflight=pipe_sched.peak_inflight(
                    name, stages, micro, virtual),
            )
        with span("startup:make_step"):
            self._make_step(sample)
        # eval_step compiles from the EVAL stream's sample batch (its
        # element spec differs from training: weight key, no aug). Built
        # HERE rather than at the first evaluate() when eval will run, so
        # any eval-config error (e.g. a native reader with no exact-eval
        # path) fails at startup — not hours in, after training finishes.
        self.eval_step = None
        # eval_steps > 0 is the single eval on-switch (eval_interval alone
        # does nothing — default_hooks logs that case), so only then pay
        # the eval pipeline build + compile up front.
        if self.config.train.eval_steps > 0:
            with span("startup:eval_build"):
                self._ensure_eval()
        # Checkpoint manager + auto-restore (MonitoredTrainingSession
        # contract: restore latest from checkpoint_dir if present).
        if self.config.checkpoint.restore_step >= 0 and not (
                self.config.checkpoint.directory
                and self.config.checkpoint.restore):
            # The knob's contract is fail-loudly; silently starting from
            # scratch because restore is off would be the exact fallback
            # it exists to prevent.
            raise ValueError(
                "checkpoint.restore_step set but restoring is disabled — "
                "need checkpoint.directory non-empty and "
                "checkpoint.restore=true"
            )
        with span("startup:restore"):
            self._open_checkpoints()

    def _make_step(self, sample) -> None:
        """The jitted train step for ``sample``'s shapes and, where a
        profile or the memory analysis is armed, its compiled form."""
        self.train_step = self.builder.make_train_step(sample)
        if getattr(self.builder, "_zero", False):
            # One record of the static shard/bucket plan so byte and
            # step-time rollups read against the overlap structure that
            # produced them (parallel/zero.plan_summary).
            from distributed_tensorflow_framework_tpu.parallel import zero
            self.writer.telemetry.emit(
                telemetry.KIND_ZERO_UPDATE,
                **zero.plan_summary(
                    self.builder._zero_plan,
                    wire_dtype=self.config.parallel.collective_dtype or None,
                    block_size=self.config.parallel.collective_block_size,
                ),
            )
        # Optimized-HLO capture for trace attribution (ProfileHook dumps
        # it next to the .xplane.pb). Only when profiling is armed: the
        # explicit lower+compile does not populate the jit call cache, so
        # it costs one extra compile — acceptable for a profiling run,
        # not for every training launch.
        self.compiled_hlo = None
        tcfg = self.config.train
        profiled = tcfg.profile_stop > tcfg.profile_start and self.runtime.is_chief
        if profiled or (tcfg.memory_analysis and self.runtime.is_chief):
            # This lower+compile populates the jit call cache, so the
            # loop's first-dispatch tally would see an already-traced
            # step — capture the collective counters here instead. A
            # lowering or compile failure propagates: it is the same
            # program the loop is about to run.
            with coll.tally() as tly:
                lowered = self.train_step.lower(self.state, sample)
            self.collectives_summary = tly.summary()
            compiled = lowered.compile()
            if profiled:
                self.compiled_hlo = compiled.as_text()
            # Static memory budget of the step (KIND_MEMORY with
            # extra.analysis) — free here, the compile is already paid.
            self.memstats.capture_compiled(compiled, label="train_step")

    def _open_checkpoints(self) -> None:
        """The checkpoint manager, and the newest (or the named)
        checkpoint restored into the state, where there is a directory."""
        if self.config.checkpoint.directory:
            from distributed_tensorflow_framework_tpu.ckpt import CheckpointManager

            self._ckpt_manager = CheckpointManager(
                self.config.checkpoint, is_chief=self.runtime.is_chief,
                telemetry_writer=self.writer.telemetry,
                mesh=self.mesh,
                process_count=self.runtime.process_count,
            )
            # Data-plane plumbing for the manifest commit record + restore
            # gate (data/shard.py): the dataset's repartition capability
            # decides whether an N→M refit may reuse its state, and
            # data.resume_strict gates the digest/host-count checks.
            self._ckpt_manager.set_data_sources(
                repartition=self.dataset.repartition,
                resume_strict=self.config.data.resume_strict)
            if self.config.checkpoint.restore:
                want = self.config.checkpoint.restore_step
                if want >= 0 and want not in self._ckpt_manager.all_steps():
                    # Saver contract: asking for a specific snapshot that
                    # does not exist (never saved, or GC'd by max_to_keep)
                    # must fail loudly, not fall back to latest.
                    raise ValueError(
                        f"checkpoint.restore_step={want} not found in "
                        f"{self.config.checkpoint.directory!r} (available: "
                        f"{sorted(self._ckpt_manager.all_steps())})"
                    )
                restored = self._ckpt_manager.restore(
                    self.state, dataset=self.dataset,
                    step=want if want >= 0 else None)
                if restored is not None:
                    self.state = restored
                    self.host_step = int(jax.device_get(self.state.step))
                    self._restored_step = self.host_step
                    # Re-align the checkpointable snapshot with the
                    # RESTORED stream position: the __init__ snapshot is
                    # the initial state, and a rollback baseline built
                    # from it would mis-compute skip ordinals.
                    self.data_ckpt_state = self.dataset.state()
                    log.info("Restored checkpoint at step %d", self.host_step)

    def default_hooks(self) -> list:
        cfg = self.config
        tp = hooks_lib.ThroughputHook(
            batch_size=cfg.data.global_batch_size,
            num_chips=self.runtime.global_device_count,
        )
        hooks = [tp, hooks_lib.LoggingHook(self.writer, cfg.train.log_interval, tp)]
        if cfg.train.nan_guard:
            hooks.append(hooks_lib.NaNGuardHook())
        if cfg.checkpoint.directory and (
                self.runtime.is_chief or self.runtime.process_count > 1):
            # Gang runs: EVERY worker beats its own heartbeat-p<i>.json so
            # the cluster supervisor can tell a hung worker from a hung
            # gang; single-process runs keep the legacy heartbeat.json.
            hooks.append(hooks_lib.HeartbeatHook(
                cluster.heartbeat_path(
                    cfg.checkpoint.directory,
                    self.runtime.process_index,
                    self.runtime.process_count),
                min_interval_s=cfg.cluster.heartbeat_interval_s,
            ))
        if cfg.model.num_experts > 0:
            hooks.append(hooks_lib.MoECollapseHook())
        if self._ckpt_manager is not None:
            hooks.append(
                hooks_lib.CheckpointHook(
                    self._ckpt_manager, cfg.checkpoint.save_interval_steps
                )
            )
        if cfg.train.eval_interval > 0:
            if cfg.train.eval_steps > 0:
                # Mid-training evals are BOUNDED by eval_steps (a full
                # 50k-image pass every interval would stall training); the
                # final eval and --eval-only walk the complete set.
                hooks.append(hooks_lib.EvalHook(
                    self.evaluate, cfg.train.eval_interval,
                    num_batches=cfg.train.eval_steps,
                ))
            else:
                # eval_steps=0 disables eval everywhere — don't silently
                # flip to a full-set pass per interval.
                log.warning(
                    "train.eval_interval=%d but eval_steps=0 — mid-training "
                    "eval disabled", cfg.train.eval_interval,
                )
        if cfg.train.profile_stop > cfg.train.profile_start and self.runtime.is_chief:
            trace_dir = os.path.join(
                cfg.checkpoint.directory or "/tmp/dtf_tpu", "traces"
            )
            hooks.append(
                hooks_lib.ProfileHook(
                    trace_dir, cfg.train.profile_start, cfg.train.profile_stop
                )
            )
        return hooks

    # --------------------------------------------------------------- train --
    def train(self, hooks: list | None = None) -> dict[str, float]:
        if self.state is None:
            self.build()
        cfg = self.config.train
        hooks = self.default_hooks() if hooks is None else hooks
        timer = self.timer
        timer.step = self.host_step
        if self.recovery is not None:
            # The two-phase snapshot's device copy, compiled here from the
            # state's avals: set-up's cost, never a compile in the loop.
            with timer.span("startup:snapshot_program"):
                self.recovery.prepare_overlap(self.state)
        with timer.span("startup:loop_entry"):
            infeed = self._enter_loop(hooks)
        last_metrics: dict[str, float] = {}
        # Bounded dispatch-ahead (train.dispatch_ahead): a deque of each
        # in-flight step's metrics; once full, sync on the OLDEST entry
        # before dispatching another step (a scalar device_get of that
        # step's first metric).
        pending: collections.deque = collections.deque()
        if self.recovery is not None:
            # Baseline snapshot: the ladder must be able to roll back even
            # if the first anomaly lands before the first clean fetch.
            # (After the startup bucket closed: its wall is ``snapshot``'s.)
            self._snapshot(force=True)
        # ``recompile`` events are of the loop: what was compiled on the
        # way here is the startup event's.
        self._compiles_seen = timer.compiles.logged
        self._compiles_seen_ns = time.time_ns()
        hook_phases = [(h, f"hook:{type(h).__name__}") for h in hooks]
        self._self_charged = {
            phase for h, phase in hook_phases
            if getattr(h, "charges_goodput_itself", False)}
        try:
            while self.host_step < cfg.total_steps:
                if supervision.preemption_requested():
                    # Graceful preemption (SIGTERM): the previous step is
                    # complete, hooks' on_end below force-saves a
                    # checkpoint, and the CLI exits GRACEFUL_PREEMPT_RC so
                    # the supervisor relaunches without burning an attempt.
                    self.preempted = True
                    log.warning(
                        "preemption requested — stopping at step %d for a "
                        "final checkpoint", self.host_step,
                    )
                    self.writer.telemetry.emit(
                        telemetry.KIND_HEALTH, step=self.host_step,
                        health={"event": "graceful_preemption",
                                "step": self.host_step},
                    )
                    # Hard-exit durability: the supervisor SIGKILLs after
                    # its grace window, so make the JSONL durable and dump
                    # the flight recorder NOW, not at interpreter exit.
                    self.writer.telemetry.flush()
                    self.flightrec.dump("graceful_preemption")
                    break
                timer.step = self.host_step + 1
                with timer.phase("infeed"):
                    batch, self.data_ckpt_state = self._next_batch(infeed)
                # Fault injection (core/faults.py, DTF_FAULTS): crash_at_step
                # SIGKILLs here; nan_grads/repeat_nan poison this step's
                # batch (NaN provenance / escalation drills) and loss_spike
                # scales it by a large finite factor (EWMA z-score drill).
                for fault in faults.fire("step_begin", step=self.host_step + 1):
                    if fault.kind in ("nan_grads", "repeat_nan"):
                        batch = _poison_batch(batch)
                    elif fault.kind == "loss_spike":
                        batch = _scale_batch(batch, 1e4)
                if cfg.dispatch_ahead > 0 and len(pending) >= cfg.dispatch_ahead:
                    with timer.phase("backpressure"):
                        float(jax.device_get(
                            next(iter(pending.popleft().values()))))
                first_dispatch = self.collectives_summary is None
                # A dispatch that traces+compiles (first step, or the one
                # after a rollback rebuild) is recompile overhead in the
                # goodput ledger, not step compute.
                compiling = first_dispatch or self._recompile_pending
                with timer.phase("compile" if compiling else "dispatch",
                                 span="train_step"):
                    if first_dispatch:
                        # First dispatch traces/compiles the step; the
                        # tally sees every collective the executable will
                        # ever run (jit traces once per shape).
                        with coll.tally() as tly:
                            self.state, metrics = self.train_step(
                                self.state, batch)
                        self.collectives_summary = tly.summary()
                    else:
                        self.state, metrics = self.train_step(self.state, batch)
                if compiling:
                    self._recompile_pending = False
                    self.goodput.count("recompiles")
                    # An iteration that compiled is no ``slow_step``, and
                    # what JAX compiled under it no ``recompile``.
                    self._judged = timer.spans[-1]
                    self._announced.append(
                        (self._judged[2], self._judged[2] + self._judged[3]))
                if cfg.dispatch_ahead > 0:
                    pending.append(metrics)
                rec = self.recovery
                if rec is not None and rec.pending and rec.pending.unsent:
                    # A launched snapshot's next share sets out for the
                    # host behind this step, ahead of the next batch.
                    with timer.phase("snapshot"):
                        rec.send_pending()
                self.host_step += 1
                if not self._startup_emitted:
                    # Restart → first-step latency (restore + input build +
                    # compile): the number the persistent XLA compilation
                    # cache (core/platform.resolve_compilation_cache)
                    # exists to shrink. Taken as the first dispatch
                    # returns: the step is in flight, not complete.
                    self._startup_emitted = True
                    self._emit_startup(time.perf_counter() - self._init_t)
                    # Construction → first dispatch returned as one span:
                    # the relaunch cost a coordinated restart pays, and
                    # the segment the gang drill expects on the critical
                    # path after a supervisor-driven relaunch.
                    self.tracer.emit_span(
                        "train.startup", self.run_span,
                        start_mono=self._init_mono,
                        end_mono=time.monotonic(),
                        first_step=self.host_step,
                        restored_step=self._restored_step)
                    self._window_mono = time.monotonic()
                    self._window_step = self.host_step
                fetch = (
                    self.host_step % cfg.log_interval == 0
                    or self.host_step >= cfg.total_steps
                )
                host_metrics = None
                if fetch:
                    # Only here does the host fully sync with the device;
                    # off-interval steps dispatch asynchronously (at most
                    # dispatch_ahead deep). The slow-step check goes first:
                    # the device still has every step in flight to run, so
                    # it costs the sync nothing.
                    with timer.phase("bookkeeping"):
                        self._report_slow_steps()
                    # A launched snapshot lands here, likewise: the host
                    # waits for its bytes while the steps in flight run.
                    # Ahead of every fetch, so ahead of whatever a fetch
                    # leads to: a rollback, the next snapshot.
                    self._finish_snapshot()
                    with timer.phase("metrics_fetch"):
                        host_metrics = {
                            k: float(v)
                            for k, v in jax.device_get(metrics).items()
                        }
                    with timer.phase("bookkeeping"):
                        host_metrics.update(timer.means())
                        self._absorb_phases()
                        pending.clear()
                    # Recovery ladder rung (train/anomaly.py): a successful
                    # rollback returns None — the anomalous metrics never
                    # reach the hooks (no NaNGuard abort, no poisoned
                    # LoggingHook record) and host_step has been rewound.
                    # Its spans are its own (``snapshot``, ``rollback``).
                    host_metrics = self._maybe_recover(host_metrics)
                    with timer.phase("bookkeeping"):
                        self._fetch_bookkeeping()
                    if host_metrics is not None:
                        last_metrics = host_metrics
                for h, phase in hook_phases:
                    with timer.phase(phase):
                        h.after_step(self, self.host_step, host_metrics)
                if self.recovery is not None and self.recovery.exhausted:
                    # Finite-anomaly escalation (loss spike / grad-norm
                    # explosion past max_rollbacks): NaNGuardHook only
                    # fires on non-finite metrics, so the loop itself is
                    # the escalation tail here — also covers
                    # train.nan_guard=false runs. Dump the flight
                    # recorder FIRST: the ring holds the rollback spans
                    # and anomaly events leading up to this escalation,
                    # and the open worker.run span is its ancestry.
                    self.flightrec.dump("persistent_anomaly")
                    raise anomaly_lib.PersistentAnomalyError(
                        self.recovery.escalation_message(),
                        provenance=self.recovery.provenance(),
                    )
        finally:
            # Stop the background producer (async_infeed): it must not
            # keep pulling from the dataset the caller may reuse/restore.
            infeed.close()
            if self.recovery is not None:
                # Nothing past the loop rolls back: a snapshot still on
                # its way is dropped, and its device copy freed.
                self.recovery.drop_pending()
            if self._ckpt_manager is not None:
                # The final force-save (CheckpointHook.on_end) must not
                # poll a closed infeed's queue for its watermark.
                self._ckpt_manager.set_data_sources(watermark_source=None)
            # Absorb the tail phases accumulated since the last fetch even
            # on the escalation path (the final rollup below only runs on
            # clean exit; an escalating or SIGKILLed attempt is covered by
            # its last periodic snapshot).
            self._absorb_phases()
            # The loop timeline goes to disk on every way out of the loop
            # — right behind the flight recorder's dump on preemption and
            # escalation, and before the hooks' on_end (a final save can
            # outlast a supervisor's grace window).
            self._report_slow_steps(final=True)
            self._dump_timeline()
        for h in hooks:
            h.on_end(self)
        if self._ckpt_manager is not None:
            # Exit/preemption barrier for the async checkpoint pipeline:
            # CheckpointHook.on_end already flushes, but custom hook lists
            # may not include it — never return (and never let the CLI exit
            # rc 83) with a commit still in flight on the saver thread.
            self._ckpt_manager.wait_until_finished()
            if (self.runtime.process_count > 1
                    and self.config.checkpoint.directory):
                # Coordinator-led exit barrier (core/cluster.py): the
                # chief confirms its manifest commit record is durable and
                # every survivor waits on the same record before returning
                # — a worker that exits early tears down the jax.distributed
                # coordinator and can strand its peers' in-flight commits.
                cluster.exit_barrier(
                    self.config.checkpoint.directory,
                    step=self.host_step,
                    timeout_s=self.config.cluster.exit_barrier_timeout_s,
                    poll_s=self.config.cluster.exit_barrier_poll_s,
                    is_chief=self.runtime.is_chief,
                )
        # Finalize AFTER the exit barrier so the last ckpt_save's
        # blocked-ms lands in the rollup, not past it.
        self.goodput.finalize(step=self.host_step)
        self.memstats.sample(step=self.host_step, final=True)
        if self.run_span is not None:
            self.run_span.end(
                status="preempted" if self.preempted else "ok",
                end_step=self.host_step)
        return last_metrics

    def _enter_loop(self, hooks: list):
        """What ``train()`` owes before its first iteration: the lineage
        check, the run's root span, the hooks' ``on_start``, the infeed
        (returned), and the startup bucket's close."""
        ck = self.config.checkpoint
        if (self._ckpt_manager is not None and ck.restore_step >= 0
                and ck.restore_step < (self._ckpt_manager.latest_step() or 0)):
            # Saving a branched lineage into a directory that already holds
            # NEWER steps would silently no-op at every already-saved step
            # (CheckpointManager.save skips existing steps) and a restart
            # would re-restore restore_step, losing the branch. Evaluating
            # an old snapshot (--eval-only) is fine; branched TRAINING
            # needs a fresh directory.
            raise ValueError(
                f"checkpoint.restore_step={ck.restore_step} is older than "
                f"the directory's latest step "
                f"({self._ckpt_manager.latest_step()}) — training would "
                f"interleave two lineages. Copy the checkpoint into a "
                f"fresh checkpoint.directory to branch, or use --eval-only."
            )
        # The worker-side root span: parented on the supervisor's attempt
        # span (DTF_TRACE_CTX) when one launched us, a fresh trace
        # otherwise. Startup/step-window/ckpt/rollback spans chain under
        # it; left open on a crash so the flight recorder's open-span
        # snapshot still shows the fault's ancestry.
        self.run_span = self.tracer.start(
            "worker.run", self._trace_parent,
            process=self.runtime.process_index, start_step=self.host_step)
        for h in hooks:
            h.on_start(self)

        infeed = prefetch_to_device(
            self.dataset, self.mesh, size=self.config.data.prefetch,
            background=self.config.data.async_infeed,
            deadline_s=self.config.resilience.infeed_deadline_s,
        )
        if self._ckpt_manager is not None:
            # Every save records the prefetch watermark (batches the
            # producer ran ahead) in its data-state commit record — the
            # post-mortem "how far ahead was the infeed?" number.
            self._ckpt_manager.set_data_sources(
                watermark_source=infeed.watermark)
        if not self._startup_accounted:
            # Construction → loop entry (restore + input/eval build; the
            # first compile lands in the recompile bucket at dispatch).
            self._startup_accounted = True
            self.goodput.add(
                "startup", time.perf_counter() - self._init_t)
        return infeed

    def _emit_startup(self, time_to_first_step_s: float) -> None:
        """The ``startup`` event, with where the time went: the timer's
        spans up to now (frozen as its ``startup``), what lay between
        them, what the process had spent before construction, and what
        JAX traced, compiled and loaded meanwhile."""
        phases_s = self.timer.freeze_startup(self.host_step - 1)
        self.writer.telemetry.emit(
            telemetry.KIND_STARTUP, step=self.host_step,
            time_to_first_step_s=time_to_first_step_s,
            restored_step=self._restored_step,
            compilation_cache_dir=(
                jax.config.jax_compilation_cache_dir or None),
            phases_s={k: round(v, 6) for k, v in phases_s.items()},
            # What the caller did while it held the trainer, between the
            # program's spans (under train.py, the few statements there).
            outside_s=round(
                time_to_first_step_s - sum(phases_s.values()), 6),
            process_s=self._process_s,
            compile=self.timer.compile_summary(),
        )

    # ------------------------------------------------------- loop timeline --
    def _fetch_bookkeeping(self) -> None:
        """What a metrics-fetch iteration owes after the fetch: the
        goodput and memory cadences, the packing census and the
        step-window span."""
        self.goodput.maybe_emit(step=self.host_step)
        self.memstats.maybe_sample(step=self.host_step)
        # Packing census (data/packing.py counters riding the iterator
        # state): goodput per padded token, emitted at the same cadence
        # as the metrics fetch. Cumulative counters — the last event of
        # an attempt is its total.
        real = self.data_ckpt_state.get(packing.REAL_TOKENS_KEY)
        if real is not None:
            self.writer.telemetry.emit(
                telemetry.KIND_DATA_PACKING, step=self.host_step,
                metrics=packing.packing_stats(
                    int(real),
                    int(self.data_ckpt_state.get(
                        packing.PADDED_TOKENS_KEY, 0))))
        # One span per log-interval window of steps — coarse enough to
        # stay cheap, fine enough that a gang restart's dead time shows
        # as a gap between the last window of attempt N and startup of
        # attempt N+1.
        now_mono = time.monotonic()
        self.tracer.emit_span(
            "train.steps", self.run_span,
            start_mono=getattr(self, "_window_mono", now_mono),
            end_mono=now_mono,
            start_step=getattr(self, "_window_step", self.host_step),
            end_step=self.host_step)
        self._window_mono = now_mono
        self._window_step = self.host_step

    def _absorb_phases(self) -> None:
        """Fold the timer's totals into the goodput ledger and start them
        anew; a hook that charges the ledger itself is left out."""
        for phase in self._self_charged:
            self.timer.totals.pop(phase, None)
        self.goodput.absorb_phases(self.timer.totals)
        self.timer.reset()

    def _report_slow_steps(self, final: bool = False) -> None:
        """One ``slow_step`` health event per iteration, among those
        completed since the last call, that spent far longer on the host
        than the block's median (``profiling.slow_iterations``): which
        step lost the time, and under which span. Called ahead
        of the fetch, while the device is busy. The iteration still
        running (this fetch's own: its fetch and hooks are yet to come)
        waits for the next call, unless ``final``. And one ``recompile``
        event per compile that the loop did not ask for."""
        if self.timer.compiles.logged != self._compiles_seen:
            self._report_recompiles()
        block = []
        for span in reversed(self.timer.spans):
            if span is self._judged:
                break
            if final or span[1] != self.timer.step:
                block.append(span)
        if not block:
            return
        self._judged = block[0]
        for slow in profiling.slow_iterations(reversed(block)):
            log.info("slow step %d: %.1f ms on the host (block median "
                     "%.1f ms): %s", slow["step"], slow["host_ms"],
                     slow["block_median_ms"], slow["spans_ms"])
            self.writer.telemetry.emit(
                telemetry.KIND_HEALTH, step=slow["step"],
                health={"event": "slow_step", **slow})

    def _report_recompiles(self) -> None:
        """One ``recompile`` health event per backend compile or cache
        load that the compile log has gained since the last call, that
        began under no span of the loop's own ``compile`` phase (the
        first step, the step after a rollback's rebuild) and that took,
        with the tracing and lowering ahead of it, over
        ``profiling.SLOW_FLOOR_MS``: the function's name, the step, and
        the ring span it began under — a device wait included, where
        another thread compiled while the loop waited."""
        log_ = self.timer.compiles
        now_ns = time.time_ns()
        entries = log_.since(self._compiles_seen_ns)
        self._compiles_seen, self._compiles_seen_ns = log_.logged, now_ns
        announced, self._announced = self._announced, []
        lead_ns = 0  # tracing and lowering since the last backend compile
        for kind, fun_name, start_ns, duration_ns, cache_hit, xla_ns in entries:
            if kind != "xla":
                lead_ns += duration_ns - xla_ns
                continue
            trace_ns, lead_ns = lead_ns, 0
            if ((trace_ns + duration_ns) * 1e-6 <= profiling.SLOW_FLOOR_MS
                    or any(a <= start_ns < b for a, b in announced)):
                continue
            under = None  # the ring is in order of ends: stop at an earlier one
            for s in reversed(self.timer.spans):
                if s[2] + s[3] <= start_ns:
                    break
                if s[2] <= start_ns:
                    under = s
                    break
            found = {
                "event": "recompile",
                "step": under[1] if under else self.timer.step,
                "fun_name": fun_name,
                "trace_ms": round(trace_ns * 1e-6, 3),
                "xla_ms": round(duration_ns * 1e-6, 3),
                "cache_hit": cache_hit,
                "under": under[0] if under else None}
            log.warning(
                "step %d compiled %s under %s: %.1f ms tracing and "
                "lowering, %.1f ms in XLA (cache hit: %s)", found["step"],
                fun_name, found["under"], found["trace_ms"],
                found["xla_ms"], cache_hit)
            self.writer.telemetry.emit(
                telemetry.KIND_HEALTH, step=found["step"], health=found)

    def _dump_timeline(self) -> str | None:
        """``loop_timeline-<pid>.json`` beside the flight recorder's
        dump (same directory resolution); no directory, no file."""
        base = self.flightrec.directory()
        if base is None:
            return None
        return self.timer.dump(
            os.path.join(base, f"loop_timeline-{os.getpid()}.json"),
            final_step=self.host_step)

    # ----------------------------------------------------- recovery ladder --
    def _next_batch(self, infeed):
        """One infeed pull behind the stall watchdog (data/infeed.py).

        With ``resilience.infeed_deadline_s`` armed, a pull that exceeds
        the deadline raises ``InfeedStallError``; the retry here waits out
        the SAME pull (the watchdog reports, it does not cancel) with
        linear backoff, emitting an ``infeed_stall`` event per attempt.
        Past ``infeed_retries`` the error propagates — the supervisor's
        heartbeat watchdog rung takes over.
        """
        rcfg = self.config.resilience
        attempt = 0
        while True:
            try:
                return next(infeed)
            except InfeedStallError as e:
                attempt += 1
                self.writer.telemetry.emit(
                    telemetry.KIND_INFEED_STALL, step=self.host_step,
                    health={"deadline_s": e.deadline_s, "attempt": attempt,
                            "max_retries": rcfg.infeed_retries},
                )
                if attempt > rcfg.infeed_retries:
                    log.error(
                        "infeed stalled past %d retries — escalating",
                        rcfg.infeed_retries,
                    )
                    raise
                backoff = rcfg.infeed_backoff_s * attempt
                log.warning(
                    "infeed stall (attempt %d/%d, deadline %.1fs) — "
                    "retrying in %.2fs", attempt, rcfg.infeed_retries,
                    e.deadline_s, backoff,
                )
                time.sleep(backoff)

    def _snapshot(self, force: bool = False) -> None:
        """The ladder's copy of the train state, when one is due, under
        the ``snapshot`` span and the ``snapshots`` / ``snapshot_bytes``
        goodput counters: launched on the device to land beside the next
        steps (``snapshots_overlapped``) where the recovery manager takes
        that path, else the blocking device→host copy — the baseline's
        (``force``: nothing is in flight to hide a transfer behind)."""
        rec = self.recovery
        if not rec.snapshot_due(self.host_step, force):
            return
        with self.timer.phase("snapshot"):
            overlapped = not force and rec.launch_snapshot(
                self.host_step, self.state, data_state=self.data_ckpt_state,
                step_temp_bytes=self._step_temp_bytes,
                # in shares, one behind each step up to the next fetch
                spread_over=self.config.train.log_interval)
            took = overlapped or rec.take_snapshot(
                self.host_step, self.state,
                data_state=self.data_ckpt_state, force=force)
        if took:
            self.goodput.count("snapshots")
            self.goodput.count(
                "snapshot_bytes",
                (rec.pending if overlapped else rec.ring.latest()).nbytes)
        if overlapped:
            self.goodput.count("snapshots_overlapped")

    def _finish_snapshot(self) -> None:
        """A launched snapshot into the ring, under the ``snapshot`` span;
        ``snapshot_finish_wait_s`` counts the host seconds that blocked."""
        rec = self.recovery
        if rec is None or rec.pending is None:
            return
        with self.timer.phase("snapshot"):
            waited = rec.finish_pending()
        self.goodput.count("snapshot_finish_wait_s", waited)

    def _step_temp_bytes(self) -> int | None:
        """The compiled step's temporaries by its own memory analysis,
        for the snapshot's headroom test (made once); None where there is
        none. After the first dispatch the lowering and the executable
        are cached: nothing is traced or compiled here."""
        try:
            compiled = self.train_step.lower(self.state, self._sample).compile()
        except Exception:
            log.exception("no memory analysis of the train step")
            return None
        analysis = memstats.compiled_memory_analysis(compiled) or {}
        return analysis.get("temp_bytes")

    def _maybe_recover(self, host_metrics: dict[str, float]) -> dict[str, float] | None:
        """Classify a fetched-metrics step; roll back if anomalous.

        Returns the metrics unchanged for clean steps (after feeding the
        EWMA baseline and opportunistically snapshotting), None when a
        rollback consumed the anomaly (host_step is rewound; the hooks
        must not see the poisoned metrics), and the ANOMALOUS metrics with
        ``recovery.exhausted`` set when the ladder is out of rungs — the
        caller escalates after the hooks run.
        """
        rec = self.recovery
        if rec is None:
            return host_metrics
        with self.timer.phase("bookkeeping"):
            verdict = rec.classify(self.host_step, host_metrics)
        if verdict is None:
            self._snapshot()
            return host_metrics
        if not rec.can_rollback():
            rec.exhausted = True
            return host_metrics
        from_step = self.host_step
        t_rb = time.monotonic()
        with self.timer.phase("rollback"):
            self.state, snap = rec.rollback(self.state, from_step=self.host_step)
            # Skip-batch semantics: host_step rewinds, the data iterator
            # does NOT — the replayed step range consumes fresh batches and
            # the poisoned region is never re-fed. Record WHICH consumed
            # ordinals were skipped into the iterator state, so a restart
            # that restores a pre-rollback data state replays the stream
            # with those ordinals discarded instead of double-counting
            # them (docs/RESILIENCE.md "Exactly-once data").
            snap_consumed = int((snap.data_state or {}).get("consumed", 0))
            live_consumed = int(self.data_ckpt_state.get("consumed", 0))
            if live_consumed > snap_consumed:
                skipped = range(snap_consumed + 1, live_consumed + 1)
                self.dataset.record_skipped(skipped)
                # REBIND into the step-aligned snapshot too (never mutate:
                # queued save snapshots share nested lists by reference) —
                # the next checkpoint's data state must carry the record.
                merged = sorted(
                    {int(o) for o in
                     self.data_ckpt_state.get("batches_skipped", ())}
                    | set(skipped))
                self.data_ckpt_state = {
                    **self.data_ckpt_state, "batches_skipped": merged}
            self.host_step = snap.step
            if self.config.resilience.lr_rewarmup_steps > 0:
                self._rebuild_with_rewarmup(snap.step)
        # Into the ledger now: this fetch's goodput event, and a crash
        # before the next fetch, must find the rollback in its bucket.
        self._absorb_phases()
        self.tracer.emit_span(
            "train.rollback", self.run_span,
            start_mono=t_rb, end_mono=time.monotonic(),
            from_step=from_step, to_step=snap.step)
        return None

    def _rebuild_with_rewarmup(self, resume_step: int) -> None:
        """Swap the LR schedule for a re-warmed copy and re-jit the step.

        optax schedule state is a bare step counter, so the restored
        opt_state is structurally identical under the new chain — the
        rebuild costs one recompile (same shapes, warm XLA cache), not a
        state migration.
        """
        steps = self.config.resilience.lr_rewarmup_steps
        log.info(
            "re-warming learning rate over steps [%d, %d) after rollback",
            resume_step, resume_step + steps,
        )
        self.builder.set_schedule_wrapper(
            lambda sched: schedules.with_rewarmup(sched, resume_step, steps))
        self.train_step = self.builder.make_train_step(self._sample)
        self._recompile_pending = True

    # ---------------------------------------------------------------- eval --
    def _ensure_eval(self):
        """Build the eval pipeline + compiled eval step ONCE per eval
        config; reused across every EvalHook firing and final eval
        (rebuilding the TFRecord pipeline per call was the round-1 waste).
        Swapping ``config.eval_data`` invalidates the cache — the next
        evaluate() rebuilds pipeline AND compiled step."""
        eval_cfg = self.config.eval_data or self.config.data
        if getattr(self, "_eval_ds", None) is None or \
                getattr(self, "_eval_cfg", None) is not eval_cfg:
            if getattr(self, "_eval_cfg", None) is not None \
                    and self._eval_cfg is not eval_cfg:
                self.eval_step = None  # element spec may differ — recompile
            self._eval_cfg = eval_cfg
            self._eval_ds = get_dataset(
                eval_cfg,
                process_index=self.runtime.process_index,
                process_count=self.runtime.process_count,
                train=False,
            )
            self._eval_start = self._eval_ds.state()
            sample_host = next(self._eval_ds)
            self._eval_ds.restore(self._eval_start)
            if self.eval_step is None:
                self.eval_step = self.builder.make_eval_step(
                    to_global(sample_host, self.mesh)
                )
        return self._eval_ds

    def evaluate(self, step: int | None = None, num_batches: int | None = None) -> dict[str, float]:
        """Exact evaluation (SURVEY.md §3.4 eval-loop contract).

        Finite eval streams (real datasets) are walked in ONE full pass —
        every validation example exactly once, padded final batch masked
        by per-example weights — and metrics are weighted means over real
        examples. Infinite streams (synthetic fallback) evaluate
        ``train.eval_steps`` batches. ``num_batches`` truncates either.
        """
        if self.state is None:
            self.build()
        ds = self._ensure_eval()
        ds.restore(self._eval_start)  # fresh pass every call
        if num_batches is not None:
            n = num_batches
            if ds.cardinality is not None:
                # Exact-by-construction: never trust config arithmetic to
                # reproduce the set size. num_batches >= cardinality means
                # "the full set" (clamped); below it is an explicit
                # truncation, surfaced loudly because a silently dropped
                # tail (e.g. eval_steps=12 vs 50k/4096=12.2) biases every
                # mid-training accuracy ever logged.
                if n >= ds.cardinality:
                    n = ds.cardinality
                else:
                    log.warning(
                        "eval truncated: %d of %d batches (set "
                        "train.eval_steps >= %d for full coverage)",
                        n, ds.cardinality, ds.cardinality,
                    )
        elif ds.cardinality is not None:
            n = ds.cardinality  # exact: the full validation set
        else:
            n = self.config.train.eval_steps
        totals: dict[str, float] = {}
        for i, (batch, _) in enumerate(prefetch_to_device(ds, self.mesh, size=2)):
            if i >= n:
                break
            m = jax.device_get(self.eval_step(self.state, batch))
            for k, v in m.items():
                totals[k] = totals.get(k, 0.0) + float(v)
        weight = totals.pop("weight_sum", 0.0)
        denom = max(weight, 1e-9)
        results = {
            f"eval_{k[: -len('_sum')]}": v / denom for k, v in totals.items()
        }
        # Real examples seen (masked tokens for MLM) — lets callers confirm
        # full-set coverage (e.g. 50000 for ImageNet validation).
        results["eval_examples"] = weight
        if step is not None:
            self.writer.write(step, results, kind=telemetry.KIND_EVAL)
        return results
