"""Training hooks — MonitoredTrainingSession's hook set, SPMD-style.

SURVEY.md §2 row 10: the reference's loop runs under
MonitoredTrainingSession with StopAtStepHook, NanTensorHook, checkpoint
saver and summary saver hooks. Same extension points here, as plain Python
objects driven by the Trainer. Hooks only ever touch host-side metric
values (already-fetched scalars) so they never force extra device syncs.
"""

from __future__ import annotations

import json
import logging
import math
import os
import time
from typing import Any, Mapping, Protocol

from distributed_tensorflow_framework_tpu.core import telemetry

log = logging.getLogger(__name__)


class Hook(Protocol):
    def on_start(self, trainer: Any) -> None: ...
    def after_step(self, trainer: Any, step: int,
                   metrics: Mapping[str, float] | None) -> None: ...
    def on_end(self, trainer: Any) -> None: ...


class BaseHook:
    # True on a hook whose ``after_step`` wall reaches the goodput ledger
    # through an event of its own: the loop then keeps its ``hook:*``
    # phase out of the ``hooks`` bucket, or the buckets would pass the
    # wall. (Read with ``getattr``: a hook need not derive from this.)
    charges_goodput_itself = False

    def on_start(self, trainer) -> None:
        pass

    def after_step(self, trainer, step, metrics) -> None:
        pass

    def on_end(self, trainer) -> None:
        pass


class NaNGuardHook(BaseHook):
    """NanTensorHook analogue: abort when the loss goes non-finite.

    Checks only at metric-fetch steps (metrics is None otherwise) to avoid
    per-step device→host syncs. The abort carries provenance — which
    metric, which step, and the last-good checkpoint to restart from — and
    lands in the run's telemetry as a ``failure`` event, so post-mortems
    don't start from a bare stack trace.

    With the in-process recovery ladder armed (train/anomaly.py) this hook
    is the ladder's ESCALATION TAIL, not the first responder: a rolled-back
    anomaly never reaches it (the Trainer suppresses the poisoned metrics),
    so a non-finite value here means the ladder is exhausted — the abort
    becomes ``PersistentAnomalyError`` carrying the ladder's provenance,
    which cli/train.py maps to supervision.ANOMALY_ESCALATION_RC so the
    supervisor can classify poisoned-data-region vs transient.
    """

    def after_step(self, trainer, step, metrics) -> None:
        if metrics is None:
            return
        for name, v in metrics.items():
            try:
                val = float(v)  # accepts python/numpy scalars + 0-d arrays
            except (TypeError, ValueError):
                continue
            if not math.isfinite(val):
                ckpt = self._last_good_checkpoint(trainer)
                self._emit_failure(trainer, step, name, v, ckpt)
                restart = (
                    f"restart from {ckpt}" if ckpt
                    else "no checkpoint saved — restart from scratch"
                )
                rec = getattr(trainer, "recovery", None)
                if rec is not None and rec.exhausted:
                    from distributed_tensorflow_framework_tpu.train.anomaly import (
                        PersistentAnomalyError)

                    raise PersistentAnomalyError(
                        f"{rec.escalation_message()} Non-finite metric "
                        f"{name}={v} at step {step}. Last good checkpoint: "
                        f"{restart}.",
                        provenance=rec.provenance(),
                    )
                raise FloatingPointError(
                    f"Non-finite metric {name}={v} at step {step} — aborting "
                    f"(NaNGuardHook; reference NanTensorHook contract). "
                    f"Last good checkpoint: {restart}."
                )

    @staticmethod
    def _last_good_checkpoint(trainer) -> str | None:
        mgr = getattr(trainer, "_ckpt_manager", None)
        if mgr is None:
            return None
        try:
            last = mgr.latest_step()
        except Exception:
            return None
        if last is None:
            return None
        return os.path.join(trainer.config.checkpoint.directory, str(last))

    @staticmethod
    def _emit_failure(trainer, step, name, value, ckpt) -> None:
        writer = getattr(trainer, "writer", None)
        if writer is None or not hasattr(writer, "telemetry"):
            return
        writer.telemetry.emit(
            telemetry.KIND_FAILURE,
            step=step,
            health={"failure": "non_finite_metric", "metric": name,
                    "value": str(value),
                    "last_good_checkpoint": ckpt or ""},
        )


class ThroughputHook(BaseHook):
    """Tracks examples/sec(/chip) — the BASELINE.json tracked metric."""

    def __init__(self, batch_size: int, num_chips: int):
        from distributed_tensorflow_framework_tpu.core.metrics import ThroughputMeter

        self.batch_size = batch_size
        self.meter = ThroughputMeter(num_chips)

    def on_start(self, trainer) -> None:
        self.meter.start()

    def after_step(self, trainer, step, metrics) -> None:
        self.meter.update(self.batch_size)

    def rates(self) -> dict[str, float]:
        return self.meter.rates()


class LoggingHook(BaseHook):
    def __init__(self, writer, interval: int, throughput: ThroughputHook | None = None):
        self.writer = writer
        self.interval = max(1, interval)
        self.throughput = throughput

    def after_step(self, trainer, step, metrics) -> None:
        # The Trainer only fetches metrics at its own log cadence; the
        # interval here additionally guards custom loops that fetch more
        # often (final step always logs).
        if metrics is None:
            return
        if step % self.interval and step < trainer.config.train.total_steps:
            return
        out = dict(metrics)
        if self.throughput is not None:
            out.update(self.throughput.rates())
            self.throughput.meter.reset()
        self.writer.write(
            step, out,
            collectives=getattr(trainer, "collectives_summary", None),
        )


class CheckpointHook(BaseHook):
    """Interval saver. With ``checkpoint.async_save`` on, ``save`` returns
    after the device→host snapshot and the commit (orbax write + manifest
    + fsync) lands on the background saver thread — the step loop is not
    blocked for the write. ``on_end`` is the flush path: the final
    force-save plus ``wait_until_finished`` block until every in-flight
    commit is durable, so both normal completion and SIGTERM graceful
    preemption (rc 83) exit with nothing half-written."""

    # ``save()`` is where this hook blocks, and the saver's ``ckpt_save``
    # event charges that wall to ``ckpt_blocked``.
    charges_goodput_itself = True

    def __init__(self, manager, interval: int):
        self.manager = manager
        self.interval = max(1, interval)

    def _traced_save(self, trainer, step: int, *, force: bool = False):
        """Save under a ``ckpt.save`` span when the trainer carries a
        tracer + run span (core/tracing.py) — with async_save on, the
        span covers the device→host snapshot the step loop actually
        blocks on, not the background commit."""
        tracer = getattr(trainer, "tracer", None)
        run_span = getattr(trainer, "run_span", None)
        span = (tracer.start("ckpt.save", run_span, step=step, force=force)
                if tracer is not None and run_span is not None else None)
        try:
            self.manager.save(step, trainer.state,
                              dataset_state=trainer.data_ckpt_state,
                              force=force)
        finally:
            if span is not None:
                span.end()

    def after_step(self, trainer, step, metrics) -> None:
        if step > 0 and step % self.interval == 0:
            self._traced_save(trainer, step)

    def on_end(self, trainer) -> None:
        self._traced_save(trainer, int(trainer.host_step), force=True)
        self.manager.wait_until_finished()


class HeartbeatHook(BaseHook):
    """Liveness file for external watchdogs (scripts/train_resilient.py).

    Atomically rewrites a small JSON file — run_id, pid, the last COMPLETED
    step, wall time, the last fetched metrics — every ``min_interval_s`` of
    wall time. A supervisor distinguishes "slow" from "wedged" by the
    record's age instead of attaching a debugger to a silent process (the
    XLA:CPU collective-freeze failure mode, core/platform.py), and asserts
    forward progress — not just liveness — from ``last_completed_step``.

    Write discipline: pid-suffixed temp file (a dying predecessor's
    half-written temp can never collide with ours), contents fsync'd, then
    one atomic ``os.replace`` — readers see the old record or the new one,
    never a torn file, on every platform where replace is atomic (POSIX
    and Windows alike).
    """

    def __init__(self, path: str, *, min_interval_s: float = 10.0):
        self.path = path
        self.min_interval_s = min_interval_s
        self._last_write = 0.0
        self._last_metrics: dict | None = None

    def on_start(self, trainer) -> None:
        self._write(trainer, step=int(trainer.host_step), status="running")

    def after_step(self, trainer, step, metrics) -> None:
        if metrics is not None:
            self._last_metrics = {k: float(v) for k, v in metrics.items()}
        now = time.time()
        if now - self._last_write >= self.min_interval_s:
            self._write(trainer, step=step, status="running", now=now)

    def on_end(self, trainer) -> None:
        status = ("preempted" if getattr(trainer, "preempted", False)
                  else "finished")
        self._write(trainer, step=int(trainer.host_step), status=status)

    def _write(self, trainer, *, step, status, now=None) -> None:
        now = time.time() if now is None else now
        record = {
            "schema": telemetry.SCHEMA,
            "run_id": getattr(trainer, "run_id", ""),
            "status": status,
            # "step" kept for readers of the original record shape;
            # last_completed_step is the explicit progress counter the
            # watchdog's crash-loop accounting uses.
            "step": step,
            "last_completed_step": step,
            "t": now,
            "pid": os.getpid(),
            "last_metrics": self._last_metrics,
        }
        tmp = f"{self.path}.{os.getpid()}.tmp"
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        with open(tmp, "w") as fh:
            json.dump(record, fh)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, self.path)  # atomic: readers never see a torn file
        self._last_write = now


class MoECollapseHook(BaseHook):
    """Detects expert-routing collapse from the step metrics.

    Collapse signatures (models/moe.py): ``moe_drop_frac`` climbing toward
    1 - 1/num_experts (all tokens racing to one expert, the rest dropped
    by capacity) and ``moe_aux_loss`` rising well above its balanced value
    of ~1.0. Either alone can be a transient; this hook warns loudly —
    structured, with the run context — once a threshold holds for
    ``patience`` consecutive metric fetches, and emits a telemetry
    ``health`` event so the collapse is visible in the run's event stream,
    not just the console. It never aborts: collapsed runs often still
    carry signal and the operator may want the checkpoint.
    """

    def __init__(self, *, drop_frac_threshold: float = 0.35,
                 aux_loss_threshold: float = 2.0, patience: int = 2):
        self.drop_frac_threshold = drop_frac_threshold
        self.aux_loss_threshold = aux_loss_threshold
        self.patience = max(1, patience)
        self._streak = 0
        self.fired_steps: list[int] = []

    def after_step(self, trainer, step, metrics) -> None:
        if metrics is None:
            return
        drop = metrics.get("moe_drop_frac")
        aux = metrics.get("moe_aux_loss")
        if drop is None and aux is None:
            return
        violations = {}
        if drop is not None and float(drop) > self.drop_frac_threshold:
            violations["moe_drop_frac"] = {
                "value": float(drop), "threshold": self.drop_frac_threshold}
        if aux is not None and float(aux) > self.aux_loss_threshold:
            violations["moe_aux_loss"] = {
                "value": float(aux), "threshold": self.aux_loss_threshold}
        if not violations:
            self._streak = 0
            return
        self._streak += 1
        if self._streak < self.patience:
            return
        self.fired_steps.append(step)
        payload = {
            "warning": "moe_collapse",
            "step": step,
            "streak": self._streak,
            "violations": violations,
        }
        log.warning("MOE COLLAPSE SUSPECTED %s", json.dumps(payload))
        writer = getattr(trainer, "writer", None)
        if writer is not None and hasattr(writer, "telemetry"):
            writer.telemetry.emit(
                telemetry.KIND_HEALTH, step=step,
                health={"warning": "moe_collapse", "streak": self._streak,
                        **{f"{k}_value": v["value"]
                           for k, v in violations.items()}},
            )


class ProfileHook(BaseHook):
    """Captures an XPlane trace over steps [start, stop) — the analogue of
    the reference's tf.profiler/timeline option (SURVEY.md §5).

    Alongside the trace it writes the compiled train step's optimized HLO
    (``train_step.hlo.txt``) when the Trainer captured it: trace events
    carry bare HLO instruction names, and the HLO text's op_name metadata
    is what lets scripts/analyze_trace.py attribute them to named scopes
    (optimizer_update etc.)."""

    def __init__(self, logdir: str, start: int, stop: int):
        self.logdir = logdir
        # after_step first fires at step=1, so a start of 0 means "from the
        # beginning"; the trace then covers steps (start, stop].
        self.start = max(1, start)
        self.stop = stop
        self._active = False

    def _dump_hlo(self, trainer) -> None:
        hlo = getattr(trainer, "compiled_hlo", None)
        if not hlo:
            return
        os.makedirs(self.logdir, exist_ok=True)
        path = os.path.join(self.logdir, "train_step.hlo.txt")
        with open(path, "w") as fh:
            fh.write(hlo)
        log.info("wrote compiled HLO for trace attribution: %s", path)

    def after_step(self, trainer, step, metrics) -> None:
        import jax

        if step >= self.start and step < self.stop and not self._active:
            self._dump_hlo(trainer)
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif step >= self.stop and self._active:
            jax.block_until_ready(trainer.state.params)
            jax.profiler.stop_trace()
            self._active = False

    def on_end(self, trainer) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False


class EvalHook(BaseHook):
    """Mid-training eval — the reference's eval loop (SURVEY.md §3.4).

    ``num_batches`` caps each firing (train.eval_steps); None walks the
    full validation set every interval — usually only wanted for small
    sets.
    """

    def __init__(self, eval_fn, interval: int, *, num_batches: int | None = None):
        self.eval_fn = eval_fn
        self.interval = max(1, interval)
        self.num_batches = num_batches

    def after_step(self, trainer, step, metrics) -> None:
        if step > 0 and step % self.interval == 0:
            self.eval_fn(step, num_batches=self.num_batches)
