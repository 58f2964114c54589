"""In-process anomaly detection + in-memory rollback (docs/RESILIENCE.md).

PR 2's recovery contract is *kill → relaunch → resume*: correct, but the
most expensive path we have (relaunch + restore + recompile) and overkill
for a single poisoned batch or transient loss spike. The systems in this
framework's lineage (TensorFlow's fault-tolerance story, TF-Replicator's
researcher-facing resilience contract) recover from transient numeric
faults *in process*; this module is that rung of the ladder:

  detect (here)  →  rollback + skip batch (train/loop.py)  →
  LR re-warmup (train/schedules.py, optional)  →
  escalate (NaNGuardHook → ANOMALY_ESCALATION_RC) only when the anomaly
  survives ``max_rollbacks`` consecutive recoveries.

Detection reads ONLY already-on-host metrics (the Trainer's metric-fetch
cadence), so the ladder adds no device syncs to off-interval steps. The
rollback ring holds device→host snapshots of the train state (the same
pack/unpack discipline as the async checkpoint pipeline, minus the disk):
restoring one costs a host→device transfer instead of a process relaunch.

A periodic snapshot is taken in two phases where the device has room for
it: ``launch_snapshot`` copies the state into device buffers of its own
(the next step donates the state's) and starts their transfer to the
host, the loop goes on dispatching, and ``finish_pending`` takes the
landed bytes into the ring — before anything reads them: the loop's next
fetch, a rollback, the next snapshot. Same step, same bytes, same ring;
only when the host holds them moves. Where the device has no room (or
cannot say), ``take_snapshot``'s blocking copy runs as it always has.

Skip-batch semantics: a rollback restores MODEL state only — the data
iterator is deliberately NOT rewound. The batches consumed between the
snapshot and the anomaly (including the offending one) are gone from the
stream, so resuming forward replays the step COUNT with fresh data. That
is the point: re-feeding the poisoned batch would reproduce the anomaly.

Every rung emits versioned telemetry (``anomaly_detected`` / ``rollback``
/ ``batch_skipped``) rolled up by scripts/analyze_trace.py run summaries.
"""

from __future__ import annotations

import collections
import logging
import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from distributed_tensorflow_framework_tpu.core import telemetry
from distributed_tensorflow_framework_tpu.core.config import ResilienceConfig

log = logging.getLogger(__name__)


class PersistentAnomalyError(FloatingPointError):
    """The recovery ladder is exhausted: ``max_rollbacks`` consecutive
    rollbacks each landed back on an anomalous step (a poisoned data
    region, not a transient). Subclasses FloatingPointError so callers of
    the pre-ladder NaNGuardHook contract keep catching it; cli/train.py
    maps it to supervision.ANOMALY_ESCALATION_RC so the supervisor can
    classify the relaunch without feeding the crash-loop breaker.
    """

    def __init__(self, message: str, provenance: dict | None = None):
        super().__init__(message)
        self.provenance = provenance or {}


@dataclass
class Verdict:
    """One anomalous classification: what fired, on which metric."""

    anomaly: str            # non_finite_metric | loss_spike | grad_norm_explosion
    metric: str
    value: float | str
    step: int
    detail: dict = field(default_factory=dict)

    def to_health(self) -> dict:
        return {"anomaly": self.anomaly, "metric": self.metric,
                "value": str(self.value), **self.detail}


class AnomalyDetector:
    """Classify a step from its already-fetched host metrics.

    Three checks, cheapest first:
      * non-finite value in ANY numeric metric (the NanTensorHook class);
      * finite ``grad_norm`` above the hard ceiling ``grad_norm_max``;
      * ``loss`` more than ``loss_spike_zscore`` EWMA standard deviations
        above its running mean (needs ``min_observations`` clean fetches
        of warmup before it may fire — a cold EWMA has no baseline).

    ``observe`` feeds the EWMA and must only be called with CLEAN metrics
    — an anomalous loss folded into the baseline would teach the detector
    that spikes are normal.
    """

    def __init__(self, cfg: ResilienceConfig):
        self.cfg = cfg
        self._n = 0
        self._mean = 0.0
        self._var = 0.0

    @property
    def observations(self) -> int:
        return self._n

    @property
    def mean(self) -> float:
        return self._mean

    @property
    def std(self) -> float:
        # Relative floor: a near-constant loss has ~zero EWMA variance and
        # would flag numeric jitter as an infinite-z spike.
        return max(math.sqrt(max(self._var, 0.0)),
                   1e-3 * abs(self._mean), 1e-8)

    def observe(self, metrics: Mapping[str, float]) -> None:
        loss = _finite_float(metrics.get("loss"))
        if loss is None:
            return
        if self._n == 0:
            self._mean, self._var = loss, 0.0
        else:
            beta = self.cfg.loss_ewma_beta
            diff = loss - self._mean
            self._mean += (1.0 - beta) * diff
            self._var = beta * (self._var + (1.0 - beta) * diff * diff)
        self._n += 1

    def classify(self, step: int, metrics: Mapping[str, float]) -> Verdict | None:
        for name, v in metrics.items():
            try:
                val = float(v)
            except (TypeError, ValueError):
                continue
            if not math.isfinite(val):
                return Verdict("non_finite_metric", name, v, step)
        gmax = self.cfg.grad_norm_max
        gnorm = _finite_float(metrics.get("grad_norm"))
        if gmax > 0 and gnorm is not None and gnorm > gmax:
            return Verdict("grad_norm_explosion", "grad_norm", gnorm, step,
                           detail={"grad_norm_max": gmax})
        zmax = self.cfg.loss_spike_zscore
        loss = _finite_float(metrics.get("loss"))
        if (zmax > 0 and loss is not None
                and self._n >= max(1, self.cfg.min_observations)):
            z = (loss - self._mean) / self.std
            if z > zmax:
                return Verdict("loss_spike", "loss", loss, step,
                               detail={"zscore": round(z, 2),
                                       "ewma_mean": round(self._mean, 6),
                                       "ewma_std": round(self.std, 6)})
        return None


def _finite_float(v: Any) -> float | None:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if math.isfinite(f) else None


# ---------------------------------------------------------------- snapshots

# What the headroom test keeps free on every device beside the copy, as a
# share of the allocator's limit.
OVERLAP_MARGIN = 0.05


def _pack(state: Any) -> Any:
    """The typed PRNG key as raw key data (the same discipline as
    ckpt/checkpoint.py's ``_pack``), so the tree is plain arrays."""
    return state.replace(rng=jax.random.key_data(state.rng))


def snapshot_state(state: Any) -> tuple[Any, Any]:
    """Device→host copy of a TrainState, checkpoint-style packed: the
    blocking path. Returns ``(host_tree, shardings_tree)`` — the
    shardings are captured so the restore lands every leaf on its
    original mesh placement, not a default device.
    """
    packed = _pack(state)
    shardings = jax.tree.map(lambda x: x.sharding, packed)
    host = jax.device_get(packed)
    return host, shardings


def restore_state(host: Any, shardings: Any, like: Any) -> Any:
    """Host→device restore of ``snapshot_state`` output. ``like`` is any
    live TrainState (its rng carries the key impl to re-wrap with)."""
    dev = jax.tree.map(jax.device_put, host, shardings)
    impl = jax.random.key_impl(like.rng)
    return dev.replace(rng=jax.random.wrap_key_data(dev.rng, impl=impl))


def _copy_packed(state: Any) -> Any:
    """Traced: the packed state in buffers of its own."""
    return jax.tree.map(jnp.copy, _pack(state))


def state_nbytes(state: Any) -> tuple[int, int]:
    """``(bytes of the packed state, most of them on any one device)``,
    from shapes, dtypes and shardings alone: nothing is read."""
    total = 0
    on_device: dict = {}
    for leaf in jax.tree.leaves(state):
        nbytes = int(getattr(leaf, "nbytes", 0))
        total += nbytes
        sharding = getattr(leaf, "sharding", None)
        if sharding is None or not nbytes:
            continue
        shard = math.prod(sharding.shard_shape(leaf.shape)) * (
            nbytes // max(1, leaf.size))
        for d in sharding.addressable_devices:
            on_device[d] = on_device.get(d, 0) + shard
    return total, max(on_device.values(), default=0)


def device_memory(device: Any) -> tuple[int, int] | None:
    """``(bytes_in_use, bytes_limit)`` as the runtime's allocator reports
    them for ``device`` now, or None where it reports neither (XLA:CPU).
    With the dispatch queue drained, what is in use is what stays live:
    a compiled program's temporaries are not in it."""
    try:
        stats = device.memory_stats()
    except Exception:  # backend without allocator stats
        return None
    if not stats or "bytes_in_use" not in stats or "bytes_limit" not in stats:
        return None
    return int(stats["bytes_in_use"]), int(stats["bytes_limit"])


def _fully_addressable(state: Any) -> bool:
    ok = True
    for leaf in jax.tree.leaves(state):
        if hasattr(leaf, "is_fully_addressable"):
            ok = ok and bool(leaf.is_fully_addressable)
    return ok


@dataclass
class Snapshot:
    step: int
    host: Any
    shardings: Any
    data_state: dict | None = None
    nbytes: int = 0  # the host copy's size


@dataclass
class PendingSnapshot:
    """A launched snapshot: the device copy whose transfer to the host is
    under way, and what the ring's entry will say of it. The transfer is
    started a share at a time (``unsent`` holds the leaves still to
    start, ``share_bytes`` what one call of ``send_pending`` starts): the
    runtime serves transfers in the order they were asked for, so a whole
    state asked for at once holds up every batch on its way in and every
    metric on its way out until it has landed."""

    step: int
    device: Any
    shardings: Any
    data_state: dict | None = None
    nbytes: int = 0
    unsent: list = field(default_factory=list)
    share_bytes: int = 0


class SnapshotRing:
    """Bounded ring of in-memory state snapshots, newest-last."""

    def __init__(self, depth: int):
        self._ring: collections.deque[Snapshot] = collections.deque(
            maxlen=max(1, depth))

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def steps(self) -> list[int]:
        return [s.step for s in self._ring]

    def push(self, snap: Snapshot) -> None:
        self._ring.append(snap)

    def latest(self) -> Snapshot:
        return self._ring[-1]


# ------------------------------------------------------------------ manager

class RecoveryManager:
    """Policy + state for the in-process recovery ladder.

    Owned by the Trainer; the loop calls ``classify`` at every metric
    fetch, ``launch_snapshot`` (or, where that declines, the blocking
    ``take_snapshot``) opportunistically on clean steps, ``finish_pending``
    once the steps that hide the transfer are dispatched, and
    ``rollback`` on an anomaly while ``can_rollback()`` holds. When it
    does not, the loop sets ``exhausted`` and lets the anomalous metrics
    flow to the hooks — NaNGuardHook (the escalation tail) raises
    ``PersistentAnomalyError`` with the provenance collected here.
    """

    def __init__(self, cfg: ResilienceConfig,
                 telemetry_writer: telemetry.TelemetryWriter | None = None):
        self.cfg = cfg
        self.detector = AnomalyDetector(cfg)
        self.ring = SnapshotRing(cfg.snapshot_depth)
        self._telemetry = telemetry_writer
        self.consecutive_rollbacks = 0
        self.total_rollbacks = 0
        self.anomalies_detected = 0
        self.exhausted = False
        self.last_verdict: Verdict | None = None
        self._last_snapshot_step: int | None = None
        self._disabled_reason: str | None = None
        # The two-phase snapshot: the launched copy that has not reached
        # the ring yet, the device copy's executable (``prepare_overlap``
        # compiles it in set-up), and the headroom test's verdict and
        # reading (None until the first periodic snapshot makes it).
        self.pending: PendingSnapshot | None = None
        self._copy_program: Any = None
        self._overlap: bool | None = None
        self.headroom: dict | None = None

    # -- telemetry helper -------------------------------------------------
    def _emit(self, kind: str, step: int, health: dict) -> None:
        if self._telemetry is not None:
            self._telemetry.emit(kind, step=step, health=health)

    @property
    def armed(self) -> bool:
        return self._disabled_reason is None

    def disable(self, reason: str) -> None:
        if self._disabled_reason is None:
            self._disabled_reason = reason
            log.warning(
                "in-memory rollback DISABLED (%s) — anomalies will "
                "escalate straight to the supervisor", reason,
            )

    # -- snapshots --------------------------------------------------------
    def snapshot_due(self, step: int, force: bool = False) -> bool:
        """Whether ``take_snapshot`` at ``step`` would copy the state
        (so the loop puts its ``snapshot`` span around copies only)."""
        if not self.armed:
            return False
        return (force or self._last_snapshot_step is None
                or step - self._last_snapshot_step
                >= max(1, self.cfg.snapshot_interval_steps))

    def take_snapshot(self, step: int, state: Any,
                      data_state: dict | None = None,
                      force: bool = False) -> bool:
        if not self.snapshot_due(step, force):
            return False
        if not _fully_addressable(state):
            # Multi-host sharded state: the device_get snapshot only sees
            # this process's shards (same restriction as the async saver's
            # snapshot path — checkpoint.async_save documents it).
            self.disable("train state is not fully addressable on this host")
            return False
        self.finish_pending()  # ring order: the older snapshot first
        host, shardings = snapshot_state(state)
        self.ring.push(Snapshot(
            step=step, host=host, shardings=shardings,
            data_state=dict(data_state or {}),
            nbytes=state_nbytes(state)[0]))
        self._last_snapshot_step = step
        return True

    # -- the two-phase snapshot -------------------------------------------
    def prepare_overlap(self, state: Any) -> None:
        """Compile the device copy ahead of time from the state's avals:
        set-up's work, so that no launch compiles inside the loop. Left
        undone (and the blocking path with it) where the ladder is off or
        the state is not this host's to copy."""
        if not self.armed or not _fully_addressable(state):
            return
        avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)
        self._copy_program = jax.jit(_copy_packed).lower(avals).compile()

    def _headroom_ok(self, state: Any, step_temp_bytes: int | None) -> bool:
        """The headroom test: on the fullest device that holds a part of
        the state, what is live now + the step's temporaries + the
        state's bytes once more + ``OVERLAP_MARGIN`` of the limit fit the
        limit. Called with the dispatch queue drained. The reading stays
        in ``headroom`` and goes out as a ``snapshot_overlap`` health
        event."""
        on_device = state_nbytes(state)[1]
        reading: dict = {"event": "snapshot_overlap",
                         "state_bytes": on_device,
                         "step_temp_bytes": step_temp_bytes}
        reason = None
        if self._copy_program is None:
            reason = "no copy program was compiled in set-up"
        elif step_temp_bytes is None:
            reason = "the step's memory analysis is unavailable"
        else:
            devices = sorted({d for leaf in jax.tree.leaves(state)
                              for d in leaf.sharding.addressable_devices},
                             key=lambda d: d.id)
            memory = [device_memory(d) for d in devices]
            if None in memory:
                silent = devices[memory.index(None)]
                reason = (f"{silent.platform} device {silent.id} reports "
                          f"no memory statistics")
            else:
                in_use, limit = min(memory, key=lambda m: m[1] - m[0])
                margin = int(OVERLAP_MARGIN * limit)
                spare = limit - in_use - step_temp_bytes - on_device - margin
                reading.update(bytes_in_use=in_use, bytes_limit=limit,
                               margin_bytes=margin, spare_bytes=spare)
                if spare < 0:
                    reason = "too little device memory is free"
        reading["admitted"] = reason is None
        if reason:
            reading["reason"] = reason
        self.headroom = reading
        log.info("recovery snapshot: %s %s",
                 "on the device, then to the host beside the next steps"
                 if reason is None else f"blocking copy ({reason})", reading)
        return reason is None

    def launch_snapshot(self, step: int, state: Any,
                        data_state: dict | None = None,
                        step_temp_bytes: Callable[[], int | None] = lambda: None,
                        spread_over: int = 1) -> bool:
        """Phase one of a periodic snapshot: copy the packed state into
        device buffers of its own and start the transfer of the first of
        ``spread_over`` shares of them to the host (``send_pending``
        starts each further one; ``finish_pending`` whatever is left).
        False where the two-phase path is not taken (the caller then
        takes the blocking one): the headroom test said no, made once at
        the first call from what ``step_temp_bytes()`` gives for the
        compiled step's temporaries, or a copy has run out of memory
        since."""
        if self._overlap is None:
            self._overlap = self._headroom_ok(state, step_temp_bytes())
            self._emit(telemetry.KIND_HEALTH, step, self.headroom)
        if not self._overlap:
            return False
        self.finish_pending()  # the next snapshot due finishes the first
        try:
            device = self._copy_program(state)
        except jax.errors.JaxRuntimeError as e:
            self._out_of_memory(e, step)
            return False
        nbytes = state_nbytes(state)[0]
        self.pending = PendingSnapshot(
            step=step, device=device,
            shardings=jax.tree.map(lambda x: x.sharding, device),
            data_state=dict(data_state or {}), nbytes=nbytes,
            unsent=jax.tree.leaves(device)[::-1],
            share_bytes=-(-nbytes // max(1, spread_over)))
        self._last_snapshot_step = step
        self.send_pending()
        return True

    def send_pending(self) -> None:
        """Start the transfer to the host of the next share of a pending
        snapshot's leaves; nothing to do once all are on their way."""
        pend = self.pending
        if pend is None:
            return
        started = 0
        while pend.unsent and started < pend.share_bytes:
            leaf = pend.unsent.pop()
            leaf.copy_to_host_async()
            started += leaf.nbytes

    def finish_pending(self) -> float:
        """Phase two: the landed host tree into the ring as the
        ``Snapshot`` it is, the device copy freed. Returns the seconds
        the host waited for the bytes (0.0 with nothing pending)."""
        pend, self.pending = self.pending, None
        if pend is None:
            return 0.0
        t0 = time.perf_counter()
        try:
            host = jax.device_get(pend.device)
        except jax.errors.JaxRuntimeError as e:
            self._out_of_memory(e, pend.step)
            # The next clean fetch takes this snapshot's place.
            self._last_snapshot_step = (
                self.ring.latest().step if len(self.ring) else None)
            return time.perf_counter() - t0
        self.ring.push(Snapshot(
            step=pend.step, host=host, shardings=pend.shardings,
            data_state=pend.data_state, nbytes=pend.nbytes))
        return time.perf_counter() - t0

    def drop_pending(self) -> None:
        """On the way out of the loop nothing will read a pending
        snapshot: free its device copy."""
        self.pending = None

    def _out_of_memory(self, err: Exception, step: int) -> None:
        """Called while handling ``err``: a copy that ran out of device
        memory switches the two-phase path off for the rest of the run;
        any other runtime error goes on up."""
        if "RESOURCE_EXHAUSTED" not in str(err):
            raise
        self._overlap = False
        log.warning(
            "recovery snapshot at step %d: the device copy ran out of "
            "memory — blocking copies from here on (%s)", step,
            str(err).splitlines()[0])

    # -- classification ---------------------------------------------------
    def classify(self, step: int, metrics: Mapping[str, float]) -> Verdict | None:
        """Classify one fetched-metrics step. Clean steps feed the EWMA
        baseline and reset the consecutive-rollback streak; anomalous
        steps emit ``anomaly_detected`` and return the verdict."""
        verdict = self.detector.classify(step, metrics)
        if verdict is None:
            self.detector.observe(metrics)
            self.consecutive_rollbacks = 0
            return None
        self.last_verdict = verdict
        self.anomalies_detected += 1
        log.warning(
            "anomaly detected at step %d: %s (%s=%s)",
            step, verdict.anomaly, verdict.metric, verdict.value,
        )
        self._emit(
            telemetry.KIND_ANOMALY, step,
            {**verdict.to_health(),
             "consecutive_rollbacks": self.consecutive_rollbacks},
        )
        return verdict

    # -- rollback ---------------------------------------------------------
    def can_rollback(self) -> bool:
        return (self.armed and (len(self.ring) > 0 or self.pending is not None)
                and self.consecutive_rollbacks < self.cfg.max_rollbacks)

    def rollback(self, live_state: Any, from_step: int) -> tuple[Any, Snapshot]:
        """Restore the newest snapshot; returns ``(state, snapshot)``.
        Emits ``rollback`` and ``batch_skipped`` — the skipped range is
        the data consumed between the snapshot and the anomaly, which the
        resumed stream will never replay (skip-batch semantics). A
        pending snapshot is the newest: it lands first."""
        self.finish_pending()
        snap = self.ring.latest()
        state = restore_state(snap.host, snap.shardings, like=live_state)
        self.consecutive_rollbacks += 1
        self.total_rollbacks += 1
        log.warning(
            "rolling back: step %d -> %d (rollback %d/%d this incident, "
            "%d total)", from_step, snap.step, self.consecutive_rollbacks,
            self.cfg.max_rollbacks, self.total_rollbacks,
        )
        self._emit(telemetry.KIND_ROLLBACK, from_step, {
            "from_step": from_step, "to_step": snap.step,
            "consecutive_rollbacks": self.consecutive_rollbacks,
        })
        self._emit(telemetry.KIND_BATCH_SKIPPED, from_step, {
            "from_step": snap.step + 1, "to_step": from_step,
            "batches": from_step - snap.step,
        })
        return state, snap

    # -- escalation -------------------------------------------------------
    def provenance(self) -> dict:
        v = self.last_verdict
        # A pending snapshot is listed as the ring would hold it.
        steps = self.ring.steps + ([self.pending.step] if self.pending else [])
        return {
            "anomaly": v.anomaly if v else None,
            "metric": v.metric if v else None,
            "value": str(v.value) if v else None,
            "step": v.step if v else None,
            "consecutive_rollbacks": self.consecutive_rollbacks,
            "max_rollbacks": self.cfg.max_rollbacks,
            "total_rollbacks": self.total_rollbacks,
            "snapshot_steps": steps[-max(1, self.cfg.snapshot_depth):],
            "disabled_reason": self._disabled_reason,
        }

    def escalation_message(self) -> str:
        v = self.last_verdict
        what = (f"{v.anomaly} ({v.metric}={v.value}) at step {v.step}"
                if v else "anomaly")
        if not self.armed:
            why = f"in-memory rollback disabled: {self._disabled_reason}"
        elif len(self.ring) == 0:
            why = "no snapshot available to roll back to"
        else:
            why = (f"{self.consecutive_rollbacks} consecutive rollbacks "
                   f"all landed back on a bad step (max_rollbacks="
                   f"{self.cfg.max_rollbacks})")
        return (
            f"Persistent anomaly: {what} — {why}. Escalating to the "
            f"supervisor (rc=ANOMALY_ESCALATION_RC): this looks like a "
            f"poisoned data region or a deterministic numeric bug, not a "
            f"transient."
        )
