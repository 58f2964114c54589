"""In-process recovery ladder (train/anomaly.py, docs/RESILIENCE.md).

Fast tier-1 coverage of every rung in isolation plus one in-process
end-to-end rollback on the LeNet slice: detector thresholds (non-finite /
grad-norm ceiling / EWMA loss-spike with warmup), the snapshot ring's
bit-exact device→host→device round trip, RecoveryManager policy
(snapshot cadence, rollback budget, escalation provenance, telemetry
emissions), the two-phase snapshot (launched on the device, landed
beside the next steps; its headroom test, its fallbacks, its counters),
and the ResilienceConfig validation seams. The subprocess
drills that prove the ladder under real fault injection live in
tests/test_recovery_drills.py (tier-2 by their slow marks).
"""

import math

import jax
import numpy as np
import pytest

from distributed_tensorflow_framework_tpu.core import faults, telemetry
from distributed_tensorflow_framework_tpu.core.config import (
    ResilienceConfig,
    load_config,
)
from distributed_tensorflow_framework_tpu.train import Trainer
from distributed_tensorflow_framework_tpu.train import anomaly

from tests.test_train_lenet import lenet_config


@pytest.fixture(autouse=True)
def _clean_plan():
    yield
    faults.install(faults.FaultPlan())  # empty plan; no env re-read


# ----------------------------------------------------------- detector ----


def _warm(det, losses):
    for x in losses:
        det.observe({"loss": x})


def test_detector_flags_non_finite_any_metric():
    det = anomaly.AnomalyDetector(ResilienceConfig())
    assert det.classify(3, {"loss": 1.0, "grad_norm": 2.0}) is None
    v = det.classify(4, {"loss": float("nan"), "grad_norm": 2.0})
    assert v is not None and v.anomaly == "non_finite_metric"
    assert v.metric == "loss" and v.step == 4
    v = det.classify(5, {"loss": 1.0, "grad_norm": float("inf")})
    assert v is not None and v.metric == "grad_norm"
    # non-numeric metrics are skipped, not classified
    assert det.classify(6, {"loss": 1.0, "note": "fine"}) is None


def test_detector_grad_norm_ceiling():
    cfg = ResilienceConfig(grad_norm_max=100.0)
    det = anomaly.AnomalyDetector(cfg)
    assert det.classify(1, {"loss": 1.0, "grad_norm": 99.0}) is None
    v = det.classify(2, {"loss": 1.0, "grad_norm": 150.0})
    assert v is not None and v.anomaly == "grad_norm_explosion"
    assert v.detail["grad_norm_max"] == 100.0
    # 0 disables the ceiling entirely
    det0 = anomaly.AnomalyDetector(ResilienceConfig(grad_norm_max=0.0))
    assert det0.classify(2, {"loss": 1.0, "grad_norm": 1e12}) is None


def test_loss_spike_needs_warmup_then_fires():
    cfg = ResilienceConfig(loss_spike_zscore=5.0, min_observations=5,
                           loss_ewma_beta=0.9)
    det = anomaly.AnomalyDetector(cfg)
    # Cold EWMA: even an absurd loss cannot fire before min_observations.
    assert det.classify(1, {"loss": 1e9}) is None
    _warm(det, [1.0, 1.01, 0.99, 1.02, 0.98])
    assert det.observations == 5
    # Normal jitter around the baseline stays clean...
    assert det.classify(10, {"loss": 1.03}) is None
    # ...while a genuine spike classifies with z-score provenance.
    v = det.classify(11, {"loss": 50.0})
    assert v is not None and v.anomaly == "loss_spike"
    assert v.detail["zscore"] > 5.0
    assert v.detail["ewma_mean"] == pytest.approx(1.0, abs=0.1)


def test_loss_spike_std_floor_tolerates_constant_loss():
    """A perfectly flat loss history has ~zero EWMA variance; the relative
    std floor must keep numeric jitter from reading as an infinite-z
    spike."""
    cfg = ResilienceConfig(loss_spike_zscore=10.0, min_observations=3)
    det = anomaly.AnomalyDetector(cfg)
    _warm(det, [2.0] * 10)
    assert det.std >= 1e-3 * 2.0
    assert det.classify(20, {"loss": 2.0 + 1e-4}) is None


def test_loss_spike_zero_disables():
    det = anomaly.AnomalyDetector(ResilienceConfig(loss_spike_zscore=0.0,
                                                   min_observations=1))
    _warm(det, [1.0] * 10)
    assert det.classify(11, {"loss": 1e9}) is None


# --------------------------------------------------------- validation ----


@pytest.mark.parametrize("key,bad,msg", [
    ("resilience.snapshot_depth", 0, "snapshot_depth"),
    ("resilience.max_rollbacks", 0, "max_rollbacks"),
    ("resilience.loss_ewma_beta", 1.5, "loss_ewma_beta"),
    ("resilience.loss_ewma_beta", 0.0, "loss_ewma_beta"),
])
def test_resilience_config_validation(key, bad, msg):
    with pytest.raises(ValueError, match=msg):
        load_config(overrides=[f"{key}={bad}"])


def test_resilience_defaults_armed():
    cfg = load_config()
    assert cfg.resilience.rollback is True
    assert cfg.resilience.snapshot_depth >= 1
    assert cfg.resilience.max_rollbacks >= 1


# ------------------------------------------------------ snapshot ring ----


def test_snapshot_ring_depth_evicts_oldest():
    ring = anomaly.SnapshotRing(depth=2)
    for step in (10, 20, 30):
        ring.push(anomaly.Snapshot(step=step, host=None, shardings=None))
    assert len(ring) == 2
    assert ring.steps == [20, 30]
    assert ring.latest().step == 30


def test_snapshot_restore_bit_exact(devices):
    """The rollback contract: restore must land the EXACT bytes of the
    snapshotted state — params, opt state, step counter, and the typed
    PRNG key — on the original shardings, after training has moved the
    live state arbitrarily far away."""
    cfg = lenet_config(**{"train.total_steps": 6, "train.log_interval": 3})
    trainer = Trainer(cfg)
    trainer.build()

    ref = jax.device_get(
        trainer.state.replace(rng=jax.random.key_data(trainer.state.rng)))
    host, shardings = anomaly.snapshot_state(trainer.state)
    trainer.train()  # move the live state well away from the snapshot

    restored = anomaly.restore_state(host, shardings, like=trainer.state)
    got = jax.device_get(
        restored.replace(rng=jax.random.key_data(restored.rng)))
    ref_leaves = jax.tree.leaves(ref)
    got_leaves = jax.tree.leaves(got)
    assert len(ref_leaves) == len(got_leaves)
    for a, b in zip(ref_leaves, got_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # placements survive the round trip: every restored leaf sits on the
    # same mesh sharding as its live counterpart, not a default device.
    for lr, ll in zip(jax.tree.leaves(restored),
                      jax.tree.leaves(trainer.state)):
        assert lr.sharding == ll.sharding


# ------------------------------------------------- two-phase snapshot ----

ROOMY = (10 << 20, 1 << 30)      # (bytes_in_use, bytes_limit): fits


@pytest.fixture
def roomy(monkeypatch):
    """XLA:CPU reports no memory statistics, so the headroom test refuses
    there: stub the seam to a device with room."""
    monkeypatch.setattr(anomaly, "device_memory", lambda d: ROOMY)


def _packed_host(state):
    return jax.device_get(
        state.replace(rng=jax.random.key_data(state.rng)))


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _built(**over):
    trainer = Trainer(lenet_config(**over))
    trainer.build()
    trainer.recovery.prepare_overlap(trainer.state)
    return trainer, trainer.recovery


def _run_steps(trainer, n):
    """``n`` steps of the loop's own jitted step: each donates the live
    state's buffers, as the loop's dispatches do behind a launch."""
    for _ in range(n):
        trainer.state, _ = trainer.train_step(trainer.state, trainer._sample)


@pytest.mark.parametrize("before,between", [(0, 1), (3, 4)])
def test_launched_snapshot_lands_bit_exact_after_further_steps(
        devices, roomy, before, between):
    """Launched at step N, finished M steps later: the ring's entry is
    step N's state to the bit — params, optimizer state, the step counter
    and the typed key — though every buffer it was copied from has been
    donated since; and it restores onto the live placements."""
    trainer, rec = _built()
    _run_steps(trainer, before)
    ref = _packed_host(trainer.state)
    assert rec.launch_snapshot(before, trainer.state,
                               data_state={"consumed": before},
                               step_temp_bytes=lambda: 0)
    assert rec.pending.step == before and len(rec.ring) == 0
    _run_steps(trainer, between)
    assert rec.finish_pending() >= 0.0
    assert rec.pending is None and rec.ring.steps == [before]
    snap = rec.ring.latest()
    _assert_trees_equal(ref, snap.host)
    assert int(snap.host.step) == before
    assert snap.data_state == {"consumed": before}
    assert snap.nbytes == sum(
        np.asarray(x).nbytes for x in jax.tree.leaves(ref))
    restored = anomaly.restore_state(snap.host, snap.shardings,
                                     like=trainer.state)
    _assert_trees_equal(ref, _packed_host(restored))
    for lr, ll in zip(jax.tree.leaves(restored),
                      jax.tree.leaves(trainer.state)):
        assert lr.sharding == ll.sharding


def test_transfer_sets_out_a_share_at_a_time(devices, roomy):
    """The runtime serves transfers in the order asked, so a launch asks
    for one of ``spread_over`` shares of the copy's bytes and
    ``send_pending`` for each further one; a finish takes whatever is
    left with it, and what lands is the whole state all the same."""
    trainer, rec = _built()
    ref = _packed_host(trainer.state)
    leaves = len(jax.tree.leaves(ref))
    assert rec.launch_snapshot(1, trainer.state, step_temp_bytes=lambda: 0,
                               spread_over=4)
    pend = rec.pending
    assert pend.share_bytes == -(-pend.nbytes // 4)
    left = [len(pend.unsent)]
    assert 0 < left[0] < leaves
    while pend.unsent:
        rec.send_pending()
        left.append(len(pend.unsent))
    assert left == sorted(left, reverse=True) and 2 <= len(left) <= 4
    rec.send_pending()                       # nothing left: a no-op
    _run_steps(trainer, 2)
    rec.finish_pending()
    _assert_trees_equal(ref, rec.ring.latest().host)
    # and a finish with most of it unsent
    assert rec.launch_snapshot(2, trainer.state, spread_over=1000)
    assert 0.8 * leaves < len(rec.pending.unsent) < leaves
    ref = _packed_host(trainer.state)
    rec.finish_pending()
    _assert_trees_equal(ref, rec.ring.latest().host)
    rec.send_pending()                       # nothing pending: a no-op


def test_rollback_finishes_a_pending_snapshot_first(devices, roomy):
    """A rollback wants the newest snapshot: one still on its way lands
    first, and the rollback is to ITS step and data state."""
    trainer, rec = _built()
    assert rec.take_snapshot(0, trainer.state, data_state={"consumed": 0},
                             force=True)
    _run_steps(trainer, 2)
    ref = _packed_host(trainer.state)
    assert rec.launch_snapshot(2, trainer.state, data_state={"consumed": 2},
                               step_temp_bytes=lambda: 0)
    _run_steps(trainer, 3)
    assert rec.can_rollback() and rec.pending is not None
    assert rec.provenance()["snapshot_steps"] == [0, 2]
    state, snap = rec.rollback(trainer.state, from_step=5)
    assert rec.pending is None and rec.ring.steps == [0, 2]
    assert snap.step == 2 and snap.data_state == {"consumed": 2}
    _assert_trees_equal(ref, _packed_host(state))


def test_next_snapshot_due_finishes_the_one_before(devices, roomy):
    """Ring order and depth as with blocking copies: a launch (or a
    blocking copy) lands the pending snapshot ahead of itself."""
    trainer, rec = _built(**{"resilience.snapshot_depth": 2})
    for step in (1, 2, 3):
        _run_steps(trainer, 1)
        assert rec.launch_snapshot(step, trainer.state,
                                   step_temp_bytes=lambda: 0)
        assert rec.pending.step == step
        assert rec.ring.steps == [1, 2, 3][:step - 1][-2:]
    assert rec.provenance()["snapshot_steps"] == [2, 3]
    _run_steps(trainer, 1)
    assert rec.take_snapshot(4, trainer.state, force=True)
    assert rec.pending is None and rec.ring.steps == [3, 4]
    assert int(rec.ring.latest().host.step) == 4


class _Device:
    platform, id = "fake", 0

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,want", [
    # What the allocator holds now and its limit; a lifetime peak near
    # the limit (a set-up that held the state twice) is not read.
    ({"bytes_in_use": 6 << 30, "peak_bytes_in_use": 15 << 30,
      "bytes_limit": 16 << 30}, (6 << 30, 16 << 30)),
    ({"bytes_in_use": 1}, None),
    ({}, None),
    (None, None),
])
def test_device_memory_reads_what_is_live_not_the_lifetime_peak(stats, want):
    assert anomaly.device_memory(_Device(stats)) == want


GIB = 1 << 30


@pytest.mark.parametrize("in_use,temp,admitted,reason", [
    # state 0.5 MB: everything hangs on what is live and the step's temps
    (6 * GIB, 3 * GIB, True, None),
    (6 * GIB, int(9.3 * GIB), False, "too little"),  # inside the 5% margin
    (12 * GIB, 4 * GIB, False, "too little"),
    (6 * GIB, None, False, "memory analysis is unavailable"),
    (None, 3 * GIB, False, "reports no memory statistics"),
])
def test_headroom_test_takes_the_blocking_path_where_it_must(
        devices, monkeypatch, in_use, temp, admitted, reason):
    trainer, rec = _built()
    monkeypatch.setattr(
        anomaly, "device_memory",
        lambda d: None if in_use is None else (in_use, 16 * GIB))
    calls = []
    launched = rec.launch_snapshot(
        1, trainer.state, step_temp_bytes=lambda: calls.append(1) or temp)
    assert launched is admitted
    assert rec.headroom["admitted"] is admitted
    assert (rec.pending is not None) is admitted
    if reason:
        assert reason in rec.headroom["reason"]
    else:
        assert rec.headroom["spare_bytes"] == (
            16 * GIB - in_use - temp - rec.headroom["state_bytes"]
            - int(anomaly.OVERLAP_MARGIN * 16 * GIB))
    # made once: the verdict stands for the run
    assert rec.launch_snapshot(2, trainer.state,
                               step_temp_bytes=lambda: calls.append(1) or 0
                               ) is admitted
    assert len(calls) == 1


def test_no_copy_program_no_launch(devices, roomy):
    trainer = Trainer(lenet_config())
    trainer.build()
    rec = trainer.recovery  # prepare_overlap never ran
    assert not rec.launch_snapshot(1, trainer.state,
                                   step_temp_bytes=lambda: 0)
    assert "no copy program" in rec.headroom["reason"]


def _out_of_memory(*_):
    raise jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
        "to allocate 5.68G. That was not possible.")


def test_copy_out_of_memory_switches_to_the_blocking_path(devices, roomy):
    trainer, rec = _built()
    assert rec.launch_snapshot(1, trainer.state, step_temp_bytes=lambda: 0)
    rec._copy_program = _out_of_memory
    assert not rec.launch_snapshot(2, trainer.state)
    assert rec.pending is None and rec.ring.steps == [1]   # 1 landed first
    good = anomaly.RecoveryManager.prepare_overlap
    good(rec, trainer.state)            # a sound program again: still off
    assert not rec.launch_snapshot(3, trainer.state)
    # any other runtime error is not swallowed
    rec2 = _built()[1]
    rec2._copy_program = lambda s: (_ for _ in ()).throw(
        jax.errors.JaxRuntimeError("INTERNAL: something else"))
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        rec2.launch_snapshot(1, trainer.state, step_temp_bytes=lambda: 0)


def _train_counting(monkeypatch, tmp_path, **over):
    """A 40-step LeNet run, a snapshot every 10 steps and a fetch every
    5; returns the trainer, its goodput counters and its health events."""
    cfg = lenet_config(**{
        "train.total_steps": 40, "train.log_interval": 5,
        "resilience.snapshot_interval_steps": 10,
        "checkpoint.directory": str(tmp_path), **over})
    trainer = Trainer(cfg)
    health = []
    trainer.writer.telemetry.add_listener(
        lambda ev: health.append(ev["health"])
        if ev.get("kind") == telemetry.KIND_HEALTH else None)
    trainer.train(hooks=[])
    return trainer, trainer.goodput.snapshot()["counters"], health


def test_loop_counts_overlapped_snapshots_and_compiles_nothing_in_the_loop(
        devices, roomy, monkeypatch, tmp_path):
    """The loop's side: the copy program is compiled in set-up (its span
    is among the startup's), the baseline is the blocking copy, every
    periodic snapshot is launched and lands at the next fetch, the last
    one (launched at the final step) is dropped on the way out, and no
    ``recompile`` event names the launch."""
    launches = []
    launch = anomaly.RecoveryManager.launch_snapshot
    monkeypatch.setattr(
        anomaly.RecoveryManager, "launch_snapshot",
        lambda self, step, *a, **k: launches.append(
            (step, self._copy_program is not None, list(self.ring.steps)))
        or launch(self, step, *a, **k))
    trainer, counters, health = _train_counting(monkeypatch, tmp_path)
    rec = trainer.recovery
    assert "startup:snapshot_program" in {s[0] for s in trainer.timer.startup}
    # program there before step 10's launch; each launch found the one
    # before it landed (at the fetch of step 15, 25, 35)
    assert launches == [(10, True, [0]), (20, True, [0, 10]),
                        (30, True, [10, 20]), (40, True, [20, 30])]
    assert rec.pending is None and rec.ring.steps == [20, 30]
    nbytes = anomaly.state_nbytes(trainer.state)[0]
    assert counters["snapshots"] == 5
    assert counters["snapshots_overlapped"] == 4
    assert counters["snapshot_bytes"] == 5 * nbytes
    assert counters["snapshot_finish_wait_s"] >= 0.0
    assert counters["recompiles"] == 1
    events = {h.get("event") for h in health}
    assert "snapshot_overlap" in events and "recompile" not in events
    assert [h["admitted"] for h in health
            if h.get("event") == "snapshot_overlap"] == [True]
    # under the loop's ``snapshot`` span: the baseline, the launches at
    # 10..40, a share sent behind some of the steps that follow each, and
    # the finishes ahead of the fetch of steps 15, 25 and 35
    steps = [s[1] for s in trainer.timer.spans if s[0] == "snapshot"]
    assert steps == sorted(steps)
    assert {0, 10, 15, 20, 25, 30, 35, 40} <= set(steps)
    assert all(s % 10 <= 5 for s in steps)


@pytest.mark.parametrize("why", ["no_statistics", "too_little", "exhausted"])
def test_loop_takes_the_blocking_path_and_counts_no_overlap(
        devices, monkeypatch, tmp_path, why):
    if why == "too_little":
        monkeypatch.setattr(anomaly, "device_memory",
                            lambda d: (16 * GIB - 1, 16 * GIB))
    elif why == "exhausted":
        monkeypatch.setattr(anomaly, "device_memory", lambda d: ROOMY)
        monkeypatch.setattr(
            anomaly.RecoveryManager, "prepare_overlap",
            lambda self, state: setattr(self, "_copy_program",
                                        _out_of_memory))
    trainer, counters, health = _train_counting(monkeypatch, tmp_path)
    assert counters["snapshots"] == 5
    assert "snapshots_overlapped" not in counters
    assert "snapshot_finish_wait_s" not in counters
    assert trainer.recovery.ring.steps == [30, 40]
    assert trainer.recovery.headroom["admitted"] is (why == "exhausted")


def test_nan_batch_rolls_back_to_a_pending_snapshot(devices, roomy):
    """The ladder's happy path with the two-phase snapshot: the snapshot
    launched at step 10 lands ahead of step 15's fetch, whose anomaly
    (the batch poisoned at step 12) then rolls back to it."""
    faults.install("nan_grads:12")
    cfg = lenet_config(**{
        "train.total_steps": 30, "train.log_interval": 5,
        "resilience.snapshot_interval_steps": 10})
    trainer = Trainer(cfg)
    landed, rollbacks = [], []
    finish = anomaly.RecoveryManager.finish_pending
    trainer.build()
    trainer.writer.telemetry.add_listener(
        lambda ev: rollbacks.append(ev["health"]["to_step"])
        if ev.get("kind") == telemetry.KIND_ROLLBACK else None)

    def watched(self):
        if self.pending is not None:
            landed.append((self.pending.step, trainer.host_step))
        return finish(self)

    anomaly.RecoveryManager.finish_pending = watched
    try:
        metrics = trainer.train()
    finally:
        anomaly.RecoveryManager.finish_pending = finish
    assert trainer.recovery.total_rollbacks == 1
    assert trainer.host_step == 30 and math.isfinite(float(metrics["loss"]))
    assert landed[0] == (10, 15) and rollbacks == [10]
    assert trainer.recovery.ring.steps[-1] > 10  # and went on snapshotting


# --------------------------------------------------- recovery manager ----


def _manager(tmp_path=None, **over):
    cfg = ResilienceConfig(**over)
    writer = None
    path = None
    if tmp_path is not None:
        path = str(tmp_path / "events.jsonl")
        writer = telemetry.TelemetryWriter(path, run_id="anomaly-test")
    return anomaly.RecoveryManager(cfg, telemetry_writer=writer), path


def test_manager_snapshot_cadence_and_force():
    rec, _ = _manager(snapshot_interval_steps=10)
    # Bypass the device round trip: stub the snapshot at the module seam.
    orig = anomaly.snapshot_state
    anomaly.snapshot_state = lambda s: ("host", None)
    try:
        state = object()
        assert rec.take_snapshot(0, state, force=True)
        assert not rec.take_snapshot(5, state)       # below the interval
        assert rec.take_snapshot(10, state)          # at the interval
        assert rec.ring.steps == [0, 10]
    finally:
        anomaly.snapshot_state = orig


def test_manager_classify_emits_and_resets_streak(tmp_path):
    rec, path = _manager(tmp_path, min_observations=1)
    rec.consecutive_rollbacks = 2
    assert rec.classify(10, {"loss": 1.0}) is None   # clean: streak resets
    assert rec.consecutive_rollbacks == 0
    assert rec.detector.observations == 1
    v = rec.classify(20, {"loss": float("nan")})
    assert v is not None
    # anomalous metrics must NOT feed the EWMA baseline
    assert rec.detector.observations == 1
    assert rec.anomalies_detected == 1
    rec._telemetry.close()
    evs = list(telemetry.read_events(path, kind=telemetry.KIND_ANOMALY))
    assert len(evs) == 1
    assert evs[0]["step"] == 20
    assert evs[0]["health"]["anomaly"] == "non_finite_metric"


def test_manager_rollback_budget_and_exhaustion():
    rec, _ = _manager(max_rollbacks=2)
    assert not rec.can_rollback()                    # no snapshot yet
    rec.ring.push(anomaly.Snapshot(step=10, host=None, shardings=None))
    orig = anomaly.restore_state
    anomaly.restore_state = lambda h, s, like: like
    try:
        assert rec.can_rollback()
        rec.rollback("state", from_step=30)
        assert rec.consecutive_rollbacks == 1 and rec.total_rollbacks == 1
        rec.rollback("state", from_step=30)
        assert not rec.can_rollback()                # budget exhausted
        # ...until a clean fetch resets the streak
        rec.classify(40, {"loss": 1.0})
        assert rec.can_rollback()
    finally:
        anomaly.restore_state = orig


def test_manager_rollback_telemetry_and_skip_accounting(tmp_path):
    rec, path = _manager(tmp_path)
    rec.ring.push(anomaly.Snapshot(step=20, host=None, shardings=None))
    orig = anomaly.restore_state
    anomaly.restore_state = lambda h, s, like: like
    try:
        _, snap = rec.rollback("state", from_step=30)
    finally:
        anomaly.restore_state = orig
    assert snap.step == 20
    rec._telemetry.close()
    rb = list(telemetry.read_events(path, kind=telemetry.KIND_ROLLBACK))
    sk = list(telemetry.read_events(path, kind=telemetry.KIND_BATCH_SKIPPED))
    assert rb[0]["health"] == {"from_step": 30, "to_step": 20,
                               "consecutive_rollbacks": 1}
    # skip-batch semantics: steps 21..30 replay with FRESH data
    assert sk[0]["health"]["batches"] == 10


def test_manager_disable_escalates_with_reason():
    rec, _ = _manager()
    rec.disable("train state is not fully addressable on this host")
    assert not rec.armed
    assert not rec.take_snapshot(0, None, force=True)
    assert not rec.can_rollback()
    assert "disabled" in rec.escalation_message()
    assert rec.provenance()["disabled_reason"]


def test_escalation_provenance_names_the_verdict():
    rec, _ = _manager(max_rollbacks=2)
    rec.classify(30, {"loss": float("nan")})
    rec.consecutive_rollbacks = 2
    prov = rec.provenance()
    assert prov["anomaly"] == "non_finite_metric"
    assert prov["step"] == 30
    assert prov["max_rollbacks"] == 2
    msg = rec.escalation_message()
    assert "non_finite_metric" in msg and "poisoned data region" in msg


def test_persistent_anomaly_error_is_a_floating_point_error():
    """The escalation tail must stay catchable by pre-ladder NaNGuardHook
    consumers (except FloatingPointError) while carrying provenance."""
    err = anomaly.PersistentAnomalyError("boom", provenance={"step": 3})
    assert isinstance(err, FloatingPointError)
    assert err.provenance == {"step": 3}


# ------------------------------------------- in-process end-to-end ----


def test_nan_batch_rolls_back_and_finishes(devices):
    """The ladder's happy path, in process and in one pytest worker: a
    single poisoned batch (nan_grads fault) is detected at the next metric
    fetch, the state rolls back to the last clean snapshot, the poisoned
    region is skipped, and the run finishes with finite metrics — no
    relaunch, no checkpoint, no supervisor."""
    faults.install("nan_grads:15")
    cfg = lenet_config(**{
        "train.total_steps": 30,
        "train.log_interval": 5,
        "resilience.snapshot_interval_steps": 5,
        "resilience.snapshot_depth": 2,
    })
    trainer = Trainer(cfg)
    metrics = trainer.train()
    assert trainer.recovery is not None
    assert trainer.recovery.total_rollbacks == 1
    assert trainer.recovery.anomalies_detected == 1
    assert not trainer.recovery.exhausted
    assert trainer.host_step == 30
    assert math.isfinite(float(metrics["loss"]))


def test_rollback_disabled_falls_back_to_nan_guard(devices):
    """resilience.rollback=false restores the PR 2 contract exactly: the
    NaN reaches NaNGuardHook and aborts the run as a FloatingPointError
    (not the escalation subclass — the ladder never armed)."""
    faults.install("nan_grads:15")
    cfg = lenet_config(**{
        "train.total_steps": 30,
        "train.log_interval": 5,
        "resilience.rollback": False,
    })
    trainer = Trainer(cfg)
    with pytest.raises(FloatingPointError) as ei:
        trainer.train()
    assert not isinstance(ei.value, anomaly.PersistentAnomalyError)
    assert trainer.recovery is None
