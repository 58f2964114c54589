"""Telemetry subsystem (core/telemetry.py + collectives tally + run-health
hooks): the ONE event schema every emitter shares (docs/OBSERVABILITY.md).

Covers the schema contract (round-trip, version check, reserved-field
policy), the per-collective byte counters under a real 2-device shard_map
trace, and the run-health hooks (heartbeat, MoE-collapse detector, NaN
provenance) on synthetic inputs.
"""

import json
import math
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_tensorflow_framework_tpu.core import telemetry
from distributed_tensorflow_framework_tpu.core.metrics import MetricWriter
from distributed_tensorflow_framework_tpu.parallel import collectives as coll
from distributed_tensorflow_framework_tpu.train import hooks as hooks_lib


# ------------------------------------------------------------- schema ----


def test_event_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="test-run")
    w.emit_run_meta(argv=["prog", "--x"], model="lenet5")
    w.emit(
        telemetry.KIND_TRAIN_STEP,
        step=3,
        metrics={"loss": 1.5},
        phases={"infeed": 0.4},
        throughput={"examples_per_sec": 100.0},
        collectives={"pmean_calls": 1, "pmean_bytes": 8, "total_bytes": 8},
    )
    w.close()

    evs = list(telemetry.read_events(path))
    assert [e["kind"] for e in evs] == [
        telemetry.KIND_RUN_META, telemetry.KIND_TRAIN_STEP]
    for e in evs:
        assert e["schema"] == telemetry.SCHEMA
        assert e["run_id"] == "test-run"
        assert telemetry.validate_event(e) == []
    meta, step_ev = evs
    assert meta["extra"]["argv"] == "prog --x"
    assert step_ev["step"] == 3
    assert step_ev["metrics"] == {"loss": 1.5}
    assert step_ev["phases"] == {"infeed": 0.4}
    assert step_ev["collectives"]["total_bytes"] == 8


def test_schema_version_is_enforced(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="r")
    w.emit(telemetry.KIND_TRAIN_STEP, step=1, metrics={"loss": 1.0})
    w.close()
    with open(path, "a") as fh:
        bad = {"schema": "dtf-telemetry/999", "run_id": "r",
               "kind": "train_step", "t": 0.0}
        fh.write(json.dumps(bad) + "\n")

    with pytest.raises(ValueError, match="schema"):
        list(telemetry.read_events(path))
    # Non-strict readers skip the unknown version instead of dying.
    lenient = list(telemetry.read_events(path, strict=False))
    assert len(lenient) == 1 and lenient[0]["step"] == 1


def test_validate_event_rejects_unknown_top_level_fields():
    ev = telemetry.make_event(
        telemetry.KIND_BENCH, run_id="r", metrics={"value": 1.0})
    assert telemetry.validate_event(ev) == []
    ev["mfu"] = 0.5  # belongs under roofline/extra, not top-level
    errors = telemetry.validate_event(ev)
    assert errors and "mfu" in errors[0]


def test_split_metrics_routes_phases_and_throughput():
    metrics, phases, throughput = telemetry.split_metrics({
        "loss": 2.0,
        "time_infeed_ms": 1.25,
        "time_dispatch_ms": 0.5,
        "examples_per_sec": 10.0,
        "tokens_per_sec": 640.0,
    })
    assert metrics == {"loss": 2.0}
    assert phases == {"infeed": 1.25, "dispatch": 0.5}
    assert throughput == {"examples_per_sec": 10.0, "tokens_per_sec": 640.0}


def test_metric_writer_emits_schema_events(tmp_path):
    writer = MetricWriter(logdir=str(tmp_path))
    writer.write(5, {"loss": 0.5, "time_infeed_ms": 1.0,
                     "examples_per_sec": 42.0},
                 collectives={"total_bytes": 128})
    writer.close()
    evs = list(telemetry.read_events(os.path.join(str(tmp_path),
                                                  "events.jsonl")))
    assert len(evs) == 1
    ev = evs[0]
    assert telemetry.validate_event(ev) == []
    assert ev["step"] == 5
    assert ev["metrics"] == {"loss": 0.5}
    assert ev["phases"] == {"infeed": 1.0}
    assert ev["throughput"] == {"examples_per_sec": 42.0}
    assert ev["collectives"] == {"total_bytes": 128}


# --------------------------------------------- collective byte counters ----


def test_collective_tally_2dev_shard_map(devices):
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    x = jax.device_put(
        np.arange(8, dtype=np.float32),
        jax.sharding.NamedSharding(mesh, P("data")))

    def f(x):
        y = coll.pmean(x, "data")            # local shard: 4 f32 = 16 B
        z = coll.all_gather(x, "data")       # local shard: 4 f32 = 16 B
        return y, z

    mapped = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=(P(None), P(None)),
        check_vma=False))
    with coll.tally() as t:
        out = mapped(x)
    jax.block_until_ready(out)

    s = t.summary()
    # Ring convention (CollectiveTally docstring): all-reduce counts 2x
    # its 16 B payload, all-gather counts its OUTPUT (n x the shard).
    assert s["pmean_calls"] == 1 and s["pmean_bytes"] == 32
    assert s["all_gather_calls"] == 1 and s["all_gather_bytes"] == 32
    assert s["total_bytes"] == 64
    # f32 wire == logical dtype: no compression, totals coincide.
    assert s["total_logical_bytes"] == 64

    # Counters record at TRACE time: a second dispatch of the same
    # executable adds nothing (the numbers describe every step).
    with coll.tally() as t2:
        jax.block_until_ready(mapped(x))
    assert t2.summary() == {"total_bytes": 0, "total_logical_bytes": 0}


def test_collective_tally_allreduce_gradients(devices):
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    grads = {"a": np.ones((4, 2), np.float32), "b": np.ones((6,), np.float32)}
    sharding = jax.sharding.NamedSharding(mesh, P())
    grads = jax.device_put(grads, sharding)

    mapped = jax.jit(jax.shard_map(
        lambda g: coll.allreduce_gradients(g, ("data",)),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    with coll.tally() as t:
        jax.block_until_ready(mapped(grads))
    s = t.summary()
    assert s["allreduce_grads_pmean_calls"] == 2  # one per tree leaf
    assert s["allreduce_grads_pmean_bytes"] == (8 + 6) * 4 * 2  # ring 2x
    assert s["total_bytes"] == (8 + 6) * 4 * 2
    assert s["total_logical_bytes"] == s["total_bytes"]


def test_collective_tally_int8_wire_vs_logical(devices):
    """The int8 block-scaled all-reduce must tally wire bytes (int8 codes
    + f32 scales) SEPARATELY from logical bytes — their ratio is the
    compression the A/B exists to measure."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    grads = {"w": np.ones((256,), np.float32)}
    grads = jax.device_put(grads, jax.sharding.NamedSharding(mesh, P()))

    mapped = jax.jit(jax.shard_map(
        lambda g: coll.allreduce_gradients(
            g, ("data",), compute_dtype="int8", block_size=64),
        mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    with coll.tally() as t:
        out = jax.block_until_ready(mapped(grads))
    s = t.summary()

    # scatter phase: 256 int8 codes + 4 blocks x 4 B scales = 272 wire,
    # vs 256 f32 = 1024 logical. gather phase: 128-elem chunk x n=2
    # output + 2x2 scales = 272 wire vs 1024 logical.
    assert s["allreduce_grads_q8_scatter_bytes"] == 272
    assert s["allreduce_grads_q8_scatter_logical_bytes"] == 1024
    assert s["allreduce_grads_q8_gather_bytes"] == 272
    assert s["allreduce_grads_q8_gather_logical_bytes"] == 1024
    assert s["total_bytes"] == 544
    assert s["total_logical_bytes"] == 2048
    # A constant tree quantizes exactly: the mean of all-ones is all-ones.
    np.testing.assert_array_equal(np.asarray(out["w"]), np.ones(256))


def test_summarize_collectives_rollup(tmp_path):
    """Per-step tallies ride train_step events; the run summary reports
    the LAST one (static per compiled program) with the wire-compression
    ratio."""
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="coll")
    w.emit(telemetry.KIND_TRAIN_STEP, step=1, metrics={"loss": 1.0},
           collectives={"total_bytes": 544, "total_logical_bytes": 2048})
    w.close()
    s = telemetry.summarize_events(path)
    assert s["collectives"] == {"total_bytes": 544,
                                "total_logical_bytes": 2048,
                                "wire_compression": round(2048 / 544, 3)}
    text = telemetry.format_run_summary(s)
    assert "collectives: 544 wire bytes/step (2,048 logical" in text
    assert "x compression" in text


def test_summarize_without_collectives(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="nocoll")
    w.emit(telemetry.KIND_TRAIN_STEP, step=1, metrics={"loss": 1.0})
    w.close()
    s = telemetry.summarize_events(path)
    assert s["collectives"] is None
    assert "collectives:" not in telemetry.format_run_summary(s)


# ------------------------------------------------------- run-health hooks ----


def _trainer_stub(tmp_path, **over):
    """The minimal Trainer surface the hooks touch."""
    events = str(tmp_path / "events.jsonl")
    writer = SimpleNamespace(
        telemetry=telemetry.TelemetryWriter(events, run_id="hook-run"))
    stub = SimpleNamespace(
        run_id="hook-run",
        host_step=0,
        writer=writer,
        config=SimpleNamespace(checkpoint=SimpleNamespace(
            directory=str(tmp_path / "ckpt"))),
        _events_path=events,
    )
    for k, v in over.items():
        setattr(stub, k, v)
    return stub


def test_heartbeat_hook_writes_atomic_liveness_file(tmp_path):
    hb_path = str(tmp_path / "heartbeat.json")
    hook = hooks_lib.HeartbeatHook(hb_path, min_interval_s=0.0)
    trainer = _trainer_stub(tmp_path)

    hook.on_start(trainer)
    rec = json.load(open(hb_path))
    assert rec["status"] == "running" and rec["step"] == 0
    assert rec["schema"] == telemetry.SCHEMA
    assert rec["run_id"] == "hook-run"

    hook.after_step(trainer, 3, {"loss": 1.25})
    trainer.host_step = 3
    hook.on_end(trainer)
    rec = json.load(open(hb_path))
    assert rec["status"] == "finished" and rec["step"] == 3
    assert rec["last_metrics"] == {"loss": 1.25}
    assert rec["pid"] == os.getpid()
    assert not os.path.exists(hb_path + ".tmp")


def test_heartbeat_hook_respects_min_interval(tmp_path):
    hb_path = str(tmp_path / "hb.json")
    hook = hooks_lib.HeartbeatHook(hb_path, min_interval_s=3600.0)
    trainer = _trainer_stub(tmp_path)
    hook.on_start(trainer)
    t0 = json.load(open(hb_path))["t"]
    hook.after_step(trainer, 1, {"loss": 1.0})  # within interval: no write
    assert json.load(open(hb_path))["t"] == t0


def test_moe_collapse_hook_fires_on_induced_collapse(tmp_path):
    hook = hooks_lib.MoECollapseHook(patience=2)
    trainer = _trainer_stub(tmp_path)

    # Healthy routing: balanced aux loss, no drops — never fires.
    for step in (1, 2, 3):
        hook.after_step(trainer, step, {"moe_drop_frac": 0.01,
                                        "moe_aux_loss": 1.02})
    assert hook.fired_steps == []

    # Induced collapse fixture: most tokens racing one expert.
    hook.after_step(trainer, 4, {"moe_drop_frac": 0.7, "moe_aux_loss": 5.0})
    assert hook.fired_steps == []  # patience not yet met
    hook.after_step(trainer, 5, {"moe_drop_frac": 0.72, "moe_aux_loss": 5.5})
    assert hook.fired_steps == [5]

    trainer.writer.telemetry.close()
    evs = list(telemetry.read_events(trainer._events_path,
                                     kind=telemetry.KIND_HEALTH))
    assert len(evs) == 1
    h = evs[0]["health"]
    assert h["warning"] == "moe_collapse" and h["streak"] == 2
    assert h["moe_drop_frac_value"] == pytest.approx(0.72)


def test_moe_collapse_streak_resets_on_recovery(tmp_path):
    hook = hooks_lib.MoECollapseHook(patience=2)
    trainer = _trainer_stub(tmp_path)
    hook.after_step(trainer, 1, {"moe_drop_frac": 0.9})
    hook.after_step(trainer, 2, {"moe_drop_frac": 0.0})  # transient recovered
    hook.after_step(trainer, 3, {"moe_drop_frac": 0.9})
    assert hook.fired_steps == []


def test_nan_guard_provenance(tmp_path):
    trainer = _trainer_stub(
        tmp_path,
        _ckpt_manager=SimpleNamespace(latest_step=lambda: 7),
    )
    hook = hooks_lib.NaNGuardHook()
    with pytest.raises(FloatingPointError) as exc:
        hook.after_step(trainer, 9, {"loss": float("nan")})
    msg = str(exc.value)
    expected_ckpt = os.path.join(trainer.config.checkpoint.directory, "7")
    assert "loss" in msg and "step 9" in msg and expected_ckpt in msg

    trainer.writer.telemetry.close()
    evs = list(telemetry.read_events(trainer._events_path,
                                     kind=telemetry.KIND_FAILURE))
    assert len(evs) == 1
    h = evs[0]["health"]
    assert h["failure"] == "non_finite_metric"
    assert h["metric"] == "loss"
    assert math.isnan(float(h["value"]))
    assert h["last_good_checkpoint"] == expected_ckpt
    assert evs[0]["step"] == 9


# ------------------------------------------- recovery-ladder rollups ----


def test_summarize_counts_recovery_ladder_events(tmp_path):
    """analyze_trace run summaries must account for every ladder rung:
    anomalies, rollbacks, skipped batches, and infeed stall retries."""
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="ladder")
    w.emit(telemetry.KIND_ANOMALY, step=30,
           health={"anomaly": "non_finite_metric", "metric": "grad_norm",
                   "value": "nan"})
    w.emit(telemetry.KIND_ROLLBACK, step=30,
           health={"from_step": 30, "to_step": 20,
                   "consecutive_rollbacks": 1})
    w.emit(telemetry.KIND_BATCH_SKIPPED, step=30,
           health={"from_step": 21, "to_step": 30, "batches": 10})
    for attempt in (1, 2, 3):
        w.emit(telemetry.KIND_INFEED_STALL, step=12,
               health={"deadline_s": 0.5, "attempt": attempt,
                       "max_retries": 20})
    w.close()

    s = telemetry.summarize_events(path)
    rec = s["recovery"]
    assert rec["anomalies"] == [{"step": 30, "anomaly": "non_finite_metric",
                                 "metric": "grad_norm"}]
    assert rec["rollbacks"] == [{"from_step": 30, "to_step": 20}]
    assert rec["batches_skipped"] == 10
    assert rec["infeed_stalls"] == 3

    text = telemetry.format_run_summary(s)
    assert "anomaly at step 30: non_finite_metric (grad_norm)" in text
    assert "rollback: step 30 -> 20" in text
    assert "batches skipped: 10" in text
    assert "infeed stalls retried: 3" in text


def test_summarize_without_ladder_events_reports_none(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="quiet")
    w.emit(telemetry.KIND_TRAIN_STEP, step=1, metrics={"loss": 1.0})
    w.close()
    s = telemetry.summarize_events(path)
    assert s["recovery"]["anomalies"] == []
    assert s["recovery"]["batches_skipped"] == 0
    assert "recovery activity: none" in telemetry.format_run_summary(s)


def test_summarize_pipeline_schedule_rollup(tmp_path):
    """A pipeline_schedule event plus train_step events roll up into the
    pipeline section: schedule identity, analytic bubble, the per-step
    logged bubble, and steady-state throughput (median of the back half
    of logged rates, past the compile ramp)."""
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="pp")
    w.emit(telemetry.KIND_PIPELINE, schedule="1f1b", stages=4,
           microbatches=8, virtual_stages=1,
           bubble_frac=3 / 11, peak_inflight=7.0)
    rates = [2.0, 9.0, 13.0, 14.0, 13.9, 14.1]  # slow compile-step head
    for i, r in enumerate(rates):
        w.emit(telemetry.KIND_TRAIN_STEP, step=i * 10,
               metrics={"loss": 5.0, "pipe_bubble_frac": 3 / 11},
               throughput={"examples_per_sec": r})
    w.close()

    pipe = telemetry.summarize_events(path)["pipeline"]
    assert pipe["schedule"] == "1f1b"
    assert pipe["stages"] == 4
    assert pipe["bubble_frac"] == pytest.approx(3 / 11)
    assert pipe["bubble_frac_logged"] == pytest.approx(3 / 11)
    assert pipe["steady_examples_per_sec"] == pytest.approx(14.0)

    text = telemetry.format_run_summary(
        telemetry.summarize_events(path))
    assert "pipeline: 1f1b S=4 M=8" in text
    assert "bubble 0.2727" in text
    assert "residency 7 acts" in text
    assert "steady 14.0 ex/s" in text


def test_summarize_without_pipeline_events(tmp_path):
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="nopipe")
    w.emit(telemetry.KIND_TRAIN_STEP, step=1, metrics={"loss": 1.0})
    w.close()
    s = telemetry.summarize_events(path)
    assert s["pipeline"] is None
    assert "pipeline:" not in telemetry.format_run_summary(s)


def test_summarize_mesh_resize_and_reshard_rollup(tmp_path):
    """The elastic events (ISSUE 6) join the recovery section: a
    supervisor mesh_resized and a restore-side ckpt_resharded both count
    as recovery activity and render with their axis transitions."""
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="elastic")
    w.emit(telemetry.KIND_MESH_RESIZED,
           from_axes={"data": 8}, to_axes={"data": 4}, visible_devices=4,
           global_batch=32, grad_accum=2, effective_batch_preserved=True)
    w.emit(telemetry.KIND_CKPT_RESHARDED, step=20,
           from_axes={"data": 8}, to_axes={"data": 4}, leaf_count=12,
           respec_agreement="12/8")
    w.close()
    s = telemetry.summarize_events(path)
    rec = s["recovery"]
    assert rec["mesh_resizes"] == [{"from_axes": {"data": 8},
                                    "to_axes": {"data": 4},
                                    "visible_devices": 4}]
    assert rec["ckpt_reshards"] == [{"step": 20, "from_axes": {"data": 8},
                                     "to_axes": {"data": 4},
                                     "leaf_count": 12}]
    text = telemetry.format_run_summary(s)
    assert "mesh resized: {data:8} -> {data:4} (4 devices visible)" in text
    assert "checkpoint resharded at step 20: {data:8} -> {data:4}" in text


def test_summarize_rolls_up_every_kind(tmp_path):
    """One event of EVERY telemetry kind → the summary accounts for each
    (the marker-audit's rollup guarantee, exercised end-to-end). New
    kinds must be added here — test_marker_audit.py enforces that every
    KIND_* has both a rollup and a test reference."""
    path = str(tmp_path / "events.jsonl")
    w = telemetry.TelemetryWriter(path, run_id="all-kinds")
    w.emit_run_meta(argv=["train.py"], config_name="lenet",
                    mesh={"data": 8})  # KIND_RUN_META
    w.emit(telemetry.KIND_TRAIN_STEP, step=1, metrics={"loss": 1.0},
           throughput={"examples_per_sec": 10.0})
    w.emit(telemetry.KIND_EVAL, step=2, metrics={"eval_loss": 1.0})
    w.emit(telemetry.KIND_BENCH, metrics={"value": 1.0},
           workload="resnet50")
    w.emit(telemetry.KIND_TRACE_SUMMARY, trace_dir="/tmp/t")
    w.emit(telemetry.KIND_HEALTH, step=3,
           health={"event": "moe_collapse"})
    w.emit(telemetry.KIND_FAILURE, step=3, health={"failure": "nan_loss"})
    w.emit(telemetry.KIND_CKPT_SAVE, step=4,
           metrics={"ckpt_save_blocked_ms": 1.0, "ckpt_save_total_ms": 2.0},
           async_save=True)
    w.emit(telemetry.KIND_STARTUP, step=4,
           time_to_first_step_s=2.5, restored_step=4)
    w.emit(telemetry.KIND_PIPELINE, schedule="gpipe", stages=2,
           microbatches=4, bubble_frac=0.2)
    w.emit(telemetry.KIND_ZERO_UPDATE, shards=8, buckets=3, bucket_mb=4.0,
           wire="float32", rs_wire_bytes=1024, ag_wire_bytes=1024,
           overlap_frac_est=0.6667, hidden_ms_est=0.01)
    w.emit(telemetry.KIND_ANOMALY, step=5,
           health={"anomaly": "loss_spike", "metric": "loss"})
    w.emit(telemetry.KIND_ROLLBACK, step=5,
           health={"from_step": 5, "to_step": 4})
    w.emit(telemetry.KIND_BATCH_SKIPPED, step=5, health={"batches": 2})
    w.emit(telemetry.KIND_INFEED_STALL, step=5, health={"attempt": 1})
    w.emit(telemetry.KIND_CKPT_QUARANTINED, step=4,
           health={"reason": "hash mismatch"})
    w.emit(telemetry.KIND_RESTORE_FALLBACK,
           health={"from_step": 4, "to_step": 2})
    w.emit(telemetry.KIND_SUPERVISOR_ATTEMPT, attempt=1, rc=137,
           classification="crashed")
    w.emit(telemetry.KIND_CRASH_LOOP, verdict="deterministic_crash_loop")
    w.emit(telemetry.KIND_MESH_RESIZED, from_axes={"data": 8},
           to_axes={"data": 4}, visible_devices=4)
    w.emit(telemetry.KIND_CKPT_RESHARDED, step=4, from_axes={"data": 8},
           to_axes={"data": 4}, leaf_count=8)
    w.emit(telemetry.KIND_SERVE_REQUEST,
           metrics={"rows": 2, "queue_wait_ms": 1.0, "latency_ms": 4.0})
    w.emit(telemetry.KIND_SERVE_BATCH,
           metrics={"rows": 2, "padded_rows": 4, "compute_ms": 3.0,
                    "queue_depth": 1})
    w.emit(telemetry.KIND_SERVE_QUEUE, metrics={"queue_depth": 2})
    w.emit(telemetry.KIND_SERVE_LATENCY,
           metrics={"p50_ms": 3.0, "p90_ms": 4.0, "p99_ms": 4.0, "count": 1},
           throughput={"requests_per_sec": 10.0, "rows_per_sec": 20.0})
    w.emit(telemetry.KIND_SERVE_RECOMPILE, bucket="rows2",
           metrics={"compile_ms": 50.0})
    w.emit(telemetry.KIND_DECODE_STEP,
           metrics={"rows": 3, "padded_rows": 4, "step_ms": 6.0,
                    "per_token_ms": 2.0, "occupancy": 0.75})
    w.emit(telemetry.KIND_KV_CACHE,
           metrics={"pages_used": 5, "pages_free": 3, "streams_active": 2,
                    "streams_waiting": 1, "evictions": 1},
           event="periodic")
    w.emit(telemetry.KIND_SERVE_ROUTE,
           metrics={"latency_ms": 5.0, "retries": 1, "status": 200},
           replica="r0", shed=False, deadline_exceeded=False)
    w.emit(telemetry.KIND_SERVE_EJECT, replica="r1", action="eject",
           reason="stale healthz")
    w.emit(telemetry.KIND_SERVE_RELOAD, metrics={"reload_ms": 120.0},
           replica="r0", ok=True, from_digest="aaaa", to_digest="bbbb")
    w.emit(telemetry.KIND_SCALE, metrics={"pressure": 0.91},
           action="up", reason="pressure 0.91 >= 0.75", replica="r3",
           from_replicas=3, to_replicas=4)
    w.emit(telemetry.KIND_ADMISSION, tenant="batch:nightly", priority=2,
           verdict="shed", retry_after_s=1.0)
    w.emit(telemetry.KIND_SPAN, metrics={"dur_ms": 12.5},
           trace="t" * 16, span="s" * 16, parent=None,
           name="serve.request", service="replica0", status="ok",
           t_start=1000.0, offset_s=0.0, attrs=None)
    w.emit(telemetry.KIND_GOODPUT, step=5,
           metrics={"wall_s": 10.0, "goodput_frac": 0.8},
           buckets={"step_compute": 8.0, "other": 2.0},
           counters={"ckpt_saves": 1}, t0=1000.0, final=True)
    w.emit(telemetry.KIND_MEMORY, step=5,
           metrics={"bytes_in_use": 100, "peak_bytes_in_use": 200,
                    "device_count": 8},
           source="train", source_kind="device_memory_stats",
           analysis={"argument_bytes": 50, "temp_bytes": 25,
                     "output_bytes": 25, "peak_bytes_est": 100})
    w.emit(telemetry.KIND_DATA_SHARD, step=0,
           shard={"process_index": 0, "process_count": 2, "host_batch": 8,
                  "global_batch": 16, "shard_mode": "block",
                  "data_parallel": 2})
    w.emit(telemetry.KIND_DATA_PACKING, step=5,
           metrics={"real_tokens": 90, "padded_tokens": 10,
                    "total_tokens": 100, "packing_efficiency": 0.9})
    w.emit(telemetry.KIND_DATA_STATE, step=4,
           plan={"action": "repartition", "from_processes": 4,
                 "to_processes": 2, "watermark": 2})
    w.emit(telemetry.KIND_AUTOTUNE_TRIAL, trial="sha256:abcd", status="done",
           score=2418.0, unit="images/sec/chip")
    w.close()

    s = telemetry.summarize_events(path)
    kind_values = {
        getattr(telemetry, name)
        for name in dir(telemetry) if name.startswith("KIND_")
    }
    assert kind_values <= set(s["kinds"]), (
        f"kinds never emitted by this test: {kind_values - set(s['kinds'])}"
    )
    assert s["meta"]["config_name"] == "lenet"
    assert s["evals"] == {"count": 1, "last_step": 2}
    assert s["bench"] == {"count": 1, "workloads": ["resnet50"]}
    assert s["trace_summaries"] == 1
    assert s["health_events"] == {"moe_collapse": 1}
    assert s["serve"]["requests"] == 1 and s["serve"]["batches"] == 1
    assert s["serve"]["queue_depth_max"] == 2
    assert s["fleet"]["requests"] == 1 and s["fleet"]["retries"] == 1
    assert s["fleet"]["ejects"] == [{"replica": "r1",
                                     "reason": "stale healthz"}]
    assert s["fleet"]["reloads"][0]["to_digest"] == "bbbb"
    assert s["fleet"]["scaling"]["ups"] == 1
    assert s["fleet"]["scaling"]["events"][0]["to_replicas"] == 4
    assert s["fleet"]["tenants"]["batch:nightly"]["shed"] == 1
    assert s["decode"]["tokens"] == 3 and s["decode"]["steps"] == 1
    assert s["decode"]["pages_used_max"] == 5
    assert s["decode"]["evictions"] == 1
    assert s["decode"]["streams_waiting_max"] == 1
    assert s["zero"]["shards"] == 8 and s["zero"]["buckets"] == 3
    assert s["goodput"]["attempts"] == 1
    assert s["goodput"]["goodput_frac"] == pytest.approx(0.8)
    assert s["memory"]["samples"] == 1
    assert s["memory"]["peak_bytes_in_use"] == 200
    assert s["spans"]["count"] == 1 and s["spans"]["traces"] == 1
    assert s["spans"]["services"] == {"replica0": 1}
    assert s["data"]["shard"]["shard_mode"] == "block"
    assert s["data"]["packing"]["packing_efficiency"] == 0.9
    assert s["recovery"]["data_restores"][0]["action"] == "repartition"
    assert s["autotune"]["ran"] == 1
    assert s["autotune"]["best"]["trial"] == "sha256:abcd"
    text = telemetry.format_run_summary(s)
    assert "run: config_name=lenet" in text
    assert "evals: 1 (last at step 2)" in text
    assert "bench results: 1 (resnet50)" in text
    assert "trace summaries: 1" in text
    assert "health events: moe_collapse=1" in text
    assert "serving: 1 requests (2 rows) in 1 batches" in text
    assert "decode: 3 tokens in 1 steps" in text
    assert "kv cache: peak 5 pages in use" in text
    assert "bucket recompiles: 1 (rows2)" in text
    assert "fleet: 1 proxied" in text and "ejections: 1" in text
    assert "scaling: 1 up / 0 down (up->4@0.91)" in text
    assert "tenant batch:nightly: routed 0, shed 1" in text
    assert "zero update sharding: 8 shards, 3 buckets" in text
    assert "goodput: 80.0% of 10.0 s wall over 1 attempt(s)" in text
    assert "spans: 1 across 1 trace(s) [replica0=1]" in text
    assert "memory: 1 sample(s)" in text
    assert "data shard: host 0/2 reads 8 of 16 rows/batch (block mode)" \
        in text
    assert "packing: 90 real / 10 padded tokens, efficiency 0.900" in text
    assert "data state restored at step 4: repartition across 4 -> 2 " \
        "hosts (watermark 2)" in text
