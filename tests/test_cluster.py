"""Gang supervision: the cluster-level fault-tolerance layer.

Two tiers, both fast (no JAX, no real gang):

* Pure-library units for core/cluster.py — heartbeat naming, the worker
  discovery env, per-worker crash-loop keying, the rejoin→drop decision,
  the gang refit (mesh fit + effective-batch preservation) and the exit
  barrier's ordering/timeout, all driven through their test seams.
* Supervisor-loop scenarios for scripts/train_cluster.py — main() runs
  in-process with ``llc.spawn_gang`` monkeypatched to launch tiny
  ``python -c`` stub workers, so the whole ladder (coordinated restart,
  chaos drop → gang refit, stale-heartbeat watchdog, rejoin timeout,
  port-race retry, crash-loop break) is exercised against real child
  processes and real signals in well under a second per scenario.

The end-to-end gang drills (a REAL 2-process jax.distributed run killed
mid-step and resumed bit-exactly) live in tests/test_cluster_drill.py
behind the slow marker.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distributed_tensorflow_framework_tpu.core import cluster  # noqa: E402
from distributed_tensorflow_framework_tpu.core import faults  # noqa: E402
from distributed_tensorflow_framework_tpu.core import goodput  # noqa: E402
from distributed_tensorflow_framework_tpu.core import supervision  # noqa: E402
from distributed_tensorflow_framework_tpu.core import telemetry  # noqa: E402


# ---------------------------------------------------------------------------
# Heartbeat file contract
# ---------------------------------------------------------------------------


class TestHeartbeatContract:
    def test_single_process_keeps_legacy_name(self):
        assert cluster.heartbeat_name(0, 1) == "heartbeat.json"

    def test_gang_names_are_per_worker(self):
        assert cluster.heartbeat_name(0, 2) == "heartbeat-p0.json"
        assert cluster.heartbeat_name(1, 2) == "heartbeat-p1.json"

    def test_out_of_range_index_is_typed_error(self):
        with pytest.raises(cluster.ClusterSpecError):
            cluster.heartbeat_name(2, 2)
        with pytest.raises(cluster.ClusterSpecError):
            cluster.heartbeat_name(-1, 2)

    def test_path_joins_ckpt_dir(self):
        assert cluster.heartbeat_path("/ck", 1, 2) == "/ck/heartbeat-p1.json"


# ---------------------------------------------------------------------------
# Worker discovery env
# ---------------------------------------------------------------------------


class TestWorkerEnv:
    def test_gang_sets_discovery_triple(self):
        env = cluster.worker_env(
            {"PATH": "/bin"}, coordinator_port=1234, num_processes=2,
            process_id=1, devices_per_proc=2)
        assert env["JAX_COORDINATOR_ADDRESS"] == "127.0.0.1:1234"
        assert env["JAX_NUM_PROCESSES"] == "2"
        assert env["JAX_PROCESS_ID"] == "1"
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "xla_force_host_platform_device_count=2" in env["XLA_FLAGS"]
        assert env["PATH"] == "/bin"  # base env preserved

    def test_single_process_strips_discovery(self):
        # A gang refit down to one process must NOT inherit the dead
        # coordinator's address — the survivor runs single-process.
        base = {"JAX_COORDINATOR_ADDRESS": "127.0.0.1:9", "JAX_NUM_PROCESSES":
                "2", "JAX_PROCESS_ID": "1"}
        env = cluster.worker_env(
            base, coordinator_port=1234, num_processes=1, process_id=0,
            devices_per_proc=4)
        for key in ("JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES",
                    "JAX_PROCESS_ID"):
            assert key not in env
        assert "xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]

    def test_base_env_not_mutated(self):
        base = {"JAX_PROCESS_ID": "7"}
        cluster.worker_env(base, coordinator_port=1, num_processes=1,
                           process_id=0, devices_per_proc=1)
        assert base == {"JAX_PROCESS_ID": "7"}

    def test_bad_process_id_is_typed_error(self):
        with pytest.raises(cluster.ClusterSpecError):
            cluster.worker_env({}, coordinator_port=1, num_processes=2,
                               process_id=2, devices_per_proc=1)


# ---------------------------------------------------------------------------
# Per-worker crash-loop keying
# ---------------------------------------------------------------------------


class TestGangBreaker:
    def test_identical_failures_trip_one_worker(self):
        b = cluster.GangBreaker(threshold=2)
        assert not b.record(1, rc=139, last_step=5, ckpt_step=5)
        assert b.record(1, rc=139, last_step=5, ckpt_step=5)

    def test_other_workers_noise_does_not_reset_streak(self):
        # The whole point of per-worker keying: worker 0's unrelated
        # failure interleaving must not launder worker 1's crash loop.
        b = cluster.GangBreaker(threshold=2)
        assert not b.record(1, rc=139, last_step=5, ckpt_step=5)
        assert not b.record(0, rc=1, last_step=9, ckpt_step=5)
        assert b.record(1, rc=139, last_step=5, ckpt_step=5)

    def test_transient_resets_that_workers_streak(self):
        b = cluster.GangBreaker(threshold=2)
        assert not b.record(1, rc=139, last_step=5, ckpt_step=5)
        assert not b.record(1, rc=85, last_step=5, ckpt_step=5,
                            transient=True)
        assert not b.record(1, rc=139, last_step=5, ckpt_step=5)

    def test_report_tags_process_id(self):
        b = cluster.GangBreaker(threshold=2)
        b.record(3, rc=1, last_step=None, ckpt_step=None)
        assert b.report(3)["process_id"] == 3
        assert b.report(9) == {"verdict": "no_failures_recorded",
                               "process_id": 9}


# ---------------------------------------------------------------------------
# Rejoin watchdog decision
# ---------------------------------------------------------------------------


class TestDecideRejoin:
    def test_disabled_watchdog(self):
        assert cluster.decide_rejoin({0: None, 1: None}, elapsed_s=99,
                                     rejoin_timeout_s=0.0) == []

    def test_window_not_elapsed(self):
        assert cluster.decide_rejoin({0: 1.0, 1: None}, elapsed_s=5,
                                     rejoin_timeout_s=10) == []

    def test_nobody_joined_means_still_booting(self):
        assert cluster.decide_rejoin({0: None, 1: None}, elapsed_s=60,
                                     rejoin_timeout_s=10) == []

    def test_overdue_workers_dropped_when_peers_joined(self):
        assert cluster.decide_rejoin({0: 1.0, 1: None, 2: None},
                                     elapsed_s=60,
                                     rejoin_timeout_s=10) == [1, 2]


# ---------------------------------------------------------------------------
# Gang refit (the cluster-level rc-84 decision)
# ---------------------------------------------------------------------------


class TestDecideRefit:
    def test_shrink_preserves_effective_batch(self):
        refit = cluster.decide_refit(
            {"data": 4}, 16, 1, process_count=1, devices_per_proc=2)
        assert refit.process_count == 1
        assert refit.n_devices == 2
        assert refit.sizes["data"] == 2
        # 16×1 over dp=4 → 8×2 over dp=2: same effective batch.
        assert (refit.global_batch, refit.grad_accum) == (8, 2)
        assert refit.batch_preserved
        assert "mesh.data=2" in refit.overrides
        assert "checkpoint.allow_reshard=true" in refit.overrides
        assert "data.global_batch_size=8" in refit.overrides
        assert "train.grad_accum_steps=2" in refit.overrides

    def test_inferred_data_axis_cannot_promise_preservation(self):
        refit = cluster.decide_refit(
            {"data": -1}, 16, 1, process_count=1, devices_per_proc=2)
        assert not refit.batch_preserved
        assert not any("global_batch_size" in o for o in refit.overrides)

    def test_zero_survivors_is_typed_error(self):
        with pytest.raises(cluster.ClusterSpecError):
            cluster.decide_refit({"data": 2}, 8, 1, process_count=0,
                                 devices_per_proc=2)


# ---------------------------------------------------------------------------
# Exit barrier
# ---------------------------------------------------------------------------


class TestExitBarrier:
    def test_already_committed_returns_without_sleep(self):
        sleeps = []
        got = cluster.exit_barrier(
            "/ck", step=5, timeout_s=10,
            latest_step_fn=lambda d: 7, sleep=sleeps.append,
            clock=lambda: 0.0)
        assert got == 7
        assert sleeps == []

    def test_waits_for_commit_record(self):
        # The ordering contract: a survivor polling the manifest must NOT
        # return before the chief's commit record for the final step
        # lands — here it lands on the third poll.
        seen = iter([None, None, 5])
        sleeps = []
        got = cluster.exit_barrier(
            "/ck", step=5, timeout_s=10, poll_s=0.25,
            latest_step_fn=lambda d: next(seen), sleep=sleeps.append,
            clock=lambda: 0.0)
        assert got == 5
        assert sleeps == [0.25, 0.25]

    def test_stale_commit_does_not_release(self):
        # A leftover commit from a PREVIOUS attempt (step 3 < final step
        # 5) must not satisfy the barrier.
        seen = iter([3, 3, 5])
        got = cluster.exit_barrier(
            "/ck", step=5, timeout_s=10,
            latest_step_fn=lambda d: next(seen), sleep=lambda s: None,
            clock=lambda: 0.0)
        assert got == 5

    def test_timeout_raises_instead_of_dropping_shards(self):
        t = iter(range(100))
        with pytest.raises(cluster.ExitBarrierTimeoutError) as e:
            cluster.exit_barrier(
                "/ck", step=5, timeout_s=3.0,
                latest_step_fn=lambda d: None, sleep=lambda s: None,
                clock=lambda: float(next(t)))
        assert "step 5" in str(e.value)


# ---------------------------------------------------------------------------
# Cluster chaos fault parsing
# ---------------------------------------------------------------------------


class TestClusterFaults:
    def test_kill_worker_parses(self):
        (f,) = faults.FaultPlan.parse("kill_worker:1:3").faults
        assert (f.kind, f.worker, f.step) == ("kill_worker", 1, 3)
        assert f.point == "gang_chaos"

    def test_tick_defaults_to_first(self):
        (f,) = faults.FaultPlan.parse("drop_worker:2").faults
        assert (f.worker, f.step) == (2, 1)

    def test_stall_worker_parses_duration(self):
        (f,) = faults.FaultPlan.parse("stall_worker:0:10s").faults
        assert (f.worker, f.seconds, f.step) == (0, 10.0, 1)

    def test_stall_worker_zero_means_forever(self):
        (f,) = faults.FaultPlan.parse("stall_worker:1:0").faults
        assert f.seconds == faults._STALL_FOREVER_S

    def test_bad_specs_raise(self):
        for spec in ("kill_worker:x", "kill_worker:-1", "kill_worker:1:0",
                     "drop_worker:", "stall_worker:-1:5"):
            with pytest.raises(ValueError):
                faults.FaultPlan.parse(spec)

    def test_fire_at_gang_chaos_point(self):
        plan = faults.FaultPlan.parse("kill_worker:1:2,stall_worker:0:5s")
        assert [f.kind for f in plan.fire("gang_chaos", step=1)] == \
            ["stall_worker"]
        assert [f.kind for f in plan.fire("gang_chaos", step=2)] == \
            ["kill_worker"]
        assert plan.fire("gang_chaos", step=2) == []  # once per process


# ---------------------------------------------------------------------------
# Gang goodput stitching (satellite 1)
# ---------------------------------------------------------------------------


def _write_goodput(path, run_id, *, t0, wall, host=None, final=True):
    ev = telemetry.make_event(
        telemetry.KIND_GOODPUT, run_id=run_id,
        metrics={"wall_s": wall, "goodput_frac": 0.8},
        t0=t0, final=final,
        buckets={"step_compute": wall * 0.8, "other": wall * 0.2},
        counters={"steps": 10},
        **({"process_id": host} if host is not None else {}))
    with open(path, "a") as fh:
        fh.write(json.dumps(ev) + "\n")


class TestGangStitch:
    def test_per_host_streams_join_by_process_id(self, tmp_path):
        chief = str(tmp_path / "events.jsonl")
        peer = str(tmp_path / "events-p1.jsonl")
        # Host 0: two attempts with a 5 s restart gap between them.
        _write_goodput(chief, "r0a", t0=100.0, wall=10.0, host=0)
        _write_goodput(chief, "r0b", t0=115.0, wall=5.0, host=0)
        # Host 1: its own timeline (different pre-ledger import time).
        _write_goodput(peer, "r1a", t0=100.5, wall=9.0, host=1)
        _write_goodput(peer, "r1b", t0=116.0, wall=4.0, host=1)
        sup = tmp_path / "supervisor_events.jsonl"
        w = telemetry.TelemetryWriter(str(sup))
        w.emit(telemetry.KIND_SUPERVISOR_ATTEMPT, attempt=1, rc=137,
               classification="crashed", process_id=1)
        w.close()

        g = goodput.stitch_attempts([chief, peer])
        assert g is not None
        # Top level stays the chief's timeline.
        assert g["wall_s"] == pytest.approx(10 + 5 + 5)
        assert g["restart_gaps"][0]["classification"] == "crashed"
        per_host = g["per_host"]
        assert set(per_host) == {"0", "1"}
        # Each host's buckets (gap included) sum to its OWN span.
        for host in per_host.values():
            assert sum(host["buckets"].values()) == \
                pytest.approx(host["wall_s"])
        assert per_host["1"]["wall_s"] == pytest.approx(9 + 4 + 6.5)
        assert per_host["1"]["restart_gaps"][0]["classification"] == "crashed"
        table = goodput.format_goodput_table(g)
        assert "host 0:" in table and "host 1:" in table

    def test_single_stream_keeps_flat_shape(self, tmp_path):
        chief = str(tmp_path / "events.jsonl")
        _write_goodput(chief, "r0", t0=100.0, wall=10.0)
        g = goodput.stitch_attempts(chief)
        assert g is not None
        assert "per_host" not in g

    def test_analyze_trace_groups_worker_streams(self, tmp_path):
        from scripts import analyze_trace as at
        paths = [str(tmp_path / n) for n in
                 ("events-p1.jsonl", "events.jsonl",
                  "supervisor_events.jsonl")]
        groups = at._group_streams(paths)
        assert groups[0] == [str(tmp_path / "events.jsonl"),
                             str(tmp_path / "events-p1.jsonl")]
        assert groups[1] == [str(tmp_path / "supervisor_events.jsonl")]

    def test_analyze_trace_merges_multiple_run_dirs(self, tmp_path):
        from scripts import analyze_trace as at
        d0, d1 = tmp_path / "host0", tmp_path / "host1"
        d0.mkdir(), d1.mkdir()
        _write_goodput(str(d0 / "events.jsonl"), "r0", t0=100.0, wall=10.0,
                       host=0)
        _write_goodput(str(d1 / "events-p1.jsonl"), "r1", t0=100.5,
                       wall=9.0, host=1)
        out = tmp_path / "summary.json"
        assert at.main([str(d0), str(d1), "--json", str(out)]) == 0
        doc = json.loads(out.read_text())
        assert doc["schema"] == "dtf-run-summary/1"
        assert len(doc["worker_streams"]) == 2
        assert set(doc["goodput_ledger"]["per_host"]) == {"0", "1"}


# ---------------------------------------------------------------------------
# Supervisor-loop scenarios (in-process main(), stub subprocess workers)
# ---------------------------------------------------------------------------

from scripts import train_cluster as tc  # noqa: E402


def _stub_crash(rc=1, text=""):
    """A worker that (optionally) prints and exits rc immediately."""
    return (f"import sys\n"
            f"print({text!r})\n"
            f"sys.exit({rc})\n")


def _stub_graceful(hb_path=None, step=3):
    """A worker that heartbeats (optionally) and honors SIGTERM with the
    graceful-preemption exit code, like a real chief force-saving."""
    return textwrap.dedent(f"""
        import json, os, signal, sys, time
        signal.signal(signal.SIGTERM, lambda *a: sys.exit(83))
        hb = {hb_path!r}
        while True:
            if hb:
                tmp = hb + "." + str(os.getpid()) + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump({{"t": time.time(), "pid": os.getpid(),
                               "last_completed_step": {step}}}, fh)
                os.replace(tmp, hb)
            time.sleep(0.05)
    """)


def _stub_beat_once_then_wedge(hb_path):
    """One heartbeat, then silence — the wedged-collective signature."""
    return textwrap.dedent(f"""
        import json, os, time
        hb = {hb_path!r}
        with open(hb, "w") as fh:
            json.dump({{"t": time.time(), "pid": os.getpid(),
                       "last_completed_step": 1}}, fh)
        time.sleep(60)
    """)


@pytest.fixture
def gang(monkeypatch, tmp_path):
    """Harness for in-process tc.main(): monkeypatched spawn that launches
    ``python -c`` stubs (one program list per attempt), plus signal-handler
    and fault-plan restoration."""
    old_handlers = {s: signal.getsignal(s)
                    for s in (signal.SIGTERM, signal.SIGINT)}
    monkeypatch.setattr(tc, "_cancelled", False)
    calls = {"procs": [], "envs": []}

    def arm(programs_by_attempt):
        def spawn(train_args, *, procs, devices_per_proc, workdir, port,
                  base_env=None):
            idx = min(len(calls["procs"]), len(programs_by_attempt) - 1)
            programs = programs_by_attempt[idx]
            calls["procs"].append(procs)
            calls["envs"].append(dict(base_env or {}))
            os.makedirs(workdir, exist_ok=True)
            children, logs = [], []
            for i in range(procs):
                log = open(os.path.join(workdir, f"worker-{i}.log"), "w")
                logs.append(log)
                children.append(subprocess.Popen(
                    [sys.executable, "-c", programs[i]],
                    stdout=log, stderr=subprocess.STDOUT))
            return children, logs
        monkeypatch.setattr(tc.llc, "spawn_gang", spawn)
        return calls

    yield arm, calls
    faults.install(None)
    for s, h in old_handlers.items():
        signal.signal(s, h)


def _classifications(events_path):
    out = []
    for ev in telemetry.read_events(
            events_path, kind=telemetry.KIND_SUPERVISOR_ATTEMPT,
            strict=False):
        out.append((ev.get("extra") or {}))
    return out


class TestGangSupervisor:
    def _ck(self, tmp_path):
        ck = tmp_path / "ck"
        ck.mkdir()
        return str(ck)

    def _run(self, tmp_path, extra_args, cmd_extra=()):
        ck = self._ck(tmp_path)
        rc = tc.main([
            "--workdir", str(tmp_path / "logs"),
            "--retry-sleep", "0.05", "--jitter", "0", "--backoff-max", "0.1",
            *extra_args,
            "--", "--set", f"checkpoint.directory={ck}", *cmd_extra,
        ])
        return rc, os.path.join(ck, "supervisor_events.jsonl"), ck

    def test_worker_crash_restarts_whole_gang(self, gang, tmp_path):
        arm, calls = gang
        ck = str(tmp_path / "ck")
        arm([
            [_stub_graceful(os.path.join(ck, "heartbeat-p0.json")),
             _stub_crash(rc=1)],
            [_stub_crash(rc=0), _stub_crash(rc=0)],
        ])
        rc, events, _ = self._run(
            tmp_path, ["--procs", "2", "--max-attempts", "3",
                       "--chaos-tick", "0"])
        assert rc == 0
        assert calls["procs"] == [2, 2]
        attempts = _classifications(events)
        assert [a["classification"] for a in attempts] == ["crashed", "done"]
        # Root cause attributed to the crashing worker; the SIGTERMed
        # survivor's 83 is fallout, not the classification.
        assert attempts[0]["process_id"] == 1
        assert attempts[0]["rc"] == 1

    def test_drop_worker_chaos_refits_gang(self, gang, tmp_path):
        arm, calls = gang
        ck = str(tmp_path / "ck")
        faults.install("drop_worker:1:1")
        arm([
            [_stub_graceful(os.path.join(ck, "heartbeat-p0.json")),
             _stub_graceful(os.path.join(ck, "heartbeat-p1.json"))],
            [_stub_crash(rc=0)],
        ])
        rc, events, _ = self._run(
            tmp_path,
            ["--procs", "2", "--devices-per-proc", "2",
             "--max-attempts", "2", "--chaos-tick", "0.2"],
            cmd_extra=["--set", "mesh.data=4",
                       "--set", "data.global_batch_size=16"])
        assert rc == 0
        # Gang shrank 2 → 1 processes and the refit consumed NO attempt.
        assert calls["procs"] == [2, 1]
        attempts = _classifications(events)
        assert [a["classification"] for a in attempts] == \
            ["gang_refit", "done"]
        assert attempts[0]["attempt"] == attempts[1]["attempt"] == 1
        (resize,) = [
            (ev.get("extra") or {}) for ev in telemetry.read_events(
                events, kind=telemetry.KIND_MESH_RESIZED, strict=False)]
        assert resize["process_count"] == 1
        assert resize["dropped_workers"] == [1]
        assert resize["to_axes"]["data"] == 2
        # 16×1 over dp=4 → 8×2 over dp=2: effective batch preserved.
        assert resize["effective_batch_preserved"] is True
        assert (resize["global_batch"], resize["grad_accum"]) == (8, 2)
        overrides = calls["envs"][1][supervision.ELASTIC_OVERRIDES_ENV]
        assert "mesh.data=2" in overrides
        assert "data.global_batch_size=8" in overrides
        assert "train.grad_accum_steps=2" in overrides

    def test_stale_heartbeat_watchdog_kills_and_restarts(self, gang,
                                                         tmp_path):
        arm, calls = gang
        ck = str(tmp_path / "ck")
        arm([
            [_stub_graceful(os.path.join(ck, "heartbeat-p0.json")),
             _stub_beat_once_then_wedge(
                 os.path.join(ck, "heartbeat-p1.json"))],
            [_stub_crash(rc=0), _stub_crash(rc=0)],
        ])
        rc, events, _ = self._run(
            tmp_path, ["--procs", "2", "--max-attempts", "3",
                       "--chaos-tick", "0",
                       "--heartbeat-timeout", "0.4",
                       "--heartbeat-poll", "0.05"])
        assert rc == 0
        attempts = _classifications(events)
        assert [a["classification"] for a in attempts] == ["hung", "done"]
        assert attempts[0]["process_id"] == 1
        assert attempts[0]["hung"] is True

    def test_rejoin_timeout_drops_and_refits(self, gang, tmp_path):
        arm, calls = gang
        ck = str(tmp_path / "ck")
        arm([
            # Worker 0 joins (heartbeats); worker 1 never does.
            [_stub_graceful(os.path.join(ck, "heartbeat-p0.json")),
             "import time; time.sleep(60)"],
            [_stub_crash(rc=0)],
        ])
        rc, events, _ = self._run(
            tmp_path, ["--procs", "2", "--max-attempts", "2",
                       "--chaos-tick", "0",
                       "--rejoin-timeout", "0.5"])
        assert rc == 0
        assert calls["procs"] == [2, 1]
        attempts = _classifications(events)
        assert [a["classification"] for a in attempts] == \
            ["gang_refit", "done"]
        (resize,) = [
            (ev.get("extra") or {}) for ev in telemetry.read_events(
                events, kind=telemetry.KIND_MESH_RESIZED, strict=False)]
        assert resize["dropped_workers"] == [1]

    def test_port_bind_race_relaunches_for_free(self, gang, tmp_path):
        arm, calls = gang
        arm([
            [_stub_crash(rc=1, text="RuntimeError: Address already in use"),
             _stub_graceful()],
            [_stub_crash(rc=0), _stub_crash(rc=0)],
        ])
        rc, events, _ = self._run(
            tmp_path, ["--procs", "2", "--max-attempts", "1",
                       "--chaos-tick", "0"])
        # max-attempts=1 and we still recovered: the bind race consumed
        # no attempt.
        assert rc == 0
        attempts = _classifications(events)
        assert [a["classification"] for a in attempts] == \
            ["port_race", "done"]

    def test_crash_loop_breaks_per_worker(self, gang, tmp_path):
        arm, calls = gang
        arm([[_stub_crash(rc=7)]])
        rc, events, _ = self._run(
            tmp_path, ["--procs", "1", "--max-attempts", "5",
                       "--chaos-tick", "0",
                       "--crash-loop-threshold", "2"])
        assert rc == 7
        assert calls["procs"] == [1, 1]  # broke after 2, not 5
        loops = [ev for ev in telemetry.read_events(
            events, kind=telemetry.KIND_CRASH_LOOP, strict=False)]
        assert len(loops) == 1
        assert (loops[0].get("extra") or {})["process_id"] == 0

    def test_cancellation_is_not_retried(self, gang, tmp_path):
        arm, calls = gang
        arm([[_stub_crash(rc=130)]])
        rc, events, _ = self._run(
            tmp_path, ["--procs", "1", "--max-attempts", "5",
                       "--chaos-tick", "0"])
        assert rc == 130
        assert calls["procs"] == [1]
        attempts = _classifications(events)
        assert [a["classification"] for a in attempts] == ["cancelled"]


# ---------------------------------------------------------------------------
# Command-knob parsing
# ---------------------------------------------------------------------------


class TestParseRejoinTimeout:
    def test_default_disabled(self):
        assert tc.parse_rejoin_timeout(["--set", "mesh.data=2"]) == 0.0

    def test_set_override_wins_last(self):
        cmd = ["--set", "cluster.rejoin_timeout_s=5",
               "--set", "cluster.rejoin_timeout_s=30"]
        assert tc.parse_rejoin_timeout(cmd) == 30.0

    def test_yaml_knob(self, tmp_path):
        cfg = tmp_path / "c.yaml"
        cfg.write_text("cluster:\n  rejoin_timeout_s: 12.5\n")
        assert tc.parse_rejoin_timeout(["--config", str(cfg)]) == 12.5


class TestGangProbe:
    """probe_gang's failure classifier — the probe itself (a real
    2-process jax spawn) belongs to the slow tier via the
    gang_capability fixture; what tier-1 pins is the signature
    contract the skip decision rides on."""

    def test_cpu_backend_signature_is_unsupported(self):
        assert cluster.is_gang_unsupported(
            "jaxlib.xla_extension.XlaRuntimeError: INVALID_ARGUMENT: "
            "Multiprocess computations aren't implemented on the CPU "
            "backend.")

    def test_environmental_flake_is_not(self):
        # A refused coordinator connection is a flake worth surfacing,
        # not a this-backend-cannot-do-gangs verdict.
        assert not cluster.is_gang_unsupported(
            "RuntimeError: connection refused: 127.0.0.1:4444")
