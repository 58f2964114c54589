"""End-to-end gang supervision drills (slow tier).

The whole cluster-resilience story against a REAL 2-process
``jax.distributed`` gang, supervised by the real scripts/train_cluster.py:

* kill drill — ``kill_worker:1:2`` SIGKILLs worker 1 mid-run; the
  supervisor must coordinate the shutdown (SIGTERM the chief, which
  force-saves via the graceful-preemption contract), relaunch the gang
  unattended, and the resumed run must reproduce the uninterrupted
  control's step metrics exactly.  The restart gap must land classified
  in the stitched per-host goodput ledger.
* drop drill — ``drop_worker:1`` loses a worker permanently; the
  supervisor must refit the mesh to the surviving process count
  (gang-level rc-84), preserve the EFFECTIVE batch via grad
  accumulation, relaunch smaller, and consume NO attempt doing it
  (enforced by running with ``--max-attempts 1``).

The drill's supervisor_events.jsonl is archived to ``DTF_GANG_DRILL_DIR``
when the tier driver sets it (scripts/run_tier1.sh), like the fleet
drill's serve bench.

Both drills gate on ``cluster.probe_gang()``: stock CPU jaxlib forms the
gang but rejects multi-process computations at compile time ("Multiprocess
computations aren't implemented on the CPU backend"), so on such hosts the
drills SKIP with the probe's evidence instead of failing.  The supervisor's
decision logic itself is covered without JAX in tests/test_cluster.py.
"""

import glob
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.slowest]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from distributed_tensorflow_framework_tpu.core import goodput  # noqa: E402
from distributed_tensorflow_framework_tpu.core import telemetry  # noqa: E402
from distributed_tensorflow_framework_tpu.core import tracing  # noqa: E402
from scripts import analyze_trace  # noqa: E402

SCRIPT = os.path.join(REPO, "scripts", "train_cluster.py")

# Both drills take the session-scoped ``gang_capability`` fixture
# (tests/conftest.py): one probe_gang() per session, skip-with-evidence
# on backends whose compiler rejects multi-process programs.


def _run_super(args, *, faults=None, timeout=600):
    env = dict(os.environ)
    env.pop("DTF_FAULTS", None)
    env.pop("DTF_FAULTS_STATE", None)
    if faults:
        env["DTF_FAULTS"] = faults
    return subprocess.run(
        [sys.executable, SCRIPT, *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


def _lenet_cmd(ck_dir, *extra):
    return [
        "--config", "configs/lenet_mnist.yaml",
        "--set", "train.log_interval=4",
        "--set", "train.eval_steps=0", "--set", "train.eval_interval=0",
        # Frequent goodput snapshots: even a SIGKILLed worker leaves a
        # recent ledger for the stitcher.
        "--set", "train.goodput_interval_s=2",
        "--set", f"checkpoint.directory={ck_dir}",
        "--set", "checkpoint.save_interval_steps=2",
        *extra,
    ]


def _step_metrics(log: str, step: int) -> str:
    m = re.search(
        rf"step {step}: (grad_norm=\S+) (learning_rate=\S+) (loss=\S+) "
        rf"(top1=\S+) (top5=\S+)", log)
    assert m, f"no step-{step} metrics line:\n{log[-2000:]}"
    return " ".join(m.groups())


def _classifications(events_path):
    return [
        str((ev.get("extra") or {}).get("classification"))
        for ev in telemetry.read_events(
            events_path, kind=telemetry.KIND_SUPERVISOR_ATTEMPT,
            strict=False)
    ]


def _archive(events_path, name):
    art = os.environ.get("DTF_GANG_DRILL_DIR")
    if art and os.path.exists(events_path):
        os.makedirs(art, exist_ok=True)
        shutil.copyfile(events_path, os.path.join(art, name))


def test_kill_worker_gang_restart_resumes_bit_exact(tmp_path, gang_capability):
    # Control: the same 2-process gang, uninterrupted.
    ctrl_ck = tmp_path / "ctrl-ck"
    r = _run_super([
        "--procs", "2", "--devices-per-proc", "2",
        "--workdir", str(tmp_path / "w-ctrl"), "--max-attempts", "1",
        "--chaos-tick", "0",
        "--", *_lenet_cmd(ctrl_ck, "--set", "train.total_steps=8",
                          "--set", "mesh.data=-1"),
    ])
    assert r.returncode == 0, r.stderr[-4000:]
    want = _step_metrics(
        (tmp_path / "w-ctrl" / "worker-0.log").read_text(), 8)

    # Drill: SIGKILL worker 1 at chaos tick 2 (seconds after the whole
    # gang heartbeated). The supervisor must SIGTERM the survivor,
    # relaunch the gang unattended, and resume to the same step-8 state.
    ck = tmp_path / "ck"
    r = _run_super([
        "--procs", "2", "--devices-per-proc", "2",
        "--workdir", str(tmp_path / "w-drill"), "--max-attempts", "3",
        "--retry-sleep", "0.2", "--jitter", "0",
        "--chaos-tick", "1",
        "--", *_lenet_cmd(ck, "--set", "train.total_steps=8",
                          "--set", "mesh.data=-1"),
    ], faults="kill_worker:1:2")
    events = str(ck / "supervisor_events.jsonl")
    _archive(events, "GANG_DRILL_EVENTS.jsonl")
    assert r.returncode == 0, r.stderr[-4000:]
    assert "chaos SIGKILL worker 1" in r.stderr, r.stderr[-4000:]

    # Root cause attributed to worker 1 (SIGKILL → 137); the run ends
    # "done" after the unattended relaunch.
    cls = _classifications(events)
    assert cls[0] == "crashed", (cls, r.stderr[-3000:])
    assert cls[-1] == "done", cls
    crashed = [
        (ev.get("extra") or {}) for ev in telemetry.read_events(
            events, kind=telemetry.KIND_SUPERVISOR_ATTEMPT, strict=False)
        if (ev.get("extra") or {}).get("classification") == "crashed"]
    assert crashed[0]["process_id"] == 1
    assert crashed[0]["rc"] == 137

    # Bit-exact resume: the relaunched gang's chief reproduces the
    # uninterrupted control's step-8 metrics.
    got = _step_metrics(
        (tmp_path / "w-drill" / "worker-0.log").read_text(), 8)
    assert got == want

    # The restart gap is classified in the stitched per-host ledger.
    streams = [str(ck / "events.jsonl")]
    if (ck / "events-p1.jsonl").exists():
        streams.append(str(ck / "events-p1.jsonl"))
    g = goodput.stitch_attempts(streams)
    assert g is not None
    assert len(g["attempts"]) >= 2, g["attempts"]
    gaps = g["restart_gaps"]
    assert gaps and gaps[0]["classification"] == "crashed", gaps
    assert g["buckets"]["restart_gap"] > 0
    # Per-host section joins both workers' streams by process_id.
    assert "0" in (g.get("per_host") or {}), sorted(g)

    # Tracing: the whole recovery is ONE supervisor-rooted trace across
    # three processes — supervisor.run → supervisor.attempt per attempt,
    # each attempt parenting both workers' worker.run spans via
    # DTF_TRACE_CTX, with the restart gap a span on the critical path.
    traces = analyze_trace.build_traces(
        analyze_trace.collect_spans(analyze_trace._events_files(str(ck))))
    sup = [t for t in traces
           if any(s["name"] == "supervisor.run" for s in t["spans"])]
    assert len(sup) == 1, [t["trace"] for t in traces]
    tree = sup[0]
    names = [s["name"] for s in tree["spans"]]
    assert names.count("supervisor.attempt") >= 2, names
    assert "supervisor.restart_gap" in names
    workers = {s["service"] for s in tree["spans"]
               if s["name"] == "worker.run"}
    assert {"worker0", "worker1"} <= workers, workers
    by_id = {s["span"]: s for s in tree["spans"]}
    for s in tree["spans"]:
        if s["name"] == "worker.run":
            assert by_id[s["parent"]]["name"] == "supervisor.attempt", s
    assert analyze_trace.critical_path(tree)["restart_gap"] > 0

    # Flight recorders fired on both sides of the fault: the supervisor
    # dumped when it classified the crash (ring holds the crashed
    # attempt's span; the still-open supervisor.run is its parent), and
    # the SIGTERMed survivor flushed its telemetry and dumped before the
    # supervisor's SIGKILL grace expired (the dump existing at all is
    # the satellite-2 durability pin).
    dumps = [json.loads(open(p).read())
             for p in glob.glob(str(ck / "flightrec-*.json"))]
    assert dumps, "no flight-recorder dump under the checkpoint dir"
    sup_dump = next(d for d in dumps if "crashed" in d["reason"])
    ring_spans = [(e.get("extra") or {}).get("name")
                  for e in sup_dump["events"]
                  if e.get("kind") == telemetry.KIND_SPAN]
    assert "supervisor.attempt" in ring_spans, ring_spans
    assert any(s["name"] == "supervisor.run"
               for s in sup_dump["open_spans"])
    assert any(d["reason"] == "graceful_preemption" for d in dumps), \
        [d["reason"] for d in dumps]
    assert all(d["schema"] == tracing.FLIGHTREC_SCHEMA for d in dumps)

    # Perfetto export for the tier driver's artifact dir.
    trace_dir = os.environ.get("DTF_TRACE_DIR")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        assert analyze_trace.main(
            [str(ck), "--spans", "--perfetto",
             os.path.join(trace_dir, "GANG_TRACE.json")]) == 0


def test_drop_worker_refits_gang_without_consuming_attempt(tmp_path, gang_capability):
    # Drop worker 1 permanently at tick 2. mesh.data=4 over 2 procs × 2
    # devices; the refit must land on data=2 over the 1 surviving
    # process and preserve the effective batch (16×1 → 8×2).
    # --max-attempts 1 makes "no attempt consumed" an execution fact:
    # the run only completes if the refit relaunch was free.
    ck = tmp_path / "ck"
    r = _run_super([
        "--procs", "2", "--devices-per-proc", "2",
        "--workdir", str(tmp_path / "w"), "--max-attempts", "1",
        "--chaos-tick", "1",
        "--", *_lenet_cmd(ck, "--set", "train.total_steps=6",
                          "--set", "mesh.data=4",
                          "--set", "data.global_batch_size=16"),
    ], faults="drop_worker:1:2")
    events = str(ck / "supervisor_events.jsonl")
    _archive(events, "GANG_DRILL_REFIT_EVENTS.jsonl")
    assert r.returncode == 0, r.stderr[-4000:]
    assert "gang refit #1" in r.stderr, r.stderr[-4000:]

    cls = _classifications(events)
    assert cls == ["gang_refit", "done"], (cls, r.stderr[-3000:])
    (resize,) = [
        (ev.get("extra") or {}) for ev in telemetry.read_events(
            events, kind=telemetry.KIND_MESH_RESIZED, strict=False)]
    assert resize["process_count"] == 1
    assert resize["dropped_workers"] == [1]
    assert resize["to_axes"]["data"] == 2
    assert resize["effective_batch_preserved"] is True
    assert (resize["global_batch"], resize["grad_accum"]) == (8, 2)

    # The relaunched survivor ran single-process on its 2 local devices.
    chief = (tmp_path / "w" / "worker-0.log").read_text()
    assert "2 local / 2 global devices" in chief, chief[-2000:]
